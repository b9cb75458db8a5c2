"""Train a tiny Llama on a variable-length corpus with the packed pipeline.

The ``paddle_tpu.data`` subsystem end to end (docs/DATA.md): a
deterministic sharded stream over a synthetic document corpus, first-fit
sequence packing into fixed [B, seq] batches (segment ids + per-document
positions feed the flash-attention mask), async device prefetch, and
``Model.prepare(opt, loss=None)`` so the packed dict batches flow into
``LlamaForCausalLM`` as kwargs. ``FitResilience(pipeline=…)`` makes the
run preemption-safe with exactly-once data. Run:
    python examples/train_packed.py
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.data import DataPipeline
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


class Corpus:
    """Synthetic documents of 8..48 tokens (a stand-in for tokenized
    text shards); deterministic per index, so any restart replays it."""

    def __init__(self, n=96, vocab=256):
        self.n, self.vocab = n, vocab

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.RandomState(1000 + i)
        return rng.randint(1, self.vocab, rng.randint(8, 49)).astype(
            np.int32)


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    net = LlamaForCausalLM(cfg)
    model = paddle.hapi.Model(net)
    model.prepare(
        paddle.optimizer.AdamW(learning_rate=3e-3,
                               parameters=net.parameters(),
                               grad_clip=nn.ClipGradByGlobalNorm(1.0)),
        loss=None)  # the network computes its own causal-LM loss

    pipeline = DataPipeline(
        Corpus(vocab=cfg.vocab_size), batch_size=2, seq_len=128,
        pack=True, base_seed=7, shuffle=True, drop_last=True,
        device_prefetch=2)

    model.fit(pipeline, epochs=2, verbose=1, log_freq=5)
    eff = pipeline.packer.efficiency_stats()
    print(f"packed {pipeline.step} batches, "
          f"mean packing efficiency {eff['mean']:.1%}")


if __name__ == "__main__":
    main()
