"""Train a tiny LM on a repeating pattern, then sample from it with the
KV-cached generate(). Run:
    python examples/generate_llama.py
"""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, vocab_size=16)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=5e-3,
                                 parameters=model.parameters())
    pattern = np.tile(np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int64), 4)
    ids = paddle.to_tensor(pattern[None, :])
    for _ in range(150):
        _, loss = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
    model.eval()
    prompt = paddle.to_tensor(pattern[None, :8])
    out = model.generate(prompt, max_new_tokens=8, temperature=0)
    print("prompt   :", pattern[:8].tolist())
    print("generated:", np.asarray(out.data)[0, 8:].tolist())


if __name__ == "__main__":
    main()
