"""Train a (tiny) Llama on synthetic data with the compiled TrainStep.

Scale up by swapping LlamaConfig.tiny() for LlamaConfig.llama3_8b() and
adding a mesh (see train_llama_spmd.py). Run:
    python examples/train_llama.py
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=3e-3, parameters=model.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(1.0))

    def loss_fn(m, ids, labels):
        _, loss = m(ids, labels=labels)
        return loss

    step = paddle.jit.TrainStep(model, loss_fn, opt)  # one XLA program
    rng = np.random.RandomState(0)
    data = (np.arange(64 * 32).reshape(64, 32) % 97).astype(np.int32)
    for it in range(30):
        batch = paddle.to_tensor(data[rng.randint(0, 64, 8)])
        loss = step(batch, batch)
        if it % 10 == 0:
            print(f"step {it}: loss {float(loss.numpy()):.4f}")
    print("done; final loss", float(loss.numpy()))


if __name__ == "__main__":
    main()
