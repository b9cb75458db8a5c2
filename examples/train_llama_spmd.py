"""The same training step SPMD over a device mesh (dp x mp).

On a TPU pod this uses the real chips; on CPU it runs on 8 virtual
devices. Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_llama_spmd.py
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.distributed import P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    n = len(__import__("jax").devices())
    mp = 2 if n % 2 == 0 else 1
    mesh = dist.init_mesh({"dp": n // mp, "mp": mp})
    print(f"mesh: dp={n // mp} mp={mp}")

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=(mp > 1)))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def loss_fn(m, ids, labels):
        _, loss = m(ids, labels=labels)
        return loss

    step = paddle.jit.TrainStep(model, loss_fn, opt, mesh=mesh,
                                input_spec=P("dp"))
    rng = np.random.RandomState(0)
    batch = (rng.randint(0, 256, ((n // mp) * 2, 16))).astype(np.int32)
    for it in range(5):
        loss = step(paddle.to_tensor(batch), paddle.to_tensor(batch))
        print(f"step {it}: loss {float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
