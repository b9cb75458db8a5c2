"""Multi-host-capable pipeline parallelism: the whole interleaved
schedule compiled into ONE program (stage hops are lax.ppermute
collectives — the same program runs across hosts on a pod).

On CPU this runs on 8 virtual devices. Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pipeline_spmd.py
"""
import jax
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.distributed.fleet as fleet
import paddle_tpu.nn as nn


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    n = len(jax.devices())
    pp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    mesh = dist.init_mesh({"dp": n // pp, "pp": pp})
    print(f"mesh: dp={n // pp} pp={pp}")

    paddle.seed(0)

    def block():  # one homogeneous trunk chunk per (stage, virtual stage)
        return nn.Sequential(nn.Linear(32, 32), nn.Tanh())

    pipe = fleet.SpmdPipelineLayer(block, num_virtual_stages=2, mesh=mesh,
                                   loss_fn=nn.MSELoss())
    engine = fleet.SpmdPipelineParallel(pipe, accumulate_steps=2 * pp)
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=engine.parameters())

    rng = np.random.RandomState(0)
    X = paddle.to_tensor(rng.randn(4 * pp, 32).astype(np.float32))
    Y = paddle.to_tensor((rng.randn(4 * pp, 32) * 0.1).astype(np.float32))
    for step in range(10):
        loss = engine.train_batch((X, Y), opt)
        if step % 3 == 0:
            stats = engine.last_schedule_stats
            print(f"step {step}: loss {float(loss.numpy()):.4f} "
                  f"(bubble {stats['bubble_fraction']}, "
                  f"{stats['n_chunks']} chunks)")
    print("done — every stage hop was a compiled collective-permute")


if __name__ == "__main__":
    main()
