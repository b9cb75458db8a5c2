"""The classic paddle static-graph workflow (Program/Executor). Run:
    python examples/static_regression.py
"""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import static


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    paddle.enable_static()
    rng = np.random.RandomState(0)
    w_true = rng.randn(13, 1).astype(np.float32)

    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [None, 13], "float32")
        y = static.data("y", [None, 1], "float32")
        pred = static.nn.fc(x, size=1)
        loss = paddle.ops.mean(paddle.ops.square(
            paddle.ops.subtract(pred, y)))
        paddle.optimizer.SGD(learning_rate=0.1).minimize(loss)

    exe = static.Executor()
    exe.run(static.default_startup_program())
    for it in range(50):
        xb = rng.randn(64, 13).astype(np.float32)
        (lv,) = exe.run(prog, feed={"x": xb, "y": xb @ w_true},
                        fetch_list=[loss])
        if it % 10 == 0:
            print(f"step {it}: loss {float(lv):.5f}")
    paddle.disable_static()


if __name__ == "__main__":
    main()
