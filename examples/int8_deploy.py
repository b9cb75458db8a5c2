"""Calibrate -> convert -> REAL int8 execution -> export.

The PTQ pipeline observes activation ranges on calibration batches,
``convert`` bakes fake-quant scales, and ``convert_to_int8`` rewrites the
model for true int8 compute (XLA's s8 x s8 -> s32 dot — 2x the bf16 MXU
rate on v5e, 4x smaller weights). Run:
    JAX_PLATFORMS=cpu python examples/int8_deploy.py
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.quantization import (
    AbsmaxObserver, FakeQuanterWithAbsMaxObserver, PTQ, QuantConfig,
    convert_to_int8,
)


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    paddle.seed(0)
    rng = np.random.RandomState(0)
    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 4))
    model.eval()

    # 1) observe activation ranges on calibration data
    ptq = PTQ(QuantConfig(activation=AbsmaxObserver(),
                          weight=FakeQuanterWithAbsMaxObserver()))
    observed = ptq.quantize(model)
    for _ in range(8):
        observed(paddle.to_tensor(rng.randn(32, 16).astype(np.float32)))

    # 2) bake scales (fake-quant simulation), then go REAL int8
    deployed = ptq.convert(observed)
    int8_model = convert_to_int8(deployed)

    x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
    fp32 = model(x).numpy()
    sim = deployed(x).numpy()
    int8 = int8_model(x).numpy()
    print("fp32 vs int8 mean |err|:", float(np.abs(fp32 - int8).mean()))
    print("simulation vs int8 match:",
          bool(np.allclose(sim, int8, atol=1e-5)))
    print("int8 weight dtype:", int8_model[0].w_q.data.dtype)

    # 3) the int8 model exports like any Layer (weights become int8
    # constants in the saved program)
    import tempfile
    with tempfile.TemporaryDirectory(prefix="int8_deploy_") as tmp:
        path = tmp + "/int8_model"
        paddle.jit.save(int8_model, path,
                        input_spec=[paddle.static.InputSpec([8, 16],
                                                            "float32")])
        served = paddle.jit.load(path)
        print("served == int8:",
              bool(np.allclose(served(x).numpy(), int8, atol=1e-6)))


if __name__ == "__main__":
    main()
