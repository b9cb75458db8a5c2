"""Export the flagship model as an XLA artifact and serve it with the
inference Predictor (the TensorRT/ONNX-engine analog; for the
continuous-batching request runtime see serve_llama.py). Run:
    python examples/export_and_serve.py
"""
import numpy as np

import paddle_tpu as paddle
from _common import build_tiny_llama
from paddle_tpu.inference import Config, Predictor
from paddle_tpu.static import InputSpec


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    import os
    import tempfile
    model = build_tiny_llama(seed=0, num_hidden_layers=1)
    with tempfile.TemporaryDirectory(prefix="llama_serving_") as tmp:
        path = os.path.join(tmp, "model")
        paddle.jit.save(model, path,
                        input_spec=[InputSpec([2, 16], "int32")])
        print("exported to", path)

        predictor = Predictor(Config(path))
        ids = np.random.RandomState(0).randint(0, 256, (2, 16)) \
            .astype(np.int32)
        (logits,) = predictor.run([ids])
        print("served logits:", np.asarray(logits).shape)


if __name__ == "__main__":
    main()
