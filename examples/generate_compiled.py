"""Whole-loop compiled generation: prefill + every decode step in ONE
XLA program over static KV buffers (decode rate on the chip: not measured
on today's installation). Run:
    JAX_PLATFORMS=cpu python examples/generate_compiled.py
"""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()

    prompt = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 256, (2, 12)).astype(np.int64))

    eager = model.generate(prompt, max_new_tokens=16, temperature=0.0)
    compiled = model.generate_compiled(prompt, max_new_tokens=16,
                                       temperature=0.0)
    same = bool((eager.numpy() == compiled.numpy()).all())
    print("greedy compiled == eager token-for-token:", same)

    # second call with the same signature reuses the compiled executable
    again = model.generate_compiled(prompt, max_new_tokens=16,
                                    temperature=0.0)
    print("deterministic:", bool((again.numpy() == compiled.numpy()).all()))
    print("generated shape:", compiled.numpy().shape,
          "(prompt 12 + 16 new)")

    # sampled decoding threads an explicit RNG split chain inside the
    # compiled loop
    sampled = model.generate_compiled(prompt, max_new_tokens=8,
                                      temperature=0.8, top_k=20)
    print("sampled tail:", sampled.numpy()[0, -8:].tolist())


if __name__ == "__main__":
    main()
