"""Record the small trace kept as ``fixtures/tiny_engine.xplane.pb.gz`` (run
once on the chip; the tests only read the file, unzipped):

    chiprun -- python3 tests/benchmark/record_engine_fixture.py chiprun_out/fixture

A one-layer model of 512 hidden (4 query heads over 1 KV head of 128, the
serving cell's page of 128 tokens and 128-token step) behind a
``ServingEngine``, warmed by one request, then traced through two requests
to idle: a few steps with prefill chunks, decode rows and both in one step
(the file has to stay under 200 KB; it holds the whole HLO text of every
distinct device op, 360 KB for this one-layer step, so it is kept zipped).
The engine's own leaves (``serving.*``) are in the host plane, its programs
in the device plane.
"""
import glob
import gzip
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import numpy as np

CFG = dict(hidden_size=512, intermediate_size=1024, num_hidden_layers=1,
           num_attention_heads=4, num_key_value_heads=1, head_dim=128,
           vocab_size=2048, rope_theta=1e6, rms_norm_eps=1e-5,
           max_position_embeddings=1024, dtype="bfloat16",
           engine=dict(max_batch=4, max_blocks=32, block_size=128,
                       prefill_chunk=124, max_blocks_per_seq=8))
#: (prompt tokens, answer tokens): the first spans two prefill chunks
REQUESTS = [(150, 2), (20, 3)]
NAME = "tiny_engine.xplane.pb.gz"


def main(out_dir):
    from benchmark import sut
    engine = sut.build_engine(CFG, 24)
    rng = np.random.default_rng(24)
    engine.submit(rng.integers(1, 2048, 30).tolist(), max_new_tokens=2)
    engine.run_until_idle()                       # compiles the one step
    prompts = [rng.integers(1, 2048, n).tolist() for n, _ in REQUESTS]
    tmp = os.path.join(out_dir, "_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for tokens, (_, answer) in zip(prompts, REQUESTS):
        engine.submit(tokens, max_new_tokens=answer)
    engine.run_until_idle()
    jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    with open(src, "rb") as f, \
            gzip.open(os.path.join(out_dir, NAME), "wb", 9) as out:
        shutil.copyfileobj(f, out)
    shutil.rmtree(tmp)
    print(engine._decode_steps, "steps in all;",
          os.path.getsize(os.path.join(out_dir, NAME)), "bytes")


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
