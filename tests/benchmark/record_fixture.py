"""Record the small trace kept as ``fixtures/tiny_tpu.xplane.pb`` (run once
on the chip; the tests only read the file):

    chiprun -- python3 tests/benchmark/record_fixture.py chiprun_out/fixture

Three rounds of four chained 1024^3 bf16 matmuls and a reduction, each
round inside a ``TraceAnnotation`` and followed by a 3 ms sleep on the
host, so the trace has ops of two kinds, nested module spans, and idle gaps
with a named host activity.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir):
    @jax.jit
    def chain(x):
        for _ in range(4):
            x = jnp.dot(x, x) * 0.01
        return jnp.sum(x.astype(jnp.float32))

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    chain(x).block_until_ready()
    tmp = os.path.join(out_dir, "_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("fixture_round", round=i):
            chain(x).block_until_ready()
        with jax.profiler.TraceAnnotation("fixture_sleep"):
            time.sleep(0.003)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out_dir, "tiny_tpu.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "tiny_tpu.xplane.pb")), "bytes")


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
