"""Test config: force an 8-virtual-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4) with the TPU-build
improvement called out there: SPMD code paths are testable single-process on a
virtual host mesh, which the reference (needing 2 real GPUs + NCCL subprocess
spawning) cannot do.

Unit tests never touch a chip: ``JAX_PLATFORMS=cpu`` and the virtual-device
flag are set before jax is imported. Pallas kernels run in interpret mode
here; a CPU run checks values and counts, never a time or a rate.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert jax.default_backend() == "cpu", "tests must run on the CPU platform"
assert jax.device_count() == 8, "tests expect an 8-device virtual mesh"


@pytest.fixture(autouse=True)
def _seed_rng():
    import paddle_tpu as pt
    pt.seed(2024)
    np.random.seed(2024)
    # exact f32 matmuls for numeric oracles (TPU runs keep the bf16 MXU default)
    pt.set_flags({"matmul_precision": "highest"})
    yield
