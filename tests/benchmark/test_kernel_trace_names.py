"""The names under which a profiler trace shows the Pallas kernels, read off
programs compiled for a described TPU v5e (nothing runs; the
``on-chip-measurement`` guide, section 2): the RPA kernel at the serving
cell's shapes must read ``rpa.N custom-call``, the flash forward and both
backward kernels at the training cell's must read as
``kernels/flash.py:TRACE_PATTERN`` expects. A rename in the program that
would silence ``rpa_roofline`` or ``flash_roofline`` fails here, with no
chip.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's library."""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import harness, xplane
from benchmark import weights as W
from benchmark.kernels import flash as flash_model
from benchmark.kernels import rpa as rpa_model

MOSAIC = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def mosaic(monkeypatch):
    """The kernels ask ``jax.default_backend()`` and would take interpret
    mode on this CPU; compile them as the chip gets them. The persistent
    compile cache cannot read such an entry back: off for the test."""
    from jax.experimental.compilation_cache import compilation_cache
    for kernel in ("flash_attention", "ragged_paged_attention"):
        # (the package exports functions under the modules' names)
        monkeypatch.setattr(
            importlib.import_module("paddle_tpu.ops.pallas." + kernel),
            "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def mosaic_names(hlo_text):
    """Each Mosaic call of a compiled program as a trace names it
    (``xplane.short_name`` of the HLO instruction)."""
    return [xplane.short_name(re.sub(r"^(ROOT )?", "", line.strip()))
            for line in hlo_text.splitlines() if MOSAIC in line]


def test_rpa_kernel_is_named_rpa_at_the_serving_cells_shapes(one_chip, mosaic):
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        default_tile_q, ragged_paged_attention, rpa_max_steps)
    cfg = harness.load_json(harness.HERE, "configs",
                            "mistral-7b-v0.3-serve-l16.json")
    z, eng = W.sizes(cfg), cfg["engine"]
    tile = default_tile_q(z["heads"] // z["kv"], jnp.bfloat16)
    tokens = eng["max_batch"] + eng["prefill_chunk"]
    steps = rpa_max_steps(tile, eng["max_blocks_per_seq"], eng["max_blocks"])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arr((eng["max_blocks"] + 1, z["kv"], eng["block_size"], z["hd"]),
               jnp.bfloat16)
    seqs = eng["max_batch"] + 1

    def step(q, k_pool, v_pool, bt, cu, ctx, ssq, sbk):
        # a jitted function named as the engine's: without a name of its
        # own the kernel's op would read ``step.N``
        return ragged_paged_attention(q, k_pool, v_pool, bt, cu, ctx, ssq, sbk)

    compiled = jax.jit(step).lower(
        arr((tokens, z["heads"], z["hd"]), jnp.bfloat16), pool, pool,
        arr((seqs, eng["max_blocks_per_seq"]), jnp.int32),
        arr((seqs + 1,), jnp.int32), arr((seqs,), jnp.int32),
        arr((tokens // tile, steps), jnp.int32),
        arr((tokens // tile, steps), jnp.int32)).compile()
    names = mosaic_names(compiled.as_text())
    assert len(names) == 1, names
    assert re.search(rpa_model.TRACE_PATTERN, names[0]), names
    assert names[0].startswith("rpa"), names


def test_flash_kernels_keep_the_names_the_benchmark_reads(one_chip, mosaic):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bhsd
    cfg = harness.load_json(harness.HERE, "configs",
                            "mistral-7b-v0.3-train-l2.json")
    mix = harness.load_json(harness.HERE, "traffic", "train-4k.json")
    z = W.sizes(cfg)

    def arr(heads):
        return jax.ShapeDtypeStruct(
            (int(mix["batch"]), heads, int(mix["seq_len"]), z["hd"]),
            jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention_bhsd(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arr(z["heads"]), arr(z["kv"]), arr(z["kv"])).compile()
    names = mosaic_names(compiled.as_text())
    assert len(names) == 3, names          # forward, dq, dk/dv
    for name in names:
        assert re.search(flash_model.TRACE_PATTERN, name), names
    assert sum(n.startswith("transpose_") for n in names) == 2, names
