"""The Mamba-2 state update over a serving step's ragged rows
(``ops/pallas/ssd.py``): the kernel in interpret mode against the XLA
scan it stands beside (decode rows and several-row chunks of fresh and of
continuing sequences, a chunk longer than the kernel's 128-row window, the
state held in float32 and in bfloat16; rows that no entry names keep their
bytes), and what the kernels of a served hybrid model compile to for a
described TPU v5e at its published widths (nothing runs there)."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import ssd


def _case(T, R, entries, dtype, H=4, P=8, N=16, seed=0):
    """Inputs for ``entries`` ``[(row, rows, fresh)]`` packed in order
    from token 0, two padding entries after them."""
    rng = np.random.default_rng(seed)
    E = len(entries) + 2
    lists = np.zeros((4, E), np.int32)
    off = 0
    for i, (row, n, fresh) in enumerate(entries):
        lists[:, i] = (row, off, n, fresh)
        off += n
    return (jnp.asarray(rng.normal(size=(R, H, P, N)), dtype),
            jnp.asarray(0.3 * rng.normal(size=(T, H, P)), jnp.float32),
            jnp.asarray(-rng.uniform(0.01, 1.0, (T, H)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
            *(jnp.asarray(v) for v in lists), len(entries))


@pytest.mark.parametrize("T, entries, dtype", [
    # decode rows of continuing and fresh sequences beside two chunks
    (20, [(1, 1, 0), (3, 1, 1), (2, 9, 0), (5, 7, 1)], jnp.float32),
    # a chunk of 140 over the 128-row window (the window clamped at the
    # step's end), a fresh chunk, decode rows on both sides
    (200, [(1, 1, 0), (4, 1, 0), (2, 140, 0), (5, 40, 1), (3, 1, 1)],
     jnp.bfloat16),
], ids=["rows-and-chunks", "long-chunk-bf16"])
def test_the_kernel_is_the_scan(monkeypatch, T, entries, dtype):
    args = _case(T, 7, entries, dtype)
    monkeypatch.setattr(ssd, "_mode", lambda: "xla")
    y0, s0 = ssd.ssd_update(*args)
    monkeypatch.setattr(ssd, "_mode", lambda: "interpret")
    y1, s1 = ssd.ssd_update(*args)
    tol = 1e-5 * float(jnp.abs(y0).max())
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=tol)
    np.testing.assert_allclose(np.asarray(s1, np.float32),
                               np.asarray(s0, np.float32),
                               atol=1e-5 if dtype == jnp.float32 else 2e-2)
    named = {row for row, _, _ in entries}
    for row in range(7):
        if row not in named:           # the null row 0 and unused slots
            np.testing.assert_array_equal(np.asarray(s1[row]),
                                          np.asarray(args[0][row]))
    # rows of the step that no live entry owns read 0
    end = sum(n for _, n, _ in entries)
    assert not np.asarray(y1[end:]).any()


# ------------------------------------------------ on a described chip --
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def mosaic(monkeypatch):
    """Compile as the chip gets it, with the persistent compile cache off
    (it cannot read such an entry back)."""
    import importlib

    from jax.experimental.compilation_cache import compilation_cache
    # the module (the package's name of the same spelling is the function)
    rpa = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    monkeypatch.setattr(ssd, "_mode", lambda: "mosaic")
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_names(compiled):
    from benchmark import xplane
    return [xplane.short_name(re.sub(r"^(ROOT )?", "", line.strip()))
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def test_the_state_update_compiles_at_granite_micro_widths(one_chip, mosaic):
    """64 heads of 64 x 128, a bf16 pool of 129 rows, a step of 640 rows:
    one Mosaic call named as ``benchmark/kernels/ssd.py`` finds it, the
    pool updated in place."""
    from benchmark.kernels import ssd as yardstick
    H, P, N, T, R, E = 64, 64, 128, 640, 129, 128

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32 = jnp.int32
    compiled = jax.jit(ssd.ssd_update, donate_argnums=0).lower(
        arr((R, H, P, N), jnp.bfloat16), arr((T, H, P), jnp.float32),
        arr((T, H), jnp.float32), arr((T, N), jnp.float32),
        arr((T, N), jnp.float32), arr((E,), i32), arr((E,), i32),
        arr((E,), i32), arr((E,), i32), arr((), i32)).compile()
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == ["ssd"], names
    assert re.search(yardstick.TRACE_PATTERN, names[0] + " custom-call")
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= R * H * P * N * 2


def test_attention_at_head_width_64_compiles(one_chip, mosaic):
    """The RPA kernel over Granite's attention pages: 32 query heads on 8
    KV heads of width 64 (half a lane tile), pages of 128."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        build_step_maps, ragged_paged_attention, rpa_max_items,
        rpa_run_pages)
    T, S, nblk, tile = 640, 128, 32, 8
    run = rpa_run_pages(128, 64, 64, 2)
    maps = build_step_maps([0, 1], [1], total_tokens=T, tile_q=tile,
                           block_size=128, max_seqs=S, run_pages=run,
                           max_items=rpa_max_items(T // tile, S, nblk, run,
                                                   tile_q=tile,
                                                   block_size=128))

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32 = jnp.int32
    pool = arr((1409, 8, 128, 64), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, *m: ragged_paged_attention(
        q, k, v, *m, sm_scale=0.015625)).lower(
        arr((T, 32, 64), jnp.bfloat16), pool, pool,
        arr((S + 1, nblk), i32), arr((S + 2,), i32), arr((S + 1,), i32),
        arr(maps.step_seq.shape, i32), arr(maps.step_blk.shape, i32),
        arr(maps.step_tile.shape, i32)).compile()
    assert [n.split(".")[0] for n in _kernel_names(compiled)] == ["rpa"]
