"""Mamba-1's selective scan over a serving step's ragged rows
(``ops/pallas/selective_scan.py``): the kernel in interpret mode against
the XLA scan it stands beside (decode rows and several-row chunks of fresh
and of continuing sequences, ragged entries over more than one 128-token
window of ``B`` and ``C``, channels over several grid blocks, the state
held in float32 and in bfloat16; rows that no entry names keep their
bytes), a state carried across two steps equal to one step over both, and
what the kernel compiles to for a described TPU v5e at
Phi-4-mini-flash's widths (nothing runs there)."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import selective_scan as sscan


def _case(T, R, entries, dtype, D=64, N=16, seed=0):
    """Inputs for ``entries`` ``[(row, rows, fresh)]`` packed in order
    from token 0, two padding entries after them; the pool ``[R, N, D]``."""
    rng = np.random.default_rng(seed)
    E = len(entries) + 2
    lists = np.zeros((4, E), np.int32)
    off = 0
    for i, (row, n, fresh) in enumerate(entries):
        lists[:, i] = (row, off, n, fresh)
        off += n
    dt = rng.uniform(1e-3, 0.2, (T, D))
    return (jnp.asarray(rng.normal(size=(R, N, D)), dtype),
            jnp.asarray(dt * rng.normal(size=(T, D)), jnp.float32),
            jnp.asarray(dt, jnp.float32),
            jnp.asarray(-np.tile(np.arange(1, N + 1)[:, None], (1, D))
                        * rng.uniform(0.5, 1.5, (N, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
            jnp.asarray(rng.normal(size=(T, N)), jnp.float32),
            *(jnp.asarray(v) for v in lists), len(entries))


@pytest.mark.parametrize("T, entries, dtype, widths", [
    # decode rows of continuing and fresh sequences beside two chunks
    (20, [(1, 1, 0), (3, 1, 1), (2, 9, 0), (5, 7, 1)], jnp.float32, {}),
    # a chunk of 140 across two 128-token windows, a fresh chunk, decode
    # rows on both sides, the pool in bf16
    (200, [(1, 1, 0), (4, 1, 0), (2, 140, 0), (5, 40, 1), (3, 1, 1)],
     jnp.bfloat16, {}),
    # decode rows alone over 1,280 channels: the kernel's channel blocks
    (8, [(2, 1, 0), (5, 1, 1), (1, 1, 0), (6, 1, 0), (4, 1, 1)],
     jnp.bfloat16, dict(D=1280)),
], ids=["rows-and-chunks", "long-chunk-bf16", "decode-rows-bf16"])
def test_the_kernel_is_the_scan(monkeypatch, T, entries, dtype, widths):
    args = _case(T, 7, entries, dtype, **widths)
    monkeypatch.setattr(sscan, "_mode", lambda: "xla")
    y0, s0 = sscan.selective_scan_update(*args)
    monkeypatch.setattr(sscan, "_mode", lambda: "interpret")
    y1, s1 = sscan.selective_scan_update(*args)
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


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_a_state_carried_across_steps_is_one_scan(monkeypatch, mode):
    """A fresh chunk of 11 rows then, next step, a chunk of 5 and a decode
    row of another slot: the second step's ``y`` is what one step over all
    16 rows gives there."""
    monkeypatch.setattr(sscan, "_mode", lambda: mode)
    state, u, dt, A, B, C = _case(16, 4, [(2, 16, 1)], jnp.float32)[:6]

    def lists(*entries):
        E = len(entries) + 1
        out = np.zeros((4, E), np.int32)
        off = 0
        for i, (row, n, fresh) in enumerate(entries):
            out[:, i] = (row, off, n, fresh)
            off += n
        return [jnp.asarray(v) for v in out] + [len(entries)]

    whole, _ = sscan.selective_scan_update(state, u, dt, A, B, C,
                                           *lists((2, 16, 1)))
    _, mid = sscan.selective_scan_update(state, u[:11], dt[:11], A, B[:11],
                                         C[:11], *lists((2, 11, 1)))
    y, _ = sscan.selective_scan_update(
        mid, u[11:], dt[11:], A, B[11:], C[11:],
        *lists((2, 5, 0), (3, 0, 0)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole[11:]),
                               atol=1e-5 * float(jnp.abs(whole).max()))


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
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(sscan, "_mode", lambda: "mosaic")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_scan_compiles_at_phi4_flash_widths(one_chip, mosaic):
    """5,120 channels of 16 states, a bf16 pool of 129 rows ``[R, N, D]``,
    a step of 640 rows: one Mosaic call named as
    ``benchmark/kernels/selective_scan.py`` finds it, the pool updated in
    place."""
    from benchmark import xplane
    from benchmark.kernels import selective_scan as yardstick
    D, N, T, R, E = 5120, 16, 640, 129, 128

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32, f32 = jnp.int32, jnp.float32
    compiled = jax.jit(sscan.selective_scan_update, donate_argnums=0).lower(
        arr((R, N, D), jnp.bfloat16), arr((T, D), f32), arr((T, D), f32),
        arr((N, D), f32), arr((T, N), f32), arr((T, N), f32),
        arr((E,), i32), arr((E,), i32), arr((E,), i32), arr((E,), i32),
        arr((), i32)).compile()
    names = [xplane.short_name(re.sub(r"^(ROOT )?", "", line.strip()))
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [n.split(".")[0] for n in names] == ["selective_scan"], names
    assert re.search(yardstick.TRACE_PATTERN, names[0] + " custom-call")
    assert compiled.memory_analysis().alias_size_in_bytes >= R * N * D * 2
