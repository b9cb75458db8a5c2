"""K/V pools of head width 64, half the lanes, stored two tokens a row
(``ops/paged_attention.tokens_a_row``): the flat write puts a token's row
where the unpacked pool has it, the RPA kernel in interpret mode reads the
packed pages as the gather reader reads the unpacked ones (a chunk and
decode rows, a causal walk and a window walk), and the kernel compiles
for a described TPU v5e with no copy of the pool around it (nothing runs
there)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    build_step_maps, ragged_paged_attention, rpa_max_items, rpa_run_pages)

BS, NB, MAXB, KV, H, HD = 8, 12, 6, 2, 4, 64


def _step(seed=0):
    """Three sequences' rows: a chunk of 11 at context 9, decode rows at
    contexts 20 and 3; their pages and the written pools, unpacked and
    packed."""
    rng = np.random.default_rng(seed)
    rows = [(11, 9), (1, 20), (1, 3)]
    T = 16
    tables = np.zeros((4, MAXB), np.int32)
    nxt = 1
    for s, (n, ctx) in enumerate(rows):
        pages = -(-(n + ctx) // BS)
        tables[s, :pages] = np.arange(nxt, nxt + pages)
        nxt += pages
    sid = np.full((T,), 3, np.int32)
    pos = np.zeros((T,), np.int32)
    cu = np.zeros((5,), np.int32)
    off = 0
    for s, (n, ctx) in enumerate(rows):
        sid[off:off + n] = s
        pos[off:off + n] = ctx + np.arange(n)
        cu[s + 1] = off + n
        off += n
    cu[len(rows) + 1:] = off
    ctxs = np.array([c for _, c in rows] + [0], np.int32)
    # every earlier key of each sequence, then this step's
    hist = [(s, p) for s, (n, ctx) in enumerate(rows) for p in range(ctx)]
    hsid = np.array([h[0] for h in hist], np.int32)
    hpos = np.array([h[1] for h in hist], np.int32)
    kv = lambda n: jnp.asarray(rng.normal(size=(n, KV, HD)), jnp.bfloat16)  # noqa
    out = {}
    for packed in (False, True):
        pack = 2 if packed else 1
        pools = [jnp.zeros((NB, KV, BS // pack, HD * pack), jnp.bfloat16)
                 for _ in range(2)]
        out[packed] = pools
    rng_state = rng.bit_generator.state
    write = jax.jit(pa.write_tokens_to_pool)
    for packed, pools in out.items():
        rng.bit_generator.state = rng_state
        hk, hv, k, v = kv(len(hist)), kv(len(hist)), kv(T), kv(T)
        for i, (new_h, new) in enumerate(((hk, k), (hv, v))):
            p = write(pools[i], new_h, tables, hsid, hpos)
            pools[i] = write(p, new, tables, sid, pos)
    q = jnp.asarray(rng.normal(size=(T, H, HD)), jnp.bfloat16)
    return q, out, tables, cu, ctxs, sid, pos


@pytest.fixture(scope="module")
def step():
    return _step(1)


def test_a_packed_pool_holds_what_the_unpacked_one_does(step):
    _, pools, *_ = step
    for a, b in zip(pools[False], pools[True]):
        assert b.shape == (NB, KV, BS // 2, 128)
        np.testing.assert_array_equal(np.asarray(pa.unpack_rows(b, HD)),
                                      np.asarray(a))


@pytest.mark.parametrize("window", [None, 7])
def test_the_kernel_reads_packed_pages_as_the_gather_reads_unpacked(
        step, window):
    q, pools, tables, cu, ctxs, sid, pos = step
    want = pa.ragged_gather_attention(q, *pools[False], tables, sid, pos,
                                      scale=0.125, window=window)
    run = rpa_run_pages(BS, HD, HD, 2, window=window)
    kv_lens = [int(c) + n for c, n in zip(ctxs[:3], np.diff(cu[:4]))]
    maps = build_step_maps(cu[:4], kv_lens, total_tokens=16, tile_q=8,
                           block_size=BS, max_seqs=3, run_pages=run,
                           window=window,
                           max_items=rpa_max_items(2, 3, MAXB, run,
                                                   window=window, tile_q=8,
                                                   block_size=BS))
    got = ragged_paged_attention(q, *pools[True], tables, cu, ctxs,
                                 maps.step_seq, maps.step_blk,
                                 maps.step_tile, sm_scale=0.125,
                                 window=window)
    live = np.asarray(sid) < 3
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=2e-2, rtol=2e-2)


def test_the_packed_read_compiles_with_no_copy_of_the_pool():
    """A step's write and the kernel's read over a bf16 pool of 1,409
    pages of 128, 8 heads of 64: nothing of the pool's size is copied."""
    import importlib
    import re

    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    kernel = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    real = kernel._interpret
    kernel._interpret = lambda: False
    try:
        T, S, nblk, tile = 640, 128, 32, 8
        run = rpa_run_pages(128, 64, 64, 2)
        maps = build_step_maps([0, 1], [1], total_tokens=T, tile_q=tile,
                               block_size=128, max_seqs=S, run_pages=run,
                               max_items=rpa_max_items(T // tile, S, nblk,
                                                       run, tile_q=tile,
                                                       block_size=128))

        def step(q, k, v, kp, vp, bt, cu, ctx, sid, pos, *m):
            kp = pa.write_tokens_to_pool(kp, k, bt, sid, pos)
            vp = pa.write_tokens_to_pool(vp, v, bt, sid, pos)
            return ragged_paged_attention(q, kp, vp, bt, cu, ctx, *m,
                                          sm_scale=0.015625), kp, vp

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        i32 = jnp.int32
        pool = arr((1409, 8, 64, 128), jnp.bfloat16)
        compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
            arr((T, 32, 64), jnp.bfloat16), arr((T, 8, 64), jnp.bfloat16),
            arr((T, 8, 64), jnp.bfloat16), pool, pool,
            arr((S + 1, nblk), i32), arr((S + 2,), i32), arr((S + 1,), i32),
            arr((T,), i32), arr((T,), i32),
            arr(maps.step_seq.shape, i32), arr(maps.step_blk.shape, i32),
            arr(maps.step_tile.shape, i32)).compile()
    finally:
        kernel._interpret = real
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # of the pool's size only the two writes' scatters (in place), no
    # copy and no other layout; a write is one scatter of whole rows, not
    # a loop of updates
    big = [ln for ln in text.splitlines()
           if re.search(r"= bf16\[(1409,8,64,128|1442816|721408)", ln)
           and re.search(r" (copy|transpose|fusion)\(", ln)]
    assert len(big) == 2 and all("/scatter" in ln for ln in big), big[:3]
    assert " while(" not in text and "dynamic-update-slice(" not in text
