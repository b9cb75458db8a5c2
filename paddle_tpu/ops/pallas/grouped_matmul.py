"""Grouped matrix multiply (MoE expert GEMM) as a Pallas TPU kernel.

The TPU answer to the reference's cutlass grouped GEMM
(``paddle/phi/kernels/fusion/cutlass/moe/moe_kernel.cu``): tokens arrive
SORTED by expert, ``group_sizes[e]`` rows belong to expert ``e``, and one
kernel computes ``out[rows_e] = lhs[rows_e] @ rhs[e]`` for every expert —
compute scales with the ACTUAL token count (plus at most one partial tile
per expert boundary), not with the padded ``E * capacity`` slot count the
einsum formulation pays, and the expert selection happens in the kernel's
index maps (scalar-prefetched metadata) instead of a materialized
one-hot/dispatch tensor.

Design (the megablocks/gmm recipe, grid over row-block x expert tiles):

* metadata — for each row block ``b`` (``bm`` rows) the experts whose row
  ranges intersect it; a tile ``t = (b, e)`` multiplies the block's rows
  masked to ``[offsets[e], offsets[e+1])`` by ``rhs[e]`` and accumulates
  into out-block ``b``. Tiles are ordered block-major so revisits of an
  output block are consecutive (the Pallas accumulation pattern); there
  are at most ``n_blocks + E`` tiles, a static bound.
* the transposed variant ``tgmm`` (``out[e] = lhs[rows_e].T @ g[rows_e]``,
  the d_rhs of autodiff) runs the same tiles EXPERT-major, accumulating
  into out-block ``e``; empty experts get one zeroing tile.
* backward: d_lhs is ``gmm`` with per-expert transposed rhs; d_rhs is
  ``tgmm`` — both exact, wired through ``custom_vjp``.

Off-TPU both kernels run in Pallas interpret mode (tests on the CPU
mesh); on chip, ``bm`` rows x full-width weights double-buffer in VMEM.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gmm", "tgmm", "gmm_aligned"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _metadata(offsets_ext, n_blocks: int, n_groups: int, bm: int,
              expert_major: bool):
    """Static-size tile metadata from the (traced) group offsets.

    ``offsets_ext`` [n_groups + 2]: 0, cumsum(group_sizes), R_pad — the
    last entry closes the sentinel pad group. Returns int32 arrays of
    length ``n_tiles = n_blocks + n_groups + 1``:

      block_ids[t], group_ids[t] — the (row-block, group) pair,
      flags[t] — bit0 valid, bit1 first-visit-of-output-block.

    Invalid (padding) tiles point at the last real tile's output block
    with bit0 clear: the kernel adds nothing and never re-zeroes.
    ``expert_major`` orders tiles (e, b) for tgmm — where every REAL group
    additionally owns at least one tile (empty experts must still zero
    their output block).
    """
    G1 = n_groups + 1          # + sentinel pad group
    starts = offsets_ext[:-1]  # [G1]
    ends = offsets_ext[1:]
    bs = jnp.arange(n_blocks, dtype=jnp.int32) * bm
    inter = (starts[None, :] < bs[:, None] + bm) & \
        (ends[None, :] > bs[:, None])           # [n_blocks, G1]
    if expert_major:
        # the output blocks are the E real groups: exclude sentinel tiles
        # (they would index out[E]); ensure every real group — including
        # EMPTY ones — owns >= 1 tile so its output block gets zeroed
        inter = inter.at[:, n_groups].set(False)
        home = jnp.clip(starts[:n_groups] // bm, 0, n_blocks - 1)
        empty = jax.nn.one_hot(home, n_blocks, dtype=jnp.bool_).T \
            & (starts[:n_groups] == ends[:n_groups])[None, :]
        inter = inter.at[:, :n_groups].set(inter[:, :n_groups] | empty)
        key = jnp.arange(G1, dtype=jnp.int32)[None, :] * n_blocks + \
            jnp.arange(n_blocks, dtype=jnp.int32)[:, None]
    else:
        key = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * G1 + \
            jnp.arange(G1, dtype=jnp.int32)[None, :]
    n_tiles = min(n_blocks + G1, n_blocks * G1)
    big = n_blocks * G1 + 1
    flat_key = jnp.where(inter, key, big).ravel()
    order = jnp.argsort(flat_key)[:n_tiles]
    valid = jnp.take(inter.ravel(), order)
    taken = jnp.take(key.ravel(), order)
    if expert_major:
        b_of, g_of = taken % n_blocks, taken // n_blocks
    else:
        b_of, g_of = taken // G1, taken % G1
    block_ids = jnp.where(valid, b_of, 0).astype(jnp.int32)
    group_ids = jnp.where(valid, g_of, n_groups).astype(jnp.int32)
    outs = group_ids if expert_major else block_ids
    prev = jnp.concatenate([jnp.full((1,), -1, outs.dtype), outs[:-1]])
    first = valid & (outs != prev)
    # invalid tiles: keep pointing at the LAST valid tile's out block so
    # the revisit chain stays monotone for Pallas
    last_valid_out = outs[jnp.maximum(jnp.sum(valid) - 1, 0)]
    outs = jnp.where(valid, outs, last_valid_out)
    nxt = jnp.concatenate([outs[1:], jnp.full((1,), -1, outs.dtype)])
    nxt_valid = jnp.concatenate([valid[1:],
                                 jnp.zeros((1,), valid.dtype)])
    last = valid & ((outs != nxt) | ~nxt_valid)
    flags = valid.astype(jnp.int32) + 2 * first.astype(jnp.int32) \
        + 4 * last.astype(jnp.int32)
    return block_ids, group_ids, outs.astype(jnp.int32), flags


def _gmm_fwd(lhs, rhs, offsets_ext, bm: int):
    """lhs [R_pad, M] sorted by group; rhs [E, M, H]; offsets_ext [E+2].
    Returns out [R_pad, H] float32."""
    R, M = lhs.shape
    E, _, H = rhs.shape
    n_blocks = R // bm
    block_ids, group_ids, outs, flags = _metadata(
        offsets_ext, n_blocks, E, bm, expert_major=False)
    n_tiles = int(block_ids.shape[0])

    def kernel(offs, bids, gids, oids, flgs, lhs_ref, rhs_ref, out_ref,
               acc_ref):
        t = pl.program_id(0)
        g = gids[t]
        start = offs[jnp.minimum(g, E)]
        end = offs[jnp.minimum(g, E) + 1]
        row0 = bids[t] * bm
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        live = (flgs[t] % 2 == 1) & (g < E)
        mask = (rows >= start) & (rows < end) & live
        x = jnp.where(mask, lhs_ref[...], 0)
        acc = jax.lax.dot(x, rhs_ref[0],
                          preferred_element_type=jnp.float32)
        first = (flgs[t] // 2) % 2 == 1
        last = flgs[t] >= 4

        # accumulate across the block's tiles in an f32 VMEM scratch;
        # write the (possibly narrower) output dtype ONCE on the block's
        # last tile — halves the out bandwidth vs an f32 out buffer
        @pl.when(first)
        def _():
            acc_ref[...] = acc

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += acc

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((bm, M),
                         lambda t, offs, bids, gids, oids, flgs:
                         (bids[t], 0)),
            pl.BlockSpec((1, M, H),
                         lambda t, offs, bids, gids, oids, flgs:
                         (jnp.minimum(gids[t], E - 1), 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, H),
                               lambda t, offs, bids, gids, oids, flgs:
                               (oids[t], 0)),
        scratch_shapes=[pltpu.VMEM((bm, H), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, H), lhs.dtype),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(offsets_ext, block_ids, group_ids, outs, flags, lhs, rhs)


def _tgmm_fwd(lhs, g, offsets_ext, E: int, bm: int):
    """d_rhs: out[e] = lhs[rows_e].T @ g[rows_e]. lhs [R_pad, M],
    g [R_pad, H] -> [E, M, H] float32."""
    R, M = lhs.shape
    H = g.shape[1]
    n_blocks = R // bm
    block_ids, group_ids, outs, flags = _metadata(
        offsets_ext, n_blocks, E, bm, expert_major=True)
    n_tiles = int(block_ids.shape[0])

    def kernel(offs, bids, gids, oids, flgs, lhs_ref, g_ref, out_ref,
               acc_ref):
        t = pl.program_id(0)
        gid = gids[t]
        start = offs[jnp.minimum(gid, E)]
        end = offs[jnp.minimum(gid, E) + 1]
        row0 = bids[t] * bm
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        live = (flgs[t] % 2 == 1) & (gid < E)
        mask = (rows >= start) & (rows < end) & live
        x = jnp.where(mask, lhs_ref[...], 0)
        acc = jax.lax.dot_general(
            x, g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]

        first = (flgs[t] // 2) % 2 == 1
        last = flgs[t] >= 4

        @pl.when(first)
        def _():
            acc_ref[...] = acc

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += acc

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((bm, M),
                         lambda t, offs, bids, gids, oids, flgs:
                         (bids[t], 0)),
            pl.BlockSpec((bm, H),
                         lambda t, offs, bids, gids, oids, flgs:
                         (bids[t], 0)),
        ],
        out_specs=pl.BlockSpec((1, M, H),
                               lambda t, offs, bids, gids, oids, flgs:
                               (oids[t], 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, M, H), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, M, H), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(offsets_ext, block_ids, group_ids, outs, flags, lhs, g)


def _block_experts(group_sizes, n_blocks, E, bm):
    """Per-row-block expert id for the ALIGNED layout (every group size a
    multiple of ``bm``): block b belongs to the unique group whose range
    contains row b*bm; trailing blocks past the data clamp to E-1 (their
    lhs rows are zero pads -> zero output)."""
    offs = jnp.cumsum(group_sizes.astype(jnp.int32))
    bs = jnp.arange(n_blocks, dtype=jnp.int32) * bm
    be = jnp.searchsorted(offs, bs, side="right").astype(jnp.int32)
    return jnp.minimum(be, E - 1)


def _gmm_aligned_fwd(lhs, rhs, block_experts, bm):
    """Mask-free grouped matmul for the aligned layout: tiles == blocks,
    one expert per block, no accumulation — the hot path (masking a
    [bm, M] tile measured ~2x the whole tile's MXU time)."""
    R, M = lhs.shape
    E, _, H = rhs.shape
    nb = R // bm

    def kernel(be, lhs_ref, rhs_ref, out_ref):
        out_ref[...] = jax.lax.dot(
            lhs_ref[...], rhs_ref[0],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bm, M), lambda t, be: (t, 0)),
            pl.BlockSpec((1, M, H), lambda t, be: (be[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, H), lambda t, be: (t, 0)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, H), lhs.dtype),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(block_experts, lhs, rhs)


def _tgmm_aligned_fwd(lhs, g, block_experts, E, bm):
    """Aligned d_rhs: blocks arrive expert-sorted, so out[e] accumulates
    over that expert's consecutive blocks in an f32 scratch. Experts with
    no block keep garbage — the caller zeroes them via (counts > 0)."""
    R, M = lhs.shape
    H = g.shape[1]
    nb = R // bm
    be = block_experts
    prev = jnp.concatenate([jnp.full((1,), -1, be.dtype), be[:-1]])
    nxt = jnp.concatenate([be[1:], jnp.full((1,), -1, be.dtype)])
    flags = ((be != prev).astype(jnp.int32) * 2
             + (be != nxt).astype(jnp.int32) * 4 + 1)

    def kernel(be_ref, flg, lhs_ref, g_ref, out_ref, acc_ref):
        t = pl.program_id(0)
        acc = jax.lax.dot_general(
            lhs_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]
        first = (flg[t] // 2) % 2 == 1
        last = flg[t] >= 4

        @pl.when(first)
        def _():
            acc_ref[...] = acc

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += acc

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bm, M), lambda t, be, flg: (t, 0)),
            pl.BlockSpec((bm, H), lambda t, be, flg: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, M, H),
                               lambda t, be, flg: (be[t], 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, M, H), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, M, H), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(be, flags, lhs, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm_aligned(lhs, rhs, group_sizes, bm: int = 512):
    """Grouped matmul over the bm-ALIGNED sorted layout: every
    ``group_sizes[e]`` is a multiple of ``bm`` (pad each group's rows up
    and zero the pad rows). No tile ever straddles a group boundary, so
    the kernel runs mask-free at dense-matmul throughput — the layout the
    MoE dispatcher produces. Returns [R, H] in lhs.dtype."""
    out, _ = _gmm_aligned_vjp_fwd(lhs, rhs, group_sizes, bm)
    return out


def _gmm_aligned_vjp_fwd(lhs, rhs, group_sizes, bm):
    R = lhs.shape[0]
    if R % bm:
        raise ValueError(f"gmm_aligned rows {R} must divide bm {bm}")
    E = rhs.shape[0]
    be = _block_experts(group_sizes, R // bm, E, bm)
    out = _gmm_aligned_fwd(lhs, rhs, be, bm)
    return out, (lhs, rhs, group_sizes, be)


def _gmm_aligned_vjp_bwd(bm, res, g):
    lhs, rhs, group_sizes, be = res
    E = rhs.shape[0]
    d_lhs = _gmm_aligned_fwd(g, jnp.swapaxes(rhs, 1, 2), be, bm)
    d_rhs = _tgmm_aligned_fwd(lhs, g, be, E, bm)
    # experts with zero blocks never wrote their slab: replace the
    # garbage (where, not multiply — uninitialized memory can be NaN)
    live = (group_sizes > 0)[:, None, None]
    d_rhs = jnp.where(live, d_rhs, 0)
    return (d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


gmm_aligned.defvjp(_gmm_aligned_vjp_fwd, _gmm_aligned_vjp_bwd)


def _offsets_ext(group_sizes, R_pad):
    off = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(group_sizes.astype(jnp.int32))])
    return jnp.concatenate([off, jnp.full((1,), R_pad, jnp.int32)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs, rhs, group_sizes, bm: int = 512):
    """Grouped matmul: ``out[rows_of_group_e] = lhs[rows] @ rhs[e]``.

    ``lhs`` [R, M] with rows SORTED by group (rows past
    ``sum(group_sizes)`` are padding and produce zeros); ``rhs``
    [E, M, H]; ``group_sizes`` [E] int. R must divide by ``bm``.
    Returns [R, H] in lhs.dtype (accumulation is f32 in VMEM scratch).
    Differentiable in lhs/rhs (group_sizes takes a zero cotangent)."""
    out, _ = _gmm_vjp_fwd(lhs, rhs, group_sizes, bm)
    return out


def _gmm_vjp_fwd(lhs, rhs, group_sizes, bm):
    R = lhs.shape[0]
    if R % bm:
        raise ValueError(f"gmm rows {R} must divide block size {bm}")
    offs = _offsets_ext(group_sizes, R)
    out = _gmm_fwd(lhs, rhs, offs, bm)
    return out, (lhs, rhs, group_sizes, offs)


def _gmm_vjp_bwd(bm, res, g):
    lhs, rhs, group_sizes, offs = res
    g = g.astype(jnp.float32)
    # d_lhs rows of group e = g rows @ rhs[e].T  -> gmm with swapped rhs
    d_lhs = _gmm_fwd(g, jnp.swapaxes(rhs, 1, 2), offs, bm)
    d_rhs = _tgmm_fwd(lhs.astype(jnp.float32), g, offs, rhs.shape[0], bm)
    return (d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def tgmm(lhs, g, group_sizes, n_groups: int, bm: int = 512):
    """Transposed grouped matmul: ``out[e] = lhs[rows_e].T @ g[rows_e]``
    (exposed for tests; gmm's backward uses it internally)."""
    R = lhs.shape[0]
    if R % bm:
        raise ValueError(f"tgmm rows {R} must divide block size {bm}")
    offs = _offsets_ext(group_sizes, R)
    return _tgmm_fwd(lhs.astype(jnp.float32), g.astype(jnp.float32),
                     offs, n_groups, bm)
