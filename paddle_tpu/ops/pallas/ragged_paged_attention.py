"""Ragged Paged Attention — one Pallas TPU kernel for mixed
prefill+decode serving batches over the block-paged KV pool.

The serving engine's read path before this kernel was the XLA gather
fallback (``ops/paged_attention.py``): materialize every row's ENTIRE
padded paged context (``pool[block_tables]`` →
``[B, max_blocks_per_seq * block_size, n_kv, hd]``) and run dense masked
softmax over it — O(B · L_max) HBM traffic per step regardless of how
much context each row really has, plus a second compiled executable
because no one kernel shape covered both ``[1, prefill_chunk]`` prefill
and ``[max_batch, 1]`` decode. Following the RPA paper (PAPERS.md,
arxiv 2604.15464) this kernel takes the batch **token-packed**:

    q              : [total_tokens, n_heads, hd] — every sequence's new
                     tokens back to back (prefill chunks with S>1 and
                     decode rows with S=1 in the same flat axis)
    k_pool/v_pool  : [num_blocks + 1, n_kv, block_size, hd]
                     (physical block 0 is the reserved null block; one
                     head's page is a whole ``[block_size, hd]`` tile, the
                     block shape Mosaic accepts — a head squeezed out of
                     the last two dims is not)
    block_tables   : [max_seqs + 1, max_blocks_per_seq] int32 — row
                     ``max_seqs`` is the all-null sentinel row that
                     padding tokens and dead grid steps resolve through
    cu_seqlens     : [max_seqs + 2] int32 — sequence s's new tokens
                     occupy flat positions [cu[s], cu[s+1])
    context_lens   : [max_seqs + 1] int32 — tokens already cached
                     BEFORE this step's writes, per sequence

and streams each sequence's KV **page by page with only its real
``context_len`` worth of pages** — no ``[B, L_max]`` materialization, no
f32 score tensor in HBM, online softmax in VMEM scratch.

Grid design
-----------
``grid = (n_kv_heads, num_q_tiles, max_steps)``. The flat token axis is
cut into fixed ``tile_q``-token tiles; a tile may span several ragged
sequences, so the inner grid dimension walks a host-built work list
(``build_step_maps``): step ``(j, i)`` names ``(sequence, kv page)`` in
scalar-prefetched int32 maps, and the K/V BlockSpec index maps chase
``block_tables[step_seq[j,i], step_blk[j,i]]`` straight from SMEM — the
pipeline's revolving buffers double-buffer the page DMAs exactly like
the classic paged kernel (boom_attention_tricks.md §9–11), with no
manual descriptors. Rows of the score tile that don't belong to the
step's sequence are masked dead (their online-softmax state is provably
untouched: p = 0 rows with α folded to carry ``m``/``l`` through), so
prefill chunks (in-chunk causal via ``kpos <= ctx + (t - cu[s])``) and
decode rows coexist in one tile. Dead padding steps map to the null
page; consecutive equal indices are not re-fetched, so the padded tail
of a tile's work list costs one null-page DMA, not one per step.

``max_steps`` is static: ``min(tile_q * max_blocks_per_seq,
pool_capacity)`` — at most ``tile_q`` sequences overlap one tile, each
bounded by its table width, and all sequences in a tile together can't
hold more pages than the pool has blocks.

Off-TPU the kernel runs in Pallas interpret mode, which is what tier-1
parity tests exercise on the CPU mesh (`tests/test_ragged_paged_attention.py`);
on the chip ``chip_smoke.py`` compares the compiled kernel against the
gather reader. ``tile_q`` registers through ``ops/pallas/autotune.py``
exactly like ``flash_attention.py``'s block sizes.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention", "build_step_maps", "rpa_tile_q",
           "rpa_max_steps", "default_tile_q"]

_LANES = 128
# finite stand-in for -inf (same trick as flash_attention.py): keeps the
# m/l/alpha arithmetic NaN-free on fully-masked tiles
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

#: tile_q candidates for the runtime autotuner
_TILE_CANDIDATES = (8, 16, 32)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_tile_q(group: int, dtype) -> int:
    """Flat-token tile height: the smallest multiple of 8 whose
    ``tile_q * group`` score rows fill whole sublane tiles of ``dtype``
    (8 rows of f32, 16 of bf16, 32 of int8) — the q block's second-minor
    dim must be a tile multiple for Mosaic. 8 everywhere except narrow
    dtypes on MHA models (``group == 1``), the no-waste floor for
    decode-heavy mixes (each decode row contributes group-many rows)."""
    sublane = 32 // jnp.dtype(dtype).itemsize
    tile = 8
    while (tile * group) % sublane:
        tile += 8
    return tile


def rpa_max_steps(tile_q: int, max_blocks_per_seq: int,
                  pool_blocks: int) -> int:
    """Static bound on the per-tile work-list length. A tile of
    ``tile_q`` tokens overlaps at most ``tile_q`` sequences; each streams
    at most ``max_blocks_per_seq`` pages; and all sequences overlapping
    one tile are distinct, so together they can't hold more pages than
    the pool has allocatable blocks."""
    return max(1, min(tile_q * max_blocks_per_seq, pool_blocks))


def build_step_maps(cu_seqlens, kv_lens, *, total_tokens, tile_q,
                    block_size, max_steps, max_seqs):
    """Host-side (numpy) kernel work list for one engine step.

    ``cu_seqlens``: int array ``[num_seqs + 1]`` — prefix sums of the
    LIVE sequences' new-token counts (packed order). ``kv_lens``: int
    array ``[num_seqs]`` — each sequence's total KV length after this
    step's writes (``context_len + new_len``).

    Returns ``(step_seq, step_blk)``, both ``[num_q_tiles, max_steps]``
    int32: for q tile ``j``, the live steps enumerate every
    ``(sequence, kv page)`` pair the tile's tokens attend over — pages
    only up to ``ceil(kv_len / block_size)``, i.e. only the real
    context. Dead steps carry the ``max_seqs`` sentinel (the all-null
    block-table row).
    """
    cu = np.asarray(cu_seqlens, np.int64)
    kv = np.asarray(kv_lens, np.int64)
    num_seqs = len(kv)
    if total_tokens % tile_q:
        raise ValueError(
            f"total_tokens {total_tokens} not a multiple of tile_q "
            f"{tile_q}")
    num_tiles = total_tokens // tile_q
    step_seq = np.full((num_tiles, max_steps), max_seqs, np.int32)
    step_blk = np.zeros((num_tiles, max_steps), np.int32)
    for j in range(num_tiles):
        lo, hi = j * tile_q, (j + 1) * tile_q
        used = 0
        for s in range(num_seqs):
            if cu[s] >= cu[s + 1] or cu[s + 1] <= lo or cu[s] >= hi:
                # no tokens at all (a new_len == 0 padding slot) or none
                # in this tile: contributes no work steps — the static
                # max_steps bound counts only sequences with real tokens
                continue
            n_pages = -(-int(kv[s]) // block_size)
            if used + n_pages > max_steps:
                raise ValueError(
                    f"tile {j} needs {used + n_pages} kv steps > "
                    f"max_steps {max_steps} — the scheduler admitted "
                    f"more pages than the static bound (bug)")
            step_seq[j, used:used + n_pages] = s
            step_blk[j, used:used + n_pages] = np.arange(n_pages)
            used += n_pages
    return step_seq, step_blk


# =========================== kernel ==========================================
def _rpa_kernel(ss_ref, sb_ref, bt_ref, cu_ref, ctx_ref,
                q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                *, tile_q, group, block_size, max_steps, max_seqs,
                sm_scale):
    j = pl.program_id(1)
    i = pl.program_id(2)
    rows = tile_q * group

    @pl.when(i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    ss = ss_ref[j, i]

    @pl.when(ss < max_seqs)
    def _compute():
        sb = sb_ref[j, i]
        q = q_ref[...]                                  # [rows, hd]
        k = k_ref[...]                                  # [bs, hd]
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        # row r of the tile is (token j*tile_q + r//group, head r%group).
        # The three bounds below are the token-space tests
        #   start <= tok < end   and   kpos <= ctx + tok - start
        # multiplied through by ``group`` (r//g >= a  <=>  r >= a*g), so
        # the kernel needs no vector integer division
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0)
        lo = cu_ref[ss] - j * tile_q        # sequence span, tile-relative
        hi = cu_ref[ss + 1] - j * tile_q
        kpos = sb * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        # one bound covers prior context, in-chunk causality, and (with
        # page enumeration stopping at ceil(kv_len/bs)) page raggedness
        visible = (r >= lo * group) & (r < hi * group) & \
            (r >= (kpos - ctx_ref[ss] + lo) * group)
        s = jnp.maximum(jnp.where(visible, s, _MASK_VALUE), _MASK_VALUE)
        m_prev = m_sc[:, :1]                            # lane-replicated
        l_prev = l_sc[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # rows with no live key in THIS step (another sequence's rows, or
        # causally-dead decode rows) would contribute exp(MASK-MASK)=1
        # per column; zeroing them keeps their l at 0 so their m/l/acc
        # state rides through untouched (alpha re-scales acc by the same
        # factor l absorbs). A row is live iff its max rose above the
        # mask value — a float compare, not a boolean reduction
        p = jnp.where(m_cur > _MASK_VALUE, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(i == max_steps - 1)
    def _finish():
        # rows that saw no live step (padding tokens): exact 0 output
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def _rpa_call(q_heads, k_pool, v_pool, step_seq, step_blk, block_tables,
              cu_seqlens, context_lens, *, tile_q, group, sm_scale):
    """``q_heads`` [n_kv, T*group, hd] (token-major rows per kv head) →
    out in the same layout."""
    n_kv, tg, hd = q_heads.shape
    block_size = k_pool.shape[2]
    max_seqs = block_tables.shape[0] - 1
    num_tiles, max_steps = step_seq.shape
    rows = tile_q * group

    kernel = functools.partial(
        _rpa_kernel, tile_q=tile_q, group=group, block_size=block_size,
        max_steps=max_steps, max_seqs=max_seqs, sm_scale=sm_scale)

    def q_map(h, j, i, ss, sb, bt, cu, ctx):
        return (h, j, 0)

    def kv_map(h, j, i, ss, sb, bt, cu, ctx):
        # scalar-prefetch chase: physical page of this step's (seq, blk).
        # Dead steps resolve through the sentinel table row to the null
        # page 0; consecutive equal indices are not re-fetched, so a
        # padded work-list tail costs one DMA, not one per step.
        return (bt[ss[j, i], sb[j, i]], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_kv, num_tiles, max_steps),
        in_specs=[
            pl.BlockSpec((None, rows, hd), q_map),
            pl.BlockSpec((None, None, block_size, hd), kv_map),
            pl.BlockSpec((None, None, block_size, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, rows, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_kv, tg, hd), q_heads.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        # the device op's name in a profiler trace (``rpa.N custom-call``;
        # without it the op is named after the jitted caller)
        name="rpa",
    )(step_seq, step_blk, block_tables, cu_seqlens, context_lens,
      q_heads, k_pool, v_pool)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, cu_seqlens,
                           context_lens, step_seq, step_blk, *,
                           sm_scale=None):
    """GQA attention for a token-packed ragged batch over paged KV.

    ``q`` [total_tokens, n_heads, hd]; pools
    ``[num_blocks + 1, n_kv, block_size, hd]`` (this step's new K/V
    already scattered in — the kernel is a pure read); metadata as
    documented in the module docstring (``build_step_maps`` produces the
    step maps). Returns ``[total_tokens, n_heads, hd]``. Outputs at
    padding tokens (sentinel ``seq_id``) are exactly 0.
    """
    T, n_heads, hd = q.shape
    n_kv = k_pool.shape[1]
    if n_heads % n_kv:
        raise ValueError(
            f"q heads {n_heads} must be a multiple of kv heads {n_kv}")
    group = n_heads // n_kv
    num_tiles = step_seq.shape[0]
    if num_tiles == 0 or T % num_tiles:
        raise ValueError(
            f"step maps have {num_tiles} tiles for {T} tokens")
    tile_q = T // num_tiles
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    # [T, n_heads, hd] -> [n_kv, T*group, hd], rows token-major within a
    # kv head so q tile j covers exactly tokens [j*tile_q, (j+1)*tile_q)
    qh = q.reshape(T, n_kv, group, hd).transpose(1, 0, 2, 3) \
          .reshape(n_kv, T * group, hd)
    out = _rpa_call(
        qh, k_pool, v_pool,
        jnp.asarray(step_seq, jnp.int32), jnp.asarray(step_blk, jnp.int32),
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(cu_seqlens, jnp.int32),
        jnp.asarray(context_lens, jnp.int32),
        tile_q=tile_q, group=group, sm_scale=float(sm_scale))
    return out.reshape(n_kv, T, group, hd).transpose(1, 0, 2, 3) \
              .reshape(T, n_heads, hd)


# =========================== tile autotune ===================================
def rpa_tile_q(budget_tokens, n_heads, n_kv, head_dim, block_size,
               max_blocks_per_seq, pool_blocks, dtype="float32") -> int:
    """The flat-token tile height for an engine at this signature —
    :func:`default_tile_q`, or (with ``FLAGS_use_autotune`` on chip) the
    winner of an on-device sweep over the ``_TILE_CANDIDATES`` at least
    that tall, measured once per signature and cached
    (``ops/pallas/autotune.py``, the flash-attention pattern). The
    engine rounds its token budget up to a multiple of the returned
    tile, so any candidate is legal."""
    default = default_tile_q(n_heads // n_kv, dtype)
    if _interpret():
        return default  # interpret mode: timing a sweep is meaningless
    from paddle_tpu.core.flags import flag
    if not flag("use_autotune"):
        return default
    from .autotune import autotune

    sig = (int(budget_tokens), int(n_heads), int(n_kv), int(head_dim),
           int(block_size), int(max_blocks_per_seq), int(pool_blocks),
           str(dtype))

    def build(tile):
        from .autotune import aot_runner
        T = -(-int(budget_tokens) // tile) * tile
        max_seqs = max(2, min(T, 8))
        max_steps = rpa_max_steps(tile, max_blocks_per_seq, pool_blocks)
        # representative mix: one prefill chunk spanning half the budget
        # plus decode rows for the rest, each with a page of context
        n_dec = min(max_seqs - 1, max(1, T // 2))
        new_lens = [T - n_dec] + [1] * n_dec
        ctx = [0] + [block_size] * n_dec
        cu = np.zeros(max_seqs + 2, np.int32)
        cu[1:len(new_lens) + 1] = np.cumsum(new_lens)
        cu[len(new_lens) + 1:] = cu[len(new_lens)]
        ctx_arr = np.zeros(max_seqs + 1, np.int32)
        ctx_arr[:len(ctx)] = ctx
        kv_lens = [n + c for n, c in zip(new_lens, ctx)]
        bt = np.zeros((max_seqs + 1, max_blocks_per_seq), np.int32)
        nxt = 1
        for s, kv in enumerate(kv_lens):
            n_pages = -(-kv // block_size)
            bt[s, :n_pages] = np.arange(nxt, nxt + n_pages)
            nxt += n_pages
        if nxt - 1 > pool_blocks:
            raise ValueError("synthetic workload exceeds pool")
        ssq, sbk = build_step_maps(
            cu[:len(new_lens) + 1], kv_lens, total_tokens=T,
            tile_q=tile, block_size=block_size, max_steps=max_steps,
            max_seqs=max_seqs)
        with jax.ensure_compile_time_eval():
            dt = jnp.dtype(dtype)
            q0 = jnp.zeros((T, n_heads, head_dim), dt)
            kp = jnp.zeros((pool_blocks + 1, n_kv, block_size, head_dim),
                           dt)
        return aot_runner(
            lambda qa, kpa, vpa: ragged_paged_attention(
                qa, kpa, vpa, bt, cu, ctx_arr, ssq, sbk),
            q0, kp, kp)

    # default first (a tie keeps it); shorter tiles would leave partial
    # sublane tiles in the q block
    cands = [default] + [t for t in _TILE_CANDIDATES if t > default]
    return autotune("ragged_paged_attention", sig, cands, build, default)
