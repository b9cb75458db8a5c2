"""Block-paged KV-cache attention — the gather-based XLA read path.

The serving engine (``paddle_tpu.serving``) stores each layer's KV cache
as a pool of fixed-size token blocks instead of one contiguous
``[B, L, n_kv, hd]`` buffer per batch:

    k_pool / v_pool : [num_blocks + 1, n_kv, block_size, hd]
                      (row 0 is the reserved null block; allocatable
                      block ids run 1..num_blocks; head-major inside a
                      block so one head's page is a whole
                      ``[block_size, hd]`` tile for the Pallas reader)
    block_tables    : [B, max_blocks_per_seq] int32 — logical block i of
                      row b lives in physical block ``block_tables[b, i]``
    context_lens    : [B] int32 — tokens already cached per row
    new_lens        : [B] int32 — valid tokens in this call's input
                      (rows may carry right-padding: a partial prefill
                      chunk, or an inactive decode slot with new_len 0)

Physical **block 0 is reserved as the null block**: padded block-table
entries point at it and every invalid token's write is redirected into
it, so padding can never clobber a live sequence's cache. The allocator
(``serving.kv_cache``) never hands block 0 out.

This mirrors the vLLM / Ragged-Paged-Attention layout (see
``/opt/skills/guides/boom_attention_tricks.md`` §8: per-sequence
``page_indices`` over non-contiguous pages). Two read paths share it:

* **gather** — a plain XLA gather (``pool[block_tables]``) + masked
  softmax. Correct on every backend; materializes each row's whole
  padded context, which is exactly the cost the kernel path removes.
  It stays as the backend-portable fallback and the parity oracle.
* **rpa** — the Ragged-Paged-Attention Pallas kernel
  (``ops/pallas/ragged_paged_attention.py``): the token-packed batch
  streams each sequence's KV page by page with online softmax, only
  the real ``context_len`` worth of pages, no dense score tensor.

``PADDLE_TPU_PAGED_ATTN_IMPL={rpa,gather,auto}`` picks the path
(``auto``, the default: rpa on TPU, gather elsewhere — off-TPU the
kernel only runs in Pallas interpret mode, a test vehicle);
:func:`impl_override` pins it programmatically (the engine's
``attn_impl=`` knob, and how parity tests compare both). The serving
engine feeds the ragged token-packed form (:class:`RaggedLayerCache`);
the per-row ``[B, S]`` form (:class:`PagedLayerCache`) remains for
non-engine callers.
"""
from __future__ import annotations

import contextlib
import math
import os
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["LayerCacheSpec", "PagedLayerCache", "RaggedLayerCache",
           "write_to_pool", "ragged_latent_attention_step",
           "write_tokens_to_pool", "gather_pool", "paged_attention_step",
           "ragged_gather_attention", "ragged_paged_attention_step",
           "paged_attention_impl", "impl_override", "mesh_override",
           "quantize_kv_slots"]


class LayerCacheSpec(NamedTuple):
    """What an attention layer keeps of a token in the paged cache; a
    served model states it (``model.kv_cache_spec()``, the same for every
    layer) and the pools are built from it
    (``serving.kv_cache.PagedKVCache``).

    ``value_dim`` None is a **latent** page (MLA): the layer writes one
    row ``[c | k_rope]`` of ``key_dim`` numbers a token under its one
    head, its values are the first ``value_cols`` columns of that same
    row, and no value pool exists."""
    kv_heads: int
    key_dim: int
    value_dim: object = None       # int, or None: no value pool
    value_cols: int = 0            # latent page: columns that are values

    @property
    def latent(self) -> bool:
        return self.value_dim is None

    @classmethod
    def kv(cls, kv_heads: int, head_dim: int) -> "LayerCacheSpec":
        """A K and a V pool of one width (GQA/MHA layers)."""
        return cls(int(kv_heads), int(head_dim), int(head_dim))


class PagedLayerCache(NamedTuple):
    """One layer's view of the paged KV state.

    Threaded through ``LlamaModel.forward(caches=[...])`` exactly like
    the ``(k, v)`` / ``(k_buf, v_buf, pos)`` cache forms; the attention
    layer dispatches on this type. ``block_tables`` / ``context_lens`` /
    ``new_lens`` are shared across layers (one table per sequence), the
    pools are per-layer.
    """
    k_pool: object        # [num_blocks + 1, n_kv, block_size, hd]
    v_pool: object        # [num_blocks + 1, n_kv, block_size, hd]
    block_tables: object  # [B, max_blocks_per_seq] int32
    context_lens: object  # [B] int32
    new_lens: object      # [B] int32


def _scatter_indices(block_tables, positions, valid, block_size):
    """(phys_block [B,S], slot [B,S]) for logical ``positions`` [B,S];
    invalid tokens are redirected to (null block 0, slot 0)."""
    nblk = block_tables.shape[1]
    blk = jnp.clip(positions // block_size, 0, nblk - 1)
    phys = jnp.take_along_axis(block_tables, blk, axis=1)
    slot = positions % block_size
    phys = jnp.where(valid, phys, 0)
    slot = jnp.where(valid, slot, 0)
    return phys, slot


def write_to_pool(pool, new, block_tables, positions, valid):
    """Scatter ``new`` [B, S, n_kv, hd] into ``pool`` at logical
    ``positions`` [B, S] through ``block_tables``; tokens with
    ``valid == False`` land in the null block."""
    phys, slot = _scatter_indices(block_tables, positions, valid,
                                  pool.shape[2])
    return pool.at[phys, :, slot].set(new.astype(pool.dtype))


def gather_pool(pool, block_tables):
    """[B, max_blocks_per_seq * block_size, n_kv, hd] contiguous view of
    each row's paged context (the XLA-gather read path); a scale pool
    ``[num_blocks + 1, n_kv, block_size]`` gathers the same way."""
    g = jnp.swapaxes(pool[block_tables], 2, 3)  # [B, nblk, bs, n_kv, hd]
    B, nblk, bs = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape(B, nblk * bs, *g.shape[3:])


def paged_attention_step(q, k, v, k_pool, v_pool, block_tables,
                         context_lens, new_lens, *, scale=None):
    """One attention step over a block-paged cache.

    ``q`` [B, S, n_heads, hd] and ``k``/``v`` [B, S, n_kv, hd] are the
    (already position-encoded) projections of this call's ``S`` input
    tokens per row — ``S`` is the prefill chunk length, or 1 in decode.
    Writes the new K/V into the pools (invalid tokens to the null
    block), gathers each row's whole paged context, and runs masked
    GQA attention: key at logical position ``l`` is visible to row
    ``b``'s query ``i`` iff ``l <= context_lens[b] + i`` — that one
    bound covers prior context, in-chunk causality, and (together with
    null-block redirection) keeps padding invisible.

    Returns ``(out [B, S, n_heads*hd], k_pool', v_pool')``. Outputs at
    padded query positions (``i >= new_lens[b]``) are garbage by
    construction and must be discarded by the caller.
    """
    B, S, n_kv, hd = k.shape
    n_heads = q.shape[2]
    grp = n_heads // n_kv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    pos = context_lens[:, None].astype(jnp.int32) + \
        jnp.arange(S, dtype=jnp.int32)[None, :]                 # [B, S]
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < \
        new_lens[:, None].astype(jnp.int32)
    k_pool = write_to_pool(k_pool, k, block_tables, pos, valid)
    v_pool = write_to_pool(v_pool, v, block_tables, pos, valid)
    keys = gather_pool(k_pool, block_tables)                    # [B, L, ...]
    vals = gather_pool(v_pool, block_tables)
    L = keys.shape[1]
    qg = q.reshape(B, S, n_kv, grp, hd)
    s = jnp.einsum("bskgh,blkh->bskgl", qg.astype(jnp.float32),
                   keys.astype(jnp.float32)) * scale
    visible = jnp.arange(L)[None, None, :] <= pos[:, :, None]   # [B, S, L]
    s = jnp.where(visible[:, :, None, None, :], s,
                  jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
    out = jnp.einsum("bskgl,blkh->bskgh", w, vals)
    return out.reshape(B, S, n_heads * hd), k_pool, v_pool


# ===================== ragged token-packed form ==============================
class RaggedLayerCache(NamedTuple):
    """One layer's view of the paged KV state in the TOKEN-PACKED form
    the unified serving step uses (ISSUE 8): the step's input is a flat
    ``[1, total_tokens]`` axis holding every scheduled sequence's new
    tokens back to back — prefill chunks (S>1) and decode rows (S=1)
    together. ``block_tables`` carries an extra all-null sentinel row
    (index ``max_seqs``) that padding tokens resolve through; metadata
    rows beyond the live sequences point at it. The RPA kernel's flat
    work list (``step_seq`` / ``step_blk`` per item, ``step_tile`` its
    CSR tile pointers) is built host-side per step
    (``ops.pallas.ragged_paged_attention.build_step_maps``); all three
    are traced INPUTS, and so is the kernel's trip count
    (``step_tile[-1]``) — shapes never change, so the engine's one
    executable serves every batch mix."""
    k_pool: object        # [num_blocks + 1, n_kv, block_size, hd]
    v_pool: object        # the same; None where the layer's page is a
    #                       latent one (``LayerCacheSpec.latent``)
    block_tables: object  # [max_seqs + 1, max_blocks_per_seq] int32
    cu_seqlens: object    # [max_seqs + 2] int32 token-span prefix sums
    context_lens: object  # [max_seqs + 1] int32 cached tokens per seq
    seq_ids: object       # [T] int32 token -> sequence (max_seqs = pad)
    positions: object     # [T] int32 absolute position per token
    step_seq: object      # [max_items] int32 work item -> sequence
    step_blk: object      # [max_items] int32 work item -> kv page index
    step_tile: object     # [num_q_tiles + 1] int32 tile -> first item
    # int8-KV quantization (ISSUE 20): per-token-slot, per-head dequant
    # multipliers paged like the pools; None on unquantized engines
    k_scale: object = None  # [num_blocks + 1, n_kv, block_size] f32
    v_scale: object = None  # [num_blocks + 1, n_kv, block_size] f32


# thread-local: two engines may trace their unified steps concurrently
# on their background threads, each under its own attn_impl pin — a
# process-global would let one trace leak its impl into the other
_impl_local = threading.local()


def paged_attention_impl() -> str:
    """Resolve the paged read-path implementation: an
    :func:`impl_override` in effect on THIS thread, else
    ``PADDLE_TPU_PAGED_ATTN_IMPL`` (``rpa`` | ``gather`` | ``auto``),
    else auto — rpa on TPU, gather elsewhere. Read at TRACE time: a
    compiled serving step keeps whatever was resolved when it traced."""
    override = getattr(_impl_local, "value", None)
    if override is not None:
        return override
    v = os.environ.get("PADDLE_TPU_PAGED_ATTN_IMPL", "auto").lower()
    if v in ("rpa", "gather"):
        return v
    if v != "auto":
        raise ValueError(
            f"PADDLE_TPU_PAGED_ATTN_IMPL={v!r} (want rpa|gather|auto)")
    return "rpa" if jax.default_backend() == "tpu" else "gather"


@contextlib.contextmanager
def impl_override(value):
    """Pin the read-path impl for the calls traced inside the block on
    the current thread (``None`` = no-op). The engine wraps its unified
    step's trace in this so ``ServingEngine(attn_impl=...)`` wins over
    the env."""
    if value is not None and value not in ("rpa", "gather"):
        raise ValueError(f"attn impl {value!r} (want rpa|gather|None)")
    prev = getattr(_impl_local, "value", None)
    _impl_local.value = value
    try:
        yield
    finally:
        _impl_local.value = prev


@contextlib.contextmanager
def mesh_override(mesh):
    """Pin a tensor-parallel mesh for the ragged calls traced inside
    the block on this thread (``None`` = single-device, a no-op). The
    serving engine wraps its unified step's trace in this; the rpa
    branch of :func:`ragged_paged_attention_step` reads it to shard_map
    the Pallas kernel over the model-parallel axis (the kernel is
    opaque to GSPMD — the gather fallback needs nothing, XLA partitions
    it from the pool/projection shardings alone)."""
    prev = getattr(_impl_local, "mesh", None)
    _impl_local.mesh = mesh
    try:
        yield
    finally:
        _impl_local.mesh = prev


def _tp_mesh():
    """(mesh, mp_axis_name) when a tensor-parallel mesh with a >1
    model axis is pinned on this thread, else None."""
    mesh = getattr(_impl_local, "mesh", None)
    if mesh is None:
        return None
    for cand in ("mp", "model", "tp"):
        if cand in mesh.axis_names and mesh.shape[cand] > 1:
            return mesh, cand
    return None


def write_tokens_to_pool(pool, new, block_tables, seq_ids, positions):
    """Scatter ``new`` [T, n_kv, hd] into ``pool`` at each token's
    ``positions`` through its sequence's block-table row. Padding tokens
    (sentinel ``seq_ids`` → the all-null table row) land in the null
    block's slot 0, so indices there repeat. Per-token dequant scales
    [T, n_kv] scatter into a scale pool ``[num_blocks + 1, n_kv,
    block_size]`` through the same indices, and a one-head latent pool
    takes its rows as ``[T, 1, kd]``.

    The pool is written as the flat table of rows it is — row
    ``(block * n_kv + head) * block_size + slot`` — so the reshape moves
    nothing and one row scatter updates it in place. Indexed as
    ``pool.at[block, :, slot]`` XLA wants a token's ``[n_kv, hd]`` tile
    contiguous and copies the whole pool to ``[block, slot, head, hd]``
    and back around every write (docs/SERVING.md "Pool layout")."""
    n_kv, bs = pool.shape[1:3]
    nblk = block_tables.shape[1]
    pos = positions.astype(jnp.int32)
    phys = block_tables[seq_ids, jnp.clip(pos // bs, 0, nblk - 1)]
    slot = jnp.where(phys == 0, 0, pos % bs)
    rows = (phys[:, None] * n_kv + jnp.arange(n_kv, dtype=jnp.int32)) * bs \
        + slot[:, None]
    row = pool.shape[3:]              # (hd,), or () in a scale pool
    return pool.reshape((-1,) + row).at[rows.reshape(-1)].set(
        new.astype(pool.dtype).reshape((-1,) + row)).reshape(pool.shape)


def _write_step_kv(pools, news, block_tables, seq_ids, positions):
    """Write each of ``news`` into its pool (K and V, and with int8 pools
    their scales). Under a model-parallel mesh the pools are sharded over
    their head axis, which the flat view of a pool would merge away (GSPMD
    would gather the pool to reshape it): there each shard flat-writes
    its own heads into its own pool shard under ``shard_map``."""
    def write(pools, news, bt, sid, pos):
        return tuple(write_tokens_to_pool(p, n, bt, sid, pos)
                     for p, n in zip(pools, news))

    tp = _tp_mesh()
    if tp is None:
        return write(pools, news, block_tables, seq_ids, positions)
    from jax.sharding import PartitionSpec as P
    mesh, ax = tp

    def over_heads(arrays):          # axis 1 of every pool and new is n_kv
        return tuple(P(None, ax, *[None] * (a.ndim - 2)) for a in arrays)

    return jax.shard_map(
        write, mesh=mesh,
        in_specs=(over_heads(pools), over_heads(news), P(), P(), P()),
        out_specs=over_heads(pools), check_vma=False)(
        pools, news, block_tables, seq_ids, positions)


def quantize_kv_slots(x):
    """Symmetric per-token, per-head int8 quantization of KV rows:
    ``x [..., n_kv, hd]`` → ``(q int8 [..., n_kv, hd], scale f32
    [..., n_kv])`` with scale = absmax/127 (the dequant multiplier).
    The granularity matches the paged scale pools — one scalar per
    ``(token slot, kv head)`` — so dequantization is a broadcast
    multiply XLA fuses into the attention reads."""
    f = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(f), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(f / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def ragged_gather_attention(q, k_pool, v_pool, block_tables, seq_ids,
                            positions, *, scale, k_scale=None,
                            v_scale=None):
    """Token-packed GQA attention via the XLA-gather fallback: gather
    every sequence's whole padded context, pick each token's row, dense
    masked softmax. Semantically identical to the rpa kernel (the parity
    oracle); costs the [T, L_max] materialization the kernel removes."""
    T, n_heads, hd = q.shape
    n_kv = k_pool.shape[1]
    grp = n_heads // n_kv
    keys = gather_pool(k_pool, block_tables)   # [max_seqs+1, L, n_kv, hd]
    vals = gather_pool(v_pool, block_tables)
    kt = keys[seq_ids]                         # [T, L, n_kv, hd]
    vt = vals[seq_ids]
    if k_scale is not None:
        # int8 pools: dequantize the gathered context in f32 (the
        # scale pools page/gather identically to the value pools)
        ksc = gather_pool(k_scale, block_tables)[seq_ids]  # [T, L, n_kv]
        vsc = gather_pool(v_scale, block_tables)[seq_ids]
        kt = kt.astype(jnp.float32) * ksc[..., None]
        vt = vt.astype(jnp.float32) * vsc[..., None]
    L = kt.shape[1]
    qg = q.reshape(T, n_kv, grp, hd)
    s = jnp.einsum("tkgh,tlkh->tkgl", qg.astype(jnp.float32),
                   kt.astype(jnp.float32)) * scale
    visible = jnp.arange(L, dtype=jnp.int32)[None, :] <= \
        positions.astype(jnp.int32)[:, None]            # [T, L]
    s = jnp.where(visible[:, None, None, :], s,
                  jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1).astype(vt.dtype)
    out = jnp.einsum("tkgl,tlkh->tkgh", w, vt)
    return out.reshape(T, n_heads, hd)


def ragged_paged_attention_step(q, k, v, k_pool, v_pool, block_tables,
                                cu_seqlens, context_lens, seq_ids,
                                positions, step_seq, step_blk, step_tile,
                                *, scale=None, k_scale=None,
                                v_scale=None):
    """One unified serving step over the token-packed ragged layout.

    ``q`` [T, n_heads, hd] and ``k``/``v`` [T, n_kv, hd] are the
    (already position-encoded) projections of the step's flat tokens.
    Writes the new K/V into the pools (padding to the null block), then
    dispatches the read path on :func:`paged_attention_impl`: the
    Pallas RPA kernel (page-streamed, online softmax) or the gather
    fallback. Returns ``(out [T, n_heads*hd], k_pool', v_pool')``;
    outputs at padding tokens are garbage (gather) or 0 (rpa) and must
    be discarded by the caller either way.

    With int8-KV pools (``k_scale``/``v_scale`` scale pools given), the
    new K/V are quantized per (token, head) before the scatter and the
    read path dequantizes on the fly; the return grows to
    ``(out, k_pool', v_pool', k_scale', v_scale')``. Only the gather
    path reads quantized pools (the Pallas kernel streams raw pages —
    the engine forces ``gather`` for int8 KV).
    """
    T, n_heads, hd = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if k_scale is not None:
        kq, ks = quantize_kv_slots(k)
        vq, vs = quantize_kv_slots(v)
        k_pool, v_pool, k_scale, v_scale = _write_step_kv(
            (k_pool, v_pool, k_scale, v_scale), (kq, vq, ks, vs),
            block_tables, seq_ids, positions)
        out = ragged_gather_attention(
            q, k_pool, v_pool, block_tables, seq_ids, positions,
            scale=scale, k_scale=k_scale, v_scale=v_scale)
        out = out.astype(q.dtype)
        return (out.reshape(T, n_heads * hd), k_pool, v_pool,
                k_scale, v_scale)
    k_pool, v_pool = _write_step_kv((k_pool, v_pool), (k, v), block_tables,
                                    seq_ids, positions)
    if paged_attention_impl() == "rpa":
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention
        tp = _tp_mesh()
        if tp is not None:
            # SPMD over the kernel's head dimension (ISSUE 15): Pallas
            # is opaque to GSPMD, so shard_map runs one kernel instance
            # per mp shard — q over n_heads, pools over n_kv (whole GQA
            # groups stay together because n_heads/n_kv shard by the
            # same factor), metadata replicated (every shard walks the
            # same work list under the same traced bound). Attention is
            # embarrassingly parallel across heads: no collective is
            # introduced here (the o_proj psum stays GSPMD's).
            from jax.sharding import PartitionSpec as P
            mesh, ax = tp
            heads = P(None, ax, None)
            pools = P(None, ax, None, None)
            rep = P()
            out = jax.shard_map(
                lambda qa, kp, vp, bt, cu, ctx, ssq, sbk, stl:
                    ragged_paged_attention(qa, kp, vp, bt, cu, ctx,
                                           ssq, sbk, stl, sm_scale=scale),
                mesh=mesh,
                in_specs=(heads, pools, pools, rep, rep, rep, rep, rep,
                          rep),
                out_specs=heads, check_vma=False)(
                q, k_pool, v_pool, block_tables, cu_seqlens,
                context_lens, step_seq, step_blk, step_tile)
        else:
            out = ragged_paged_attention(
                q, k_pool, v_pool, block_tables, cu_seqlens,
                context_lens, step_seq, step_blk, step_tile,
                sm_scale=scale)
    else:
        out = ragged_gather_attention(
            q, k_pool, v_pool, block_tables, seq_ids, positions,
            scale=scale)
    return out.reshape(T, n_heads * hd), k_pool, v_pool


# ===================== latent (MLA) pages ====================================
def ragged_latent_gather_attention(q, pool, block_tables, seq_ids,
                                   positions, *, value_cols, scale):
    """The gather fallback of the latent read (and the kernel's parity
    oracle): every sequence's whole padded context gathered, each token's
    row picked, dense masked softmax in float32. ``q`` [T, n_heads, kd]
    absorbed queries; returns ``[T, n_heads, value_cols]``."""
    rows = gather_pool(pool, block_tables)[seq_ids][:, :, 0]   # [T, L, kd]
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("thd,tld->thl", q.astype(jnp.float32), rows) * scale
    visible = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] <= \
        positions.astype(jnp.int32)[:, None]
    s = jnp.where(visible[:, None, :], s, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("thl,tlc->thc", w, rows[..., :value_cols])


def ragged_latent_attention_step(q, rows, pool, block_tables, cu_seqlens,
                                 context_lens, seq_ids, positions, step_seq,
                                 step_blk, step_tile, *, value_cols, scale):
    """One unified serving step of a latent-attention (MLA) layer, read
    **absorbed**: ``q`` [T, n_heads, kd] is ``[W_UK^T q_nope | q_rope]``,
    ``rows`` [T, kd] the step's new cache rows ``[c | k_rope]``. Writes
    the rows into the layer's one pool, then reads it with the RPA
    kernel's latent form (``rpa_mla``: every query head shares the page,
    values are its first ``value_cols`` columns) or the gather fallback,
    by :func:`paged_attention_impl`. Returns ``(u [T, n_heads,
    value_cols], pool')``; ``u`` at padding tokens is garbage (gather)
    or 0 (rpa), as in :func:`ragged_paged_attention_step`."""
    pool = write_tokens_to_pool(pool, rows[:, None, :], block_tables, seq_ids,
                                positions)
    if paged_attention_impl() == "rpa":
        if _tp_mesh() is not None:
            raise NotImplementedError(
                "a latent pool has one head: it is replicated, not "
                "sharded over a model-parallel axis")
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention
        u = ragged_paged_attention(
            q, pool, None, block_tables, cu_seqlens, context_lens,
            step_seq, step_blk, step_tile, sm_scale=scale,
            value_cols=value_cols)
    else:
        u = ragged_latent_gather_attention(
            q, pool, block_tables, seq_ids, positions,
            value_cols=value_cols, scale=scale).astype(q.dtype)
    return u, pool
