"""Block-paged KV-cache attention — the gather-based XLA read path.

The serving engine (``paddle_tpu.serving``) stores each layer's KV cache
as a pool of fixed-size token blocks instead of one contiguous
``[B, L, n_kv, hd]`` buffer per batch:

    k_pool / v_pool : [num_blocks + 1, n_kv, block_size, hd]
                      (row 0 is the reserved null block; allocatable
                      block ids run 1..num_blocks; head-major inside a
                      block so one head's page is a whole
                      ``[block_size, hd]`` tile for the Pallas reader)
    block_tables    : [max_seqs + 1, max_blocks_per_seq] int32 — logical
                      block i of sequence s lives in physical block
                      ``block_tables[s, i]``; the last row is all null
    seq_ids         : [T] int32 — the sequence of each of the step's
                      packed tokens (``max_seqs`` = budget padding)
    positions       : [T] int32 — each token's absolute position

Physical **block 0 is reserved as the null block**: padded block-table
entries point at it and every padding token's write is redirected into
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

The engine hands each layer a :class:`RaggedLayerCache`; the layer
projects and rotates the step's rows and calls :func:`attend`, the one
place where a step's rows are written and the pages read. Who reads is
decided there from what the cache holds and says (:func:`paged_attention_impl`):
rpa on TPU, gather elsewhere — off-TPU the kernel only runs in Pallas
interpret mode, a test vehicle — and gather over int8 pools.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["LayerCacheSpec", "StateCacheSpec", "RaggedLayerCache",
           "StateLayerCache", "attend",
           "write_tokens_to_pool", "gather_pool", "ragged_gather_attention",
           "ragged_latent_gather_attention", "paged_attention_impl",
           "quantize_kv_slots", "tokens_a_row", "unpack_rows"]

#: lanes of a TPU vector register: the minor width a pool's row is laid at
LANES = 128


def tokens_a_row(width: int) -> int:
    """Tokens a pool row holds for K or V rows ``width`` wide: 2 for half
    the lanes (a head width of 64), stored two tokens to a ``LANES``-wide
    row so that the pool's minor dimension is whole lanes (XLA lays a
    ``[.., block_size, 64]`` pool out head-width major, and every flat
    write and every kernel read then copies the whole pool to another
    layout: on a TPU v5e 29 ms of a 115 ms serving step over four layers'
    pools); 1 otherwise."""
    return 2 if 2 * int(width) == LANES else 1


class LayerCacheSpec(NamedTuple):
    """What an attention layer keeps of a token in the paged cache; a
    served model states it (``model.kv_cache_spec()``: one spec for every
    layer, or a list of one a layer) and the pools are built from it
    (``serving.kv_cache.PagedKVCache``). Layers of equal spec form a
    **group**: one allocator, one block table a sequence and one kernel
    work list a step (docs/SERVING.md "Layer groups").

    ``value_dim`` None is a **latent** page (MLA): the layer writes one
    row ``[c | k_rope]`` of ``key_dim`` numbers a token under its one
    head, its values are the first ``value_cols`` columns of that same
    row, and no value pool exists.

    ``window`` None: a token sees every earlier key. An int: key ``j`` is
    visible to query ``i`` iff ``0 <= i - j < window`` (the last ``window``
    positions, the query's own among them); pages wholly behind the
    window of a sequence's next token are released by the cache manager."""
    kv_heads: int
    key_dim: int
    value_dim: object = None       # int, or None: no value pool
    value_cols: int = 0            # latent page: columns that are values
    window: object = None          # int, or None: every earlier key

    @property
    def latent(self) -> bool:
        return self.value_dim is None

    @classmethod
    def kv(cls, kv_heads: int, head_dim: int, window=None) -> "LayerCacheSpec":
        """A K and a V pool of one width (GQA/MHA layers)."""
        return cls(int(kv_heads), int(head_dim), int(head_dim),
                   window=None if window is None else int(window))


class StateCacheSpec(NamedTuple):
    """What a state-space layer keeps of a sequence: no pages, one row of
    fixed size a serving slot, whatever the sequence's length. ``conv`` is
    ``(width - 1, channels)``: the causal convolution's last inputs;
    ``ssm`` is ``(heads, head_dim, state_dim)``: the recurrent state;
    both stored in the model's dtype. A served model states it
    for such a layer in place of a :class:`LayerCacheSpec`; the layers
    that state one form the cache's **state group** (docs/SERVING.md
    "State layers")."""
    conv: tuple
    ssm: tuple


def unpack_rows(pool, width):
    """A pool of several tokens a row (:func:`tokens_a_row`) as
    ``[num_blocks + 1, n_kv, block_size, width]``; any other as it is."""
    if pool.shape[-1] == width:
        return pool
    nb, n_kv, rows, lanes = pool.shape
    return pool.reshape(nb, n_kv, rows * (lanes // width), width)


def gather_pool(pool, block_tables):
    """[B, max_blocks_per_seq * block_size, n_kv, hd] contiguous view of
    each row's paged context (the XLA-gather read path); a scale pool
    ``[num_blocks + 1, n_kv, block_size]`` gathers the same way."""
    g = jnp.swapaxes(pool[block_tables], 2, 3)  # [B, nblk, bs, n_kv, hd]
    B, nblk, bs = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape(B, nblk * bs, *g.shape[3:])


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
    # not arrays: who reads the pages (``"rpa"`` | ``"gather"``; None =
    # by platform, :func:`paged_attention_impl`), and the tensor-parallel
    # mesh the pools are sharded over, if any. The engine resolves both
    # at construction and states them on the caches it builds in its
    # trace, so two engines tracing at once cannot see each other's.
    impl: object = None
    mesh: object = None
    # not an array either: the layer's attention window
    # (``LayerCacheSpec.window``), None where it sees every earlier key.
    # ``block_tables`` and the work list are then its group's: released
    # pages are null in the table and the list never names them
    window: object = None

    def live_mask(self):
        """``[1, T]`` bool Tensor over the step's packed tokens: False at
        budget padding (the sentinel sequence id), which must choose no
        expert and count in no expert's rows."""
        from paddle_tpu import ops
        sentinel = self.block_tables.shape[0] - 1
        return ops.less_than(ops.reshape(self.seq_ids, [1, -1]),
                             ops.full([1, 1], sentinel, "int32"))

    def pools(self):
        """The arrays a step writes (pools, then scale pools; those this
        cache holds), as a layer's ``apply_op`` closure returns them."""
        return tuple(getattr(self, n) for n in self._written())

    def with_pools(self, pools):
        """This cache over the written ``pools`` (the order of
        :meth:`pools`)."""
        return self._replace(**dict(zip(self._written(), pools)))

    def _written(self):
        return [n for n in ("k_pool", "v_pool", "k_scale", "v_scale")
                if getattr(self, n) is not None]


class StateLayerCache(NamedTuple):
    """One state-space layer's view of its state rows in the unified
    serving step: row ``rows[e]`` of each pool is step entry ``e``'s (its
    slot's, one more than the slot: row 0 is the **null row** that budget
    padding resolves to and no sequence owns). An entry whose first row
    this step is at position 0 (``context_lens[e] == 0``) starts from zero
    state, so a slot's row needs no clearing between its sequences. The
    token axis' metadata is the step's, as in :class:`RaggedLayerCache`."""
    ssm: object           # [slots + 1, heads, head_dim, state_dim]
    conv: object          # [slots + 1, (width - 1) * channels]
    rows: object          # [max_seqs + 1] int32 entry -> row (0: none)
    cu_seqlens: object    # [max_seqs + 2] int32 token-span prefix sums
    context_lens: object  # [max_seqs + 1] int32 tokens cached before
    seq_ids: object       # [T] int32 token -> entry (max_seqs = pad)
    positions: object     # [T] int32 absolute position per token

    def pools(self):
        return (self.ssm, self.conv)

    def with_pools(self, pools):
        return self._replace(ssm=pools[0], conv=pools[1])


def paged_attention_impl(impl=None, *, quantized: bool = False) -> str:
    """Who reads the pages: ``impl`` where one is given (``"rpa"`` |
    ``"gather"``), else rpa on TPU and gather elsewhere; always gather
    over int8 pools (``quantized``), since the kernel streams raw pages
    and knows nothing of the scale pools. Read at TRACE time: a compiled
    serving step keeps what was resolved when it traced."""
    if impl not in (None, "rpa", "gather"):
        raise ValueError(f"attn impl {impl!r} (want rpa|gather|None)")
    if quantized:
        return "gather"
    if impl is not None:
        return impl
    return "rpa" if jax.default_backend() == "tpu" else "gather"


def _tp_mesh(mesh):
    """(mesh, mp_axis_name) when ``mesh`` has a >1 model axis, else
    None."""
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
    and back around every write (docs/SERVING.md "Pool layout"). In a
    pool of several tokens a row (:func:`tokens_a_row`) a token's ``hd``
    numbers go to its place in its row."""
    n_kv, per_page = pool.shape[1:3]
    pack = pool.shape[3] // new.shape[-1] if pool.ndim == 4 else 1
    bs = per_page * pack
    nblk = block_tables.shape[1]
    pos = positions.astype(jnp.int32)
    phys = block_tables[seq_ids, jnp.clip(pos // bs, 0, nblk - 1)]
    slot = jnp.where(phys == 0, 0, pos % bs)
    rows = (phys[:, None] * n_kv + jnp.arange(n_kv, dtype=jnp.int32)) \
        * per_page + (slot // pack)[:, None]
    row = pool.shape[3:]              # (hd,), or () in a scale pool
    flat = pool.reshape((-1,) + row)
    new = new.astype(pool.dtype)
    if pack == 2:
        # whole rows, each the token's half beside its row partner's: this
        # step's partner (the token before or after it, where it lands in
        # the same row: a sequence's tokens lie together in order), else
        # what the row holds. Partners then write the same row twice
        # alike, and the scatter stays one of whole rows (a scatter of
        # half rows is a loop of single updates on the chip)
        old = flat[rows]                                 # [T, n_kv, 2 hd]
        lead = rows[:, 0]
        after = jnp.concatenate([lead[1:] == lead[:-1], jnp.zeros(1, bool)])
        before = jnp.concatenate([jnp.zeros(1, bool), after[:-1]])
        first = (slot % 2 == 0)[:, None, None]
        w = new.shape[-1]
        lo = jnp.where(first, new, jnp.where(
            before[:, None, None], jnp.roll(new, 1, 0), old[..., :w]))
        hi = jnp.where(~first, new, jnp.where(
            after[:, None, None], jnp.roll(new, -1, 0), old[..., w:]))
        new = jnp.concatenate([lo, hi], axis=-1)
    return flat.at[rows.reshape(-1)].set(
        new.reshape((-1,) + row)).reshape(pool.shape)


def _write_step_kv(pools, news, block_tables, seq_ids, positions, tp):
    """Write each of ``news`` into its pool (K and V, and with int8 pools
    their scales). Under a model-parallel mesh (``tp``, of
    :func:`_tp_mesh`) the pools are sharded over their head axis, which
    the flat view of a pool would merge away (GSPMD would gather the pool
    to reshape it): there each shard flat-writes its own heads into its
    own pool shard under ``shard_map``."""
    def write(pools, news, bt, sid, pos):
        return tuple(write_tokens_to_pool(p, n, bt, sid, pos)
                     for p, n in zip(pools, news))

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


def _visible(positions, L, window):
    """``[T, L]`` bool: key ``j`` visible to the token at ``positions[t]``
    (causal, and under a ``window`` only its last ``window`` positions)."""
    kpos = jnp.arange(L, dtype=jnp.int32)[None, :]
    pos = positions.astype(jnp.int32)[:, None]
    visible = kpos <= pos
    if window is not None:
        visible &= kpos > pos - int(window)
    return visible


def ragged_gather_attention(q, k_pool, v_pool, block_tables, seq_ids,
                            positions, *, scale, k_scale=None,
                            v_scale=None, window=None):
    """Token-packed GQA attention via the XLA-gather fallback: gather
    every sequence's whole padded context, pick each token's row, dense
    masked softmax. Semantically identical to the rpa kernel (the parity
    oracle); costs the [T, L_max] materialization the kernel removes."""
    T, n_heads, hd = q.shape
    n_kv = k_pool.shape[1]
    grp = n_heads // n_kv
    # [max_seqs+1, L, n_kv, hd]
    keys = gather_pool(unpack_rows(k_pool, hd), block_tables)
    vals = gather_pool(unpack_rows(v_pool, hd), block_tables)
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
    visible = _visible(positions, L, window)            # [T, L]
    s = jnp.where(visible[:, None, None, :], s,
                  jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1).astype(vt.dtype)
    out = jnp.einsum("tkgl,tlkh->tkgh", w, vt)
    return out.reshape(T, n_heads, hd)


def ragged_latent_gather_attention(q, pool, block_tables, seq_ids,
                                   positions, *, value_cols, scale):
    """The gather fallback of the latent read (and the kernel's parity
    oracle): every sequence's whole padded context gathered, each token's
    row picked, dense masked softmax in float32. ``q`` [T, n_heads, kd]
    absorbed queries; returns ``[T, n_heads, value_cols]``."""
    rows = gather_pool(pool, block_tables)[seq_ids][:, :, 0]   # [T, L, kd]
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("thd,tld->thl", q.astype(jnp.float32), rows) * scale
    visible = _visible(positions, rows.shape[1], None)
    s = jnp.where(visible[:, None, :], s, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("thl,tlc->thc", w, rows[..., :value_cols])


def attend(cache, q, k, v=None, *, scale=None, value_cols=0):
    """One layer's share of the unified serving step: write the step's
    rows into ``cache``'s pools, read the pages, return ``(out, cache')``.
    The one place that knows the cache's fields; it branches on what the
    cache holds, never on who calls.

    * **K/V pools**: ``q`` [T, n_heads, hd], ``k``/``v`` [T, n_kv, hd],
      already position-encoded; ``out`` [T, n_heads, hd]. Where the cache
      carries scale pools (int8 KV) the new rows are quantized per
      (token, head) before the scatter and the read dequantizes.
    * **a latent pool** (``v_pool`` None, MLA read **absorbed**): ``q``
      [T, n_heads, kd] is ``[W_UK^T q_nope | q_rope]``, ``k`` [T, kd] the
      step's rows ``[c | k_rope]``, whose first ``value_cols`` columns
      are the values; ``out`` [T, n_heads, value_cols].

    The reader is :func:`paged_attention_impl` of ``cache.impl`` and the
    scales, and is told the cache's ``window``. ``out`` at padding tokens
    is garbage (gather) or 0 (rpa) and the caller discards it either way."""
    bt, sid, pos = cache.block_tables, cache.seq_ids, cache.positions
    work = (bt, cache.cu_seqlens, cache.context_lens, cache.step_seq,
            cache.step_blk, cache.step_tile)
    latent = cache.v_pool is None
    if latent != (v is None):
        raise NotImplementedError(
            "latent attention is served over a latent pool (v_pool None) "
            "and K/V attention over a K and a V pool")
    quantized = cache.k_scale is not None
    window = cache.window
    if latent and window is not None:
        raise NotImplementedError("latent pages are read whole: no model "
                                  "here keeps them under a window")
    rpa = paged_attention_impl(cache.impl, quantized=quantized) == "rpa"
    tp = _tp_mesh(cache.mesh)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if rpa:
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention

    if latent:
        pool = write_tokens_to_pool(cache.k_pool, k[:, None, :], bt, sid, pos)
        if not rpa:
            out = ragged_latent_gather_attention(
                q, pool, bt, sid, pos, value_cols=value_cols,
                scale=scale).astype(q.dtype)
        elif tp is not None:
            raise NotImplementedError(
                "a latent pool has one head: it is replicated, not "
                "sharded over a model-parallel axis")
        else:
            out = ragged_paged_attention(q, pool, None, *work, sm_scale=scale,
                                         value_cols=value_cols)
        return out, cache._replace(k_pool=pool)

    if quantized:
        kq, ks = quantize_kv_slots(k)
        vq, vs = quantize_kv_slots(v)
        k_pool, v_pool, k_scale, v_scale = _write_step_kv(
            (cache.k_pool, cache.v_pool, cache.k_scale, cache.v_scale),
            (kq, vq, ks, vs), bt, sid, pos, tp)
        out = ragged_gather_attention(
            q, k_pool, v_pool, bt, sid, pos, scale=scale, k_scale=k_scale,
            v_scale=v_scale, window=window).astype(q.dtype)
        return out, cache._replace(k_pool=k_pool, v_pool=v_pool,
                                   k_scale=k_scale, v_scale=v_scale)

    k_pool, v_pool = _write_step_kv((cache.k_pool, cache.v_pool), (k, v),
                                    bt, sid, pos, tp)
    if not rpa:
        out = ragged_gather_attention(q, k_pool, v_pool, bt, sid, pos,
                                      scale=scale, window=window)
    elif tp is None:
        out = ragged_paged_attention(q, k_pool, v_pool, *work, sm_scale=scale,
                                     window=window)
    else:
        # SPMD over the kernel's head dimension (ISSUE 15): Pallas is
        # opaque to GSPMD, so shard_map runs one kernel instance per mp
        # shard — q over n_heads, pools over n_kv (whole GQA groups stay
        # together because n_heads/n_kv shard by the same factor),
        # metadata replicated (every shard walks the same work list
        # under the same traced bound). Attention is embarrassingly
        # parallel across heads: no collective is introduced here (the
        # o_proj psum stays GSPMD's). The gather reader needs nothing:
        # XLA partitions it from the pool/projection shardings alone.
        from jax.sharding import PartitionSpec as P
        mesh, ax = tp
        heads = P(None, ax, None)
        pools = P(None, ax, None, None)
        out = jax.shard_map(
            lambda qa, kp, vp, *w: ragged_paged_attention(
                qa, kp, vp, *w, sm_scale=scale, window=window),
            mesh=mesh, in_specs=(heads, pools, pools) + (P(),) * len(work),
            out_specs=heads, check_vma=False)(q, k_pool, v_pool, *work)
    return out, cache._replace(k_pool=k_pool, v_pool=v_pool)
