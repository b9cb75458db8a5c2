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
                     padding tokens and sentinel work items resolve through
    cu_seqlens     : [max_seqs + 2] int32 — sequence s's new tokens
                     occupy flat positions [cu[s], cu[s+1])
    context_lens   : [max_seqs + 1] int32 — tokens already cached
                     BEFORE this step's writes, per sequence

and streams each sequence's KV **page by page with only the pages its
tokens can see** — no ``[B, L_max]`` materialization, no f32 score
tensor in HBM, online softmax in VMEM scratch.

Grid design
-----------
``grid = (n_kv_heads, n_items)`` with ``n_items`` a TRACED int32: the
kernel's trip count follows the step's live work (the pattern of
megablox ``gmm``'s ``num_active_tiles``). The flat token axis is cut into
fixed ``tile_q``-token tiles; a tile may span several ragged sequences,
so the inner grid dimension walks one host-built **flat work list** for
the whole call (``build_step_maps``), sorted by q tile. **A work item is
``(tile, sequence, run)``: a run of up to ``P`` consecutive pages of the
sequence's block-table row that the tile's tokens can see**, named in
scalar-prefetched int32 arrays (``step_blk[w]`` is the run's first page:
pages ``step_blk[w] ... + P - 1``). An item pays a grid step's fixed
cost once for ``P`` pages. On a causal walk it makes one online-softmax
update: ``P`` pages of keys under one max / exp / sum pass and one
rescale of the ``[rows, value_width]`` f32 accumulator; on a window walk
one update a page, in page order, so a row's output is bit for bit that
of one-page items wherever the runs were laid (a drafting engine lays a
window walk from the shorter context's first page). ``P`` is read off the
pool's shape and the layer's window (:func:`rpa_run_pages`), never set by
a caller: 4 for a latent pool of 512 value columns in pages of 128 (its
accumulator), 4 for a K/V pool of head width 128 in bf16 pages of 128
(an item's fetch pays for its fixed cost), 2 for the same pool under a
window of one page.

The q and output BlockSpecs are indexed by the item's tile, and the pool
is handed to ``pallas_call`` under ``P`` BlockSpecs of one page each,
whose index maps chase ``block_tables[step_seq[w], step_blk[w] + i]``
straight from SMEM — the pipeline's revolving buffers double-buffer plain
page DMAs exactly like the classic paged kernel
(boom_attention_tricks.md §9–11), with no manual descriptors, and a q
tile is fetched once for its whole run of items. A run's pages past the
sequence's last resolve to the null page through the table's null padding
(past the table's width the index is clamped to its last) and the causal
mask kills their keys: ``kpos`` counts from the run's first page. The
list is in CSR form: tile ``j`` owns items ``[step_tile[j], step_tile[j +
1])`` and ``n_items = step_tile[-1]``; the online-softmax scratch is
initialised at a tile's first item and the output block written at its
last. A tile lists a sequence's runs only up to its **causal horizon**
there (the pages that hold a key its last token of that sequence may
see), not the pages the step's later tiles write. A walk's runs are laid
from its first page: page 0 for a causal walk, and under a layer's
attention **window** (key ``j`` visible to query ``i`` iff ``0 <= i - j <
window``) the page that holds the first key the tile's first token of
that sequence can see, so a tile walks ``O(window + tile_q)`` keys
however long the sequence is, a walk that spans at most ``P`` pages is
one item wherever it starts, and the pages the cache manager released
behind the window (null in the table) are never named. The windowed call
is ``rpa_win`` in a trace. Every tile owns at least one item — a
tile of only padding tokens gets one sentinel item (sequence
``max_seqs``, the null page, no compute) — so every output block is
written and padding rows stay exactly 0. Rows of the score tile that
don't belong to the item's sequence are masked dead (their
online-softmax state is provably untouched: p = 0 rows with α folded to
carry ``m``/``l`` through), so prefill chunks (in-chunk causal via
``kpos <= ctx + (t - cu[s])``) and decode rows coexist in one tile.

The arrays are sized by the static :func:`rpa_max_items` =
``ceil(max_blocks_per_seq / P) * (num_q_tiles + max_seqs)`` (under a
window, the pages a walk can span in place of the table's width): a
sequence is re-walked once per tile it spans, and all sequences together
span at most ``num_q_tiles + n_seqs - 1`` tiles. The bound never uses the
pool's size (sequences that share prefix pages are distinct rows that
name the same pages), and it sizes arrays only — nothing walks it. A
caller that holds per-tile maps ``[num_q_tiles, k]`` padded with the
sentinel (``rpa_max_steps`` wide, each entry a run's first page the same
way) may hand those instead: the wrapper compacts them into the same
flat list on the device and both reach the one ``pallas_call``.

Off-TPU the kernel runs in Pallas interpret mode, which is what tier-1
parity tests exercise on the CPU mesh (`tests/test_ragged_paged_attention.py`);
on the chip ``chip_smoke.py`` compares the compiled kernel against the
gather reader. ``tile_q`` registers through ``ops/pallas/autotune.py``
exactly like ``flash_attention.py``'s block sizes.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention", "build_step_maps", "StepMaps",
           "rpa_tile_q", "rpa_max_items", "rpa_max_steps", "rpa_run_pages",
           "default_tile_q"]

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


#: bytes a K/V work item fetches a kv head, at least: what the pipeline
#: moves in the fixed cost of one grid step. Measured on a v5e (PR 34,
#: the kernel alone at four cells' shapes, runs of 1 to 8 pages of
#: ``[128, 128]`` bf16): a grid step costs 0.41-0.51 us besides 0.11-0.14
#: us for each 64 KiB K and V page it fetches, so 3.7 pages' fetch pays
#: for the fixed cost
_ITEM_BYTES = 256 * 1024
#: the longest run: the score tile ``[rows, P * block_size]`` f32 and the
#: run's page buffers stay small in VMEM
_MAX_RUN = 8


def rpa_run_pages(block_size: int, key_width: int, value_width: int,
                  itemsize: int, *, latent: bool = False,
                  window=None) -> int:
    """Pages a work item names (``P``), read off the pool's shape and the
    layer's window, never set. A run of ``P`` pages spreads a cost paid
    once an item over its pages:

    - the rescale of a latent item's ``[rows, value_width]`` f32
      accumulator: keys an item at least the value width, ``value_width //
      block_size`` (at or under one accumulator element a score element, a
      flash kernel's ratio);
    - for a K/V pool, the grid step's fixed cost: the K and V pages of an
      item fetch at least ``_ITEM_BYTES``. A latent item's rows are every
      query head over its one page, and its accumulator sets its run.

    Under a ``window`` the run is no longer than the pages one token's
    window can span, ``ceil((window - 1) / block_size) + 1``: a longer
    one fetches pages no token of a decode walk sees. At most
    ``_MAX_RUN``. A latent pool of 512 value columns in pages of 128: 4;
    a K/V pool of head width 128 in bf16 pages of 128: 4, under a window
    of one page 2; 16-token pages: 8."""
    run = int(value_width) // int(block_size)
    if not latent:
        page = int(block_size) * (int(key_width) + int(value_width)) \
            * int(itemsize)
        run = max(run, -(-_ITEM_BYTES // page))
    if window is not None:
        run = min(run, -(-(int(window) - 1) // int(block_size)) + 1)
    return max(1, min(run, _MAX_RUN))


def rpa_max_items(num_tiles: int, max_seqs: int, max_blocks_per_seq: int,
                  run_pages: int = 1, *, window=None, tile_q: int = 0,
                  block_size: int = 0) -> int:
    """Static length of the flat work list's arrays. An item is a run of
    up to ``run_pages`` consecutive pages of one sequence for one q tile.
    Sequences are packed back to back, so a sequence is walked once per q
    tile it spans and all ``n`` sequences together span at most
    ``num_tiles + n - 1`` tiles; each walk streams at most
    ``ceil(max_blocks_per_seq / run_pages)`` runs, and a tile without
    work adds one sentinel item where it adds no walk. Sound when
    sequences share prefix pages (the pool's size is no part of it). It
    sizes arrays only: the kernel walks the live length. Under a
    ``window`` (with the list's ``tile_q`` and ``block_size``) a walk
    spans the keys from the first its first token sees to its last
    token's own, ``window + tile_q - 1`` of them: at most
    ``ceil((window + tile_q) / block_size) + 1`` pages, where that is
    fewer than the table's width, laid in runs from the first."""
    pages = max_blocks_per_seq
    if window is not None:
        pages = min(pages, -(-(int(window) + tile_q) // block_size) + 1)
    # a walk's runs are laid from its first page
    return -(-pages // run_pages) * (num_tiles + max_seqs)


def rpa_max_steps(tile_q: int, max_blocks_per_seq: int,
                  pool_blocks: int | None = None, run_pages: int = 1) -> int:
    """Width of per-tile ``[num_q_tiles, k]`` maps, for a caller that
    hands :func:`ragged_paged_attention` those instead of the flat list:
    a tile of ``tile_q`` tokens overlaps at most ``tile_q`` sequences and
    each streams at most ``ceil(max_blocks_per_seq / run_pages)`` runs.
    ``pool_blocks`` is accepted and ignored: sequences that share prefix
    pages walk the same pages once each, so the pool's size bounds
    nothing."""
    del pool_blocks
    return max(1, tile_q * -(-max_blocks_per_seq // run_pages))


class StepMaps(NamedTuple):
    """The flat work list of one engine step (:func:`build_step_maps`)."""
    step_seq: np.ndarray    # [max_items] int32 — item w's sequence
    step_blk: np.ndarray    # [max_items] int32 — item w's run's first page
    step_tile: np.ndarray   # [num_q_tiles + 1] int32 — CSR tile pointers
    live: int               # items that name a real (sequence, run)
    pages: int              # real pages the live items name (<= P a run)
    pages_causal: int = 0   # pages the same walks name with no window

    @property
    def walked(self) -> int:
        """The kernel's inner grid bound: ``live`` + tiles without work."""
        return int(self.step_tile[-1])


def build_step_maps(cu_seqlens, kv_lens, *, total_tokens, tile_q,
                    block_size, max_items, max_seqs,
                    run_pages=1, window=None, slack=None) -> StepMaps:
    """Host-side (numpy) kernel work list for one engine step.

    ``cu_seqlens``: int array ``[num_seqs + 1]`` — prefix sums of the
    LIVE sequences' new-token counts (packed order). ``kv_lens``: int
    array ``[num_seqs]`` — each sequence's total KV length after this
    step's writes (``context_len + new_len``). ``run_pages``: the pages an
    item names (:func:`rpa_run_pages` of the pool the kernel will read).

    Returns :class:`StepMaps`: the items sorted by q tile, tile ``j``'s
    in ``[step_tile[j], step_tile[j + 1])``. An item ``(sequence, p)``
    names the run of pages ``[p, p + run_pages)`` of the sequence's
    block-table row (``step_blk`` is ``p``, the run's first page), and a
    tile lists for each of its sequences the runs up to the tile's
    **causal horizon** there: the pages that hold a key the tile's last
    token of that sequence may see, ``ceil((context + tokens of the
    sequence up to the tile's end) / block_size)`` — not the pages the
    step's later tiles write. The runs are laid from the walk's first
    page: page 0, or with ``window`` the page that holds the first key the
    tile's **first** token of that sequence can see (key ``max(0, context
    + tokens of the sequence before the tile's first - window + 1)``), so
    a walk of at most ``run_pages`` pages is one item wherever it starts.
    A run's pages past the horizon are masked inside the kernel.
    ``pages_causal`` counts what the same walks would name without the
    window. ``slack`` (``[num_seqs]``, default nought): a sequence's
    context may turn out that many keys shorter than ``kv_lens`` says (an
    acceptance the device has not reported yet); a windowed walk then
    starts where the shorter context's would, and its further end is the
    longer's. A tile no
    sequence reaches owns one sentinel item (sequence ``max_seqs``, the
    all-null block-table row); the arrays' tail past ``step_tile[-1]``
    is never walked and carries the same.
    """
    cu = [int(c) for c in cu_seqlens]
    # context + the sequence's own tokens before flat position t is
    # base[s] + t: keys a token at t - 1 may see
    base = [int(kv) - cu[s + 1] for s, kv in enumerate(kv_lens)]
    num_seqs = len(base)
    if total_tokens % tile_q:
        raise ValueError(
            f"total_tokens {total_tokens} not a multiple of tile_q "
            f"{tile_q}")
    num_tiles = total_tokens // tile_q
    seqs, blks, step_tile = [], [], [0]
    first = empty_tiles = pages = pages_causal = 0
    for j in range(num_tiles):
        lo, hi = j * tile_q, (j + 1) * tile_q
        # sequences are packed in order: the tile's are a contiguous run
        while first < num_seqs and cu[first + 1] <= lo:
            first += 1
        s = first
        while s < num_seqs and cu[s] < hi:
            if cu[s] < cu[s + 1]:   # a new_len == 0 slot owns no tokens
                seen = -(-(base[s] + min(hi, cu[s + 1])) // block_size)
                # the first page that holds a key the tile's first token
                # of the sequence (position base + max(lo, cu)) can see;
                # the walk's runs are laid from there
                page0 = 0 if window is None else \
                    max(0, base[s] - (slack[s] if slack else 0)
                        + max(lo, cu[s]) - window + 1) // block_size
                firsts = range(page0, seen, run_pages)
                seqs += [s] * len(firsts)
                blks += firsts
                pages += seen - page0
                pages_causal += seen
            s += 1
        if len(seqs) == step_tile[-1]:
            seqs.append(max_seqs)
            blks.append(0)
            empty_tiles += 1
        step_tile.append(len(seqs))
    walked = len(seqs)
    if walked > max_items:
        raise ValueError(
            f"the step needs {walked} work items > max_items {max_items} "
            f"— the scheduler admitted more pages than the static bound "
            f"(bug)")
    step_seq = np.full((max_items,), max_seqs, np.int32)
    step_blk = np.zeros((max_items,), np.int32)
    step_seq[:walked] = seqs
    step_blk[:walked] = blks
    return StepMaps(step_seq, step_blk, np.asarray(step_tile, np.int32),
                    walked - empty_tiles, pages, pages_causal)


def _flatten_maps(step_seq, step_blk, max_seqs):
    """Per-tile maps ``[num_tiles, k]`` (dead steps carry the ``max_seqs``
    sentinel) → the flat list ``(step_seq, step_blk, step_tile)`` on the
    device: live entries compacted in row-major order, a tile with none
    keeping its first (sentinel) entry."""
    num_tiles, k = step_seq.shape
    live = step_seq < max_seqs
    keep = live | ((jnp.arange(k) == 0)[None, :]
                   & ~jnp.any(live, axis=1, keepdims=True))
    step_tile = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(jnp.sum(keep, axis=1, dtype=jnp.int32))])
    # a stable sort on "dropped" moves the kept entries to the front in
    # their order; the tail past step_tile[-1] is never walked
    order = jnp.argsort(~keep.reshape(-1), stable=True)
    return (step_seq.reshape(-1)[order], step_blk.reshape(-1)[order],
            step_tile)


# =========================== kernel ==========================================
def _rpa_kernel(to_ref, ss_ref, sb_ref, tp_ref, bt_ref, cu_ref, ctx_ref,
                q_ref, *rest, tile_q, group, block_size, max_seqs,
                sm_scale, run_pages, value_cols=None, window=None, pack=1):
    # ``rest``: the run's ``run_pages`` key pages (one ref a page), then
    # as many value pages, then the output and the scratch. With
    # ``value_cols`` the page is a latent one and its values are the
    # first ``value_cols`` columns of the key page itself (one pool, one
    # DMA a page); there are no value refs then
    k_refs, rest = rest[:run_pages], rest[run_pages:]
    if value_cols is None:
        v_refs, rest = rest[:run_pages], rest[run_pages:]
    o_ref, m_sc, l_sc, acc_sc = rest
    w = pl.program_id(1)
    j = to_ref[w]
    rows = tile_q * group

    @pl.when(w == tp_ref[j])
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    ss = ss_ref[w]

    def page(ref):
        """A page as ``[block_size, width]`` rows; a page of ``pack``
        tokens a row (``ops/paged_attention.tokens_a_row``) as its
        lane-groups one under the other: the keys of in-row place 0, then
        those of place 1, ... (``key_pos`` gives each its position)."""
        rows_ = ref[...]
        if pack == 1:
            return rows_
        width = rows_.shape[1] // pack
        return jnp.concatenate([rows_[:, i * width:(i + 1) * width]
                                for i in range(pack)], axis=0)

    def key_pos(keys):
        """``[rows, keys]``: each key's place after ``first`` in pages laid
        out by ``page`` (shifts and masks: ``block_size`` and ``pack`` are
        powers of two where ``pack`` > 1)."""
        c = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        if pack == 1:
            return c
        per_page = block_size // pack
        lp, lr = per_page.bit_length() - 1, pack.bit_length() - 1
        lb = block_size.bit_length() - 1
        return ((c >> lb) << lb) + ((c & (per_page - 1)) << lr) \
            + ((c >> lp) & (pack - 1))

    def update(q, k, v, first):
        """One online-softmax update over ``k``'s keys (``first`` the
        position of the first)."""
        keys = k.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        # row r of the tile is (token j*tile_q + r//group, head r%group).
        # The three bounds below are the token-space tests
        #   start <= tok < end   and   kpos <= ctx + tok - start
        # multiplied through by ``group`` (r//g >= a  <=>  r >= a*g), so
        # the kernel needs no vector integer division
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
        lo = cu_ref[ss] - j * tile_q        # sequence span, tile-relative
        hi = cu_ref[ss + 1] - j * tile_q
        kpos = first + key_pos(keys)
        # one bound covers prior context, in-chunk causality, page
        # raggedness and the run's pages past the sequence's last (null
        # pages, or under the clamp the table's last: their ``kpos`` lies
        # beyond every token's position)
        visible = (r >= lo * group) & (r < hi * group) & \
            (r >= (kpos - ctx_ref[ss] + lo) * group)
        if window is not None:
            # kpos > ctx + tok - start - window, through ``group`` too
            visible &= r < (kpos - ctx_ref[ss] + lo + window) * group
        s = jnp.maximum(jnp.where(visible, s, _MASK_VALUE), _MASK_VALUE)
        m_prev = m_sc[:, :1]                            # lane-replicated
        l_prev = l_sc[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # rows with no live key in THIS update (another sequence's rows,
        # or causally-dead decode rows) would contribute exp(MASK-MASK)=1
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

    @pl.when(ss < max_seqs)
    def _compute():
        sb = sb_ref[w]
        q = q_ref[...]                                  # [rows, hd]
        if window is not None:
            # a window walk starts where its first token's window does,
            # which a drafting engine knows only to within its pending
            # draft: one update a page, in page order, so a row's output
            # does not depend on where the runs were laid (a page wholly
            # masked for the row leaves its state as it was). The run
            # still pays the grid step's fixed cost once
            for i in range(run_pages):
                update(q, page(k_refs[i]), page(v_refs[i]),
                       (sb + i) * block_size)
        else:
            # a causal walk's runs lie on one grid from page 0: its pages
            # back to back, one update and one rescale of the [rows, vd]
            # f32 accumulator a run
            def run_of(refs):
                pages = [page(r) for r in refs]
                return pages[0] if run_pages == 1 else \
                    jnp.concatenate(pages, axis=0)
            k = run_of(k_refs)
            update(q, k, run_of(v_refs) if value_cols is None
                   else k[:, :value_cols], sb * block_size)

    @pl.when(w + 1 == tp_ref[j + 1])
    def _finish():
        # rows that saw no live step (padding tokens): exact 0 output
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def _rpa_call(q_heads, k_pool, v_pool, step_seq, step_blk, step_tile,
              block_tables, cu_seqlens, context_lens, *, tile_q, group,
              sm_scale, value_cols=None, window=None):
    """``q_heads`` [n_kv, T*group, hd] (token-major rows per kv head) →
    out in the same layout, ``vd`` wide: the value pool's width, or with
    ``v_pool`` None (a latent pool) the ``value_cols`` first columns of
    the key page."""
    n_kv, tg, hd = q_heads.shape
    latent = v_pool is None
    # a K/V pool may hold ``pack`` tokens a row (a head width under the
    # lanes: ``ops/paged_attention.tokens_a_row``); a page is then
    # ``block_size / pack`` rows of the pool's lanes
    pack = 1 if latent else k_pool.shape[3] // hd
    vd = int(value_cols) if latent else v_pool.shape[3] // pack
    rows_a_page, block_size = k_pool.shape[2], k_pool.shape[2] * pack
    if pack > 1 and (pack & (pack - 1) or block_size & (block_size - 1)):
        raise ValueError(f"{pack} tokens a row in pages of {block_size}: "
                         f"both are powers of two")
    run_pages = rpa_run_pages(block_size, k_pool.shape[3] // pack, vd,
                              k_pool.dtype.itemsize, latent=latent,
                              window=window)
    max_seqs = block_tables.shape[0] - 1
    table_width = block_tables.shape[1]
    rows = tile_q * group
    # item w's tile: the tile pointers at or below w, less the first
    # (items past the live length read the last tile; none is walked)
    tile_of = jnp.sum(
        step_tile[None, 1:-1] <= jnp.arange(
            step_seq.shape[0], dtype=jnp.int32)[:, None],
        axis=1, dtype=jnp.int32)

    kernel = functools.partial(
        _rpa_kernel, tile_q=tile_q, group=group, block_size=block_size,
        max_seqs=max_seqs, sm_scale=sm_scale, run_pages=run_pages,
        value_cols=vd if latent else None, window=window, pack=pack)

    def q_map(h, w, to, ss, sb, tp, bt, cu, ctx):
        return (h, to[w], 0)

    def page_map(i):
        def kv_map(h, w, to, ss, sb, tp, bt, cu, ctx):
            # scalar-prefetch chase: physical page i of this item's run,
            # which starts at page ``sb[w]``. Past the sequence's pages
            # the table's null padding gives the null page 0 (past the
            # table's width the index is clamped to its last: a page the
            # mask kills); a sentinel item resolves through the sentinel
            # table row
            page = sb[w] + i
            if run_pages > 1:
                page = jnp.minimum(page, table_width - 1)
            return (bt[ss[w], page], h, 0, 0)
        return kv_map

    def page_specs(width):
        # one page a BlockSpec: the pipeline double-buffers plain page
        # DMAs, a run is ``run_pages`` of them side by side
        return [pl.BlockSpec((None, None, rows_a_page, width * pack),
                             page_map(i)) for i in range(run_pages)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        # the inner bound is traced: the live length of the work list
        grid=(n_kv, step_tile[-1]),
        in_specs=[pl.BlockSpec((None, rows, hd), q_map)] + page_specs(hd)
        + ([] if latent else page_specs(vd)),
        out_specs=pl.BlockSpec((None, rows, vd), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, vd), jnp.float32),
        ],
    )
    pools = (k_pool,) * run_pages + (() if latent else (v_pool,) * run_pages)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_kv, tg, vd), q_heads.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        # the device op's name in a profiler trace (``rpa.N custom-call``,
        # ``rpa_mla.N custom-call`` over a latent pool, ``rpa_win.N`` under
        # a window; without it the op is named after the jitted caller)
        name="rpa_mla" if latent else "rpa" if window is None else "rpa_win",
    )(tile_of, step_seq, step_blk, step_tile, block_tables, cu_seqlens,
      context_lens, q_heads, *pools)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, cu_seqlens,
                           context_lens, step_seq, step_blk,
                           step_tile=None, *, sm_scale=None,
                           value_cols=None, window=None):
    """GQA attention for a token-packed ragged batch over paged KV.

    With ``v_pool`` None the pool is a **latent** one (MLA read absorbed:
    ``k_pool`` ``[num_blocks + 1, 1, block_size, kd]`` holds a token's
    ``[c | k_rope]`` row, ``q`` is ``[W_UK^T q_nope | q_rope]``): the
    values are the first ``value_cols`` columns of the key page, every
    query head shares the one page, and the output is
    ``[total_tokens, n_heads, value_cols]``. Same work list, same body;
    the kernel is then named ``rpa_mla`` in a trace.

    ``window`` (static): key ``j`` is visible to query ``i`` iff ``0 <= i
    - j < window``; the work list should then come from
    ``build_step_maps(window=...)`` (a causal list is correct too, and
    walks pages the mask kills). Named ``rpa_win`` in a trace.

    ``q`` [total_tokens, n_heads, hd]; pools
    ``[num_blocks + 1, n_kv, block_size, hd]`` (this step's new K/V
    already scattered in — the kernel is a pure read); metadata as
    documented in the module docstring. The work list is either the
    flat one ``build_step_maps`` produces (1-D ``step_seq`` / ``step_blk``
    with the CSR ``step_tile`` ``[num_q_tiles + 1]``, in which every tile
    owns at least one item) or, with ``step_tile`` None, per-tile maps
    ``[num_q_tiles, k]`` padded with the ``max_seqs`` sentinel, which are
    flattened here. Returns ``[total_tokens, n_heads, hd]``. Outputs at
    padding tokens (sentinel ``seq_id``) are exactly 0.
    """
    T, n_heads, hd = q.shape
    n_kv = k_pool.shape[1]
    if n_heads % n_kv:
        raise ValueError(
            f"q heads {n_heads} must be a multiple of kv heads {n_kv}")
    group = n_heads // n_kv
    if (v_pool is None) != (value_cols is not None):
        raise ValueError("value_cols goes with a latent pool (v_pool None)")
    # (a pool of two tokens a row is twice the head width wide)
    vd = int(value_cols) if v_pool is None \
        else v_pool.shape[3] // (k_pool.shape[3] // hd)
    step_seq = jnp.asarray(step_seq, jnp.int32)
    step_blk = jnp.asarray(step_blk, jnp.int32)
    if step_tile is None:
        step_seq, step_blk, step_tile = _flatten_maps(
            step_seq, step_blk, block_tables.shape[0] - 1)
    step_tile = jnp.asarray(step_tile, jnp.int32)
    num_tiles = step_tile.shape[0] - 1
    if num_tiles <= 0 or T % num_tiles:
        raise ValueError(
            f"the work list has {num_tiles} tiles for {T} tokens")
    tile_q = T // num_tiles
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    # [T, n_heads, hd] -> [n_kv, T*group, hd], rows token-major within a
    # kv head so q tile j covers exactly tokens [j*tile_q, (j+1)*tile_q)
    qh = q.reshape(T, n_kv, group, hd).transpose(1, 0, 2, 3) \
          .reshape(n_kv, T * group, hd)
    out = _rpa_call(
        qh, k_pool, v_pool, step_seq, step_blk, step_tile,
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(cu_seqlens, jnp.int32),
        jnp.asarray(context_lens, jnp.int32),
        tile_q=tile_q, group=group, sm_scale=float(sm_scale),
        value_cols=value_cols,
        window=None if window is None else int(window))
    return out.reshape(n_kv, T, group, vd).transpose(1, 0, 2, 3) \
              .reshape(T, n_heads, vd)


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
        run = rpa_run_pages(block_size, head_dim, head_dim,
                            jnp.dtype(dtype).itemsize)
        ssq, sbk, stl = build_step_maps(
            cu[:len(new_lens) + 1], kv_lens, total_tokens=T,
            tile_q=tile, block_size=block_size,
            max_items=rpa_max_items(T // tile, max_seqs,
                                    max_blocks_per_seq, run),
            max_seqs=max_seqs, run_pages=run)[:3]
        with jax.ensure_compile_time_eval():
            dt = jnp.dtype(dtype)
            q0 = jnp.zeros((T, n_heads, head_dim), dt)
            kp = jnp.zeros((pool_blocks + 1, n_kv, block_size, head_dim),
                           dt)
        return aot_runner(
            lambda qa, kpa, vpa: ragged_paged_attention(
                qa, kpa, vpa, bt, cu, ctx_arr, ssq, sbk, stl),
            q0, kp, kp)

    # default first (a tie keeps it); shorter tiles would leave partial
    # sublane tiles in the q block
    cands = [default] + [t for t in _TILE_CANDIDATES if t > default]
    return autotune("ragged_paged_attention", sig, cands, build, default)
