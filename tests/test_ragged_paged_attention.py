"""Ragged Paged Attention kernel (ISSUE 8 tentpole).

Parity contract, all tier-1 cheap (interpret mode on the CPU mesh, tiny
shapes — the 870s tier-1 cutoff counts dots):

* kernel vs gather fallback vs an eager per-sequence oracle on random
  ragged mixes of prefill chunks and decode rows, across block sizes
  {8, 16}, GQA ratios {1, 4}, and metadata rows with ``new_len == 0``
  (padding slots contribute no tokens and no kernel work);
* token-level equality through ``ServingEngine`` greedy decode under
  BOTH settings of the impl knob — the engine-level acceptance check
  (the preemption/resume variant rides the slow lane);
* the host-side work-list builder's invariants (an item is a run of P
  pages a tile can see: every visible (token, key) pair in exactly one
  item of its tile, no item beyond the tile's causal horizon, one
  sentinel item for a tile without work, the static bound honored under
  adversarial packings, a window walk laid in runs from its first page),
  both pool forms against their gather reader and the eager reference at
  P in {1, 2, 4} and at the rule's own P, under windows too, and the
  rule's values at the cells' pool shapes;
* the kernel's dynamic grid bound: parity where the work list is short,
  long, absent for whole tiles or names shared pages, in both forms the
  wrapper takes, and a Mosaic compile for a described v5e at the serving
  cell's shapes;
* the step's K/V write: the one writer against a numpy loop for every
  pool form it serves, and the compiled step holds no instruction of a
  pool's shape but parameter, write and bitcast (no whole-pool copy).
"""
import contextlib
import importlib
import json
import os
import re
from unittest import mock

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.paged_attention import (
    RaggedLayerCache, attend, paged_attention_impl, ragged_gather_attention,
    ragged_latent_gather_attention, write_tokens_to_pool)
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    build_step_maps, default_tile_q, ragged_paged_attention, rpa_max_items,
    rpa_max_steps, rpa_run_pages)
from paddle_tpu.serving import ServingEngine

RPA = importlib.import_module("paddle_tpu.ops.pallas.ragged_paged_attention")


# ---------------- raw kernel parity ------------------------------------------
def _ragged_case(rng, seqs, block_size, n_kv, grp, hd=16, tile_q=8,
                 mbps=6, pool_blocks=24, pad_tiles=0, shared=(),
                 value_cols=None, window=None, run=None, dtype=np.float32):
    """Build one token-packed ragged scenario: ``seqs`` is a list of
    ``(new_len, context_len)`` — new_len 0 models a padding slot whose
    metadata row exists but owns no tokens. ``pad_tiles`` appends q tiles
    of only padding tokens; ``shared`` lists ``(s, s0, n_pages)``:
    sequence ``s`` names the first ``n_pages`` pages of the earlier
    ``s0`` as its own prefix (and holds the same keys there). With
    ``value_cols`` the K pool is read as a latent one (``n_kv`` 1): the
    values are a key row's first ``value_cols`` columns. Under a
    ``window`` the pages wholly behind a sequence's window are released:
    null in its table, their rows junk (another sequence's by now). The
    work list's items are runs of the pages the kernel reads off those
    shapes (``rpa_run_pages``), or of ``run`` pages where it is given
    (:func:`_run_rpa` then holds the kernel to it). Returns everything
    the two impls and the eager oracle need."""
    n_heads = n_kv * grp
    max_seqs = len(seqs) + 1          # one extra never-used row
    total_new = sum(n for n, _ in seqs)
    T = (-(-max(total_new, 1) // tile_q) + pad_tiles) * tile_q
    shared = {s: (s0, npg) for s, s0, npg in shared}

    bt = np.zeros((max_seqs + 1, mbps), np.int32)
    nxt = 1
    kv_lens = []
    for s, (n, c) in enumerate(seqs):
        kv = n + c
        kv_lens.append(kv)
        npg = -(-kv // block_size) if kv else 0
        s0, pre = shared.get(s, (0, 0))
        bt[s, :pre] = bt[s0, :pre]
        bt[s, pre:npg] = np.arange(nxt, nxt + npg - pre)
        nxt += npg - pre
    assert nxt - 1 <= pool_blocks

    cu = np.zeros(max_seqs + 2, np.int32)
    cu[1:len(seqs) + 1] = np.cumsum([n for n, _ in seqs])
    cu[len(seqs) + 1:] = cu[len(seqs)]
    ctx = np.zeros(max_seqs + 1, np.int32)
    ctx[:len(seqs)] = [c for _, c in seqs]
    sid = np.full(T, max_seqs, np.int32)
    pos = np.zeros(T, np.int32)
    off = 0
    for s, (n, c) in enumerate(seqs):
        sid[off:off + n] = s
        pos[off:off + n] = c + np.arange(n)
        off += n

    kp = np.zeros((pool_blocks + 1, n_kv, block_size, hd), np.float32)
    vp = np.zeros_like(kp)
    full_k, full_v = [], []
    for s, (n, c) in enumerate(seqs):
        fk = rng.randn(n + c, n_kv, hd).astype(np.float32)
        fv = rng.randn(n + c, n_kv, hd).astype(np.float32)
        if s in shared:
            s0, pre = shared[s]
            assert pre * block_size <= min(c, seqs[s0][1])
            fk[:pre * block_size] = full_k[s0][:pre * block_size]
            fv[:pre * block_size] = full_v[s0][:pre * block_size]
        if dtype != np.float32:       # the oracle sees what the pool holds
            fk, fv = (np.asarray(jnp.asarray(a, dtype).astype(jnp.float32))
                      for a in (fk, fv))
        full_k.append(fk)
        full_v.append(fv)
        for t in range(c):            # prior context from earlier steps
            kp[bt[s, t // block_size], :, t % block_size] = fk[t]
            vp[bt[s, t // block_size], :, t % block_size] = fv[t]
    if window is not None:
        for s, (n, c) in enumerate(seqs):
            behind = bt[s, :max(0, c - window + 1) // block_size]
            kp[behind], vp[behind] = 1e3, -1e3
            bt[s, :len(behind)] = 0
    q = rng.randn(T, n_heads, hd).astype(np.float32)
    if dtype != np.float32:
        q = np.asarray(jnp.asarray(q, dtype).astype(jnp.float32))
    knew = np.zeros((T, n_kv, hd), np.float32)
    vnew = np.zeros((T, n_kv, hd), np.float32)
    off = 0
    for s, (n, c) in enumerate(seqs):
        knew[off:off + n] = full_k[s][c:]
        vnew[off:off + n] = full_v[s][c:]
        off += n

    kp2, vp2 = (write_tokens_to_pool(
        jnp.asarray(pool, dtype), jnp.asarray(new, dtype), jnp.asarray(bt),
        jnp.asarray(sid), jnp.asarray(pos)) for pool, new in ((kp, knew),
                                                              (vp, vnew)))
    if value_cols is not None:
        assert n_kv == 1
        full_v = [fk[..., :value_cols] for fk in full_k]
    rule = rpa_run_pages(block_size, hd, value_cols or hd,
                         np.dtype(dtype).itemsize,
                         latent=value_cols is not None, window=window)
    run = rule if run is None else run
    maps = build_step_maps(cu[:len(seqs) + 1], kv_lens,
                           total_tokens=T, tile_q=tile_q,
                           block_size=block_size,
                           max_items=rpa_max_items(
                               T // tile_q, max_seqs, mbps, run,
                               window=window, tile_q=tile_q,
                               block_size=block_size),
                           max_seqs=max_seqs, run_pages=run, window=window)
    return dict(q=q, kp=kp2, vp=vp2, bt=bt, cu=cu, ctx=ctx, sid=sid,
                pos=pos, maps=maps, full_k=full_k, full_v=full_v,
                seqs=seqs, max_seqs=max_seqs, grp=grp, hd=hd,
                tile_q=tile_q, kv_lens=kv_lens, block_size=block_size,
                run=run, forced=run != rule, mbps=mbps,
                value_cols=value_cols, window=window, dtype=dtype)


def _run_rpa(c, maps=None):
    """The kernel over case ``c``'s pools; where the case forced its run
    length, the kernel's rule is held to it for the call."""
    ssq, sbk, stl = (maps or c["maps"])[:3]
    latent = c["value_cols"] is not None
    forced = mock.patch.object(RPA, "rpa_run_pages",
                               lambda *a, **k: c["run"]) \
        if c["forced"] else contextlib.nullcontext()
    with forced:
        out = ragged_paged_attention(
            jnp.asarray(c["q"], c["dtype"]), c["kp"],
            None if latent else c["vp"], jnp.asarray(c["bt"]),
            jnp.asarray(c["cu"]), jnp.asarray(c["ctx"]), ssq, sbk, stl,
            value_cols=c["value_cols"], window=c["window"])
    return np.asarray(out.astype(jnp.float32))


def _run_gather(c):
    args = [jnp.asarray(c[k]) for k in ("bt", "sid", "pos")]
    scale = 1.0 / np.sqrt(c["hd"])
    q = jnp.asarray(c["q"], c["dtype"])
    if c["value_cols"] is not None:
        return np.asarray(ragged_latent_gather_attention(
            q, c["kp"], *args, value_cols=c["value_cols"],
            scale=scale).astype(jnp.float32))
    return np.asarray(ragged_gather_attention(
        q, c["kp"], c["vp"], *args, scale=scale,
        window=c["window"]).astype(jnp.float32))


def _eager_oracle(case):
    """Per-sequence dense softmax over the contiguous K/V (the keys a
    token's window lets it see) — the ground truth both paged impls must
    match."""
    q, seqs = case["q"], case["seqs"]
    grp, hd = case["grp"], case["hd"]
    scale = 1.0 / np.sqrt(hd)
    ref = np.zeros((q.shape[0], q.shape[1], case["value_cols"] or hd),
                   np.float32)
    off = 0
    for s, (n, c) in enumerate(seqs):
        K, V = case["full_k"][s], case["full_v"][s]
        for i in range(n):
            t = off + i
            lo = 0 if case["window"] is None else \
                max(0, c + i - case["window"] + 1)
            kvis, vvis = K[lo:c + i + 1], V[lo:c + i + 1]
            for h in range(q.shape[1]):
                kh = h // grp
                sc = (kvis[:, kh] @ q[t, h]) * scale
                w = np.exp(sc - sc.max())
                w /= w.sum()
                ref[t, h] = w @ vvis[:, kh]
        off += n
    return ref


@pytest.mark.parametrize("block_size,grp", [(8, 1), (8, 4), (16, 1),
                                            (16, 4)])
def test_kernel_matches_gather_and_eager(block_size, grp):
    """RPA (interpret) vs gather vs eager on a random ragged mix:
    prefill chunks crossing q-tiles and pages, decode rows at varied
    context depths, and a new_len == 0 padding slot in the middle."""
    rng = np.random.RandomState(block_size * 10 + grp)
    seqs = [(5, 0), (1, 2 * block_size + 3), (0, 0), (1, 3),
            (9, block_size)]
    c = _ragged_case(rng, seqs, block_size, n_kv=2, grp=grp)
    out_rpa = _run_rpa(c)
    out_g = _run_gather(c)
    ref = _eager_oracle(c)
    valid = c["sid"] < c["max_seqs"]
    np.testing.assert_allclose(out_rpa[valid], ref[valid], atol=2e-5)
    np.testing.assert_allclose(out_g[valid], ref[valid], atol=2e-5)
    # padding tokens: the kernel produces exact zeros (l == 0 guard)
    assert np.all(out_rpa[~valid] == 0.0)


#: rows (new, context) in pages of 8 under tiles of 8: a 13-token chunk
#: over 43 cached tokens straddles pages 5 and 6 and ends in a tile it
#: shares with three decode rows; the first of them names the chunk's
#: first five pages as its own prefix; a slot without tokens; a row with
#: no context; a 6-token chunk across pages 0 and 1; then an empty tile.
#: 7 and 6 pages a sequence and a table 7 wide: multiples of no run
_RUN_MIX = [(13, 43), (1, 41), (1, 7), (0, 0), (1, 0), (6, 5)]
#: the same under a window, in pages of 8 and a table 7 wide: decode pairs
#: (a row and its draft) whose windows start in pages 1, 3 and 4 beside a
#: 13-token chunk, a decode row, a slot without tokens and a 6-token
#: chunk; then an empty tile. Under windows of 8 and 20 keys most walks
#: start in the middle of a run of 2 or 4 pages
_WIN_RUN_MIX = [(2, 17), (13, 30), (2, 33), (1, 47), (0, 0), (2, 44),
                (6, 21)]


@pytest.mark.parametrize("run", [1, 2, 4, "rule"])
@pytest.mark.parametrize("form", ["kv", "latent", "kv_window_8",
                                  "kv_window_20"])
def test_runs_of_pages_match_gather_and_eager(form, run):
    """An item is a run of ``run`` pages (held there for the test, or the
    pages the kernel's rule reads off the pool: a latent pool's value
    width over the page, at least 256 KiB of K and V pages an item for a
    K/V pool, 8 at most, under a window the pages one token's window
    spans): both pool forms against their gather reader and the eager
    reference, over page counts and a table width that are no multiple of
    the run (its last pages resolve to the null page, or under the clamp
    to the table's last, and are masked), shared prefix pages, a chunk
    that straddles pages, decode rows and pairs beside a chunk in one
    tile, an empty tile; under a window of one page and of several, walks
    that start in the middle of a run and pages released behind the
    window (junk rows a walk must not name). Under a window a run updates
    the softmax state once a page: its output is that of items of one
    page, bit for bit, wherever the runs were laid."""
    seed = 17 * len(str(run)) + len(form)
    rng = np.random.RandomState(seed)
    window = int(form.rsplit("_", 1)[1]) if "window" in form else None
    if form == "latent":
        # the latent rule is its value width over the page
        vd = 8 * (4 if run == "rule" else run)
        kw = dict(n_kv=1, grp=4, hd=vd + 8, value_cols=vd,
                  shared=[(1, 0, 5)])
        mix, forced = _RUN_MIX, None
    else:
        kw = dict(n_kv=2, grp=2, hd=16, window=window)
        if window is None:
            kw["shared"] = [(1, 0, 5)]
        mix, forced = (_RUN_MIX if window is None else _WIN_RUN_MIX,
                       None if run == "rule" else run)
    c = _ragged_case(rng, mix, 8, mbps=7, pad_tiles=1, run=forced,
                     pool_blocks=30, **kw)
    if window is not None:
        pages = _ragged_case(np.random.RandomState(seed), mix, 8, mbps=7,
                             pad_tiles=1, run=1, pool_blocks=30, **kw)
    if run == "rule":
        # float32 pages of 8 x 16: the fetch term asks for 256 runs, the
        # cap gives 8; a window of 8 keys spans 2 pages, of 20 keys 4
        assert c["run"] == {None: 4 if form == "latent" else 8, 8: 2,
                            20: 4}[window]
    else:
        assert c["run"] == run
    assert bool(c["bt"].shape[1] % c["run"]) == (c["run"] > 1)
    maps = c["maps"]
    assert maps.walked == maps.live + 1             # the empty tile
    assert maps.live <= maps.pages <= c["run"] * maps.live
    if window is not None and c["run"] > 1:
        # runs are laid from a walk's first page: some start mid-run
        firsts = maps.step_blk[:maps.walked][
            maps.step_seq[:maps.walked] < c["max_seqs"]]
        assert np.any(firsts % c["run"])
    out, ref = _run_rpa(c), _eager_oracle(c)
    valid = c["sid"] < c["max_seqs"]
    np.testing.assert_allclose(out[valid], ref[valid], atol=2e-5)
    np.testing.assert_allclose(out[valid], _run_gather(c)[valid], atol=2e-5)
    assert np.all(out[~valid] == 0.0)
    if window is not None:
        np.testing.assert_array_equal(out, _run_rpa(pages))
    # the per-tile form names the same runs
    per_tile = _run_rpa(c, _per_tile_maps(
        c, rpa_max_steps(c["tile_q"], c["mbps"], run_pages=c["run"])))
    np.testing.assert_array_equal(per_tile, out)


@pytest.mark.parametrize("run", [2, 4])
def test_a_window_walk_reads_the_same_wherever_its_runs_start(run):
    """A drafting engine builds a window walk under ``slack`` (its pending
    draft may be rejected): the walk then starts a page earlier where the
    shorter context's window does, and its runs group the pages otherwise.
    Every row's output is the same bit for bit: the kernel updates a window
    walk's softmax state once a page. (With one update a run, bf16 serving
    on the chip gave other tokens with drafts than without.)"""
    seqs = [(2, 47), (1, 31), (2, 23), (13, 30), (1, 55)]
    c = _ragged_case(np.random.RandomState(run), seqs, 8, n_kv=2, grp=2,
                     mbps=8, pool_blocks=40, window=8, run=run,
                     dtype=jnp.bfloat16)
    cu = c["cu"][:len(seqs) + 1]
    unsure = build_step_maps(
        cu, c["kv_lens"], total_tokens=c["q"].shape[0], tile_q=8,
        block_size=8, max_seqs=c["max_seqs"], run_pages=run, window=8,
        slack=[1] * len(seqs), max_items=10 ** 4)
    firsts = [{int(b) for b, q in zip(m.step_blk[:m.walked],
                                      m.step_seq[:m.walked]) if q == 0}
              for m in (c["maps"], unsure)]
    assert firsts[0] != firsts[1]          # the runs are laid otherwise
    np.testing.assert_array_equal(_run_rpa(c, unsure), _run_rpa(c))


@pytest.mark.parametrize("window", [None, 128, 300])
def test_a_kv_pool_at_the_cells_widths_reads_at_the_rules_run(window):
    """A K/V pool of head width 128 in bf16 pages of 128 tokens, as the
    serving cells hold it: the kernel reads it at the rule's own run, 4
    pages (2 under a window of one page, 4 under one of three), and
    agrees with the gather reader and the reference over decode pairs,
    decode rows at depths that end anywhere in a run, and a chunk."""
    rng = np.random.RandomState(3 if window is None else window)
    seqs = [(2, 700), (1, 130), (24, 1000), (2, 255), (1, 9), (2, 520)]
    c = _ragged_case(rng, seqs, 128, n_kv=1, grp=2, hd=128, mbps=9,
                     pool_blocks=30, window=window, dtype=jnp.bfloat16)
    assert not c["forced"]
    assert c["run"] == {None: 4, 128: 2, 300: 4}[window]
    out, ref = _run_rpa(c), _eager_oracle(c)
    valid = c["sid"] < c["max_seqs"]
    # bf16 probabilities and values: a few parts in a thousand
    np.testing.assert_allclose(out[valid], ref[valid], atol=3e-2)
    np.testing.assert_allclose(out[valid], _run_gather(c)[valid], atol=3e-2)
    assert np.all(out[~valid] == 0.0)


def _per_tile_maps(c, width):
    """The flat list of case ``c`` as per-tile maps ``[num_tiles, width]``
    padded with the sentinel: the form a caller without the flat list
    hands the kernel."""
    ssq, sbk, stl = c["maps"][:3]
    num_tiles = len(stl) - 1
    seq2 = np.full((num_tiles, width), c["max_seqs"], np.int32)
    blk2 = np.zeros((num_tiles, width), np.int32)
    for j in range(num_tiles):
        items = [w for w in range(stl[j], stl[j + 1])
                 if ssq[w] < c["max_seqs"]]
        seq2[j, :len(items)] = ssq[items]
        blk2[j, :len(items)] = sbk[items]
    return seq2, blk2, None


#: name -> (rows as (new, context) with block_size 8, keywords of the case)
_WALK_CASES = {
    # 16 decode rows at varied depths fill two tiles; nothing else
    "decode_only": ([(1, 3 + 5 * i) for i in range(16)],
                    dict(mbps=11, pool_blocks=120)),
    # a 21-token chunk with 16 cached tokens spans tiles 0-3 beside rows
    "chunk_over_tiles_beside_decode": (
        [(1, 9), (1, 30), (1, 1), (21, 16), (1, 17)],
        dict(pool_blocks=40)),
    # three trailing tiles hold only padding tokens: one sentinel item each
    "trailing_padding_tiles": ([(3, 6), (1, 12)], dict(pad_tiles=3)),
    "one_live_row": ([(1, 20)], dict(pad_tiles=1)),
    # two rows name the same five prefix pages; 9 pages hold a step whose
    # tile names 6 + 6 + 1: the pool's size bounds no work list
    "shared_prefix_small_pool": (
        [(5, 40), (3, 40), (1, 4)],
        dict(pool_blocks=9, shared=[(1, 0, 5)])),
}


@pytest.mark.parametrize("form", ["flat", "per_tile"])
@pytest.mark.parametrize("name", sorted(_WALK_CASES))
def test_kernel_walks_the_live_work(name, form):
    """Parity with the gather reader where the work list is short, long,
    absent for whole tiles, or names shared pages; padding rows read
    exactly 0; and the walk is the live items plus one item for each
    tile without work, whatever the static bounds are."""
    seqs, kw = _WALK_CASES[name]
    rng = np.random.RandomState(len(name))
    c = _ragged_case(rng, seqs, 8, n_kv=2, grp=2, **kw)
    maps = c["maps"]
    tile_q, num_tiles = c["tile_q"], len(maps.step_tile) - 1
    touched = set()
    off = 0
    for n, _ in seqs:
        touched.update(range(off // tile_q, -(-(off + n) // tile_q)))
        off += n
    # each sequence walks, for every tile it spans, the runs of pages up
    # to that tile's causal horizon
    seen = [-(-(ctx + min((j + 1) * tile_q, cu1) - cu0) // 8)
            for (_, ctx), cu0, cu1 in zip(seqs, c["cu"], c["cu"][1:])
            for j in range(cu0 // tile_q, -(-cu1 // tile_q))]
    assert maps.live == sum(-(-n // c["run"]) for n in seen)
    assert maps.pages == sum(seen)
    assert maps.walked == maps.live + num_tiles - len(touched)
    if name == "shared_prefix_small_pool":
        assert maps.pages > kw["pool_blocks"]
        assert kw["pool_blocks"] < tile_q * 6       # < tile_q x mbps
    out = _run_rpa(c, maps if form == "flat" else _per_tile_maps(
        c, rpa_max_steps(tile_q, 11, run_pages=c["run"])))
    valid = c["sid"] < c["max_seqs"]
    np.testing.assert_allclose(out[valid], _run_gather(c)[valid],
                               atol=2e-5)
    assert np.all(out[~valid] == 0.0)


def _covered(maps, max_seqs):
    """``{(tile, seq): [pages in walk order]}`` of a flat list, and the
    tiles that hold only a sentinel item."""
    got, sentinel_tiles = {}, []
    stl = maps.step_tile
    for j in range(len(stl) - 1):
        items = range(stl[j], stl[j + 1])
        assert len(items) >= 1              # every tile owns an item
        seqs = [int(maps.step_seq[w]) for w in items]
        if seqs == [max_seqs]:
            sentinel_tiles.append(j)
            continue
        assert max_seqs not in seqs         # no sentinel beside real work
        for w in items:
            got.setdefault((j, int(maps.step_seq[w])), []).append(
                int(maps.step_blk[w]))
    return got, sentinel_tiles


@pytest.mark.parametrize("run", [1, 2, 4])
def test_step_maps_cover_each_page_exactly_once(run):
    """Work-list invariants: for every tile, each overlapping sequence
    contributes the runs of ``run`` pages up to the tile's causal horizon
    in it (``ceil((context + its tokens up to the tile's end) /
    block_size)`` pages: nothing a later tile writes, nothing more, in
    order), each named by its first page (0, ``run``, ...), empty
    sequences contribute none, a tile without work owns one sentinel
    item, and the tail past the live length carries the sentinel."""
    cu = np.array([0, 5, 5, 6, 16])  # seq 1 is a new_len == 0 slot
    kv_lens = [5, 8, 29, 47]         # seq 3: 10 tokens over tiles 0-1
    tile_q, bs, max_seqs = 8, 8, 6
    maps = build_step_maps(cu, kv_lens, total_tokens=32,
                           tile_q=tile_q, block_size=bs,
                           max_items=rpa_max_items(4, max_seqs, 6, run),
                           max_seqs=max_seqs, run_pages=run)
    want, pages = {}, 0
    for j in range(4):
        lo, hi = j * tile_q, (j + 1) * tile_q
        for s in range(4):
            if cu[s] < cu[s + 1] and cu[s + 1] > lo and cu[s] < hi:
                ctx = kv_lens[s] - (cu[s + 1] - cu[s])
                seen = -(-(ctx + min(hi, cu[s + 1]) - cu[s]) // bs)
                want[(j, s)] = list(range(0, seen, run))
                pages += seen
    # the 10-token chunk: tile 0 sees 37 + 2 keys (5 pages), tile 1 all 6
    assert len(want[(0, 3)]) == -(-5 // run)
    assert len(want[(1, 3)]) == -(-6 // run)
    got, sentinel_tiles = _covered(maps, max_seqs)
    assert got == want                      # each run once, in order
    assert sentinel_tiles == [2, 3]
    assert maps.live == sum(len(v) for v in want.values())
    assert maps.pages == pages
    assert maps.walked == maps.live + len(sentinel_tiles)
    assert np.all(maps.step_seq[maps.walked:] == max_seqs)
    assert list(maps.step_tile) == sorted(maps.step_tile)
    with pytest.raises(ValueError, match="max_items"):
        build_step_maps(cu, kv_lens, total_tokens=32, tile_q=tile_q,
                        block_size=bs, max_items=5, max_seqs=max_seqs,
                        run_pages=run)


@pytest.mark.parametrize("run", [1, 2, 4])
@pytest.mark.parametrize("packing", ["straddlers", "single_tokens",
                                     "random"])
def test_step_maps_stay_inside_the_static_bound(packing, run):
    """The arrays' static length holds under the packings that make the
    most (tile, sequence) pairs, every sequence at full table width: a
    sequence across each tile boundary, one sequence a token, and random
    cuts of the token axis. A sequence's last tile walks the whole table,
    an earlier one the runs up to its horizon."""
    tile_q, bs, mbps, num_tiles = 8, 4, 5, 6
    T = tile_q * num_tiles
    rng = np.random.RandomState(3)
    if packing == "straddlers":
        # 6, then 4 across every boundary with 4 more between them
        cuts = [[0, 6] + [t for b in range(8, T, 8) for t in (b + 2, b + 6)]
                + [T]]
    elif packing == "single_tokens":
        cuts = [list(range(T + 1))]
    else:
        cuts = [[0] + sorted(rng.choice(np.arange(1, T), size=rng.randint(
            1, T - 1), replace=False).tolist()) + [T] for _ in range(80)]
        # a sequence's new tokens fit its table
        cuts = [cu for cu in cuts if max(np.diff(cu)) <= mbps * bs]
        assert len(cuts) >= 40
    full = list(range(0, mbps, run))        # the runs' first pages
    for cu in cuts:
        n = len(cu) - 1
        bound = rpa_max_items(num_tiles, n, mbps, run)
        maps = build_step_maps(cu, [mbps * bs] * n, total_tokens=T,
                               tile_q=tile_q, block_size=bs,
                               max_items=bound, max_seqs=n, run_pages=run)
        got, sentinel_tiles = _covered(maps, n)
        assert not sentinel_tiles
        last = {s: -(-b // tile_q) - 1 for s, b in enumerate(cu[1:])}
        assert all(v == full[:len(v)] and (j < last[s] or v == full)
                   for (j, s), v in got.items())
        spans = sum(-(-b // tile_q) - a // tile_q
                    for a, b in zip(cu, cu[1:]))
        assert len(got) == spans <= num_tiles + n - 1
        assert len(full) * n <= maps.walked == maps.live \
            <= len(full) * spans <= bound


@pytest.mark.parametrize("window", [None, 4, 10])
@pytest.mark.parametrize("run", [1, 2, 4])
def test_every_visible_pair_lies_in_one_item_of_its_tile(run, window):
    """The list's contract with the kernel's mask, on random steps: every
    (token, key) pair the token may see lies in exactly one item of the
    token's tile; a (tile, sequence) walk is laid in consecutive runs from
    the page of the first key its first token sees (page 0 without a
    window), so it is as few items as its pages allow, one where it spans
    at most ``run`` pages; no item lies wholly beyond its tile's horizon
    (its first key is one some token of the tile sees); the walk is inside
    the static bound for this run and window."""
    tile_q, bs, mbps, max_seqs = 8, 4, 9, 7
    rng = np.random.RandomState(run + (window or 0))
    one_item_walks = 0
    for _ in range(40):
        n = rng.randint(1, max_seqs + 1)
        new = rng.choice([0, 1, 1, 2, 2, 5, 11, 19], size=n)
        ctx = np.array([rng.randint(0, mbps * bs - m + 1) for m in new])
        cu = np.concatenate([[0], np.cumsum(new)])
        T = -(-max(int(cu[-1]), 1) // tile_q) * tile_q + tile_q
        bound = rpa_max_items(T // tile_q, max_seqs, mbps, run,
                              window=window, tile_q=tile_q, block_size=bs)
        maps = build_step_maps(cu, ctx + new, total_tokens=T, tile_q=tile_q,
                               block_size=bs, max_items=bound,
                               max_seqs=max_seqs, run_pages=run,
                               window=window)
        assert maps.walked <= bound
        got, _ = _covered(maps, max_seqs)
        keys = run * bs
        for s in range(n):
            for t in range(cu[s], cu[s + 1]):
                firsts = got[(t // tile_q, s)]
                p = ctx[s] + t - cu[s]            # the token's position
                lo = 0 if window is None else max(0, p - window + 1)
                # each key the token sees lies in exactly one run
                for key in range(lo, p + 1):
                    assert sum(f * bs <= key < f * bs + keys
                               for f in firsts) == 1
        for (j, s), firsts in got.items():
            first_tok = max(j * tile_q, cu[s])
            last_tok = min((j + 1) * tile_q, cu[s + 1]) - 1
            p0 = ctx[s] + first_tok - cu[s]
            page0 = 0 if window is None else max(0, p0 - window + 1) // bs
            seen = -(-(ctx[s] + last_tok + 1 - cu[s]) // bs)
            assert firsts == list(range(page0, seen, run))
            if seen - page0 <= run:
                assert len(firsts) == 1
                one_item_walks += 1
            assert firsts[-1] * bs <= ctx[s] + last_tok - cu[s]
        assert maps.pages == sum(
            -(-(ctx[s] + min((j + 1) * tile_q, cu[s + 1]) - cu[s]) // bs)
            - (0 if window is None else max(
                0, ctx[s] + max(j * tile_q, cu[s]) - cu[s] - window + 1)
               // bs)
            for j, s in got)
    assert one_item_walks > 0


@pytest.mark.parametrize("window,run", [(4, 2), (8, 2), (8, 3), (10, 4)])
def test_a_window_walk_of_at_most_run_pages_is_one_item(window, run):
    """Decode rows and pairs at every offset of a page: under a window
    whose walk spans at most ``run`` pages wherever it starts, each
    (tile, sequence) is exactly one item, named by the page of its first
    visible key: a run laid from a multiple of ``run`` would make a walk
    that starts in a run's last page two items."""
    bs, tile_q = 4, 8
    for new in (1, 2):
        for ctx in range(0, 40):
            p0 = ctx                         # the first new token
            spans = (ctx + new - 1) // bs - max(0, p0 - window + 1) // bs + 1
            if spans > run:
                continue
            maps = build_step_maps([0, new], [ctx + new], total_tokens=8,
                                   tile_q=tile_q, block_size=bs,
                                   max_items=2, max_seqs=1, run_pages=run,
                                   window=window)
            assert maps.live == 1 == maps.walked
            assert maps.step_blk[0] == max(0, p0 - window + 1) // bs
            assert maps.pages == spans


def test_the_run_length_is_read_off_the_pool_and_the_window():
    """The rule's values at the pools the cells hold, and at the sizes
    that bound it: the latent pool's accumulator (512 value columns in
    pages of 128: 4, and 6 for a 96-column pool in pages of 16 whatever
    its bytes), a K/V pool's fetch (at least 256 KiB of K and V pages an
    item: 4 for head width 128 in bf16 pages of 128, 2 in float32, 8 at
    most: 16-token pages), and under a window the pages one token's
    window spans (a window of one page: 2; of 4,096 keys: no cap)."""
    assert rpa_run_pages(128, 640, 512, 2, latent=True) == 4
    assert rpa_run_pages(16, 128, 96, 4, latent=True) == 6
    assert rpa_run_pages(128, 128, 128, 2) == 4
    assert rpa_run_pages(128, 128, 128, 4) == 2
    assert rpa_run_pages(16, 128, 128, 2) == 8
    assert rpa_run_pages(16, 16, 16, 4) == 8
    assert rpa_run_pages(128, 128, 128, 2, window=128) == 2
    assert rpa_run_pages(128, 128, 128, 2, window=129) == 2
    assert rpa_run_pages(128, 128, 128, 2, window=130) == 3
    assert rpa_run_pages(128, 128, 128, 2, window=4096) == 4
    assert rpa_run_pages(8, 16, 16, 4, window=1) == 1
    # 1 at least, 8 at most, whatever the shapes
    assert rpa_run_pages(1024, 512, 512, 4) == 1
    assert rpa_run_pages(16, 2048, 1024, 2, latent=True) == 8


def test_the_latent_list_at_run_4_is_the_parents():
    """The latent pool's work list at P 4 names the pages it named before
    runs were laid from a walk's first page: a causal walk starts at page
    0, so item ``w``'s first page ``step_blk[w]`` is 4 x the run index the
    parent named (the kernel then fetches ``bt[s, step_blk[w] + i]`` where
    the parent fetched ``bt[s, 4 * run + i]``, and counts ``kpos`` from
    the same key), and the output is the parent's arithmetic on the same
    pages: the per-tile form, the gather reader and the reference agree."""
    rng = np.random.RandomState(30)
    c = _ragged_case(rng, _RUN_MIX, 8, n_kv=1, grp=4, hd=40, value_cols=32,
                     mbps=7, pad_tiles=1, shared=[(1, 0, 5)])
    assert c["run"] == 4 and not c["forced"]
    maps = c["maps"]
    # the parent's list: for each (tile, sequence), runs 0 .. ceil(seen/4)
    parent_seq, parent_run = [], []
    tile_q, cu = c["tile_q"], c["cu"]
    for j in range(len(maps.step_tile) - 1):
        lo, hi = j * tile_q, (j + 1) * tile_q
        n0 = len(parent_seq)
        for s, (n, ctx) in enumerate(_RUN_MIX):
            if n and cu[s] < hi and cu[s + 1] > lo:
                seen = -(-(ctx + min(hi, cu[s + 1]) - cu[s]) // 8)
                parent_seq += [s] * -(-seen // 4)
                parent_run += range(-(-seen // 4))
        if len(parent_seq) == n0:
            parent_seq.append(c["max_seqs"])
            parent_run.append(0)
    assert list(maps.step_seq[:maps.walked]) == parent_seq
    assert list(maps.step_blk[:maps.walked]) == [4 * r for r in parent_run]
    out = _run_rpa(c)
    per_tile = _run_rpa(c, _per_tile_maps(
        c, rpa_max_steps(c["tile_q"], c["mbps"], run_pages=4)))
    np.testing.assert_array_equal(per_tile, out)
    valid = c["sid"] < c["max_seqs"]
    np.testing.assert_allclose(out[valid], _eager_oracle(c)[valid],
                               atol=2e-5)
    np.testing.assert_allclose(out[valid], _run_gather(c)[valid], atol=2e-5)


# ---------------- the step's K/V write -----------------------------------------
def _write_case(pool_shape, dtype, seqs, table, mbps=4, pad=0):
    """A token-packed write: ``seqs`` lists ``(new_len, context_len)``,
    ``table`` each sequence's physical pages; ``pad`` padding tokens
    follow (sentinel sequence, the all-null table row)."""
    bt = np.zeros((len(seqs) + 1, mbps), np.int32)
    for s, pages in enumerate(table):
        bt[s, :len(pages)] = pages
    sid = np.concatenate([np.full(n, s, np.int32)
                          for s, (n, _) in enumerate(seqs)]
                         + [np.full(pad, len(seqs), np.int32)])
    pos = np.concatenate([c + np.arange(n, dtype=np.int32)
                          for n, c in seqs] + [np.zeros(pad, np.int32)])
    return dict(pool_shape=pool_shape, dtype=dtype, bt=bt, sid=sid, pos=pos)


_WRITE_CASES = {
    # the serving cell's page: 8 kv heads, 128 slots, 128 wide, bf16
    "bf16_kv_8x128x128": _write_case(
        (6, 8, 128, 128), jnp.bfloat16, [(5, 120), (1, 3)],
        [[2, 4], [1]], pad=2),
    "int8_values": _write_case(
        (9, 2, 8, 16), jnp.int8, [(6, 5), (1, 17)], [[3, 1], [5, 6, 7]]),
    "f32_scale_pool_3d": _write_case(
        (9, 2, 8), jnp.float32, [(6, 5), (1, 17)], [[3, 1], [5, 6, 7]]),
    "latent_one_head_640": _write_case(
        (7, 1, 8, 640), jnp.bfloat16, [(9, 4), (1, 0)], [[6, 2], [3]],
        pad=6),
    "padding_only": _write_case(
        (5, 2, 8, 16), jnp.float32, [], [], pad=8),
    # both name page 3 as their first page and write behind it
    "shared_prefix_page": _write_case(
        (9, 2, 8, 16), jnp.float32, [(3, 8), (2, 10)], [[3, 1], [3, 2]]),
    "chunk_across_pages": _write_case(
        (9, 2, 8, 16), jnp.float32, [(13, 6)], [[4, 8, 2]]),
    "last_page_of_the_table": _write_case(
        (9, 2, 8, 16), jnp.float32, [(1, 31), (2, 29)],
        [[1, 2, 3, 4], [5, 6, 7, 8]]),
}


@pytest.mark.parametrize("name", list(_WRITE_CASES))
def test_writer_matches_numpy_loop(name):
    """``write_tokens_to_pool`` against a loop over tokens, for every
    form of pool the step writes (K/V pages, int8 values and their 3-D
    scale pools, a one-head latent page) and the index cases that can go
    wrong in the flat row arithmetic. Outside the null block the pool is
    the loop's, bit for bit; in it only slot 0 may change."""
    c = _WRITE_CASES[name]
    rng = np.random.RandomState(len(name))
    shape = c["pool_shape"]
    nb1, n_kv, bs = shape[:3]
    T = len(c["sid"])

    def draw(sh):
        x = rng.randn(*sh) * 4
        return np.asarray(jnp.asarray(x).astype(c["dtype"]))

    pool, new = draw(shape), draw((T, n_kv) + shape[3:])
    want = pool.copy()
    for t in range(T):
        if c["sid"][t] < len(c["bt"]) - 1:        # not a padding token
            page = c["bt"][c["sid"][t], c["pos"][t] // bs]
            assert page > 0
            want[page, :, c["pos"][t] % bs] = new[t]
    got = np.asarray(write_tokens_to_pool(
        jnp.asarray(pool), jnp.asarray(new), jnp.asarray(c["bt"]),
        jnp.asarray(c["sid"]), jnp.asarray(c["pos"])))
    assert got.dtype == pool.dtype and got.shape == pool.shape
    np.testing.assert_array_equal(got[1:], want[1:])
    np.testing.assert_array_equal(got[0, :, 1:], pool[0, :, 1:])
    if T and (c["sid"] == len(c["bt"]) - 1).all():
        assert (got[0, :, 0] != pool[0, :, 0]).any()   # padding landed there


@pytest.fixture(scope="module")
def v5e_chip():
    """A described TPU v5e to compile for (nothing runs). Inside a
    fixture, never at import: only the worker that runs this file loads
    the TPU's library."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _compiling_for_the_chip(monkeypatch):
    """Interpret mode off, and no persistent cache entry (it could not be
    read back): as tests/benchmark/test_kernel_trace_names.py."""
    import importlib
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    mod = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    monkeypatch.setattr(mod, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_flat_list_kernel_compiles_with_mosaic_at_serving_shapes(
        v5e_chip, monkeypatch):
    """The dynamic grid bound lowers for the chip: the kernel at the
    serving cell's shapes (``benchmark/configs``), fed the flat list the
    engine builds, compiles with Mosaic into one ``rpa`` custom call. A
    lowering error of the traced bound shows here, on a CPU."""
    import jax

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs",
                           "mistral-7b-v0.3-serve-l16.json")) as f:
        cfg = json.load(f)
    eng = cfg["engine"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    tile = default_tile_q(heads // kv, jnp.bfloat16)
    tokens = eng["max_batch"] + eng["prefill_chunk"]
    assert tokens % tile == 0
    seqs = eng["max_batch"] + 1
    # an item is a run of 4 pages: 256 KiB of K and V a kv head
    run = rpa_run_pages(eng["block_size"], hd, hd, 2)
    assert run == 4
    items = rpa_max_items(tokens // tile, eng["max_batch"],
                          eng["max_blocks_per_seq"], run)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    pool = arr((eng["max_blocks"] + 1, kv, eng["block_size"], hd),
               jnp.bfloat16)
    with _compiling_for_the_chip(monkeypatch):
        text = jax.jit(ragged_paged_attention).lower(
            arr((tokens, heads, hd), jnp.bfloat16), pool, pool,
            arr((seqs, eng["max_blocks_per_seq"]), jnp.int32),
            arr((seqs + 1,), jnp.int32), arr((seqs,), jnp.int32),
            arr((items,), jnp.int32), arr((items,), jnp.int32),
            arr((tokens // tile + 1,), jnp.int32)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1, calls
    assert "rpa" in calls[0].split("=")[0], calls[0]


def test_window_and_full_kernels_compile_at_the_two_group_cell(
        v5e_chip, monkeypatch):
    """7 query heads a KV head (a ``[112, 128]`` q block in bf16) at the
    shapes of ``smallthinker-21b-a3b-serve-l12``: one program with a full
    layer's call and a window layer's compiles with Mosaic into an ``rpa``
    and an ``rpa_win`` custom call, the names the benchmark's two
    rooflines tell the groups' device time apart by."""
    import re
    import jax
    from benchmark import xplane
    from benchmark.kernels import rpa_win

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "smallthinker-21b-a3b-serve-l12.json")) as f:
        cfg = json.load(f)
    eng, heads, kv, hd = (cfg["engine"], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    window, bs = cfg["sliding_window_size"], eng["block_size"]
    tile = default_tile_q(heads // kv, jnp.bfloat16)
    assert (heads // kv, tile) == (7, 16)
    tokens = -(-(eng["max_batch"] + eng["prefill_chunk"]) // tile) * tile
    seqs, width = eng["max_batch"] + 1, eng["max_blocks_per_seq"]
    # runs of 4 pages in both groups: a window of 4,096 keys caps nothing
    run = rpa_run_pages(bs, hd, hd, 2)
    assert run == rpa_run_pages(bs, hd, hd, 2, window=window) == 4
    items = rpa_max_items(tokens // tile, eng["max_batch"], width, run)
    win_items = rpa_max_items(tokens // tile, eng["max_batch"], width, run,
                              window=window, tile_q=tile, block_size=bs)
    assert win_items * 32 == items * 9    # 34 pages a walk, not 128

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def pool(name):
        return arr((eng["max_blocks"][name] + 1, kv, bs, hd), jnp.bfloat16)

    def two_layers(q, kf, vf, kw, vw, btf, btw, cu, ctx, sf, bf, tf, sw, bw,
                   tw):
        return (ragged_paged_attention(q, kf, vf, btf, cu, ctx, sf, bf, tf),
                ragged_paged_attention(q, kw, vw, btw, cu, ctx, sw, bw, tw,
                                       window=window))

    with _compiling_for_the_chip(monkeypatch):
        text = jax.jit(two_layers).lower(
            arr((tokens, heads, hd), jnp.bfloat16), pool("full"),
            pool("full"), pool("window"), pool("window"),
            arr((seqs, width)), arr((seqs, width)), arr((seqs + 1,)),
            arr((seqs,)), arr((items,)), arr((items,)),
            arr((tokens // tile + 1,)), arr((win_items,)),
            arr((win_items,)), arr((tokens // tile + 1,))
        ).compile().as_text()
    names = sorted(
        xplane.short_name(re.sub(r"^(ROOT )?", "", ln.strip()))
        for ln in text.splitlines() if "tpu_custom_call" in ln)
    assert len(names) == 2, names
    assert re.search(rpa_win.FULL_TRACE_PATTERN, names[0]), names
    assert not re.search(rpa_win.TRACE_PATTERN, names[0]), names
    assert re.search(rpa_win.TRACE_PATTERN, names[1]), names
    assert not re.search(rpa_win.FULL_TRACE_PATTERN, names[1]), names


def test_window_and_full_kernels_compile_at_the_drafted_cell(
        v5e_chip, monkeypatch):
    """8 query heads a KV head under a window of one page (128 = the page)
    at the shapes of ``k-exaone-236b-a23b-serve-ep8-l5``, whose token axis
    holds two rows a decode slot (the row and its draft's): a full layer's
    call and a window layer's compile with Mosaic into an ``rpa`` and an
    ``rpa_win`` custom call."""
    import re
    import jax
    from benchmark import xplane
    from benchmark.kernels import rpa_win

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "k-exaone-236b-a23b-serve-ep8-l5.json")) as f:
        cfg = json.load(f)
    eng, heads, kv, hd = (cfg["engine"], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    window, bs = cfg["sliding_window"], eng["block_size"]
    assert window == bs == 128 and heads // kv == 8
    tile = default_tile_q(heads // kv, jnp.bfloat16)
    slots = eng["max_batch"] * (1 + eng["draft_tokens"])
    tokens = -(-(slots + eng["prefill_chunk"]) // tile) * tile
    seqs, width = eng["max_batch"] + 1, eng["max_blocks_per_seq"]
    # runs of 4 pages in the full group, of 2 under the window of one page
    run, win_run = (rpa_run_pages(bs, hd, hd, 2, window=w)
                    for w in (None, window))
    assert (run, win_run) == (4, 2)
    items = rpa_max_items(tokens // tile, eng["max_batch"], width, run)
    win_items = rpa_max_items(tokens // tile, eng["max_batch"], width,
                              win_run, window=window, tile_q=tile,
                              block_size=bs)
    # a walk under the window: the page the first key lies in, the pages
    # of the tile's own keys, one more for where it starts in a page (3),
    # in runs of 2 from the first: 2 items, where a causal walk is 7
    assert -(-(window + tile) // bs) + 1 == 3
    assert win_items * 7 == items * 2

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def pool(name):
        return arr((eng["max_blocks"][name] + 1, kv, bs, hd), jnp.bfloat16)

    def two_layers(q, kf, vf, kw, vw, btf, btw, cu, ctx, sf, bf, tf, sw, bw,
                   tw):
        return (ragged_paged_attention(q, kf, vf, btf, cu, ctx, sf, bf, tf),
                ragged_paged_attention(q, kw, vw, btw, cu, ctx, sw, bw, tw,
                                       window=window))

    with _compiling_for_the_chip(monkeypatch):
        text = jax.jit(two_layers).lower(
            arr((tokens, heads, hd), jnp.bfloat16), pool("full"),
            pool("full"), pool("window"), pool("window"),
            arr((seqs, width)), arr((seqs, width)), arr((seqs + 1,)),
            arr((seqs,)), arr((items,)), arr((items,)),
            arr((tokens // tile + 1,)), arr((win_items,)),
            arr((win_items,)), arr((tokens // tile + 1,))
        ).compile().as_text()
    names = sorted(
        xplane.short_name(re.sub(r"^(ROOT )?", "", ln.strip()))
        for ln in text.splitlines() if "tpu_custom_call" in ln)
    assert len(names) == 2, names
    assert re.search(rpa_win.FULL_TRACE_PATTERN, names[0]), names
    assert re.search(rpa_win.TRACE_PATTERN, names[1]), names


def test_latent_kernel_compiles_at_the_latent_cell(v5e_chip, monkeypatch):
    """The kernel's latent form (one 640-column pool, values its first 512
    columns, 128 query heads on the one page) at the shapes of
    ``openpangu-ultra-moe-718b-serve-ep16-l5`` compiles with Mosaic into one
    ``rpa_mla`` custom call that reads the pool as it lies."""
    import jax

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "openpangu-ultra-moe-718b-serve-ep16-l5.json")) as f:
        cfg = json.load(f)
    eng, heads = cfg["engine"], cfg["num_attention_heads"]
    rank, row = cfg["kv_lora_rank"], \
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    cols = -(-row // 128) * 128
    tile = default_tile_q(heads, jnp.bfloat16)
    tokens = -(-(eng["max_batch"] + eng["prefill_chunk"]) // tile) * tile
    seqs = eng["max_batch"] + 1
    # the run the rule gives at these shapes: 512 value columns in pages
    # of 128 tokens
    run = rpa_run_pages(eng["block_size"], cols, rank, 2, latent=True)
    assert run == 4
    items = rpa_max_items(tokens // tile, eng["max_batch"],
                          eng["max_blocks_per_seq"], run)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    with _compiling_for_the_chip(monkeypatch):
        text = jax.jit(
            lambda q, pool, bt, cu, ctx, ss, sb, st: ragged_paged_attention(
                q, pool, None, bt, cu, ctx, ss, sb, st, sm_scale=0.07,
                value_cols=rank)).lower(
            arr((tokens, heads, cols), jnp.bfloat16),
            arr((eng["max_blocks"] + 1, 1, eng["block_size"], cols),
                jnp.bfloat16),
            arr((seqs, eng["max_blocks_per_seq"]), jnp.int32),
            arr((seqs + 1,), jnp.int32), arr((seqs,), jnp.int32),
            arr((items,), jnp.int32), arr((items,), jnp.int32),
            arr((tokens // tile + 1,), jnp.int32)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "rpa_mla" in calls[0]
    # the 640-column pool reaches the kernel as it lies: no whole-pool copy
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f",1,{eng['block_size']},{cols}]" in ln]


def _pool_shaped(text, pool_shape, dtype="bf16", ignore=()):
    """Opcode of every instruction of the compiled ``text`` whose result
    is a pool, as it lies or as the flat table of rows the writer sees.
    ``ignore`` names opcodes to leave out; the guard at a deployment's
    shapes passes none."""
    flat = (int(np.prod(pool_shape[:3])),) + tuple(pool_shape[3:])
    shapes = tuple(f"{dtype}[{','.join(map(str, sh))}]"
                   for sh in (pool_shape, flat))
    ops = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = (\S+) ([\w\-]+)\(", ln)
        if m and m.group(1).startswith(shapes) and m.group(2) not in ignore:
            ops.append(m.group(2))
    return ops


@pytest.mark.parametrize("cell", ["mistral-7b-v0.3-serve-l16",
                                  "openpangu-ultra-moe-718b-serve-ep16-l5"])
def test_step_writes_its_pools_in_place_at_serving_shapes(
        cell, v5e_chip, monkeypatch):
    """Two layers of the serving step's attention at a cell's shapes
    (``benchmark/configs``), pools donated, compiled for the chip: each
    pool is a parameter, bitcast to the flat table of rows, updated by one
    row scatter in its write fusion and bitcast back for the kernel. No
    ``copy`` nor any other instruction has a pool's shape: the write
    moves the step's rows and nothing else."""
    import jax

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", cell + ".json")) as f:
        cfg = json.load(f)
    eng, heads = cfg["engine"], cfg["num_attention_heads"]
    latent = "kv_lora_rank" in cfg
    if latent:
        kv, grp = 1, heads
        hd = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128
    else:
        kv = cfg["num_key_value_heads"]
        grp, hd = heads // kv, cfg["head_dim"]
    tile = default_tile_q(grp, jnp.bfloat16)
    tokens = -(-(eng["max_batch"] + eng["prefill_chunk"]) // tile) * tile
    seqs = eng["max_batch"] + 1
    items = rpa_max_items(
        tokens // tile, eng["max_batch"], eng["max_blocks_per_seq"],
        rpa_run_pages(eng["block_size"], hd,
                      cfg["kv_lora_rank"] if latent else hd, 2,
                      latent=latent))
    pool_shape = (eng["max_blocks"] + 1, kv, eng["block_size"], hd)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    meta = (arr((seqs, eng["max_blocks_per_seq"])), arr((seqs + 1,)),
            arr((seqs,)), arr((tokens,)), arr((tokens,)), arr((items,)),
            arr((items,)), arr((tokens // tile + 1,)))
    q = arr((tokens, heads, hd), jnp.bfloat16)
    pool = arr(pool_shape, jnp.bfloat16)

    if latent:
        new = (arr((tokens, hd), jnp.bfloat16),)
        pools = (pool, pool)

        def step(q, rows, p0, p1, *meta):
            kw = dict(value_cols=cfg["kv_lora_rank"], scale=0.07)
            u, c0 = attend(RaggedLayerCache(p0, None, *meta, impl="rpa"),
                           q, rows, **kw)
            u = jnp.pad(u, ((0, 0), (0, 0), (0, hd - u.shape[-1])))
            u, c1 = attend(RaggedLayerCache(p1, None, *meta, impl="rpa"),
                           u, rows * 2, **kw)
            return (u,) + c0.pools() + c1.pools()
    else:
        new = (arr((tokens, kv, hd), jnp.bfloat16),) * 2
        pools = (pool,) * 4

        def step(q, k, v, kp0, vp0, kp1, vp1, *meta):
            o, c0 = attend(RaggedLayerCache(kp0, vp0, *meta, impl="rpa"),
                           q, k, v)
            o, c1 = attend(RaggedLayerCache(kp1, vp1, *meta, impl="rpa"),
                           o, k * 2, v * 2)
            return (o,) + c0.pools() + c1.pools()

    donate = tuple(range(1 + len(new), 1 + len(new) + len(pools)))
    with _compiling_for_the_chip(monkeypatch):
        text = jax.jit(step, donate_argnums=donate).lower(
            q, *new, *pools, *meta).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2, calls
    assert all(("rpa_mla" if latent else "rpa") in c.split("=")[0]
               for c in calls), calls
    ops = _pool_shaped(text, pool_shape)
    assert set(ops) <= {"parameter", "bitcast", "fusion", "scatter"}, ops
    assert ops.count("scatter") == ops.count("fusion") == len(pools), ops


def _guard_llama():
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=512, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256))
    return m


def _guard_latent():
    from paddle_tpu.models.pangu_moe import (PanguMoeConfig,
                                             PanguMoeForCausalLM)
    pt.seed(0)
    return PanguMoeForCausalLM(PanguMoeConfig.tiny(
        held_experts=(0, 1, 2, 3), kv_lora_rank=96, qk_rope_head_dim=32,
        num_hidden_layers=2))


@pytest.mark.parametrize("build,engine_kw", [
    (_guard_llama, {"attn_impl": "rpa"}),
    (_guard_llama, {"attn_impl": "gather"}),
    (_guard_llama, {"kv_dtype": "int8"}),
    (_guard_latent, {"attn_impl": "rpa"}),
], ids=["rpa", "gather", "int8", "latent"])
def test_engine_step_writes_each_pool_once_in_place(
        build, engine_kw, v5e_chip, monkeypatch):
    """The whole step of a small engine, as ``ServingEngine`` builds it
    through the model's attention layers and ``attend``, compiled for the
    chip with the pools donated as on a TPU: under either reader, with
    int8 pools and with a latent pool, every pool is written by exactly
    one row scatter, and nothing else of a pool's shape is computed (no
    ``copy``, no transpose). A layer that writes its pool any other way
    than through the one writer fails here. (An int8 engine's scale
    pools are held to the one scatter only: their flat view is no bitcast
    in the chip's tiled layout.)"""
    import jax

    model = build()
    model.eval()
    model.bfloat16()
    eng = ServingEngine(model, max_batch=4, max_blocks=1024,
                        max_blocks_per_seq=8, block_size=16,
                        prefill_chunk=16, **engine_kw)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        eng._lowered_step().args_info[0])
    step = eng._step.__wrapped__
    with _compiling_for_the_chip(monkeypatch):
        # a fresh function: jax.jit reuses a trace by the function's
        # identity, and the engine's own was made for this CPU
        text = jax.jit(lambda *a: step(*a), donate_argnums=(2, 3, 4, 5)) \
            .lower(*args).compile().as_text()
    latent = eng.cache.v_pools[0] is None
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and ("%rpa_mla." if latent else "%rpa.") in ln.split("=")[0]]
    assert len(calls) == (2 if eng.attn_impl == "rpa" else 0), calls
    pool = eng.cache.k_pools[0]
    n_pools = 2 if latent else 4
    # a pool of this test's size fits the chip's fast memory and XLA
    # prefetches it there (copy-start/copy-done between memory spaces),
    # which a pool of a deployment's size cannot be: only here are those
    # two left out, the guard at serving shapes above counts them
    prefetch = ("copy-start", "copy-done")
    ops = _pool_shaped(text, pool.shape,
                       {"bfloat16": "bf16", "int8": "s8"}[str(pool.dtype)],
                       ignore=prefetch)
    assert set(ops) <= {"parameter", "bitcast", "fusion", "scatter"}, ops
    assert ops.count("scatter") == n_pools, ops
    if eng.kv_dtype is not None:
        scales = _pool_shaped(text, eng.cache.k_scales[0].shape, "f32",
                              ignore=prefetch)
        assert scales.count("scatter") == 4, scales


def test_hand_built_cache_picks_its_reader(monkeypatch):
    """A cache built by hand with no reader named reads through gather
    off a TPU (and would through the kernel on one); a reader it is given
    is the one that runs; int8 pools read through gather whoever asks;
    anything else is refused."""
    import importlib
    import jax
    assert paged_attention_impl() == "gather"            # this CPU
    assert paged_attention_impl("rpa") == "rpa"
    assert paged_attention_impl("rpa", quantized=True) == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_attention_impl() == "rpa"
    assert paged_attention_impl("gather") == "gather"
    monkeypatch.undo()
    with pytest.raises(ValueError, match="bogus"):
        paged_attention_impl("bogus")

    mod = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    kernel_calls = []
    real = mod.ragged_paged_attention
    monkeypatch.setattr(mod, "ragged_paged_attention",
                        lambda *a, **kw: kernel_calls.append(1)
                        or real(*a, **kw))
    c = _ragged_case(np.random.RandomState(2), [(3, 5), (1, 9)], 4, 2, 2)
    meta = [jnp.asarray(c[k]) for k in ("bt", "cu", "ctx", "sid", "pos")] \
        + [c["maps"].step_seq, c["maps"].step_blk, c["maps"].step_tile]
    n_kv, hd = c["kp"].shape[1], c["kp"].shape[3]
    new = jnp.ones((c["q"].shape[0], n_kv, hd), jnp.float32)
    outs = {}
    for impl, kernel in ((None, 0), ("gather", 0), ("rpa", 1)):
        kernel_calls.clear()
        cache = RaggedLayerCache(c["kp"], c["vp"], *meta, impl=impl)
        outs[impl], cache2 = attend(cache, jnp.asarray(c["q"]), new, new)
        assert len(kernel_calls) == kernel, impl
        assert cache2.impl == impl and len(cache2.pools()) == 2
    n = sum(n for n, _ in c["seqs"])
    np.testing.assert_array_equal(np.asarray(outs[None]),
                                  np.asarray(outs["gather"]))
    np.testing.assert_allclose(np.asarray(outs["rpa"])[:n],
                               np.asarray(outs["gather"])[:n],
                               atol=1e-5, rtol=1e-5)


# ---------------- engine-level acceptance ------------------------------------
def _tiny(seed=0):
    pt.seed(seed)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=True))
    m.eval()
    return m


def _eager_continuation(model, prompt, max_new_tokens):
    out = model.generate(pt.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=max_new_tokens,
                         temperature=0.0).numpy()[0]
    return [int(t) for t in out[len(prompt):]]


def test_engine_token_streams_identical_across_impls():
    """ISSUE 8 acceptance: bit-level equal greedy token streams from
    ``ServingEngine`` under both impl knob settings, each also matching
    the eager oracle; exactly ONE unified executable per engine, and a
    chunked multi-chunk prefill (prompt >> prefill_chunk) triggers no
    second compile after warmup."""
    model = _tiny(11)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 128, 11), rng.randint(1, 128, 4)]
    streams = {}
    for impl in ("gather", "rpa"):
        eng = ServingEngine(model, max_batch=2, max_blocks=16,
                            block_size=4, prefill_chunk=4,
                            attn_impl=impl)
        handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        streams[impl] = [h.result(30)["token_ids"] for h in handles]
        # prompt 11 >> chunk 4: three chunks rode the SAME executable
        assert eng.step_traces == 1
        assert eng.stats()["attn_impl"] == impl
        eng.cache.assert_no_leaks()
    assert streams["rpa"] == streams["gather"]
    assert streams["rpa"] == [
        _eager_continuation(model, p, 5) for p in prompts]


def test_engine_counts_the_pages_its_items_name():
    """Each step's ``serving.dispatch`` span carries ``rpa_pages`` beside
    ``rpa_live`` / ``rpa_walked`` and ``serving_rpa_steps_total`` grows by
    the same under ``kind="pages"``: the pages the step's live items name,
    between one and P an item (P read off the pool: float32 K/V pages of 4
    tokens x 16 wide ask for the longest run, 8), and what the step's own
    list says."""
    from paddle_tpu.serving.engine import serving_metrics
    model = _tiny(3)
    eng = ServingEngine(model, max_batch=4, max_blocks=48, block_size=4,
                        prefill_chunk=16, attn_impl="rpa")
    assert eng._run_pages == 8
    assert eng._maps_kw[0]["max_items"] == rpa_max_items(
        eng.step_tokens // eng._tile_q, 4, eng.cache.max_blocks_per_seq, 8)
    build, leaf = eng._build_step_maps, eng._leaf
    built, dispatched = [], []
    eng._build_step_maps = lambda *a, **k: (
        built.append(build(*a, **k)) or built[-1])

    def record(name, step, **args):
        ev = leaf(name, step, **args)
        if name == "serving.dispatch":
            dispatched.append(ev)
        return ev
    eng._leaf = record
    counter = serving_metrics()["rpa_steps"]
    before = {k: counter.value(kind=k) for k in ("live", "walked", "pages")}
    rng = np.random.RandomState(5)
    handles = [eng.submit(rng.randint(1, 128, n), max_new_tokens=4)
               for n in (50, 3, 9)]
    eng.run_until_idle()
    assert all(len(h.result(30)["token_ids"]) == 4 for h in handles)
    assert len(built) == len(dispatched) >= 6
    for m, ev in zip(built, dispatched):
        assert ev.args["rpa_live"] == m.live
        assert ev.args["rpa_walked"] == m.walked
        assert ev.args["rpa_pages"] == m.pages
        assert 0 < m.live <= m.pages <= 8 * m.live
    grown = {k: counter.value(kind=k) - before[k] for k in before}
    assert grown == {"live": sum(m.live for m in built),
                     "walked": sum(m.walked for m in built),
                     "pages": sum(m.pages for m in built)}
    # the 50-token prompt's later chunks see 5 to 13 pages a tile: some
    # runs are full, a walk's last one is not
    assert 1.0 < grown["pages"] / grown["live"] < 8.0


@pytest.mark.slow
def test_engine_impl_parity_under_preemption():
    """Tight pool forces preemption-by-recompute mid-decode; the resumed
    token streams stay identical across impls and vs the solo oracle
    (the acceptance's preemption/resume-trace clause)."""
    streams = {}
    for impl in ("gather", "rpa"):
        model = _tiny(5)
        eng = ServingEngine(model, max_batch=3, max_blocks=8,
                            block_size=4, prefill_chunk=4,
                            attn_impl=impl)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 128, n) for n in (9, 12, 7)]
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run_until_idle()
        streams[impl] = [h.result(30)["token_ids"] for h in handles]
        assert eng.scheduler.num_preemptions >= 1
        assert streams[impl] == [
            _eager_continuation(model, p, 8) for p in prompts]
        eng.cache.assert_no_leaks()
    assert streams["rpa"] == streams["gather"]
