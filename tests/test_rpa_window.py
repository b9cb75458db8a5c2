"""The attention window in the RPA kernel and in its work list (ISSUE 31):
``rpa`` with ``window`` in interpret mode against the gather reader and a
dense oracle (group 7 included, released pages null in the table), and
``build_step_maps(window=...)`` against a brute-force list of the (tile,
sequence, run) triples that hold a visible key."""
import importlib
import os
from unittest import mock

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.paged_attention import (ragged_gather_attention,
                                            write_tokens_to_pool)
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    build_step_maps, default_tile_q, ragged_paged_attention, rpa_max_items,
    rpa_run_pages)

RPA = importlib.import_module("paddle_tpu.ops.pallas.ragged_paged_attention")


def _case(rng, seqs, *, window, block_size=8, n_kv=1, grp=7, hd=16,
          tile_q=8, mbps=12, release=True):
    """A token-packed step: ``seqs`` ``(new, context)``. Pages wholly behind
    the window of a sequence's first new token are **released**: null in
    the table, their pool rows overwritten with junk (another sequence's
    pages by now), so a reader that walks them reads wrong values. With
    ``hd`` 16 over pages of 8 the kernel's items are runs of 2 pages, over
    pages of 16 single pages."""
    n_heads, S = n_kv * grp, len(seqs) + 1
    total = sum(n for n, _ in seqs)
    T = -(-max(total, 1) // tile_q) * tile_q
    bt = np.zeros((S + 1, mbps), np.int32)
    pool_blocks = sum(-(-(n + c) // block_size) for n, c in seqs) + 2
    kp = np.zeros((pool_blocks + 1, n_kv, block_size, hd), np.float32)
    vp = np.zeros_like(kp)
    cu = np.zeros(S + 2, np.int32)
    ctx = np.zeros(S + 1, np.int32)
    sid = np.full(T, S, np.int32)
    pos = np.zeros(T, np.int32)
    q = rng.standard_normal((T, n_heads, hd)).astype(np.float32)
    knew = np.zeros((T, n_kv, hd), np.float32)
    vnew = np.zeros_like(knew)
    full, nxt, off = [], 1, 0
    for s, (n, c) in enumerate(seqs):
        fk = rng.standard_normal((n + c, n_kv, hd)).astype(np.float32)
        fv = rng.standard_normal((n + c, n_kv, hd)).astype(np.float32)
        full.append((fk, fv))
        npg = -(-(n + c) // block_size)
        bt[s, :npg] = np.arange(nxt, nxt + npg)
        nxt += npg
        for t in range(c):
            kp[bt[s, t // block_size], :, t % block_size] = fk[t]
            vp[bt[s, t // block_size], :, t % block_size] = fv[t]
        if release and window is not None:
            first = max(0, c - window + 1) // block_size
            for b in bt[s, :first]:
                kp[b] = 1e3                 # someone else's page by now
                vp[b] = -1e3
            bt[s, :first] = 0
        cu[s + 1] = off + n
        ctx[s] = c
        sid[off:off + n] = s
        pos[off:off + n] = c + np.arange(n)
        knew[off:off + n], vnew[off:off + n] = fk[c:], fv[c:]
        off += n
    cu[len(seqs) + 1:] = off
    j = [jnp.asarray(a) for a in (bt, sid, pos)]
    kp = write_tokens_to_pool(jnp.asarray(kp), jnp.asarray(knew), *j)
    vp = write_tokens_to_pool(jnp.asarray(vp), jnp.asarray(vnew), *j)
    kv_lens = [n + c for n, c in seqs]
    # what the kernel reads off vp and the window
    run = rpa_run_pages(block_size, hd, hd, 4, window=window)
    maps = build_step_maps(
        cu[:len(seqs) + 1], kv_lens, total_tokens=T, tile_q=tile_q,
        block_size=block_size, max_seqs=S, window=window, run_pages=run,
        max_items=rpa_max_items(T // tile_q, S, mbps, run, window=window,
                                tile_q=tile_q, block_size=block_size))
    return dict(q=q, kp=kp, vp=vp, bt=bt, cu=cu, ctx=ctx, sid=sid, pos=pos,
                maps=maps, full=full, seqs=seqs, grp=grp, hd=hd,
                window=window)


def _oracle(c):
    """Dense softmax a token over the keys its window lets it see."""
    out = np.zeros_like(c["q"])
    off = 0
    for (n, ctx), (fk, fv) in zip(c["seqs"], c["full"]):
        for i in range(n):
            p = ctx + i
            lo = 0 if c["window"] is None else max(0, p - c["window"] + 1)
            for h in range(c["q"].shape[1]):
                g = h // c["grp"]
                s = fk[lo:p + 1, g] @ c["q"][off + i, h] / np.sqrt(c["hd"])
                w = np.exp(s - s.max())
                out[off + i, h] = (w / w.sum()) @ fv[lo:p + 1, g]
        off += n
    return out


def _rpa(c):
    m = c["maps"]
    return np.asarray(ragged_paged_attention(
        jnp.asarray(c["q"]), c["kp"], c["vp"], jnp.asarray(c["bt"]),
        jnp.asarray(c["cu"]), jnp.asarray(c["ctx"]), m.step_seq, m.step_blk,
        m.step_tile, window=c["window"]))


def _gather(c):
    return np.asarray(ragged_gather_attention(
        jnp.asarray(c["q"]), c["kp"], c["vp"], jnp.asarray(c["bt"]),
        jnp.asarray(c["sid"]), jnp.asarray(c["pos"]),
        scale=1.0 / np.sqrt(c["hd"]), window=c["window"]))


#: a chunk that straddles the window's edge over three windows of context,
#: decode rows well past the window, a first chunk, a sequence inside it
SEQS = [(16, 70), (1, 90), (12, 0), (1, 20), (3, 29)]


@pytest.mark.parametrize("block_size", [8, 16], ids=["runs2", "pages"])
@pytest.mark.parametrize("grp,n_kv", [(7, 1), (7, 2), (1, 2), (4, 1)],
                         ids=["g7", "g7x2", "mha", "g4"])
def test_windowed_rpa_matches_gather_and_the_oracle(grp, n_kv, block_size):
    """float32 in interpret mode: the two readers agree to the last bits
    of a float32 softmax (1e-5 absolute on outputs of unit scale) and both
    with a dense oracle over the visible keys alone. Released pages hold
    junk of magnitude 1e3: one key read from them would move the output by
    hundreds."""
    rng = np.random.default_rng(grp * 10 + n_kv)
    tile = default_tile_q(grp, jnp.float32)
    c = _case(rng, SEQS, window=32, grp=grp, n_kv=n_kv, tile_q=tile,
              block_size=block_size)
    ref = _oracle(c)
    live = c["sid"] < len(SEQS) + 1
    np.testing.assert_allclose(_gather(c)[live], ref[live], atol=1e-5)
    got = _rpa(c)
    np.testing.assert_allclose(got[live], ref[live], atol=1e-5)
    assert (got[~live] == 0).all()


def test_group_7_tile_heights():
    """7 query heads a KV head: 8 tokens a tile in float32 (56 rows), 16 in
    bf16 (112 rows: whole 16-row sublane tiles), 32 in int8."""
    assert default_tile_q(7, jnp.float32) == 8
    assert default_tile_q(7, jnp.bfloat16) == 16
    assert default_tile_q(7, jnp.int8) == 32


def test_a_window_no_shorter_than_the_context_changes_nothing():
    """The causal list under a window wider than every context reads what
    a causal walk of one-page items reads, bit for bit (a window walk's
    runs update the softmax state once a page)."""
    rng = np.random.default_rng(5)
    c = _case(rng, SEQS, window=None)
    wide = dict(c, window=128)
    one = build_step_maps(
        c["cu"][:len(SEQS) + 1], [n + x for n, x in SEQS],
        total_tokens=c["q"].shape[0], tile_q=8, block_size=8,
        max_seqs=len(SEQS) + 1, run_pages=1, max_items=10 ** 4)
    with mock.patch.object(RPA, "rpa_run_pages", lambda *a, **k: 1):
        pages = _rpa(dict(c, maps=one))
    np.testing.assert_array_equal(_rpa(wide), pages)


def test_dropping_the_window_fails_the_comparison():
    """The planted fault: a window layer read without its bound (the
    table still holds every page) is far from the windowed oracle."""
    rng = np.random.default_rng(6)
    c = _case(rng, SEQS, window=32, release=False)
    ref = _oracle(c)
    live = c["sid"] < len(SEQS) + 1
    causal = dict(c, window=None)
    assert np.abs(_rpa(causal)[live] - ref[live]).max() > 0.05
    np.testing.assert_allclose(_rpa(c)[live], ref[live], atol=1e-5)


# ------------------------------------------------------------ the list --
def _brute(cu, kv_lens, tile_q, block_size, window):
    """``{(tile, sequence): the pages that hold a key some token of the
    tile can see}``, by looking at every (token, key) pair."""
    want = {}
    for s, kv in enumerate(kv_lens):
        base = kv - (cu[s + 1] - cu[s])
        for t in range(cu[s], cu[s + 1]):
            p = base + (t - cu[s])
            lo = 0 if window is None else max(0, p - window + 1)
            want.setdefault((t // tile_q, s), set()).update(
                range(lo // block_size, p // block_size + 1))
    return want


@pytest.mark.parametrize("window", [None, 8, 32, 100])
@pytest.mark.parametrize("run_pages", [1, 2, 3, 4])
def test_the_list_names_exactly_the_runs_that_hold_a_visible_key(
        window, run_pages):
    """A (tile, sequence) walk is the fewest runs that cover the pages
    holding a key some token of the tile sees: consecutive runs laid from
    the first such page, every one of them holding such a key, each such
    page in exactly one run."""
    rng = np.random.default_rng(7)
    tile_q, block_size, S = 8, 8, 8
    for _ in range(8):
        new = [int(n) for n in rng.integers(0, 30, 6)]
        ctx = [int(c) for c in rng.integers(0, 200, 6)]
        cu = np.concatenate([[0], np.cumsum(new)])
        T = -(-max(int(cu[-1]), 1) // tile_q) * tile_q
        kv = [n + c for n, c in zip(new, ctx)]
        mbps = 32
        m = build_step_maps(
            cu, kv, total_tokens=T, tile_q=tile_q, block_size=block_size,
            max_seqs=S, run_pages=run_pages, window=window,
            max_items=rpa_max_items(T // tile_q, S, mbps, run_pages,
                                    window=window, tile_q=tile_q,
                                    block_size=block_size))
        got = {}
        for j in range(T // tile_q):
            for w in range(m.step_tile[j], m.step_tile[j + 1]):
                if m.step_seq[w] < S:
                    got.setdefault((j, int(m.step_seq[w])), []).append(
                        int(m.step_blk[w]))
        want = _brute(cu, kv, tile_q, block_size, window)
        # a tile's visible pages of a sequence are contiguous: from its
        # FIRST token's first visible page to its LAST token's own
        assert set(got) == set(want)
        for key, firsts in got.items():
            pages = sorted(want[key])
            assert pages == list(range(pages[0], pages[-1] + 1))
            assert firsts == list(range(pages[0], pages[-1] + 1, run_pages))
        assert m.live == sum(len(v) for v in got.values())
        causal = build_step_maps(
            cu, kv, total_tokens=T, tile_q=tile_q, block_size=block_size,
            max_seqs=S, run_pages=1, max_items=10 ** 5)
        assert m.pages_causal == causal.pages
        assert m.pages <= m.pages_causal
        if window is None:
            assert m.pages == m.pages_causal


def test_a_window_group_s_list_is_sized_by_the_window():
    """``ceil((window + tile_q) / block_size) + 1`` pages a walk, not the
    table's width: 34 of 128 at the cell's shapes."""
    full = rpa_max_items(66, 32, 128)
    win = rpa_max_items(66, 32, 128, window=4096, tile_q=16, block_size=128)
    assert full == 128 * 98 and win == 34 * 98
    # a window wider than the table changes nothing
    assert rpa_max_items(66, 32, 16, window=4096, tile_q=16,
                         block_size=128) == 16 * 98
