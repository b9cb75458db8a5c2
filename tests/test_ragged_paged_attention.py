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
* the host-side work-list builder's invariants (every (sequence, page)
  pair exactly once per overlapping tile, only real pages, static
  bound honored).
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.paged_attention import (
    impl_override, paged_attention_impl, ragged_gather_attention,
    write_tokens_to_pool)
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    build_step_maps, ragged_paged_attention, rpa_max_steps)
from paddle_tpu.serving import ServingEngine


# ---------------- raw kernel parity ------------------------------------------
def _ragged_case(rng, seqs, block_size, n_kv, grp, hd=16, tile_q=8,
                 mbps=6, pool_blocks=24):
    """Build one token-packed ragged scenario: ``seqs`` is a list of
    ``(new_len, context_len)`` — new_len 0 models a padding slot whose
    metadata row exists but owns no tokens. Returns everything the two
    impls and the eager oracle need."""
    n_heads = n_kv * grp
    max_seqs = len(seqs) + 1          # one extra never-used row
    total_new = sum(n for n, _ in seqs)
    T = -(-max(total_new, 1) // tile_q) * tile_q
    max_steps = rpa_max_steps(tile_q, mbps, pool_blocks)

    bt = np.zeros((max_seqs + 1, mbps), np.int32)
    nxt = 1
    kv_lens = []
    for s, (n, c) in enumerate(seqs):
        kv = n + c
        kv_lens.append(kv)
        npg = -(-kv // block_size) if kv else 0
        bt[s, :npg] = np.arange(nxt, nxt + npg)
        nxt += npg
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
        full_k.append(fk)
        full_v.append(fv)
        for t in range(c):            # prior context from earlier steps
            kp[bt[s, t // block_size], :, t % block_size] = fk[t]
            vp[bt[s, t // block_size], :, t % block_size] = fv[t]
    q = rng.randn(T, n_heads, hd).astype(np.float32)
    knew = np.zeros((T, n_kv, hd), np.float32)
    vnew = np.zeros((T, n_kv, hd), np.float32)
    off = 0
    for s, (n, c) in enumerate(seqs):
        knew[off:off + n] = full_k[s][c:]
        vnew[off:off + n] = full_v[s][c:]
        off += n

    kp2 = write_tokens_to_pool(jnp.asarray(kp), jnp.asarray(knew),
                               jnp.asarray(bt), jnp.asarray(sid),
                               jnp.asarray(pos))
    vp2 = write_tokens_to_pool(jnp.asarray(vp), jnp.asarray(vnew),
                               jnp.asarray(bt), jnp.asarray(sid),
                               jnp.asarray(pos))
    ssq, sbk = build_step_maps(cu[:len(seqs) + 1], kv_lens,
                               total_tokens=T, tile_q=tile_q,
                               block_size=block_size,
                               max_steps=max_steps, max_seqs=max_seqs)
    return dict(q=q, kp=kp2, vp=vp2, bt=bt, cu=cu, ctx=ctx, sid=sid,
                pos=pos, ssq=ssq, sbk=sbk, full_k=full_k, full_v=full_v,
                seqs=seqs, max_seqs=max_seqs, grp=grp, hd=hd)


def _eager_oracle(case):
    """Per-sequence dense softmax over the contiguous K/V — the ground
    truth both paged impls must match."""
    q, seqs = case["q"], case["seqs"]
    grp, hd = case["grp"], case["hd"]
    scale = 1.0 / np.sqrt(hd)
    ref = np.zeros((q.shape[0], q.shape[1], hd), np.float32)
    off = 0
    for s, (n, c) in enumerate(seqs):
        K, V = case["full_k"][s], case["full_v"][s]
        for i in range(n):
            t = off + i
            kvis, vvis = K[:c + i + 1], V[:c + i + 1]
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
    out_rpa = np.asarray(ragged_paged_attention(
        jnp.asarray(c["q"]), c["kp"], c["vp"], jnp.asarray(c["bt"]),
        jnp.asarray(c["cu"]), jnp.asarray(c["ctx"]), c["ssq"], c["sbk"]))
    out_g = np.asarray(ragged_gather_attention(
        jnp.asarray(c["q"]), c["kp"], c["vp"], jnp.asarray(c["bt"]),
        jnp.asarray(c["sid"]), jnp.asarray(c["pos"]),
        scale=1.0 / np.sqrt(c["hd"])))
    ref = _eager_oracle(c)
    valid = c["sid"] < c["max_seqs"]
    np.testing.assert_allclose(out_rpa[valid], ref[valid], atol=2e-5)
    np.testing.assert_allclose(out_g[valid], ref[valid], atol=2e-5)
    # padding tokens: the kernel produces exact zeros (l == 0 guard)
    assert np.all(out_rpa[~valid] == 0.0)


def test_step_maps_cover_each_page_exactly_once():
    """Work-list invariants: for every tile, each overlapping sequence
    contributes exactly ceil(kv_len / block_size) steps (its REAL pages,
    nothing more), empty sequences contribute none, and dead steps carry
    the sentinel."""
    cu = np.array([0, 5, 5, 6, 16])  # seq 1 is a new_len == 0 slot
    kv_lens = [5, 8, 9, 16]
    tile_q, bs, max_seqs = 8, 8, 6
    ssq, sbk = build_step_maps(cu, kv_lens, total_tokens=16,
                               tile_q=tile_q, block_size=bs,
                               max_steps=rpa_max_steps(tile_q, 4, 32),
                               max_seqs=max_seqs)
    for j in range(2):
        lo, hi = j * tile_q, (j + 1) * tile_q
        want = {}
        for s in range(4):
            if cu[s] < cu[s + 1] and cu[s + 1] > lo and cu[s] < hi:
                want[s] = -(-kv_lens[s] // bs)
        got = {}
        for s, b in zip(ssq[j], sbk[j]):
            if s == max_seqs:
                continue
            got.setdefault(int(s), []).append(int(b))
        assert {s: len(b) for s, b in got.items()} == want
        for s, blocks in got.items():
            assert blocks == list(range(want[s]))  # each page once, in order
    with pytest.raises(ValueError, match="max_steps"):
        build_step_maps(cu, kv_lens, total_tokens=16, tile_q=tile_q,
                        block_size=bs, max_steps=1, max_seqs=max_seqs)


def test_impl_knob_resolution(monkeypatch):
    """auto = gather off-TPU; env and override win in that order."""
    monkeypatch.delenv("PADDLE_TPU_PAGED_ATTN_IMPL", raising=False)
    assert paged_attention_impl() == "gather"  # CPU mesh
    monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN_IMPL", "rpa")
    assert paged_attention_impl() == "rpa"
    with impl_override("gather"):
        assert paged_attention_impl() == "gather"
    assert paged_attention_impl() == "rpa"
    monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN_IMPL", "bogus")
    with pytest.raises(ValueError, match="bogus"):
        paged_attention_impl()


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
        eng.cache.allocator.assert_no_leaks()
    assert streams["rpa"] == streams["gather"]
    assert streams["rpa"] == [
        _eager_continuation(model, p, 5) for p in prompts]


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
        eng.cache.allocator.assert_no_leaks()
    assert streams["rpa"] == streams["gather"]
