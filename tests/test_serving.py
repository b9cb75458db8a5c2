"""Continuous-batching serving subsystem (paddle_tpu.serving).

Coverage contract (ISSUE 2, upgraded by ISSUE 8): block
alloc/free/refcount invariants (no leak after preemption), a short
request admitted while a long one is mid-decode with both matching
their sequential baselines, the HTTP ``/generate`` round trip, a
compile-exactly-once guard over the ONE unified token-packed step
executable, and unified-step scheduler invariants (decode-first
starvation-freedom, multi-chunk budget packing, stale-entry preemption
safety). The full ≥8-concurrent-request acceptance run is marked
``slow``; a single-request smoke stays in tier-1. RPA-vs-gather kernel
parity lives in ``test_ragged_paged_attention.py``.
"""
import json
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.paged_attention import LayerCacheSpec
from paddle_tpu.serving import (BlockAllocator, Server, ServingEngine)
from paddle_tpu.serving.scheduler import RequestState


def _tiny(seed=0):
    pt.seed(seed)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=True))
    m.eval()
    return m


def _eager_continuation(model, prompt, max_new_tokens, eos_token_id=None):
    """Solo greedy baseline: the tokens after the prompt."""
    out = model.generate(pt.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=max_new_tokens, temperature=0.0,
                         eos_token_id=eos_token_id).numpy()[0]
    return [int(t) for t in out[len(prompt):]]


@pytest.fixture(scope="module")
def served():
    """One model + engine shared by the tier-1 tests — engine reuse
    across tests doubles as an organic compile-once check."""
    model = _tiny(0)
    eng = ServingEngine(model, max_batch=4, max_blocks=32, block_size=4,
                        prefill_chunk=4)
    return model, eng


# ---------------- block allocator invariants ---------------------------------
def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(8)
    assert a.num_free() == 8 and a.capacity == 8
    blocks = a.allocate(5)
    assert len(set(blocks)) == 5 and 0 not in blocks  # null block reserved
    assert a.blocks_in_use() == 5 and a.num_free() == 3
    a.free(blocks)
    assert a.blocks_in_use() == 0 and a.num_free() == 8
    a.assert_no_leaks()


def test_allocator_exhaustion_and_double_free():
    a = BlockAllocator(2)
    blocks = a.allocate(2)
    with pytest.raises(MemoryError):
        a.allocate(1)
    a.free(blocks)
    with pytest.raises(ValueError, match="double free"):
        a.free([blocks[0]])


def test_allocator_refcount_shared_block():
    a = BlockAllocator(4)
    (b,) = a.allocate(1)
    a.incref(b)
    assert a.refcount(b) == 2
    a.free([b])                      # first holder drops it
    assert a.blocks_in_use() == 1    # still live: second holder
    a.free([b])
    assert a.blocks_in_use() == 0
    with pytest.raises(ValueError):
        a.incref(b)


def test_decode_outranks_prefill_for_the_last_block():
    """Unified-step planning order (ISSUE 8): decode plans FIRST, so an
    OLDER running request takes the pool's last block ahead of a younger
    prompt's prefill chunk — FCFS holds exactly when the pool is the
    contended resource, and the running request is never starved by a
    streaming prompt."""
    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.scheduler import Request, Scheduler

    cache = PagedKVCache(num_layers=1, num_blocks=3, block_size=4,
                         spec=LayerCacheSpec.kv(1, 4))
    sch = Scheduler(cache, max_batch=2, prefill_chunk=4)
    a = Request(prompt_tokens=[1] * 8)   # older: running, block-boundary
    sch.add(a)
    b = Request(prompt_tokens=[2] * 8)   # younger: about to prefill
    sch.add(b)
    sch._admit()
    a.tables[0] = cache.groups[0].allocator.allocate(2)
    a.prefill_pos = a.num_cached = 8     # next decode needs a 3rd block
    a.state = RequestState.RUNNING
    a.generated = [5]
    plan = sch.schedule()
    # A's decode takes the last free block; B's chunk finds the pool
    # empty and must WAIT (evicting would require a victim younger than
    # B — there is none) — never run through an all-null block table
    assert a in plan.decode and len(a.tables[0]) == 3
    assert plan.prefills == []
    assert b.slot is not None and b.state is RequestState.PREFILL
    assert b.tables == [[]]             # waiting, not corrupted


def test_multi_chunk_packing_and_budget():
    """Several prompts' chunks ride ONE step up to the token budget,
    FCFS order, each capped at prefill_chunk; running decoders are all
    planned first and never skipped while prompts stream
    (starvation-freedom under the unified step)."""
    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.scheduler import Request, Scheduler

    cache = PagedKVCache(num_layers=1, num_blocks=32, block_size=4,
                         spec=LayerCacheSpec.kv(1, 4))
    sch = Scheduler(cache, max_batch=4, prefill_chunk=4, step_tokens=8)
    d = Request(prompt_tokens=[9] * 4)          # oldest: mid-decode
    sch.add(d)
    p1 = Request(prompt_tokens=[1] * 10)        # long prompt, streams
    p2 = Request(prompt_tokens=[2] * 3)
    p3 = Request(prompt_tokens=[3] * 6)
    for r in (p1, p2, p3):
        sch.add(r)
    sch._admit()
    d.tables[0] = cache.groups[0].allocator.allocate(1)
    d.prefill_pos = d.num_cached = 4
    d.state = RequestState.RUNNING
    d.generated = [7]
    plan = sch.schedule()
    # decode first, then chunks FCFS into the remaining 7-token budget:
    # p1 gets its full 4-token chunk, p2 its whole 3-token prompt; p3
    # must wait for the next step
    assert plan.decode == [d]
    assert [(r is p1 or r is p2 or r is p3, n)
            for r, n in plan.prefills] == [(True, 4), (True, 3)]
    assert plan.prefills[0][0] is p1 and plan.prefills[1][0] is p2
    assert plan.total_tokens == 8 <= sch.step_tokens
    # the long prompt streams: next plan gives its SECOND chunk and p3
    # enters; decode is still never skipped
    for seq, n in plan.prefills:
        seq.prefill_pos += n
        seq.num_cached += n
    p2.state = RequestState.RUNNING          # p2's prompt is complete
    p2.generated = [1]
    plan2 = sch.schedule()
    assert d in plan2.decode and p2 in plan2.decode
    assert plan2.prefills[0][0] is p1 and plan2.prefills[0][1] == 4
    assert plan2.total_tokens <= sch.step_tokens


def test_prefill_candidate_preempted_mid_loop_is_skipped():
    """A prefill candidate evicted by a SENIOR candidate's allocation
    earlier in the same _plan_prefills loop must be skipped, not
    planned: planning it would attach fresh blocks to a slotless WAITING
    request (invisible to _pick_victim, so senior requests would starve
    on an unreclaimable block) or spuriously evict a third sequence for
    a plan entry the engine discards anyway."""
    import time as _time

    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.scheduler import Request, Scheduler

    cache = PagedKVCache(num_layers=1, num_blocks=3, block_size=4,
                         spec=LayerCacheSpec.kv(1, 4))
    sch = Scheduler(cache, max_batch=2, prefill_chunk=4, step_tokens=8)
    senior = Request(prompt_tokens=[1] * 4)
    sch.add(senior)
    _time.sleep(0.001)
    junior = Request(prompt_tokens=[2] * 12)  # mid-prefill, holds blocks
    sch.add(junior)
    sch._admit()
    junior.tables[0] = cache.groups[0].allocator.allocate(2)
    junior.prefill_pos = junior.num_cached = 8
    cache.groups[0].allocator.allocate(1)               # drain the last free block
    plan = sch.schedule()
    # senior's chunk evicts junior (frees 2, takes 1, 1 left); the loop
    # then reaches junior — now WAITING/slotless — and must skip it
    assert [r for r, _ in plan.prefills] == [senior]
    assert junior.state is RequestState.WAITING and junior.slot is None
    assert junior.tables == [[]]             # no blocks parked on it
    assert cache.groups[0].allocator.num_free() == 1


def test_evicted_plan_entry_goes_stale_not_corrupt():
    """Protected-victim guarantee under the unified step: when a
    senior prefill's allocation preempts a younger request that the SAME
    plan already scheduled for decode, the victim's entry is left stale
    (slot released, state WAITING) — exactly what the engine's
    stale-entry filter checks — and its blocks are returned, never
    written through."""
    import time as _time

    from paddle_tpu.serving import PagedKVCache
    from paddle_tpu.serving.scheduler import Request, Scheduler

    cache = PagedKVCache(num_layers=1, num_blocks=2, block_size=4,
                         spec=LayerCacheSpec.kv(1, 4))
    sch = Scheduler(cache, max_batch=2, prefill_chunk=4, step_tokens=5)
    old = Request(prompt_tokens=[1] * 4)     # senior, needs 1 block
    sch.add(old)
    _time.sleep(0.001)
    young = Request(prompt_tokens=[2] * 4)   # junior: running on 1 block
    sch.add(young)
    sch._admit()
    young.tables[0] = cache.groups[0].allocator.allocate(1)
    young.prefill_pos = young.num_cached = 3  # 4th token fits block 1
    young.state = RequestState.RUNNING
    young.generated = [5]
    cache.groups[0].allocator.allocate(1)               # drain the rest of the pool
    plan = sch.schedule()
    # young decodes within its block -> planned; old's 4-token chunk
    # then needs a block -> evicts young (the only junior victim)
    assert young in plan.decode
    assert sch.num_preemptions == 1
    assert young.slot is None and young.state is RequestState.WAITING
    assert young.tables == [[]]              # returned, not dangling
    # the engine-side stale filter must drop it
    live = [s for s in plan.decode
            if s.slot is not None and s.state is RequestState.RUNNING]
    assert live == []
    # and the senior prefill got real blocks for its planned chunk
    assert plan.prefills and plan.prefills[0][0] is old
    seq, n = plan.prefills[0]
    assert cache.blocks_for(seq.prefill_pos + n) <= len(seq.tables[0])


# ---------------- engine: tier-1 smoke ---------------------------------------
def test_engine_single_request_matches_eager(served):
    model, eng = served
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 128, 9)
    h = eng.submit(prompt, max_new_tokens=8)
    eng.run_until_idle()
    res = h.result(timeout=30)
    assert res["token_ids"] == _eager_continuation(model, prompt, 8)
    assert res["finish_reason"] == "length"
    assert res["ttft_s"] > 0 and res["latency_s"] >= res["ttft_s"]
    assert eng.cache.groups[0].allocator.blocks_in_use() == 0
    assert eng.step_traces == 1  # ONE unified executable, traced once


def test_engine_streaming_and_eos(served):
    model, eng = served
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, 128, 6)
    first = _eager_continuation(model, prompt, 1)[0]
    got = []
    h = eng.submit(prompt, max_new_tokens=10, eos_token_id=first,
                   on_token=lambda req, tok: got.append(tok))
    eng.run_until_idle()
    res = h.result(timeout=30)
    # greedy first token IS the eos: one streamed token, eos finish
    assert res["token_ids"] == [first] == got
    assert res["finish_reason"] == "eos"
    eng.cache.assert_no_leaks()


def test_short_request_joins_mid_decode(served):
    """Continuous batching: a short request admitted while a long one is
    mid-decode; both match their solo sequential baselines and the short
    one finishes first."""
    model, eng = served
    rng = np.random.RandomState(2)
    long_p, short_p = rng.randint(1, 128, 14), rng.randint(1, 128, 5)
    h_long = eng.submit(long_p, max_new_tokens=16)
    while h_long._req.state is not RequestState.RUNNING:
        assert eng.step()
    eng.step()  # at least one pure-decode step before the newcomer
    h_short = eng.submit(short_p, max_new_tokens=3)
    eng.run_until_idle()
    assert h_short.result(30)["token_ids"] == \
        _eager_continuation(model, short_p, 3)
    assert h_long.result(30)["token_ids"] == \
        _eager_continuation(model, long_p, 16)
    assert h_short._req.finish_time < h_long._req.finish_time
    assert eng.step_traces == 1  # the newcomer reused the executable


@pytest.mark.slow
def test_preemption_recompute_no_leak():
    """A pool too small for all admitted sequences forces preemption-by-
    recompute; outputs stay equal to the solo baselines and every block
    returns to the pool. (Slow lane: needs its own engine — tier-1 keeps
    the allocator invariants + shared-engine leak asserts.)"""
    model = _tiny(5)
    eng = ServingEngine(model, max_batch=3, max_blocks=8, block_size=4,
                        prefill_chunk=4)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 128, n) for n in (9, 12, 7)]
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle()
    for hd, p in zip(handles, prompts):
        assert hd.result(30)["token_ids"] == \
            _eager_continuation(model, p, 8)
    assert eng.scheduler.num_preemptions >= 1
    eng.cache.assert_no_leaks()
    assert eng.step_traces == 1
    # recompute-tail invariant (ISSUE 15): across every admission, a
    # request prefills AT MOST its pending demand minus what the prefix
    # cache served — readmission never recomputes a cached block
    for hd in handles:
        r = hd._req
        assert r.prefilled_tokens <= \
            r.admitted_pending_total - r.cached_tokens_total
        if r.preemptions == 0:
            assert r.prefilled_tokens == \
                r.admitted_pending_total - r.cached_tokens_total


def test_submit_validation(served):
    _, eng = served
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    with pytest.raises(ValueError, match="max sequence length"):
        eng.submit([1] * 8, max_new_tokens=10_000)


def test_abort_releases_queued_request():
    """Resilience seam (docs/RESILIENCE.md): aborting a request frees
    its queue entry/slot/blocks and fails the handle — the HTTP server
    uses this when a request blows its deadline_s. Engine never steps,
    so no compile cost in tier-1."""
    model = _tiny(7)
    eng = ServingEngine(model, max_batch=2, max_blocks=16, block_size=4,
                        prefill_chunk=4)
    h1 = eng.submit([1, 2, 3], max_new_tokens=4)
    h2 = eng.submit([4, 5, 6], max_new_tokens=4)
    assert eng.abort(h1.req_id, reason="client deadline")
    assert not eng.abort(h1.req_id)      # already finished: no-op
    assert not eng.abort(424242)         # unknown id: no-op
    with pytest.raises(RuntimeError, match="client deadline"):
        h1.result(1)
    # the aborted request left the scheduler entirely; the other stays
    assert h2._req in eng.scheduler.waiting or h2._req.slot is not None
    assert h1._req not in eng.scheduler.waiting and h1._req.slot is None
    assert eng.stats()["waiting"] + eng.stats()["running"] == 1
    eng.cache.assert_no_leaks()
    # (the request ledger is one a process: leave nothing in flight for
    # the tests of other files that a worker runs after this one)
    eng.shutdown(drain=False)


# ---------------- HTTP front-end ---------------------------------------------
def test_http_generate_roundtrip(served):
    """Rides the shared module engine (no extra compile in tier-1): the
    server only wraps the engine's already-traced executables."""
    model, eng = served
    rng = np.random.RandomState(4)
    prompt = [int(t) for t in rng.randint(1, 128, 6)]
    srv = Server(eng).start()
    try:
        req = urllib.request.Request(
            srv.url + "/generate",
            data=json.dumps({"prompt_ids": prompt,
                             "max_new_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        res = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert res["token_ids"] == _eager_continuation(model, prompt, 5)
        assert res["ttft_ms"] > 0

        hz = json.loads(urllib.request.urlopen(
            srv.url + "/healthz", timeout=10).read())
        assert hz["status"] == "ok" and hz["step_compiles"] == 1
        # KV-pool pressure is visible to operators before preemption
        # starts churning (ISSUE 8 satellite)
        assert 0.0 <= hz["kv_headroom"] <= 1.0
        assert hz["attn_impl"] in ("rpa", "gather")
        # fleet identity fields (ISSUE 13): which rank of which job
        # answered, and is it actually making progress
        assert hz["rank"] == 0 and hz["job_id"]
        assert hz["last_step_age_seconds"] >= 0.0
        fz = json.loads(urllib.request.urlopen(
            srv.url + "/fleetz", timeout=10).read())
        assert fz["job_id"] == hz["job_id"] and "local_goodput" in fz

        # streaming: one NDJSON line per token, then the summary
        req = urllib.request.Request(
            srv.url + "/generate",
            data=json.dumps({"prompt_ids": prompt, "max_new_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        lines = [json.loads(ln) for ln in urllib.request.urlopen(
            req, timeout=60).read().decode().strip().split("\n")]
        toks = [ln["token"] for ln in lines if "token" in ln]
        assert toks == _eager_continuation(model, prompt, 4)
        assert lines[-1]["done"] is True

        bad = urllib.request.Request(srv.url + "/generate", data=b"nope",
                                     headers={"Content-Type": "text/plain"})
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(bad, timeout=10)
    finally:
        # engine outlives the listener (later tests may reuse it)
        srv.close(stop_engine=False)
    eng.cache.assert_no_leaks()


def test_metrics_families_exposed(served):
    """serving_* metric families are live in the registry after an
    engine run (acceptance: non-zero TTFT + token totals). Drives one
    request itself so the test holds in isolation."""
    from paddle_tpu.observability import get_registry
    model, eng = served
    h = eng.submit(np.random.RandomState(6).randint(1, 128, 4),
                   max_new_tokens=2)
    eng.start()  # idempotent — the HTTP test may have started the loop
    h.result(timeout=60)
    reg = get_registry()
    ttft = reg.get("serving_ttft_seconds")
    toks = reg.get("serving_tokens_total")
    assert ttft is not None and ttft.stats() and ttft.stats()["count"] > 0
    assert toks is not None and toks.total() > 0
    text = reg.prometheus_text()
    for family in ("serving_ttft_seconds", "serving_tokens_total",
                   "serving_queue_depth", "serving_requests_running",
                   "serving_kv_blocks_in_use",
                   "serving_inter_token_seconds"):
        assert family in text


def test_rpa_walk_follows_live_work_under_one_executable():
    """Steps of very different live counts (no work, 16 decode rows, a
    long prompt's chunks over a growing context beside them) run the ONE
    executable: the RPA kernel's trip count is a traced input. Streams
    equal the gather reader's, and ``serving_rpa_steps_total`` grows by
    each step's work list: live items, and walked = live + one item for
    each q tile without work."""
    from paddle_tpu.serving.engine import serving_metrics
    model = _tiny(4)
    rng = np.random.RandomState(12)
    short = [rng.randint(1, 128, 2 + i % 3) for i in range(16)]
    long_prompt = rng.randint(1, 128, 70)
    counter = serving_metrics()["rpa_steps"]
    streams, walks = {}, []
    for impl in ("gather", "rpa"):
        eng = ServingEngine(model, max_batch=16, max_blocks=96,
                            block_size=4, prefill_chunk=16,
                            attn_impl=impl)
        build = eng._build_step_maps
        built = []
        eng._build_step_maps = lambda *a, **k: (
            built.append(build(*a, **k)) or built[-1])
        before = (counter.value(kind="walked"), counter.value(kind="live"),
                  counter.value(kind="pages"))
        assert eng.step() is False          # no work: nothing dispatched
        handles = [eng.submit(p, max_new_tokens=8 + i % 5)
                   for i, p in enumerate(short)]
        for _ in range(5):          # 48 prompt tokens, 16 a step; 1 more
            eng.step()
        decode_rows = sum(r.state is RequestState.RUNNING
                          for r in eng.scheduler.slotted())
        handles.append(eng.submit(long_prompt, max_new_tokens=4))
        while eng.step():
            pass
        streams[impl] = [h.result(30)["token_ids"] for h in handles]
        assert eng.step_traces == 1
        eng.cache.assert_no_leaks()
        grown = (counter.value(kind="walked") - before[0],
                 counter.value(kind="live") - before[1])
        if impl == "gather":
            assert not built and grown == (0, 0)
            continue
        assert decode_rows == 16
        num_tiles = eng.step_tokens // eng._tile_q
        for m in built:
            sentinels = int(np.sum(m.step_seq[:m.walked] == eng.max_batch))
            assert m.walked == m.live + sentinels
            assert num_tiles <= m.walked <= eng._maps_kw[0]["max_items"]
            # a tile holds the sentinel item only where it has no work
            assert sentinels == sum(
                m.step_tile[j + 1] - m.step_tile[j] == 1
                and m.step_seq[m.step_tile[j]] == eng.max_batch
                for j in range(num_tiles))
        walks = [m.walked for m in built]
        pages = [m.pages for m in built]
        assert all(m.live <= m.pages <= eng._run_pages * m.live
                   for m in built)
        assert grown == (sum(walks), sum(m.live for m in built))
        assert counter.value(kind="pages") - before[2] == sum(pages)
    assert streams["rpa"] == streams["gather"]
    assert streams["rpa"][-1] == _eager_continuation(model, long_prompt, 4)
    # the bound moved with the work: a lone decode tail walks a few
    # items, the long prompt's last chunks the runs of pages each tile can
    # see (an item names up to 8 pages here: float32 K/V pages of 4 x 16)
    assert max(walks) >= 2 * min(walks), walks
    assert max(pages) >= 4 * min(pages), pages


# ---------------- generate_loop early exit (satellite) -----------------------
def test_generate_loop_breaks_on_all_eos():
    """The eager decode loop must stop as soon as every row has hit
    eos_token_id — not run all max_new_tokens steps."""
    from paddle_tpu.models.generation import generate_loop

    m = _tiny(7)
    ids = pt.to_tensor(np.random.RandomState(8).randint(
        1, 128, (1, 6)).astype(np.int64))
    eos = int(m.generate(ids, max_new_tokens=1,
                         temperature=0.0).numpy()[0, -1])
    calls = {"decode": 0}

    def prefill(x):
        caches = [(None, None)] * m.cfg.num_hidden_layers
        h, caches = m.model(x, caches=caches)
        return m._logits(h[:, -1:]), caches

    def decode(tok, caches):
        calls["decode"] += 1
        h, caches = m.model(tok, caches=caches)
        return m._logits(h), caches

    out = generate_loop(prefill, decode, ids, max_new_tokens=20,
                        temperature=0.0, eos_token_id=eos)
    n_new = out.numpy().shape[1] - 6
    assert n_new < 20, "loop ran the full budget despite universal eos"
    # the loop may decode only while some row is unfinished
    assert calls["decode"] == n_new - 1


@pytest.mark.slow
def test_moe_served_independent_of_inactive_slots():
    """MoE through the engine: inactive decode slots and padded prefill
    tails must not perturb expert-capacity routing for real tokens — the
    same request gives identical tokens whether it runs in a 1-slot or a
    4-slot engine (regression for garbage tokens stealing GShard
    capacity positions), and matches the eager oracle here."""
    from paddle_tpu.models.moe import MoeConfig, MoeForCausalLM

    pt.seed(3)
    cfg = MoeConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                    moe_intermediate_size=32, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    num_experts=4, num_experts_per_tok=2,
                    num_shared_experts=1, first_k_dense_replace=1)
    m = MoeForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(21)
    p = rng.randint(1, 128, 9)
    outs = []
    for mb in (1, 4):
        eng = ServingEngine(m, max_batch=mb, max_blocks=32, block_size=4,
                            prefill_chunk=4)
        h = eng.submit(p, max_new_tokens=6)
        eng.run_until_idle()
        outs.append(h.result(30)["token_ids"])
        eng.cache.assert_no_leaks()
    assert outs[0] == outs[1], \
        "occupancy changed an MoE request's routing/output"
    assert m.aux_loss() is None  # decode tracers cleared via the hook
    assert outs[0] == _eager_continuation(m, p, 6)


# ---------------- acceptance integration (slow) ------------------------------
@pytest.mark.slow
def test_serving_acceptance_concurrent_mixed():
    """ISSUE 2 acceptance: >= 8 concurrent requests with mixed
    prompt/output lengths — decode compiles exactly once, every KV block
    returns to the pool, serving metrics are non-zero, every output
    token-matches its sequential baseline."""
    model = _tiny(9)
    eng = ServingEngine(model, max_batch=8, max_blocks=48, block_size=4,
                        prefill_chunk=8)
    rng = np.random.RandomState(11)
    lens = [5, 11, 17, 8, 13, 7, 20, 9, 15, 6]
    mnts = [6, 10, 4, 12, 8, 5, 7, 9, 3, 11]
    prompts = [rng.randint(1, 128, n) for n in lens]
    eng.start()
    handles = [eng.submit(p, max_new_tokens=mn)
               for p, mn in zip(prompts, mnts)]
    eng.drain(timeout=300)
    for hd, p, mn in zip(handles, prompts, mnts):
        assert hd.result(30)["token_ids"] == \
            _eager_continuation(model, p, mn)
    assert eng.step_traces == 1
    eng.cache.assert_no_leaks()
    eng.shutdown()

    from paddle_tpu.observability import get_registry
    reg = get_registry()
    assert reg.get("serving_ttft_seconds").stats()["count"] >= 10
    assert reg.get("serving_tokens_total").total() > 0


@pytest.mark.slow
def test_http_concurrent_clients():
    """Parallel HTTP clients against one server: every response matches
    its solo baseline (the engine multiplexes them into one batch)."""
    model = _tiny(10)
    eng = ServingEngine(model, max_batch=4, max_blocks=32, block_size=4,
                        prefill_chunk=4)
    rng = np.random.RandomState(12)
    prompts = [[int(t) for t in rng.randint(1, 128, n)]
               for n in (5, 9, 12, 7, 10)]
    results = [None] * len(prompts)

    with Server(eng) as srv:
        def client(i):
            req = urllib.request.Request(
                srv.url + "/generate",
                data=json.dumps({"prompt_ids": prompts[i],
                                 "max_new_tokens": 6}).encode(),
                headers={"Content-Type": "application/json"})
            results[i] = json.loads(
                urllib.request.urlopen(req, timeout=120).read())

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    for i, p in enumerate(prompts):
        assert results[i]["token_ids"] == _eager_continuation(model, p, 6)
    eng.cache.assert_no_leaks()


def test_request_span_chain_in_trace(served, tmp_path):
    """PR 6 tentpole: the engine writes a per-request span chain
    (queue_wait -> prefill_chunk(s) -> decode -> request_done) into the
    trace layer, so a slow TTFT decomposes into admission vs
    compile vs preemption right in the merged trace."""
    from paddle_tpu.observability import trace
    model, eng = served
    trace.disable()
    trace.enable(str(tmp_path), rank=0)
    try:
        prompt = list(range(1, 7))
        h = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_idle()
        res = h.result(timeout=60)
    finally:
        writer_path = trace.active().path
        trace.disable()
    events = [json.loads(ln) for ln in open(writer_path)][1:]
    mine = [e for e in events
            if (e.get("args") or {}).get("req") == res["request_id"]]
    names = [e["name"] for e in mine]
    assert "queue_wait" in names
    # prefill_chunk=4 and a 6-token prompt: two chunks
    assert names.count("prefill_chunk") == 2
    assert "decode" in names and "request_done" in names
    # chain ordering: queue_wait ends before the first prefill chunk
    # starts; decode covers first->last token; done is terminal
    qw = next(e for e in mine if e["name"] == "queue_wait")
    pf = [e for e in mine if e["name"] == "prefill_chunk"]
    dec = next(e for e in mine if e["name"] == "decode")
    done = next(e for e in mine if e["name"] == "request_done")
    assert qw["ts"] + qw["dur"] <= pf[0]["ts"]
    assert pf[-1]["ts"] + pf[-1]["dur"] <= dec["ts"] + dec["dur"]
    assert done["args"]["finish_reason"] == "length"
    assert done["args"]["generated"] == 4
    assert done["args"]["ttft_s"] > 0
    # compile attribution rides the chunk spans (engine is warm: 0)
    assert all("compiles" in e["args"] for e in pf)
    # and the queue-wait histogram got its observation
    from paddle_tpu.observability import get_registry
    qwh = get_registry().get("serving_queue_wait_seconds")
    assert qwh is not None and qwh.stats()["count"] >= 1
