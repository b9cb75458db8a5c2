"""The serving loop one step ahead (ISSUE 32): a step's tokens are sampled
inside the compiled program and feed the next step on the device; the run
loop dispatches step n+1 while step n runs and harvests step n after.
``step()`` by hand is the same two halves in the serial order. Both orders
serve the same tokens (greedy, and sampled from the same keys), an EOS ends
a request one row late without trace, preemption and aborts of sequences in
flight go serial, prefix hashes and window pages close as before, the
counter says which steps ran ahead, and the one executable is traced once."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import paddle_tpu as pt
from paddle_tpu.core import generator as G
from paddle_tpu.models.generation import sample_rows, sample_token
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import serving_metrics
from paddle_tpu.serving.scheduler import RequestState

ENGINE = dict(max_batch=4, max_blocks=64, block_size=4, prefill_chunk=8)


@pytest.fixture(scope="module")
def model():
    """Untied, wide-initialised: its greedy continuations do not repeat."""
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, tie_word_embeddings=False,
        initializer_range=0.2))
    m.eval()
    return m


_built = []


def engine(model, **kw):
    _built.append(ServingEngine(model, **{**ENGINE, **kw}))
    return _built[-1]


@pytest.fixture(autouse=True)
def stop_the_loops():
    yield
    while _built:
        _built.pop().shutdown(drain=False)


def eager(model, prompt, n):
    out = model.generate(pt.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=n, temperature=0.0).numpy()[0]
    return [int(t) for t in out[len(prompt):]]


def drive(eng, order):
    """Serve what was submitted: by hand (serial) or under the run loop."""
    if order == "serial":
        eng.run_until_idle()
        return
    eng.start()
    eng.drain(timeout=120)


def dispatched(**labels):
    return serving_metrics()["dispatched"].value(**labels)


PROMPTS = [np.random.default_rng(5).integers(1, 128, n).tolist()
           for n in (5, 17, 9, 30, 12, 3)]
SAMPLING = [{}, dict(temperature=0.8, top_k=10), {},
            dict(temperature=1.2, top_p=0.9),
            dict(temperature=0.6, top_k=20, top_p=0.8),
            dict(temperature=1.0)]


# ------------------------------------------------ (a) one result, two orders --
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_serial_and_ahead_orders_serve_the_same_tokens(model, sampled):
    served = {}
    for order in ("serial", "ahead"):
        pt.seed(11)                      # the host stream the keys come from
        eng = engine(model)
        handles = [eng.submit(p, max_new_tokens=6 + i,
                              **(SAMPLING[i] if sampled else {}))
                   for i, p in enumerate(PROMPTS)]
        drive(eng, order)
        served[order] = [h.result(timeout=60)["token_ids"] for h in handles]
        assert eng.step_traces == 1
        eng.cache.assert_no_leaks()
    assert served["serial"] == served["ahead"]
    for i, (p, toks) in enumerate(zip(PROMPTS, served["ahead"])):
        assert len(toks) == 6 + i
        if not (sampled and SAMPLING[i]):
            # a greedy row beside sampled rows of the same step is its
            # argmax still
            assert toks == eager(model, p, 6 + i)
    if sampled:                          # and the draws are draws
        assert served["ahead"][3] != eager(model, PROMPTS[3], 9)


def test_sample_rows_draws_what_sample_token_draws_a_row():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 500)).astype(np.float32) * 3)
    temps = [0.0, 0.7, 1.3, 1.0, 0.0, 0.5]
    top_ks = [0, 5, 0, 50, 3, 0]
    top_ps = [1.0, 1.0, 0.8, 0.9, 0.5, 1.0]
    base, _ = G.next_key_parts()
    keys = jax.vmap(lambda c: jax.random.fold_in(base, c))(
        jnp.arange(10, 16, dtype=jnp.uint32))
    got = jax.jit(sample_rows)(
        logits, jnp.asarray(temps, jnp.float32),
        jnp.asarray(top_ks, jnp.int32), jnp.asarray(top_ps, jnp.float32),
        keys)
    want = [int(sample_token(logits[i:i + 1], temps[i], top_ks[i], top_ps[i],
                             key=jax.random.fold_in(base, 10 + i))[0])
            for i in range(6)]
    assert np.asarray(got).tolist() == want
    assert want[0] == int(np.argmax(logits[0])) \
        and want[4] == int(np.argmax(logits[4]))
    # ties go to the first index, as np.argmax
    tied = jnp.zeros((2, 9), jnp.float32).at[:, [3, 7]].set(1.0)
    zeros = jnp.zeros((2,), jnp.float32)
    assert np.asarray(sample_rows(tied, zeros, zeros.astype(jnp.int32),
                                  zeros + 1.0, keys[:2])).tolist() == [3, 3]


def test_the_greedy_branch_of_the_compiled_step_holds_no_sort(model):
    """The sort of the sampler sits in one branch of a conditional on "any
    row samples"; the entry computation and the other branch have none."""
    import re
    hlo = engine(model).compiled_hlo()
    comps = dict(re.findall(r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}",
                            hlo, re.S | re.M))
    with_sort = {name for name, body in comps.items() if " sort(" in body}
    assert with_sort, "the sampled branch sorts"
    cond, = re.findall(r"conditional\(.*?branch_computations=\{([^}]*)\}|"
                       r"conditional\(.*?true_computation=%?([\w.\-]+), "
                       r"false_computation=%?([\w.\-]+)", hlo)
    branches = [b.strip().lstrip("%") for b in
                (cond[0].split(",") if cond[0] else cond[1:])]
    assert len(branches) == 2

    def reaches_sort(name, seen=()):
        if name in with_sort:
            return True
        called = re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                            comps.get(name, ""))
        return any(reaches_sort(c, seen + (name,)) for c in called
                   if c not in seen)
    assert sorted(reaches_sort(b) for b in branches) == [False, True]
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo, re.M).group(1)
    assert entry not in with_sort


# ------------------------------------------------------- (b) an EOS at step n --
@pytest.mark.parametrize("order", ["serial", "ahead"])
def test_an_eos_ends_the_request_and_the_row_behind_it_is_dropped(model,
                                                                  order):
    prompt = PROMPTS[2]
    full = eager(model, prompt, 14)
    k = next(i for i in range(3, 14) if full[i] not in full[:i])
    eng = engine(model)
    streamed = []
    h = eng.submit(prompt, max_new_tokens=14, eos_token_id=full[k],
                   on_token=lambda req, tok: streamed.append(tok))
    drive(eng, order)
    res = h.result(timeout=60)
    assert res["token_ids"] == full[:k + 1] == streamed
    assert res["finish_reason"] == "eos"
    # under the run loop the step after the EOS was in flight already: one
    # more token was sampled for the request, and never emitted
    assert h._req.num_sampled == k + 1 + (order == "ahead")
    assert eng.cache.groups[0].allocator.blocks_in_use() == 0
    eng.cache.assert_no_leaks()
    # the slot serves the next request, on the same executable
    again = eng.submit(PROMPTS[0], max_new_tokens=5)
    drive(eng, order)
    assert again.result(timeout=60)["token_ids"] == eager(model, PROMPTS[0], 5)
    assert eng.step_traces == 1


# ------------------------------------- (c) preemption beside a step in flight --
def test_preempting_a_sequence_in_flight_harvests_first(model):
    """Two decoding sequences over a pool that holds one to its end: the
    older needs a page, the younger (its newest token on the device) is the
    victim. The plan waits for the harvest, the recompute text holds every
    generated token, and the outputs are the unpreempted ones."""
    a, b = PROMPTS[2][:6], PROMPTS[4][:6]
    eng = engine(model, max_batch=2, max_blocks=7, prefix_cache=False)
    before = dispatched(order="serial", reason="preempt")
    ha = eng.submit(a, max_new_tokens=12)
    hb = eng.submit(b, max_new_tokens=12)
    drive(eng, "ahead")
    assert ha.result(60)["token_ids"] == eager(model, a, 12)
    assert hb.result(60)["token_ids"] == eager(model, b, 12)
    assert hb._req.preemptions >= 1 and ha._req.preemptions == 0
    assert eng.scheduler.num_preemptions >= 1
    assert dispatched(order="serial", reason="preempt") - before >= 1
    # what the recompute prefilled: the prompt and the tokens it had then
    assert hb._req.prefilled_tokens > len(b)
    assert eng.step_traces == 1
    eng.cache.assert_no_leaks()


# ------------------------------------------------------- (d) prefix hashes --
def test_blocks_of_generated_text_are_registered_as_under_the_serial_order(
        model):
    prompt = PROMPTS[3][:10]
    index, cached = {}, {}
    for order in ("serial", "ahead"):
        eng = engine(model)
        first = eng.submit(prompt, max_new_tokens=11)
        drive(eng, order)
        text = prompt + first.result(60)["token_ids"]
        # 10 + 11 tokens, the last never fed: 20 cached, five full blocks
        index[order] = set(eng.cache.groups[0].prefix_cache._index)
        assert len(index[order]) == 5
        second = eng.submit(text[:18] + [1, 2, 3], max_new_tokens=3)
        drive(eng, order)
        second.result(60)
        cached[order] = second._req.cached_prompt_tokens
        assert eng.step_traces == 1
        eng.cache.assert_no_leaks()
    assert index["serial"] == index["ahead"]
    # the second ask found the blocks that hold generated tokens too
    assert cached["serial"] == cached["ahead"] == 16 > len(prompt)


# --------------------------------------- (e) two cache groups with a window --
def test_window_pages_go_back_and_both_pools_close_at_drain():
    from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerForCausalLM)
    pt.seed(3)
    m = SmallThinkerForCausalLM(SmallThinkerConfig.tiny())
    m.eval()
    rng = np.random.default_rng(9)
    asks = [(rng.integers(1, 128, 100).tolist(), 20),   # over three windows
            (rng.integers(1, 128, 21).tolist(), 12)]
    released = serving_metrics()["kv_released"]
    served, gone = {}, {}
    for order in ("serial", "ahead"):
        before = released.value(group="window")
        eng = engine(m, max_blocks={"full": 48, "window": 24}, block_size=8,
                     prefill_chunk=16, attn_impl="gather")
        handles = [eng.submit(p, max_new_tokens=n) for p, n in asks]
        drive(eng, order)
        served[order] = [h.result(60)["token_ids"] for h in handles]
        gone[order] = released.value(group="window") - before
        assert len(eng.cache.groups) == 2 and eng.step_traces == 1
        assert all(g.allocator.blocks_in_use() == 0
                   for g in eng.cache.groups)
        eng.cache.assert_no_leaks()
    assert served["serial"] == served["ahead"]
    # 120 tokens are 15 pages: all but the last window's went back as the
    # sequence moved on, in either order
    assert gone["serial"] == gone["ahead"] >= 10
    assert released.value(group="full") == 0


# ------------------------------------------- (f) abort of a sequence in flight --
def test_an_abort_in_flight_drops_the_row_and_the_next_step_goes_serial(
        model):
    eng = engine(model)
    ha = eng.submit(PROMPTS[0], max_new_tokens=8)
    hb = eng.submit(PROMPTS[2], max_new_tokens=8)
    assert eng._dispatch()               # both prompts' chunks in flight
    assert len(eng._flights) == 1
    before = dispatched(order="serial", reason="abort")
    assert eng.abort(ha.req_id)
    assert eng.abort(ha.req_id) is False           # already finished
    with pytest.raises(RuntimeError, match="aborted"):
        ha.result(timeout=5)
    assert eng._dispatch()               # harvests the step in flight first
    assert len(eng._flights) == 1
    assert dispatched(order="serial", reason="abort") - before == 1
    eng.run_until_idle()
    assert ha.token_ids == []            # its sampled token was never emitted
    assert hb.result(60)["token_ids"] == eager(model, PROMPTS[2], 8)
    assert eng.step_traces == 1
    eng.cache.assert_no_leaks()


def test_an_abort_under_the_run_loop_leaves_the_others_whole(model):
    import threading
    eng = engine(model)
    seen = threading.Event()
    ha = eng.submit(PROMPTS[1], max_new_tokens=40,
                    on_token=lambda req, tok: seen.set())
    hb = eng.submit(PROMPTS[4], max_new_tokens=20)
    eng.start()
    assert seen.wait(60)                 # decoding, a step in flight
    assert eng.abort(ha.req_id)
    assert hb.result(60)["token_ids"] == eager(model, PROMPTS[4], 20)
    eng.shutdown(drain=True, timeout=60)
    assert ha._req.state is RequestState.FAILED
    got = ha.token_ids
    assert got == eager(model, PROMPTS[1], 40)[:len(got)] and len(got) < 40
    assert eng.step_traces == 1
    eng.cache.assert_no_leaks()


# ---------------------------------------------- (g) the counter, and why serial --
def test_steady_work_runs_ahead_and_the_step_after_a_wait_is_serial(model):
    eng = engine(model)
    steps = serving_metrics()["steps"]
    counts = lambda: (steps.value(kind="unified"), dispatched(order="ahead"),
                      dispatched(order="serial", reason="idle"))
    s0, a0, i0 = counts()
    handles = [eng.submit(p, max_new_tokens=10) for p in PROMPTS[:3]]
    eng.start()
    for h in handles:
        h.result(timeout=60)
    s1, a1, i1 = counts()
    # the requests waited when the loop started: one step found nothing in
    # flight, every other was dispatched behind the one before
    assert i1 - i0 == 1 and a1 - a0 == (s1 - s0) - 1 >= 10
    late = eng.submit(PROMPTS[3], max_new_tokens=4)   # after an idle wait
    late.result(timeout=60)
    s2, a2, i2 = counts()
    assert i2 - i1 == 1 and a2 - a1 == (s2 - s1) - 1
    eng.shutdown(drain=True, timeout=60)
    assert eng.step_traces == 1
    # by hand every step is serial
    by_hand = engine(model)
    by_hand.submit(PROMPTS[0], max_new_tokens=3)
    by_hand.run_until_idle()
    s3, a3, i3 = counts()
    assert a3 == a2 and i3 - i2 == s3 - s2 == 3


def test_a_copy_on_write_beside_a_step_in_flight_goes_serial(model):
    """A prompt cached whole and aligned diverges through a block copy; with
    a step in flight the copy waits for its harvest."""
    eng = engine(model)
    doc = PROMPTS[3][:8]                 # two whole blocks
    eng.submit(doc, max_new_tokens=2)
    eng.run_until_idle()
    long = eng.submit(PROMPTS[1], max_new_tokens=12)
    assert eng._dispatch() and len(eng._flights) == 1
    before = dispatched(order="serial", reason="cow")
    again = eng.submit(doc, max_new_tokens=6)
    assert eng._dispatch()
    assert dispatched(order="serial", reason="cow") - before == 1
    assert again._req.cached_prompt_tokens == 7
    eng.run_until_idle()
    assert again.result(60)["token_ids"] == eager(model, doc, 6)
    assert long.result(60)["token_ids"] == eager(model, PROMPTS[1], 12)
    assert eng.step_traces == 1
    eng.cache.assert_no_leaks()


def test_the_numerics_twin_runs_serial(model, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_NUMERICS", "1")
    monkeypatch.setenv("PADDLE_TPU_NUMERICS_EVERY", "3")
    eng = engine(model)
    before = dispatched(order="serial", reason="numerics")
    h = eng.submit(PROMPTS[1], max_new_tokens=9)
    drive(eng, "ahead")
    assert h.result(60)["token_ids"] == eager(model, PROMPTS[1], 9)
    # 3 chunks and 8 decode rows: steps 3, 6 and 9 ran the twin (step 1 too,
    # with nothing in flight)
    assert dispatched(order="serial", reason="numerics") - before == 3
    assert eng.step_traces == 2          # the step and its twin, once each


# ------------------------------------------------- (h) one executable, traced once --
def test_one_trace_over_a_run_that_mixes_them_all(model):
    """Steps by hand, then greedy and sampled rows, an EOS, an abort and a
    preemption under the run loop, on one engine."""
    eng = engine(model, max_batch=3, max_blocks=10, prefix_cache=False)
    pt.seed(4)
    full = eager(model, PROMPTS[2], 10)
    head = eng.submit(PROMPTS[1], max_new_tokens=4)
    eng.run_until_idle()
    assert head.result(60)["token_ids"] == eager(model, PROMPTS[1], 4)
    handles = [
        eng.submit(PROMPTS[0], max_new_tokens=14),
        eng.submit(PROMPTS[4], max_new_tokens=14, temperature=0.9, top_k=8),
        eng.submit(PROMPTS[2], max_new_tokens=10, eos_token_id=full[4]),
        eng.submit(PROMPTS[5], max_new_tokens=30),
    ]
    eng.start()
    handles[2].result(timeout=60)
    eng.abort(handles[3].req_id)
    for h in handles[:3]:
        h.result(timeout=60)
    eng.shutdown(drain=True, timeout=60)
    assert handles[0].token_ids == eager(model, PROMPTS[0], 14)
    assert handles[2].token_ids == full[:full.index(full[4]) + 1]
    assert len(handles[1].token_ids) == 14
    assert eng.scheduler.num_preemptions >= 1
    assert eng.step_traces == 1 and eng.stats()["step_compiles"] == 1
    assert not eng.has_pending() and not eng._flights
    eng.cache.assert_no_leaks()


# ------------------------- submitters and aborters beside the loop's two halves --
def test_submits_and_aborts_from_many_threads_beside_the_run_loop(model):
    """The engine lock is not held while the loop waits for a step's tokens:
    ``submit`` and ``abort`` run between a dispatch and its harvest. Eight
    threads (more than this machine's cores are not needed to interleave:
    the switch interval is cut short) submit and abort against the running
    loop; every request that was left alone is served whole, every aborted
    one a prefix of its answer, nothing leaks, one trace."""
    import sys
    import threading
    eng = engine(model, max_batch=4, max_blocks=48)
    want = {i: eager(model, p, 9) for i, p in enumerate(PROMPTS)}
    done, errors = [], []

    def client(k):
        try:
            for j in range(4):
                i = (k + j) % len(PROMPTS)
                h = eng.submit(PROMPTS[i], max_new_tokens=9)
                if (k + j) % 3 == 0:
                    eng.abort(h.req_id)
                done.append((i, h))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng.start()
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads) and not errors
        eng.drain(timeout=120)
    finally:
        sys.setswitchinterval(was)
    assert len(done) == 32
    for i, h in done:
        assert h.wait(timeout=5)
        got = h.token_ids
        if h._req.state is RequestState.FINISHED:
            assert got == want[i]
        else:
            assert h._req.finish_reason == "aborted" \
                and got == want[i][:len(got)]
    assert sum(h._req.state is RequestState.FINISHED for _, h in done) >= 20
    assert eng.step_traces == 1 and not eng._flights and not eng._retiring
    eng.cache.assert_no_leaks()
