"""The program's spans in the JAX profiler's trace, on the CPU backend:
``RecordEvent`` lands in the host plane with its args, and a tiny
``ServingEngine`` tiles each step with its leaves."""
import glob
import os
import threading

import jax
import pytest

import benchmark_tiny as tiny
from benchmark import spans, sut
from paddle_tpu.profiler import RecordEvent, annotate


def _traced(tmp_path, body):
    """Run ``body()`` inside a profiler session; the host's events whose
    name starts with ``serving.``, ``span.`` or ``comm::``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return spans.read(path, also=("span.", "comm::")).host


# ------------------------------------------------------------ RecordEvent --
def test_record_event_lands_in_the_host_plane_with_its_args(tmp_path):
    def body():
        with RecordEvent("span.early", args={"step": 3, "rows": "1@5;112@0",
                                             "share": 0.5}):
            pass
        ev = RecordEvent("span.late", args={"step": 4}, cat="serving")
        ev.begin()
        ev.args["compiled"] = 1
        ev.end()
        with RecordEvent("span.bare"):
            pass
        with RecordEvent("span.odd", args={"axes": ("dp", "mp"),
                                           "eq": "a=b#c"}):
            pass
    host = {n: (e - s, stats) for n, s, e, stats in _traced(tmp_path, body)}
    assert host["span.early"][1] == {"step": 3, "rows": "1@5;112@0",
                                     "share": 0.5}
    assert host["span.late"][1] == {"step": 4, "compiled": 1}
    assert host["span.bare"][1] == {}
    # what the TraceMe encoding splits on is replaced, nothing is lost
    assert host["span.odd"][1] == {"axes": "('dp'; 'mp')", "eq": "a:b_c"}
    assert all(d > 0 for d, _ in host.values())


def test_record_event_without_a_session_writes_nothing(tmp_path):
    assert annotate("span.none", {"step": 1}) is None
    ev = RecordEvent("span.none", args={"step": 1})
    with ev:
        ev.args["late"] = 2
    assert ev._ann is None and ev._t0 is None
    assert os.listdir(str(tmp_path)) == []


def test_a_pair_that_crosses_threads_is_written_once(tmp_path):
    def body():
        ev = RecordEvent("span.crossing", args={"step": 1})
        ev.begin()
        t = threading.Thread(target=ev.end)
        t.start()
        t.join()
        ev.end()                              # a second end is nothing
    (name, s, e, stats), = _traced(tmp_path, body)
    assert name == "span.crossing" and e > s and stats == {"step": 1}


def test_comm_spans_reach_the_trace(tmp_path):
    from paddle_tpu.observability.comm import comm_scope

    def body():
        with comm_scope("all_reduce", ("dp", "mp"), nbytes=4096):
            pass
    (name, s, e, stats), = _traced(tmp_path, body)
    assert name == "comm::all_reduce" and e > s
    assert stats == {"bytes": 4096, "axes": "dpxmp"}


# ------------------------------------------------------------- the engine --
PROMPTS = [(list(range(1, 21)), 3), (list(range(30, 35)), 2),
           (list(range(40, 70)), 4)]


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """A tiny engine traced from its first step (the one that compiles) to
    idle: the host's ``serving.*`` events in time order."""
    engine = sut.build_engine(tiny.CFG, 7)

    def body():
        for tokens, answer in PROMPTS:
            engine.submit(tokens, max_new_tokens=answer)
        engine.run_until_idle()
    host = _traced(tmp_path_factory.mktemp("engine_trace"), body)
    # (an engine some earlier test of this process left idling writes its
    # ``serving.idle_wait`` into the same trace: the step's leaves only)
    return engine, [h for h in host if h[0] in spans.STEP_LEAVES]


def _by_step(host):
    steps = {}
    for name, s, e, stats in host:
        steps.setdefault(stats["step"], []).append((name, s, e, stats))
    return steps


def test_every_step_has_its_leaves_once_in_order(engine_trace):
    engine, host = engine_trace
    steps = _by_step(host)
    ran = engine._decode_steps
    assert ran >= 4 and sorted(steps) == list(range(1, ran + 2))
    for n in range(1, ran + 1):
        assert tuple(h[0] for h in steps[n]) == spans.STEP_LEAVES, n
    # the call that found nothing to run: no step, three leaves
    assert [h[0] for h in steps[ran + 1]] == [
        "serving.lock", "serving.plan", "serving.gauges"]


def test_leaves_tile_the_step_and_nothing_spans_it(engine_trace):
    _, host = engine_trace
    for (_, _, e0, _), (_, s1, _, _) in zip(host, host[1:]):
        assert s1 >= e0                       # back to back, none nested
    for leaves in _by_step(host).values():
        first, last = leaves[0][1], leaves[-1][2]
        assert not any(s <= first and e >= last and len(leaves) > 1
                       for _, s, e, _ in host)
        covered = sum(e - s for _, s, e, _ in leaves)
        # (between two leaves lies one Python statement; half leaves room
        # for a thread that a loaded machine parks there)
        assert covered > 0.5 * (last - first)


def test_dispatch_says_what_ran(engine_trace):
    _, host = engine_trace
    cfg = tiny.CFG["engine"]
    dispatch = [h[3] for h in host if h[0] == "serving.dispatch"]
    assert [d["compiled"] for d in dispatch] == [1] + [0] * (len(dispatch) - 1)
    ran = []                                  # every row of every step
    for d in dispatch:
        rows = [tuple(int(v) for v in r.split("@"))
                for r in str(d["rows"]).split(";")]
        assert len(rows) == d["decode_rows"] + d["prefill_rows"]
        assert all(n == 1 for n, _ in rows[:d["decode_rows"]])
        assert sum(n for n, _ in rows[d["decode_rows"]:]) \
            == d["prefill_tokens"] <= cfg["prefill_chunk"] + cfg["max_batch"]
        ran += rows
    # the rows are what was submitted: each prompt starts once at context
    # 0, and every token but a request's last sampled one passes the step
    assert sum(1 for _, ctx in ran if ctx == 0) == len(PROMPTS)
    assert sum(n for n, _ in ran) == sum(len(t) + a - 1 for t, a in PROMPTS)
    commit = [h[3] for h in host if h[0] == "serving.commit"]
    assert sum(c["tokens_out"] for c in commit) == sum(a for _, a in PROMPTS)


def test_idle_wait_is_a_leaf_of_the_run_loop(tmp_path):
    engine = sut.build_engine(tiny.CFG, 7)

    def body():
        engine.start()
        engine.submit(list(range(1, 9)), max_new_tokens=2).result(timeout=120)
        threading.Event().wait(0.25)
        engine.shutdown(drain=True, timeout=30)
    host = [h for h in _traced(tmp_path, body) if h[0].startswith("serving.")]
    waits = [h for h in host if h[0] == "serving.idle_wait"]
    assert waits and all("step" not in h[3] for h in waits)
    assert all(0 < e - s < 1e9 for _, s, e, _ in waits)     # the 0.1 s timeout
    assert {h[0] for h in host} == set(spans.STEP_LEAVES) | {"serving.idle_wait"}
