"""``step_ahead_pct.chat``: the share of the engine's steps dispatched while
the step before was still unharvested, read from ``serving.dispatch``'s
``ahead`` on a small hand-built span list, through the harness; nothing
where no step carries it (a program before the loop ran ahead); and the
argument as a tiny engine writes it into the profiler's trace on the CPU
backend, by hand (every step serial) and under its run loop."""
import glob
import os

import jax
import pytest

import benchmark_tiny as tiny
from benchmark import harness, spans, sut
from test_rpa_live_step_pct import _loaded, _steps

NAME = "step_ahead_pct.chat"
RUN = {"xplane_path": "made-by-hand"}


def test_share_is_counted_over_the_whole_steps(monkeypatch):
    host = _steps({"ahead": 0}, {"ahead": 1}, {"ahead": 1}, {"ahead": 1},
                  {"ahead": 0})
    # a step cut by the span's end after its dispatch: not counted
    host += [("serving.lock", 9000e3, 9001e3, {"step": 6}),
             ("serving.dispatch", 9100e3, 9200e3, {"step": 6, "ahead": 1})]
    # the call that found nothing to run, and the run loop's wait
    host += [("serving.lock", 9500e3, 9501e3, {"step": 7}),
             ("serving.idle_wait", 9600e3, 9700e3, {})]
    _loaded(monkeypatch, host)
    assert harness.read_layer_metric(NAME, RUN) == pytest.approx(60.0)


def test_a_loop_that_never_ran_ahead_reads_nought_not_nothing(monkeypatch):
    _loaded(monkeypatch, _steps({"ahead": 0}, {"ahead": 0}))
    assert harness.read_layer_metric(NAME, RUN) == 0.0


def test_nothing_to_read_where_no_step_carries_the_argument(monkeypatch):
    # the parent's spans: the step's leaves and their other arguments
    _loaded(monkeypatch, _steps({"rpa_live": 900, "rpa_walked": 902}, {}))
    assert harness.read_layer_metric(NAME, RUN) is None
    # no whole step; no serving spans at all
    _loaded(monkeypatch, _steps({"ahead": 1}, leaves=spans.STEP_LEAVES[:4]))
    assert harness.read_layer_metric(NAME, RUN) is None
    _loaded(monkeypatch, [("TrainStep", 0.0, 1e6, {})])
    assert harness.read_layer_metric(NAME, RUN) is None


def test_steps_without_the_argument_are_left_out(monkeypatch):
    _loaded(monkeypatch, _steps({"ahead": 1}, {}, {"ahead": 0}, {}))
    assert harness.read_layer_metric(NAME, RUN) == pytest.approx(50.0)


def test_the_manifest_names_the_metric_for_its_cell():
    entry, = [m for m in harness.load_manifest()["per_layer"]
              if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "engine step loop",
        "moves": "itl_p95_ms", "workloads": ["serve-chat"]}


def _trace(tmp_path, body):
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
    return path


@pytest.mark.parametrize("order", ["by_hand", "run_loop"])
def test_a_tiny_engine_writes_what_the_reader_reads(order, tmp_path):
    """``step()`` by hand dispatches and harvests in turn: every step
    serial, the metric reads nought. The run loop keeps one step in flight
    while there is work: all but the first step after its wait run ahead."""
    engine = sut.build_engine(tiny.CFG, 7)
    engine.submit(list(range(1, 5)), max_new_tokens=2)
    engine.run_until_idle()              # compile outside the session
    prompts = [(list(range(1, 21)), 12), (list(range(30, 35)), 9)]

    def body():
        if order == "by_hand":
            for tokens, answer in prompts:
                engine.submit(tokens, max_new_tokens=answer)
            engine.run_until_idle()
            return
        handles = [engine.submit(tokens, max_new_tokens=answer)
                   for tokens, answer in prompts]
        engine.start()
        for h in handles:
            h.result(timeout=120)
        engine.shutdown(drain=True, timeout=30)
    path = _trace(tmp_path, body)
    dispatch = [st for n, _, _, st in spans.read(path).host
                if n == "serving.dispatch"]
    assert len(dispatch) >= 10 and all(st["ahead"] in (0, 1)
                                       for st in dispatch)
    value = harness.read_layer_metric(NAME, {"xplane_path": path})
    if order == "by_hand":
        assert value == 0.0 and not any(st["ahead"] for st in dispatch)
        return
    # both requests were waiting when the loop started: one serial step,
    # then every step is dispatched behind the one in flight
    assert [st["ahead"] for st in dispatch] == [0] + [1] * (len(dispatch) - 1)
    assert value == pytest.approx(100.0 * (len(dispatch) - 1) / len(dispatch))
