"""``rpa_live_step_pct.*``: the share of the RPA kernel's grid walk that is
live work, read from the args of ``serving.dispatch`` on a small recorded
span list, through the harness; nothing where no step carries them (a
program before the flat work list, or the gather reader); and the args as a
tiny engine writes them into the profiler's trace on the CPU backend."""
import glob
import os

import jax
import pytest

import benchmark_tiny as tiny
from benchmark import harness, spans, sut

NAMES = ["rpa_live_step_pct.chat", "rpa_live_step_pct.flood"]
RUN = {"xplane_path": "made-by-hand"}


def _steps(*steps, leaves=spans.STEP_LEAVES):
    """A host plane of whole steps, 1 ms each: ``steps`` are the args of
    each step's ``serving.dispatch`` beside ``step``."""
    host = []
    for n, args in enumerate(steps, start=1):
        for i, name in enumerate(leaves):
            t = (n * 1000 + i * 100) * 1e3
            stats = {"step": n}
            if name == "serving.dispatch":
                stats.update(decode_rows=1, rows="1@5", **args)
            host.append((name, t, t + 100e3, stats))
    return host


def _loaded(monkeypatch, host):
    ev = spans.Events(ops={}, modules=[], host=sorted(host,
                                                      key=lambda h: h[1]))
    monkeypatch.setattr(spans, "load", lambda path: ev)


@pytest.mark.parametrize("name", NAMES)
def test_share_is_summed_over_the_whole_steps(name, monkeypatch):
    host = _steps({"rpa_live": 30, "rpa_walked": 44},
                  {"rpa_live": 900, "rpa_walked": 902},
                  {"rpa_live": 14, "rpa_walked": 28})
    # a step cut by the span's end after its dispatch: not counted
    host += [("serving.lock", 9000e3, 9001e3, {"step": 4}),
             ("serving.dispatch", 9100e3, 9200e3,
              {"step": 4, "rpa_live": 0, "rpa_walked": 5000})]
    # the call that found nothing to run, and the run loop's wait
    host += [("serving.lock", 9500e3, 9501e3, {"step": 5}),
             ("serving.idle_wait", 9600e3, 9700e3, {})]
    _loaded(monkeypatch, host)
    assert harness.read_layer_metric(name, RUN) == pytest.approx(
        100.0 * (30 + 900 + 14) / (44 + 902 + 28))


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_where_no_step_carries_the_args(name, monkeypatch):
    # the parent's spans: the step's leaves, no rpa_* args
    _loaded(monkeypatch, _steps({}, {}))
    assert harness.read_layer_metric(name, RUN) is None
    # one of the two alone is no reading
    _loaded(monkeypatch, _steps({"rpa_live": 3}, {"rpa_walked": 9}))
    assert harness.read_layer_metric(name, RUN) is None
    # no whole step; no serving spans at all
    _loaded(monkeypatch, _steps({"rpa_live": 3, "rpa_walked": 9},
                                leaves=spans.STEP_LEAVES[:4]))
    assert harness.read_layer_metric(name, RUN) is None
    _loaded(monkeypatch, [("TrainStep", 0.0, 1e6, {})])
    assert harness.read_layer_metric(name, RUN) is None


def test_steps_without_the_args_are_left_out_of_both_sums(monkeypatch):
    _loaded(monkeypatch, _steps({"rpa_live": 10, "rpa_walked": 20}, {},
                                {"rpa_live": 30, "rpa_walked": 40}))
    assert harness.read_layer_metric(NAMES[0], RUN) == pytest.approx(
        100.0 * 40 / 60)


@pytest.mark.parametrize("impl", ["rpa", "gather"])
def test_a_tiny_engine_writes_what_the_reader_reads(impl, tmp_path):
    """The engine's ``serving.dispatch`` carries the step's work-list
    counts under the RPA kernel and not under the gather reader."""
    engine = sut.build_engine(tiny.CFG, 7, {"attn_impl": impl})
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    engine.submit(list(range(1, 5)), max_new_tokens=2)
    engine.run_until_idle()              # compile outside the session
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        engine.submit(list(range(1, 41)), max_new_tokens=3)
        engine.submit(list(range(50, 55)), max_new_tokens=6)
        engine.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    dispatch = [st for n, _, _, st in spans.read(path).host
                if n == "serving.dispatch"]
    assert len(dispatch) >= 4
    value = harness.read_layer_metric(NAMES[0], {"xplane_path": path})
    if impl == "gather":
        assert value is None
        assert not any("rpa_live" in st or "rpa_walked" in st
                       for st in dispatch)
        return
    tiles = engine.step_tokens // engine._tile_q
    for st in dispatch:
        assert 0 < st["rpa_live"] <= st["rpa_walked"] <= \
            st["rpa_live"] + tiles - 1
    assert value == pytest.approx(
        100.0 * sum(st["rpa_live"] for st in dispatch)
        / sum(st["rpa_walked"] for st in dispatch))
    assert 30.0 < value < 100.0
