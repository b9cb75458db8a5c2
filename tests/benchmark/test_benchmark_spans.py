"""``benchmark/spans.py``: the clock bracket and the split of the device's
idle time over the program's spans, on hand-made events and on the traces
recorded on a TPU v5e (``record_fixture.py``, ``record_engine_fixture.py``);
and the five readers through the harness."""
import gzip
import os
import shutil

import pytest

from benchmark import harness, spans

HERE = os.path.dirname(__file__)
FIXTURE = os.path.join(HERE, "fixtures", "tiny_tpu.xplane.pb")
ENGINE_FIXTURE_GZ = os.path.join(HERE, "fixtures", "tiny_engine.xplane.pb.gz")
US = 1e3


def _host(*events):
    return sorted(((n, s, e, dict(st)) for n, s, e, st in events),
                  key=lambda h: h[1])


def _program(rid, launch, start, end, seen, shift):
    """One program: launched and seen done on the host's clock; the device
    reports it ``shift`` early."""
    return ((rid, start - shift, end - shift),
            [(spans.LAUNCH, launch, launch + 5, {"run_id": rid}),
             (spans.DONE, seen, seen + 5, {"run_id": rid})])


# -------------------------------------------------------------- the clock --
def test_bracket_is_the_tightest_over_every_program():
    shift = 1500.0
    a, ha = _program(1, launch=100, start=400, end=900, seen=1000, shift=shift)
    b, hb = _program(2, launch=2000, start=2050, end=2500, seen=2900,
                     shift=shift)
    lo, hi = spans.offset_bracket([a, b], _host(*ha, *hb))
    # lower: b started 50 after its launch; upper: a was seen 100 after its end
    assert (lo, hi) == (shift - 50, shift + 100)
    assert lo <= shift <= hi


def test_no_bracket_without_both_sides_or_with_no_shift_that_fits():
    a, ha = _program(1, launch=100, start=400, end=900, seen=1000, shift=0.0)
    assert spans.offset_bracket([a], _host(ha[0])) is None      # never seen
    assert spans.offset_bracket([a], _host(ha[1])) is None      # no launch
    assert spans.offset_bracket([], _host(*ha)) is None
    # a program longer than launch to completion leaves no shift
    late = (1, 0.0, 1200.0)
    assert spans.offset_bracket([late], _host(*ha)) is None
    assert spans.split(spans.Events(ops={"/device:TPU:0": [(0, 1)]},
                                    modules=[late], host=_host(*ha))) is None


# -------------------------------------------------------------- the split --
def _events(shift=0.0):
    """Two steps of 1000 us on the host's clock. The device runs each
    step's program from 300 to 800 of the step, launched 20 us before and
    seen done 20 us after; the leaves tile the step."""
    ops, modules, host = [], [], []
    cuts = [0, 10, 60, 210, 310, 860, 960, 1000]   # leaf boundaries, us
    for n in (1, 2):
        t0 = (n - 1) * 1000
        m, h = _program(n, launch=(t0 + 280) * US, start=(t0 + 300) * US,
                        end=(t0 + 800) * US, seen=(t0 + 820) * US, shift=shift)
        modules.append(m)
        host += h
        ops += [((t0 + 300) * US - shift, (t0 + 500) * US - shift),
                ((t0 + 500) * US - shift, (t0 + 800) * US - shift)]
        for name, a, b in zip(spans.STEP_LEAVES, cuts, cuts[1:]):
            host.append((name, (t0 + a) * US, (t0 + b) * US, {"step": n}))
    return spans.Events(ops={"/device:TPU:0": ops}, modules=modules,
                        host=_host(*host))


def test_a_gap_is_cut_at_span_boundaries():
    sp = spans.split(_events())
    # one gap: 800 of step 1 to 300 of step 2, 500 us: the last 60 us of
    # step 1's fetch, its commit and gauges, step 2's lock, plan and pack,
    # and the first 90 us of its dispatch
    assert sp.idle_s == pytest.approx(500e-6)
    rows = sp.rows
    assert rows["serving.fetch"][:2] == [2, pytest.approx(1100e-6)]
    inside = {n.split(".")[1]: round(r[2] * 1e6) for n, r in rows.items()}
    assert inside == {"fetch": 60, "commit": 100, "gauges": 40, "lock": 10,
                      "plan": 50, "pack": 150, "dispatch": 90}
    assert sum(r[2] for r in rows.values()) == pytest.approx(sp.idle_s)


def test_the_shift_is_found_and_applied():
    shift = 1.4e6
    plain, shifted = spans.split(_events()), spans.split(_events(shift))
    lo, hi = shifted.bracket
    assert (lo, hi) == (shift - 20 * US, shift + 20 * US)
    for name, row in plain.rows.items():
        assert shifted.rows[name][2] == pytest.approx(row[2], abs=1e-9)
    assert shifted.idle_s == pytest.approx(plain.idle_s)


def test_step_host_ms_is_the_median_of_whole_steps(monkeypatch):
    ev = _events()
    # a third step, cut by the span's end after its dispatch: not counted;
    # a fourth, whole, with a pack three times as long
    ev.host += [("serving.lock", 2000 * US, 2010 * US, {"step": 3}),
                ("serving.dispatch", 2210 * US, 2310 * US, {"step": 3})]
    for name, a, b in zip(spans.STEP_LEAVES,
                          [0, 10, 60, 510, 610, 1160, 1260],
                          [10, 60, 510, 610, 1160, 1260, 1300]):
        ev.host.append((name, (3000 + a) * US, (3000 + b) * US, {"step": 4}))
    monkeypatch.setattr(spans, "load", lambda path: ev)
    run = {"xplane_path": "made-by-hand"}
    # a step's leaves less fetch: 1000 - 550 = 450 us; the long one 750
    assert spans.step_host_ms(run) == pytest.approx(0.450)
    assert len(spans.split_of(run).steps) == 4


def test_readers_read_nothing_without_spans_or_without_a_bracket(monkeypatch):
    ev = _events()
    bare = spans.Events(ops=ev.ops, modules=ev.modules,
                        host=[h for h in ev.host
                              if h[0] in (spans.LAUNCH, spans.DONE)])
    no_clock = spans.Events(ops=ev.ops, modules=[], host=ev.host)
    run = {"xplane_path": "made-by-hand"}
    for events in (bare, no_clock):
        monkeypatch.setattr(spans, "load", lambda path, e=events: e)
        assert spans.step_host_ms(run) is None
        assert spans.idle_named_pct(run) is None
        assert spans.train_enqueue_ms(run) is None


# --------------------------------------------------- the recorded traces --
def test_fixture_bracket_excludes_zero():
    lo, hi = spans.offset_bracket_ns(FIXTURE)
    # the device plane is 1.2-1.7 ms early (ISSUE 24): run 4's program
    # "starts" 1.17 ms before the host launched it
    assert 1.2e6 < lo < hi < 1.7e6


def test_fixture_idle_falls_in_the_sleeps_after_the_shift():
    ev = spans.read(FIXTURE, also=("fixture_",))
    sp = spans.split(ev)
    sleep, rounds = sp.rows["fixture_sleep"], sp.rows["fixture_round"]
    assert sleep[0] == rounds[0] == 3
    # the device is idle while the host sleeps 3 ms, three times; the last
    # sleep follows the last op and is no gap
    assert 2 * 3e-3 < sleep[2] <= sleep[1]
    assert sleep[2] + rounds[2] == pytest.approx(sp.idle_s, rel=0.01)
    # unshifted, part of that idle would be read into the rounds instead
    unshifted = spans.Idle(ev.ops, 0.0)
    assert sum(unshifted.inside_s(s, e) for n, s, e, _ in ev.host
               if n == "fixture_sleep") < sleep[2] - 1e-3


@pytest.fixture(scope="module")
def engine_fixture(tmp_path_factory):
    """The recorded engine trace, unzipped (it is kept zipped: under 200 KB)."""
    if not os.path.exists(ENGINE_FIXTURE_GZ):
        pytest.fail("the recorded engine trace is missing")
    path = str(tmp_path_factory.mktemp("engine_fixture") / "tiny_engine.xplane.pb")
    with gzip.open(ENGINE_FIXTURE_GZ, "rb") as f, open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    return path


def test_engine_fixture_bracket_and_table(engine_fixture):
    sp = spans.split(spans.load(engine_fixture))
    lo, hi = sp.bracket
    assert 1e6 < lo < hi < 2e6 and hi - lo < 0.5e6   # 1.30-1.58 ms early
    assert len(spans.whole_steps(sp)) == 4
    assert len(sp.steps) == 5                        # and the idle last call
    assert set(spans.STEP_LEAVES) == set(sp.rows)
    named = sum(r[2] for r in sp.rows.values())
    assert 0.95 * sp.idle_s < named <= sp.idle_s * 1.0001
    # the device waits longest while the host copies the step's inputs up
    # and launches it
    assert max(sp.rows, key=lambda n: sp.rows[n][2]) == "serving.dispatch"
    for calls, host_s, idle_s in sp.rows.values():
        assert calls in (4, 5) and 0 <= idle_s <= host_s


# ------------------------------------------------------------ the readers --
@pytest.mark.parametrize("name", ["step_host_ms.chat", "step_host_ms.flood",
                                  "idle_named_pct.chat",
                                  "idle_named_pct.flood"])
def test_serving_readers_through_the_harness(name, engine_fixture):
    run = {"xplane_path": engine_fixture}
    value = harness.read_layer_metric(name, run)
    if name.startswith("idle_named_pct"):
        assert 95.0 < value <= 100.0
    else:
        # lock .. gauges less fetch: about 4 ms a step on the chip's host
        assert 2.0 < value < 8.0
    # the train cell's span is not in a serving trace, and the other way
    assert harness.read_layer_metric("train_enqueue_ms", run) is None
    assert harness.read_layer_metric(name, {"xplane_path": FIXTURE}) is None


def test_train_enqueue_ms_through_the_harness(tmp_path):
    """A ``TrainStep`` span written by ``RecordEvent`` on the CPU backend:
    the reader gives its median duration, and needs no device plane."""
    import glob
    import time

    import jax
    from paddle_tpu.profiler import RecordEvent
    jax.profiler.start_trace(str(tmp_path))
    outer = []                       # each span, timed from outside it
    for ms in (2, 6, 4):
        t = time.perf_counter()
        with RecordEvent("TrainStep"):
            time.sleep(ms / 1e3)
        outer.append(1e3 * (time.perf_counter() - t))
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    value = harness.read_layer_metric("train_enqueue_ms",
                                      {"xplane_path": path})
    # a sleep may overrun on a loaded machine, never fall short
    assert 4.0 <= value <= sorted(outer)[1]
    assert harness.read_layer_metric("step_host_ms.chat",
                                     {"xplane_path": path}) is None
