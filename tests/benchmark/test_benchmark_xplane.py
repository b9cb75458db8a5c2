"""The reduction from a trace to numbers: on hand-made events, and on the
small trace recorded on a TPU v5e (``record_fixture.py``)."""
import os

import pytest

from benchmark import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny_tpu.xplane.pb")


def test_union_merges_overlaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_self_times_take_nested_ops_out():
    ops = [("while", 0, 100), ("fusion", 10, 40), ("fusion", 50, 70),
           ("copy", 55, 60), ("dot", 120, 150)]
    total, count = xplane.self_times(ops)
    assert total == {"while": 50, "fusion": 45, "copy": 5, "dot": 30}
    assert count == {"while": 1, "fusion": 2, "copy": 1, "dot": 1}
    assert sum(total.values()) == 130          # == the union: nothing twice


def test_gap_named_by_the_most_specific_host_event():
    host = [("bench_window", 0, 1000), ("engine_step", 90, 400),
            ("sample", 100, 200), ("elsewhere", 600, 700)]
    assert xplane.name_gap(110, 190, host) == "sample"
    assert xplane.name_gap(90, 390, host) == "engine_step"
    assert xplane.name_gap(450, 500, host) == xplane.NO_HOST
    # nothing covers half of it: the largest overlap names it
    assert xplane.name_gap(380, 700, host) == "elsewhere"


def _write_events(monkeypatch, device, host):
    monkeypatch.setattr(xplane, "read_events", lambda path: (device, host))


def test_reduce_busy_idle_and_sums(monkeypatch):
    ms = 1e6
    dev = {"/device:TPU:0": [("dot", 0, 2 * ms), ("fusion", 2 * ms, 3 * ms),
                             ("dot", 5 * ms, 7 * ms), ("dot", 7.001 * ms, 8 * ms)],
           "/device:TPU:1": []}
    host = [("sample", 3.2 * ms, 4.8 * ms)]
    _write_events(monkeypatch, dev, host)
    r = xplane.reduce("unused")
    assert r.n_devices == 1
    assert r.busy_s == pytest.approx(5.999e-3)
    assert r.window_s == pytest.approx(8e-3)
    assert r.device_ops["dot"] == pytest.approx(4.999e-3)
    assert r.op_counts == {"dot": 3, "fusion": 1}
    assert r.idle_gaps["sample"] == pytest.approx(2e-3)
    assert r.idle_gaps["short_gaps"] == pytest.approx(1e-6)
    assert r.gap_count == 1
    assert r.op_seconds(r"^dot") == pytest.approx(4.999e-3)
    assert r.busy_s + sum(r.idle_gaps.values()) == pytest.approx(r.window_s)


def test_reduce_without_device_events(monkeypatch):
    _write_events(monkeypatch, {}, [("x", 0, 1)])
    r = xplane.reduce("unused")
    assert r.busy_s == 0.0 and r.device_ops == {}


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.fail("the recorded trace is missing")
    return xplane.reduce(FIXTURE)


def test_fixture_busy_and_idle(recorded):
    r = recorded
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    # three rounds of a ~0.1 ms program, each followed by a 3 ms sleep
    assert 6e-3 < r.window_s < 60e-3
    assert r.busy_s / r.window_s < 0.5
    assert r.busy_s + sum(r.idle_gaps.values()) == pytest.approx(r.window_s)


def test_fixture_per_op_sums(recorded):
    r = recorded
    assert sum(r.device_ops.values()) == pytest.approx(r.busy_s, rel=1e-6)
    # four chained matmuls a round, three rounds
    dots = {n: c for n, c in r.op_counts.items()
            if "fusion" in n or "dot" in n or "convolution" in n}
    assert sum(dots.values()) >= 12
    assert r.top_ops(3)[0][1] >= r.top_ops(3)[-1][1]


def test_fixture_gaps_carry_the_host_activity(recorded):
    names = dict(recorded.top_gaps(10))
    assert "fixture_sleep" in names
    assert names["fixture_sleep"] > 5e-3       # two or three 3 ms sleeps
