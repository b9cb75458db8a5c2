"""Python's collector on the trace: ``profiler.trace_gc`` makes each
collection a ``python.gc`` span and a ``python_gc_pause_seconds``
observation; the three readers put the device's idle time and the host's
pauses on it; and the expert-row counter is filed under one acquisition of
its lock a step."""
import gc
import glob
import gzip
import os
import shutil
import threading
import types

import jax
import numpy as np
import pytest

import benchmark_tiny as tiny
from benchmark import harness, spans, sut
from benchmark.layer_metrics import _gc, _pangu
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.profiler import GC_PAUSE_FAMILY, GC_SPAN, trace_gc
from paddle_tpu.serving.engine import ServingEngine

HERE = os.path.dirname(__file__)
ENGINE_FIXTURE_GZ = os.path.join(HERE, "fixtures", "tiny_engine.xplane.pb.gz")
READERS = ("idle_gc_pct", "gc_ms_per_s", "gc_pause_max_ms.chat")
US = 1e3


def _pauses(generation):
    st = trace_gc().stats(generation=generation)
    return st["count"] if st else 0


# --------------------------------------------------------------- the hook --
def test_installing_the_hook_twice_leaves_one_callback():
    assert trace_gc() is trace_gc()
    hooks = [c for c in gc.callbacks if type(c).__name__ == "_GcSpans"]
    assert len(hooks) == 1
    from paddle_tpu.observability import get_registry
    assert get_registry().get(GC_PAUSE_FAMILY) is trace_gc()


def test_a_collection_is_counted_by_generation_and_exposed():
    before = _pauses(2)
    gc.collect()
    assert _pauses(2) == before + 1
    from paddle_tpu.observability import get_registry
    text = get_registry().prometheus_text()
    assert 'python_gc_pause_seconds_bucket{generation="2",le="0.0001"}' in text
    assert 'python_gc_pause_seconds_bucket{generation="2",le="2.0"}' in text


def test_a_collection_under_the_histogram_s_own_lock_waits_for_the_next():
    """A scrape that copies the family holds its lock; a collection on that
    thread must not wait for it (it would wait for itself)."""
    pauses, before = trace_gc(), _pauses(2)
    with pauses._lock:
        gc.collect()
    assert _pauses(2) == before
    gc.collect()
    assert _pauses(2) == before + 2


def _traced(path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    found, = glob.glob(os.path.join(str(path), "plugins", "profile", "*",
                                    "*.xplane.pb"))
    return found


def test_a_forced_collection_is_a_span_inside_the_step_s_leaf(tmp_path):
    """A traced tiny engine under its run loop (``start()`` arms the hook);
    one step's ``serving.plan`` runs a full collection."""
    engine = sut.build_engine(tiny.CFG, 7)
    plan, forced = engine._plan, []

    def collecting():
        if not forced:
            forced.append(gc.collect())
        return plan()
    engine._plan = collecting

    def body():
        engine.start()
        engine.submit(list(range(1, 9)), max_new_tokens=3).result(timeout=120)
        engine.shutdown(drain=True, timeout=30)
    before = _pauses(2)
    path = _traced(tmp_path, body)
    assert forced and _pauses(2) >= before + 1
    host = spans.read(path, also=(GC_SPAN,)).host
    leaves = [h for h in host if h[0] == "serving.plan"]
    inside = [(s, e, st) for n, s, e, st in host
              if n == GC_SPAN and st.get("generation") == 2
              and any(ls <= s and e <= le for _, ls, le, _ in leaves)]
    assert inside, [h for h in host if h[0] == GC_SPAN]
    s, e, stats = inside[0]
    assert e > s and {"collected", "uncollectable"} <= set(stats)
    # the collection is no step leaf: the step's tables do not see it
    sp_leaves = {n for n, *_ in spans.read(path).host}
    assert GC_SPAN not in sp_leaves


# ------------------------------------------------------------ the readers --
def _events(gcs=()):
    """Two steps of 1000 us on the host's clock; the device runs each
    step's program from 300 to 800 of it, so it idles 800-1300; ``gcs``
    are ``(start, end)`` us of collections."""
    ops, modules, host = [], [], []
    cuts = [0, 10, 60, 210, 310, 860, 960, 1000]
    for n in (1, 2):
        t0 = (n - 1) * 1000
        modules.append((n, (t0 + 300) * US, (t0 + 800) * US))
        host += [(spans.LAUNCH, (t0 + 280) * US, (t0 + 285) * US,
                  {"run_id": n}),
                 (spans.DONE, (t0 + 820) * US, (t0 + 825) * US,
                  {"run_id": n})]
        ops.append(((t0 + 300) * US, (t0 + 800) * US))
        for name, a, b in zip(spans.STEP_LEAVES, cuts, cuts[1:]):
            host.append((name, (t0 + a) * US, (t0 + b) * US, {"step": n}))
    host += [(GC_SPAN, a * US, b * US, {"generation": 0, "collected": 0,
                                        "uncollectable": 0})
             for a, b in gcs]
    return spans.Events(ops={"/device:TPU:0": ops}, modules=modules,
                        host=sorted(host, key=lambda h: h[1]))


def _read(monkeypatch, ev, instrumented=True):
    monkeypatch.setattr(_gc, "load", lambda path: ev)
    monkeypatch.setattr(_gc, "instrumented", lambda: instrumented)
    run = {"kind": "open_loop", "xplane_path": "made-by-hand",
           "traced": {"span_s": 2e-3}}
    return {n: harness.read_layer_metric(n, run) for n in READERS}


def test_readers_count_idle_inside_a_collection_cut_at_its_ends(monkeypatch):
    # 700-850 holds the gap's first 50 us, 1250-1400 its last 50: 100 of 500
    got = _read(monkeypatch, _events(gcs=[(700, 850), (1250, 1400)]))
    assert got["idle_gc_pct"] == pytest.approx(20.0)
    assert got["gc_ms_per_s"] == pytest.approx(0.3 / 2e-3)
    assert got["gc_pause_max_ms.chat"] == pytest.approx(0.15)
    # a collection wholly inside the gap holds its whole length
    got = _read(monkeypatch, _events(gcs=[(900, 1000)]))
    assert got["idle_gc_pct"] == pytest.approx(20.0)


def test_readers_read_nought_without_a_collection_and_nothing_without_hook(
        monkeypatch):
    assert _read(monkeypatch, _events()) == dict.fromkeys(READERS, 0.0)
    assert _read(monkeypatch, _events(gcs=[(900, 1000)]),
                 instrumented=False) == dict.fromkeys(READERS, None)


def test_gc_spans_leave_the_step_tables_as_they_were(monkeypatch):
    plain, with_gc = _events(), _events(gcs=[(880, 990), (1250, 1400)])
    run = {"xplane_path": "made-by-hand"}
    read = {}
    for key, ev in (("plain", plain), ("gc", with_gc)):
        monkeypatch.setattr(spans, "load", lambda path, e=ev: e)
        read[key] = (spans.idle_named_pct(run), spans.step_host_ms(run))
    assert read["gc"] == read["plain"]


@pytest.fixture(scope="module")
def engine_fixture(tmp_path_factory):
    """The serving trace recorded on a TPU v5e (``record_engine_fixture.py``)."""
    path = str(tmp_path_factory.mktemp("gc_fixture") / "tiny_engine.xplane.pb")
    with gzip.open(ENGINE_FIXTURE_GZ, "rb") as f, open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    return path


def test_a_collection_in_a_recorded_trace(engine_fixture, monkeypatch):
    """A collection planted in the middle half of the recorded step's
    longest-idle ``serving.dispatch``: ``idle_gc_pct`` is the idle of that
    half over all idle, and the ``serving.*`` readers read as before."""
    ev = spans.read(engine_fixture)
    sp = spans.split(ev)
    idle = spans.Idle(ev.ops, sum(sp.bracket) / 2.0)
    _, s, e, _ = max((h for h in ev.host if h[0] == "serving.dispatch"),
                     key=lambda h: idle.inside_s(h[1], h[2]))
    a, b = s + (e - s) / 4, e - (e - s) / 4
    assert 0 < idle.inside_s(a, b) < idle.inside_s(s, e)
    planted = spans.Events(ops=ev.ops, modules=ev.modules, host=sorted(
        ev.host + [(GC_SPAN, a, b, {"generation": 2})], key=lambda h: h[1]))
    got = _read(monkeypatch, planted)
    assert got["idle_gc_pct"] == pytest.approx(
        100.0 * idle.inside_s(a, b) / sp.idle_s)
    assert got["gc_pause_max_ms.chat"] == pytest.approx((b - a) / 1e6)
    run = {"xplane_path": engine_fixture}
    before = [spans.idle_named_pct(run), spans.step_host_ms(run)]
    monkeypatch.setattr(spans, "load", lambda path: planted)
    assert [spans.idle_named_pct(run), spans.step_host_ms(run)] == before


# ------------------------------------------------------ the expert rows --
class _CountingLock:
    def __init__(self):
        self.lock, self.taken = threading.Lock(), 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def test_a_step_s_expert_rows_are_filed_under_one_lock(monkeypatch):
    """``_publish_moe_rows`` against the increment a pair it replaced:
    ``registry_rows()`` reads the same, and the lock is taken once a step."""
    rng = np.random.default_rng(35)
    steps = [rng.integers(0, 4, (12, 64)) * (rng.random((12, 64)) < 0.7)
             for _ in range(3)]
    by_pair, by_step = MetricsRegistry(), MetricsRegistry()
    name = "serving_moe_expert_rows_total"
    pairs = by_pair.counter(name)
    for rows in steps:
        for layer, expert in zip(*np.nonzero(rows)):
            pairs.inc(int(rows[layer, expert]), layer=str(layer),
                      expert=str(expert))
    family = by_step.counter(name)
    family._lock = _CountingLock()
    engine = types.SimpleNamespace(_moe_keys=[], _m_moe_rows=family)
    leaf = types.SimpleNamespace(args={})
    for rows in steps:
        ServingEngine._publish_moe_rows(engine, rows, leaf)
    assert family._lock.taken == len(steps)
    assert leaf.args["moe_live"] == int(np.count_nonzero(steps[-1]))

    import paddle_tpu.observability as obs
    read = {}
    for key, reg in (("pair", by_pair), ("step", by_step)):
        monkeypatch.setattr(obs, "get_registry", lambda r=reg: r)
        read[key] = _pangu.registry_rows()
    assert read["step"] == read["pair"] and len(read["pair"]) > 500


def test_inc_many_refuses_a_negative_amount_and_keeps_the_cap():
    from paddle_tpu.observability.metrics import label_key
    c = MetricsRegistry().counter("c")
    with pytest.raises(ValueError):
        c.inc_many([label_key(a=1)], [-1])
    assert c.total() == 0.0
    c._max_label_sets = 2
    with pytest.warns(RuntimeWarning):
        c.inc_many([label_key(a=i) for i in range(4)], [1, 2, 3, 4])
    assert c.value(a=0) == 1 and c.value(overflow="true") == 7
