"""``rpa_full_pages_per_item`` and ``rpa_win_pages_per_item``: how full the
RPA kernel's work items are in each group of a cache of two (pages named
over items that name a run), read from the per-group args of
``serving.dispatch`` on a small hand-built span list, through the harness;
nothing where no step carries a group's pages and items (a cache of one
group, or the gather reader); and the args as a tiny engine of window and
full layers writes them into the profiler's trace on the CPU backend."""
import glob
import os

import jax
import pytest

from benchmark import harness, spans
from test_rpa_live_step_pct import _loaded, _steps

RUN = {"xplane_path": "made-by-hand"}
NAMES = {"full": "rpa_full_pages_per_item",
         "window": "rpa_win_pages_per_item"}


@pytest.mark.parametrize("group", sorted(NAMES))
def test_fill_is_summed_over_the_whole_steps(group, monkeypatch):
    def args(live, pages, other=(1, 1)):
        mine = {f"rpa_live_{group}": live, f"rpa_pages_{group}": pages}
        rest = "window" if group == "full" else "full"
        mine.update({f"rpa_live_{rest}": other[0],
                     f"rpa_pages_{rest}": other[1],
                     "rpa_live": 999, "rpa_pages": 1})
        return mine
    host = _steps(args(300, 1100), args(120, 240, (7, 50)), args(14, 14))
    # a step cut by the span's end after its dispatch: not counted
    host += [("serving.lock", 9000e3, 9001e3, {"step": 4}),
             ("serving.dispatch", 9100e3, 9200e3,
              {"step": 4, **args(5000, 5000)})]
    # the call that found nothing to run, and the run loop's wait
    host += [("serving.lock", 9500e3, 9501e3, {"step": 5}),
             ("serving.idle_wait", 9600e3, 9700e3, {})]
    _loaded(monkeypatch, host)
    assert harness.read_layer_metric(NAMES[group], RUN) == pytest.approx(
        (1100 + 240 + 14) / (300 + 120 + 14))


@pytest.mark.parametrize("group", sorted(NAMES))
def test_nothing_to_read_where_no_step_carries_the_group(group, monkeypatch):
    name = NAMES[group]
    # a cache of one group: the bare names only
    _loaded(monkeypatch, _steps({"rpa_live": 900, "rpa_walked": 902,
                                 "rpa_pages": 3000}))
    assert harness.read_layer_metric(name, RUN) is None
    # the gather reader's spans: none
    _loaded(monkeypatch, _steps({}, {}))
    assert harness.read_layer_metric(name, RUN) is None
    # pages alone are no reading; nor are steps without a live item
    _loaded(monkeypatch, _steps({f"rpa_pages_{group}": 12}))
    assert harness.read_layer_metric(name, RUN) is None
    _loaded(monkeypatch, _steps({f"rpa_live_{group}": 0,
                                 f"rpa_pages_{group}": 0}))
    assert harness.read_layer_metric(name, RUN) is None
    # no whole step; no serving spans at all
    _loaded(monkeypatch, _steps({f"rpa_live_{group}": 3,
                                 f"rpa_pages_{group}": 9},
                                leaves=spans.STEP_LEAVES[:4]))
    assert harness.read_layer_metric(name, RUN) is None
    _loaded(monkeypatch, [("TrainStep", 0.0, 1e6, {})])
    assert harness.read_layer_metric(name, RUN) is None


@pytest.mark.parametrize("name", sorted(NAMES.values()))
def test_the_manifest_names_the_metric_for_the_two_group_cells(name):
    entry, = [m for m in harness.load_manifest()["per_layer"]
              if m["name"] == name]
    assert entry == {
        "name": name, "unit": "pages", "better": "higher",
        "source": "program_span", "layer": "RPA kernel",
        "moves": "serve_tokens_per_s",
        "workloads": ["serve-kexaone-reason", "serve-smallthinker-mixed"]}


@pytest.mark.parametrize("impl", ["rpa", "gather"])
def test_a_tiny_two_group_engine_writes_what_the_readers_read(impl,
                                                               tmp_path):
    """A small engine of window and full layers (float32 K/V pages of 8
    tokens x 16: runs of 8 pages in the full group, of 5 under the window
    of 32 keys) writes each step's pages and items a group under the RPA
    kernel and neither under the gather reader."""
    import paddle_tpu as pt
    from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerForCausalLM)
    from paddle_tpu.serving import ServingEngine
    pt.seed(0)
    model = SmallThinkerForCausalLM(SmallThinkerConfig.tiny())
    model.eval()
    engine = ServingEngine(model, max_batch=4,
                           max_blocks={"full": 64, "window": 32},
                           max_blocks_per_seq=24, block_size=8,
                           prefill_chunk=32, attn_impl=impl)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    engine.submit(list(range(1, 5)), max_new_tokens=2)
    engine.run_until_idle()              # compile outside the session
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        engine.submit(list(range(1, 121)), max_new_tokens=3)
        engine.submit(list(range(50, 55)), max_new_tokens=6)
        engine.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    dispatch = [st for n, _, _, st in spans.read(path).host
                if n == "serving.dispatch"]
    assert len(dispatch) >= 4
    runs = {"full" if kw["window"] is None else "window": kw["run_pages"]
            for kw in engine._maps_kw}
    for group, name in NAMES.items():
        value = harness.read_layer_metric(name, {"xplane_path": path})
        if impl == "gather":
            assert value is None
            assert not any(f"rpa_pages_{group}" in st for st in dispatch)
            continue
        run = runs[group]
        assert run == {"full": 8, "window": 5}[group]
        for st in dispatch:
            live, pages = st[f"rpa_live_{group}"], st[f"rpa_pages_{group}"]
            assert 0 < live <= pages <= run * live
        assert value == pytest.approx(
            sum(st[f"rpa_pages_{group}"] for st in dispatch)
            / sum(st[f"rpa_live_{group}"] for st in dispatch))
        # the 120-token prompt's chunks walk up to 15 pages a tile, and
        # its window walks 5 or 6 pages: runs are part full
        assert 1.0 < value < run
