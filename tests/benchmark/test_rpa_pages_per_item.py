"""``rpa_pages_per_item.pangu-docs``: how full the RPA kernel's work items
are (pages named over items that name a run), read from the args of
``serving.dispatch`` on a small hand-built span list, through the harness;
nothing where no step carries ``rpa_pages`` (a program before the run of
pages, or the gather reader); and the args as a tiny latent-attention engine
writes them into the profiler's trace on the CPU backend."""
import glob
import os

import jax
import pytest

from benchmark import harness, spans
from test_rpa_live_step_pct import _loaded, _steps

NAME = "rpa_pages_per_item.pangu-docs"
RUN = {"xplane_path": "made-by-hand"}


def test_fill_is_summed_over_the_whole_steps(monkeypatch):
    host = _steps(
        {"rpa_live": 30, "rpa_walked": 44, "rpa_pages": 117},
        {"rpa_live": 1200, "rpa_walked": 1200, "rpa_pages": 4700},
        {"rpa_live": 14, "rpa_walked": 28, "rpa_pages": 14})
    # a step cut by the span's end after its dispatch: not counted
    host += [("serving.lock", 9000e3, 9001e3, {"step": 4}),
             ("serving.dispatch", 9100e3, 9200e3,
              {"step": 4, "rpa_live": 5000, "rpa_walked": 5000,
               "rpa_pages": 5000})]
    # the call that found nothing to run, and the run loop's wait
    host += [("serving.lock", 9500e3, 9501e3, {"step": 5}),
             ("serving.idle_wait", 9600e3, 9700e3, {})]
    _loaded(monkeypatch, host)
    assert harness.read_layer_metric(NAME, RUN) == pytest.approx(
        (117 + 4700 + 14) / (30 + 1200 + 14))


def test_nothing_to_read_where_no_step_carries_the_pages(monkeypatch):
    # the parent's spans: items counted, no pages
    _loaded(monkeypatch, _steps({"rpa_live": 900, "rpa_walked": 902},
                                {"rpa_live": 3, "rpa_walked": 9}))
    assert harness.read_layer_metric(NAME, RUN) is None
    # the gather reader's: neither
    _loaded(monkeypatch, _steps({}, {}))
    assert harness.read_layer_metric(NAME, RUN) is None
    # pages alone are no reading; nor are steps without a live item
    _loaded(monkeypatch, _steps({"rpa_pages": 12}))
    assert harness.read_layer_metric(NAME, RUN) is None
    _loaded(monkeypatch, _steps({"rpa_live": 0, "rpa_walked": 3,
                                 "rpa_pages": 0}))
    assert harness.read_layer_metric(NAME, RUN) is None
    # no whole step; no serving spans at all
    _loaded(monkeypatch, _steps({"rpa_live": 3, "rpa_pages": 9},
                                leaves=spans.STEP_LEAVES[:4]))
    assert harness.read_layer_metric(NAME, RUN) is None
    _loaded(monkeypatch, [("TrainStep", 0.0, 1e6, {})])
    assert harness.read_layer_metric(NAME, RUN) is None


def test_steps_without_the_pages_are_left_out_of_both_sums(monkeypatch):
    _loaded(monkeypatch, _steps(
        {"rpa_live": 10, "rpa_walked": 20, "rpa_pages": 35},
        {"rpa_live": 500, "rpa_walked": 500},
        {"rpa_live": 30, "rpa_walked": 40, "rpa_pages": 45}))
    assert harness.read_layer_metric(NAME, RUN) == pytest.approx(80 / 40)


def test_the_manifest_names_the_metric_for_its_cell():
    entry, = [m for m in harness.load_manifest()["per_layer"]
              if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "pages", "better": "higher",
        "source": "program_span", "layer": "RPA kernel",
        "moves": "serve_tokens_per_s", "workloads": ["serve-pangu-docs"]}


@pytest.mark.parametrize("impl", ["rpa", "gather"])
def test_a_tiny_latent_engine_writes_what_the_reader_reads(impl, tmp_path):
    """A small latent-attention engine (96 value columns in pages of 16
    tokens: runs of 6 pages) writes each step's ``rpa_pages`` beside
    ``rpa_live`` under the RPA kernel and neither under the gather
    reader."""
    import paddle_tpu as pt
    from paddle_tpu.models.pangu_moe import (PanguMoeConfig,
                                             PanguMoeForCausalLM)
    from paddle_tpu.serving import ServingEngine
    pt.seed(0)
    model = PanguMoeForCausalLM(PanguMoeConfig.tiny(
        held_experts=(0, 1, 2, 3), kv_lora_rank=96, qk_rope_head_dim=32,
        num_hidden_layers=2))
    model.eval()
    engine = ServingEngine(model, max_batch=4, max_blocks=64,
                           max_blocks_per_seq=16, block_size=16,
                           prefill_chunk=32, attn_impl=impl)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    engine.submit(list(range(1, 5)), max_new_tokens=2)
    engine.run_until_idle()              # compile outside the session
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        engine.submit(list(range(1, 151)), max_new_tokens=3)
        engine.submit(list(range(50, 55)), max_new_tokens=6)
        engine.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    dispatch = [st for n, _, _, st in spans.read(path).host
                if n == "serving.dispatch"]
    assert len(dispatch) >= 4
    value = harness.read_layer_metric(NAME, {"xplane_path": path})
    if impl == "gather":
        assert value is None
        assert not any("rpa_pages" in st for st in dispatch)
        return
    run = engine._run_pages
    assert run == 6
    for st in dispatch:
        assert 0 < st["rpa_live"] <= st["rpa_pages"] <= run * st["rpa_live"]
    assert value == pytest.approx(
        sum(st["rpa_pages"] for st in dispatch)
        / sum(st["rpa_live"] for st in dispatch))
    # the 150-token prompt's later chunks walk 5 to 10 pages a tile: runs
    # of 6 are part full
    assert 1.0 < value < run
