"""Model ``phi4flash`` in the benchmark: the manifest's rules on the tree,
its configuration's arithmetic (the whole model, nothing cut, every
published value held but the assumed ones), its kernels' counts by hand in
the published differential form, each new reader on hand-built spans and
ops (a number in range; nothing where the program writes no such span),
and its cell through the whole run flow at a tiny size: a sound run reads
nought against its own reference, a planted fault in the reference reads
over the limit."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import benchmark_tiny as tiny
import manifest_rules as rules
from benchmark import harness, spans, xplane
from benchmark import weights_phi4flash as W
from benchmark.kernels import phi4flash_model as pm, rpa, rpa_win
from benchmark.kernels import selective_scan
from benchmark.reference import phi4flash as R
from phi4flash_tiny import CFG

CELL = "serve-phi4flash-reason"
CONFIG = "benchmark/configs/phi-4-mini-flash-reasoning-serve.json"
PUBLISHED = "benchmark/published/phi-4-mini-flash-reasoning.json"
#: the tiny float32 model against its own reference reads nought; the
#: planted fault reads tenths of a logit
TINY_LIMIT = {"served_gap_max": 0.05, "served_gap_mean": 0.001}
SEED = 19
NAMES = [m["name"] for m in harness.load_manifest()["per_layer"]
         if m.get("workloads") == [CELL]]


def reason_mix():
    mix = harness.load_json(harness.HERE, "traffic", "reason-2k.json")
    mix.update(
        sessions_per_s=4.0, cycle_sessions=16, warmup_prompt=20,
        check_pad_to=256, check_positions=600, check_requests=6,
        trace_start_s=0.2, trace_seconds=0.5,
        prefix={"pool": 4, "share": 0.3,
                "tokens": {"dist": "fixed", "value": 16}},
        prompt_total={"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 10, "max": 90},
        suffix={"dist": "fixed", "value": 4, "min": 4},
        answer={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 3,
                "max": 40})
    return mix


# ------------------------------------------------------- the manifest --
def test_the_manifest_rules_pass_on_the_tree():
    manifest = harness.load_manifest()
    rules.check_all(manifest, harness.ROOT)
    conf, = [c for c in manifest["configs"]
             if c["name"] == "phi-4-mini-flash-reasoning-serve"]
    assert conf["reduced"] == []
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["traffic"], cell["chips"]) == ("reason-2k", 1)
    assert len(NAMES) == 12
    assert all(n.endswith(".phi4-reason") for n in NAMES)
    entry, = [m for m in manifest["end_to_end"]
              if m["name"] == "serve_tokens_per_s"]
    w = entry["workloads"]
    assert w.index(CELL) < w.index("serve-smallthinker-mixed")
    # added to nothing else that the benchmark had
    assert [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if CELL in m.get("workloads", ())] == \
        ["serve_tokens_per_s"] + NAMES


def test_the_configuration_holds_every_published_value():
    """Each key of the catalog's config under the source's own name, as
    published; what the file adds beside them is what ``assumed`` lists."""
    cfg = harness.load_json(harness.ROOT, CONFIG)
    pub = harness.load_json(harness.ROOT, PUBLISHED)
    for key, value in pub["config"].items():
        assert cfg[key] == value, key
    assert cfg["head_dim"] == pub["derived"]["head_dim"] == 64
    assumed = " ".join(cfg["assumed"])
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "initializer_range"):
        assert key in assumed, key
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160)


def test_the_mix_is_the_cell_s():
    mix = harness.load_json(harness.HERE, "traffic", "reason-2k.json")
    assert mix["prefix"] == {"pool": 4, "share": 0.3, "tokens": {
        "dist": "fixed", "value": 256}}
    assert mix["prompt_total"] == {"dist": "lognormal", "median": 2048,
                                   "sigma": 0.5, "min": 512, "max": 6144}
    assert mix["answer"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.5, "min": 192, "max": 2048}
    assert (mix["asks_per_session"], mix["backlog"]) == (1, "cut")
    assert mix["check_requests"] == 6
    assert mix["check_pad_to"] >= 6144 + 2048
    assert mix["check_positions"] >= mix["check_requests"] * 2048
    # a 51 s window is offered no more sessions than the cycle holds
    assert mix["cycle_sessions"] >= 51 * mix["sessions_per_s"]
    assert mix["trace_start_s"] + mix["trace_seconds"] <= 45


def test_the_configuration_holds_what_its_file_says():
    """The configuration's arithmetic, in millions of parameters: a Mamba block
    119.90, a window or full block 98.32, a cross block 91.77, a GMU block
    104.87, the tied embedding 512.16; 3,852.56 M in all, 7.705 GB in
    bf16. A slot's state rows: 9 x (16 x 5120 + 3 x 5120) numbers, 1.75 MB
    in bf16; a token's K/V 5,120 bytes a layer."""
    cfg = harness.load_json(harness.ROOT, CONFIG)
    n, z = W.n_params(cfg), W.sizes(cfg)
    mil = lambda v: round(v / 1e6, 2)    # noqa: E731
    assert mil(n["mamba"]) == 119.90
    assert mil(n["window"]) == mil(n["full"]) == 98.32
    assert mil(n["cross"]) == 91.77 and mil(n["gmu"]) == 104.87
    assert mil(n["embed"]) == 512.16
    assert mil(n["total"]) == 3852.56
    assert round(2 * n["total"] / 1e9, 3) == 7.705
    assert pm.layer_kinds(cfg) == {"mamba": 9, "window": 8, "full": 1,
                                   "gmu": 7, "cross": 7}
    assert z["kinds"][16:18] == ("mamba", "full")
    state = 16 * 5120 + 3 * 5120
    assert round(9 * state * 2 / 1e6, 2) == 1.75
    assert pm.key_bytes(cfg) == 5120
    eng = cfg["engine"]
    assert eng["max_blocks_per_seq"] * eng["block_size"] >= 6144 + 2048
    pool = 5120 * eng["block_size"]
    mem = 2 * n["total"] + pool * (eng["max_blocks"]["full"] + 1) \
        + 8 * pool * (eng["max_blocks"]["window"] + 1) \
        + 9 * state * 2 * (eng["max_batch"] + 1)
    assert 13.0e9 < mem < 14.0e9
    # a token that samples at context 1000: every layer's projections,
    # MLPs, recurrences and convolutions, 8 window layers' 512 pairs at
    # most, the full and cross layers' 1000 each, the head
    m = pm.matmul_params(cfg)
    want = 2 * (9 * (m["mamba"] + m["mlp"]) + 9 * (m["attention"] + m["mlp"])
                + 7 * (m["gmu"] + m["mlp"]) + 7 * (m["cross"] + m["mlp"])) \
        + 9 * (6 * 16 * 5120 + 2 * 4 * 5120) \
        + 2 * 40 * (64 + 128) * (8 * 512 + 8 * 1000) + 2 * m["head"]
    assert pm.forward_flops_per_token(cfg, 1000) == want


def test_the_kernels_least_work_is_the_published_form():
    """The scan: each slot's state and convolution rows once each way,
    each row's x, dt, z and y and its B and C, 6 N D a row. Attention: a
    pair costs QK^T at 64 wide for 40 half-heads and each softmax times
    the 128-wide values, not the 128-wide products of the pair-head
    pages; a key's K/V is 5,120 bytes."""
    cfg = harness.load_json(harness.ROOT, CONFIG)
    rows = [(1, 50), (1, 7), (10, 0)]
    flops, nbytes = selective_scan.required(rows, 5120, 16, 4)
    assert flops == 6 * 16 * 5120 * 12
    assert nbytes == 3 * 2 * (16 + 3) * 5120 * 2 + 12 * (4 * 5120 + 32) * 2
    f, b = pm.attention_required(cfg, rows)
    pairs = 51 + 8 + 55
    assert f == pairs * 2 * 40 * (64 + 128)
    assert f == 1.5 * rpa.required(rows, 40, 20, 64)[0]
    assert b == rpa.required(rows, 40, 20, 64)[1]
    f, b = pm.attention_required(cfg, [(1, 600), (4, 510)], window=512)
    assert f == (512 + 511 + 3 * 512) * 2 * 40 * 192
    assert f == 1.5 * rpa_win.required([(1, 600), (4, 510)], 40, 20, 64,
                                       512)[0]
    f, b = pm.cross_required(cfg, 3, 300)
    assert (f, b) == (300 * 15360, 300 * 5120 + 3 * 10240)


# ----------------------------------------------- the readers, by hand --
def _host(steps):
    host = []
    for n, step in enumerate(steps, start=1):
        for i, name in enumerate(spans.STEP_LEAVES):
            t = (n * 1000 + i * 100) * 1e3
            stats = {"step": n}
            stats.update(step.get(name.split(".")[1], {}))
            host.append((name, t, t + 100e3, stats))
    return host


def _run(monkeypatch, steps, ops, with_trace=True):
    cfg = harness.load_json(harness.ROOT, CONFIG)
    ev = spans.Events(ops={}, modules=[], host=sorted(
        _host(steps), key=lambda h: h[1]))
    monkeypatch.setattr(spans, "load", lambda path: ev)
    trace = xplane.Reduced(window_s=1.0, busy_s=0.8, n_devices=1,
                           device_ops=dict(ops),
                           op_counts={n: 1 for n in ops})
    return {
        "kind": "open_loop", "cfg": cfg, "peaks": tiny.PEAKS,
        "window_s": 45.0, "xplane_path": "made-by-hand",
        "counters": {"steps": 1500.0, "prefix_hit_tokens": 0.0,
                     "prompt_tokens": 900.0},
        "trace": trace if with_trace else None,
        "traced": {"span_s": 1.0, "rows": [], "counters": {
            "prompt_tokens": 512.0, "generated_tokens": 300.0}},
        "stats": {"kv_groups": {
            "window": {"blocks": 704, "free": 200},
            "full": {"blocks": 3072, "free": 1072}}}}


STEPS = [
    {"dispatch": {"rows": "1@400;1@900;1@2000;512@0", "decode_rows": 3,
                  "prefill_rows": 1, "ssm_slots": 4, "cross_rows": 3,
                  "cross_keys": 401 + 901 + 2001},
     "commit": {"tokens_out": 3}},
    {"dispatch": {"rows": "1@401;1@901;1@2001;300@512", "decode_rows": 3,
                  "prefill_rows": 1, "ssm_slots": 4, "cross_rows": 4,
                  "cross_keys": 402 + 902 + 2002 + 812},
     "commit": {"tokens_out": 4}}]
OPS = {"rpa.3 custom-call": 0.004, "rpa.9 custom-call": 0.004,
       "rpa_win.2 custom-call": 0.002,
       "selective_scan.1 custom-call": 0.2,
       "selective_scan.44 custom-call": 0.2, "fusion.11": 0.3}


def test_each_reader_returns_its_number(monkeypatch):
    run = _run(monkeypatch, STEPS, OPS)
    got = {n.rsplit(".", 1)[0]: harness.read_layer_metric(n, run)
           for n in NAMES}
    assert all(v is not None for k, v in got.items()
               if k not in ("step_host_ms", "idle_named_pct")), got
    for name in ("serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "scan_roofline"):
        assert 0 < got[name] < 105, (name, got)
    cfg = run["cfg"]
    rows = [(1, 400), (1, 900), (1, 2000), (512, 0), (1, 401), (1, 901),
            (1, 2001), (300, 512)]
    least = lambda f, b: max(f / tiny.PEAKS["bf16_flops_per_s"],  # noqa
                             b / tiny.PEAKS["hbm_bytes_per_s"])
    f, b = selective_scan.required(rows, 5120, 16, 4)
    assert got["scan_roofline"] == pytest.approx(
        100 * least(9 * f, 9 * b) / 0.4)
    assert got["scan_time_pct"] == pytest.approx(50.0)
    keys = sum(s["dispatch"]["cross_keys"] for s in STEPS)
    f, b = pm.attention_required(cfg, rows)
    cf, cb = pm.cross_required(cfg, 7, keys)
    assert got["rpa_roofline"] == pytest.approx(
        100 * least(f + 7 * cf, b + 7 * cb) / 0.008)
    f, b = pm.attention_required(cfg, rows, window=512)
    assert got["rpa_win_roofline"] == pytest.approx(
        100 * least(8 * f, 8 * b) / 0.002)
    assert got["cross_rows_pct"] == pytest.approx(100 * 7 / 818)
    flops = 818 * pm.self_row_flops(cfg) + 7 * pm.cross_row_flops(cfg) \
        + pm.pair_flops(cfg) * (8 * pm.visible_pairs(rows, 512)
                                + pm.visible_pairs(rows) + 7 * keys) \
        + 7 * 2 * pm.matmul_params(cfg)["head"]
    assert got["serve_mfu_pct"] == pytest.approx(
        100 * flops / tiny.PEAKS["bf16_flops_per_s"])
    assert got["engine_step_ms"] == pytest.approx(30.0)
    assert got["device_idle_pct"] == pytest.approx(20.0)
    assert got["full_pool_used_pct"] == pytest.approx(100 * 2000 / 3072)
    assert got["win_pool_used_pct"] == pytest.approx(100 * 504 / 704)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """A program without the cross-decoder's skip (its dispatch spans
    carry no ``cross_rows``, no scan in its trace) and a run without a
    trace: None, never 0."""
    bare = [{"dispatch": {"rows": "8@0", "decode_rows": 0},
             "commit": {"tokens_out": 1}}]
    run = _run(monkeypatch, bare, {"rpa.3 custom-call": 0.001})
    for name in ("scan_roofline", "scan_time_pct", "cross_rows_pct",
                 "rpa_roofline", "serve_mfu_pct"):
        assert harness.read_layer_metric(name + ".phi4-reason", run) \
            is None, name
    run = _run(monkeypatch, STEPS, OPS, with_trace=False)
    run["traced"] = {}
    for name in ("serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "scan_roofline", "scan_time_pct", "cross_rows_pct",
                 "device_idle_pct"):
        assert harness.read_layer_metric(name + ".phi4-reason", run) \
            is None, name


# ----------------------------------------------- the cell, at a tiny size --
@pytest.fixture(scope="module")
def served():
    ctx = tiny.context(CELL, reason_mix(), cfg=CFG, seed=SEED, seconds=2.0)
    return harness.run_cell(ctx)


def test_the_tiny_cell_reads_nought_against_its_own_reference(served):
    line = tiny.result(CELL, served, TINY_LIMIT)
    assert line["correct"] is True, (line["compared"], served.notes)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = tiny.result(CELL, served, TINY_LIMIT, traced=True)["metrics"]
    assert traced["engine_step_ms.phi4-reason"]["value"] > 0
    assert 0 < traced["win_pool_used_pct.phi4-reason"]["value"] <= 100
    # no trace on a CPU: the trace's readers return nothing, never 0
    for name in ("scan_roofline", "scan_time_pct", "cross_rows_pct",
                 "rpa_roofline", "serve_mfu_pct", "device_idle_pct"):
        assert name + ".phi4-reason" not in traced


def _reread(served):
    from benchmark.kinds import open_loop
    run = served.run
    sample = open_loop.check_sample(run["records"], SEED,
                                    int(run["mix"]["check_requests"]))
    return open_loop.served_gaps(sample, SEED, run["cfg"], run["mix"],
                                 "exact", R.serve_logits)


_REAL_LAMBDA = R.lambda_init


def test_a_planted_fault_fails_the_limits(served, monkeypatch):
    """The served tokens of the sound run read against a reference whose
    ``lam_init`` is a layer deeper: the distance the program would read
    against the sound reference had the fault been its own."""
    R._forward_fn.cache_clear()
    with monkeypatch.context() as m:
        m.setattr(R, "lambda_init", lambda depth: _REAL_LAMBDA(depth + 1))
        gaps = _reread(served)
    R._forward_fn.cache_clear()
    numbers = {"served_gap_max": float(gaps["served"].max()),
               "served_gap_mean": float(gaps["served"].mean())}
    ok, compared = harness.judge(numbers, TINY_LIMIT)
    assert ok is False, compared
    assert np.isfinite(numbers["served_gap_max"])
