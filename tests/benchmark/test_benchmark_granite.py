"""Model ``granite_hybrid`` in the benchmark: the manifest's rules on the
tree, its configuration's arithmetic (the whole model, nothing cut), its
kernels' counts by hand, each new reader on hand-built spans and ops (a
number in range; nothing where the program writes no such span), and its
cell through the whole run flow at a tiny size: a sound run reads nought
against its own reference, a planted fault in the reference reads over the
limit."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import benchmark_tiny as tiny
import manifest_rules as rules
from benchmark import harness, spans, xplane
from benchmark import weights_granite_hybrid as W
from benchmark.kernels import granite_hybrid_model as gm, rpa, ssd
from benchmark.reference import granite_hybrid as R
from granite_tiny import CFG

CELL = "serve-granite-chat"
CONFIG = "benchmark/configs/granite-4.0-h-micro-serve.json"
#: the tiny float32 model against its own reference reads nought; the
#: planted faults read tenths of a logit and more
TINY_LIMIT = {"served_gap_max": 0.05, "served_gap_mean": 0.001}
SEED = 17
NAMES = [m["name"] for m in harness.load_manifest()["per_layer"]
         if m.get("workloads") == [CELL]]


def chat_mix():
    mix = harness.load_json(harness.HERE, "traffic", "chat-hi.json")
    mix.update(
        sessions_per_s=5.0, cycle_sessions=16, warmup_prompt=20,
        check_pad_to=256, check_positions=600, check_requests=6,
        trace_start_s=0.2, trace_seconds=0.5,
        prefix={"pool": 4, "share": 0.3,
                "tokens": {"dist": "fixed", "value": 16}},
        prompt_total={"dist": "lognormal", "median": 30, "sigma": 0.8,
                      "min": 6, "max": 90},
        suffix={"dist": "fixed", "value": 4, "min": 4},
        answer={"dist": "lognormal", "median": 12, "sigma": 0.7, "min": 2,
                "max": 40})
    return mix


# ------------------------------------------------------- the manifest --
def test_the_manifest_rules_pass_on_the_tree():
    manifest = harness.load_manifest()
    rules.check_all(manifest, harness.ROOT)
    conf, = [c for c in manifest["configs"]
             if c["name"] == "granite-4.0-h-micro-serve"]
    assert conf["reduced"] == []
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["traffic"], cell["chips"]) == ("chat-hi", 1)
    assert len(NAMES) == 9
    assert all(n.endswith(".granite-chat") for n in NAMES)
    for shared in ("serve_tokens_per_s", "cached_prompt_pct"):
        entry, = [m for m in manifest["end_to_end"] + manifest["per_layer"]
                  if m["name"] == shared]
        w = entry["workloads"]
        assert w.index(CELL) < w.index("serve-smallthinker-mixed")


def test_the_mix_is_the_issue_s():
    mix = harness.load_json(harness.HERE, "traffic", "chat-hi.json")
    assert mix["prefix"] == {"pool": 4, "share": 0.3, "tokens": {
        "dist": "fixed", "value": 256}}
    assert mix["prompt_total"] == {"dist": "lognormal", "median": 512,
                                   "sigma": 0.8, "min": 32, "max": 3072}
    assert mix["answer"] == {"dist": "lognormal", "median": 192,
                             "sigma": 0.7, "min": 16, "max": 1024}
    assert mix["suffix"]["value"] == 32
    assert (mix["asks_per_session"], mix["backlog"]) == (1, "cut")
    assert "rotate" not in mix
    assert mix["check_pad_to"] == 3072 + 1024
    assert mix["check_positions"] >= mix["check_requests"] * 1024
    # a 51 s window is offered no more sessions than the cycle holds
    assert mix["cycle_sessions"] >= 51 * mix["sessions_per_s"]
    assert mix["trace_start_s"] + mix["trace_seconds"] <= 42


def test_the_configuration_holds_what_its_file_says():
    """The issue's arithmetic, in millions of parameters: a Mamba block
    76.18 (in_proj 17.43, out_proj 8.39, conv 0.02, MLP 50.33), an
    attention block 60.82, the tied embedding 205.52; 36 + 4 blocks and the
    embedding 3,191 M, 6.38 GB in bf16. A slot's state rows: 36 x (64 x 64
    x 128 + 3 x 4352) numbers, 38.7 MB in bf16."""
    cfg = harness.load_json(harness.ROOT, CONFIG)
    n, z, s = W.n_params(cfg), W.sizes(cfg), W.leaf_shapes(cfg)
    mil = lambda v: round(v / 1e6, 2)    # noqa: E731
    assert mil(n["mamba"]) == 76.18 and mil(n["attention"]) == 60.82
    assert mil(np.prod(s["in_proj"])) == 17.43
    assert mil(np.prod(s["out_proj"])) == 8.39
    assert mil(np.prod(s["mlp_in"]) + np.prod(s["mlp_out"])) == 50.33
    assert mil(n["embed"]) == 205.52
    assert round(n["total"] / 1e6) == 3191
    assert round(2 * n["total"] / 1e9, 2) == 6.38
    assert gm.layer_kinds(cfg) == (36, 4)
    assert [i for i, k in enumerate(z["kinds"]) if k == "attention"] == \
        [5, 15, 25, 35]
    assert (z["di"], z["conv"], z["hd"]) == (4096, 4352, 64)
    state = 64 * 64 * 128 + 3 * 4352
    assert round(36 * state * 2 / 1e6, 1) == 38.7
    eng = cfg["engine"]
    assert eng["max_blocks_per_seq"] * eng["block_size"] >= 4096
    # weights, state rows and K/V pages leave room for a step's work
    kv = 4 * 8 * 64 * 2 * 2 * eng["block_size"] * (eng["max_blocks"] + 1)
    rows = 36 * state * 2 * (eng["max_batch"] + 1)
    assert 12.0e9 < 2 * n["total"] + kv + rows < 14.5e9
    # a token at context 1000: projections and MLPs of 40 layers, 36
    # recurrences and convolutions, 4 layers' attention pairs, the head
    m = gm.matmul_params(cfg)
    want = 2 * (36 * (m["mamba"] + m["mlp"]) + 4 * (m["attention"]
                                                   + m["mlp"])) \
        + 36 * (4 * 64 * 64 * 128 + 2 * 4 * 4352) \
        + 4 * 4 * 32 * 64 * 1000 + 2 * m["head"]
    assert gm.forward_flops_per_token(cfg, 1000) == want


def test_the_state_update_s_least_work():
    """Two decode rows and a chunk of 10: each slot's state read and
    written once, each row's inputs and output once, 4 H P N a row."""
    rows = [(1, 50), (1, 7), (10, 0)]
    flops, nbytes = ssd.required(rows, 64, 64, 128, 4, 4352)
    hpn = 64 * 64 * 128
    assert flops == 4 * hpn * 12
    assert nbytes == 3 * 2 * (hpn + 3 * 4352) * 2 \
        + 12 * (2 * 4096 + 64 + 256) * 2


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
        "stats": {}}


STEPS = [
    {"dispatch": {"rows": "1@400;1@900;1@2000;512@0", "decode_rows": 3,
                  "prefill_rows": 1, "ssm_slots": 4, "ssm_fresh": 1,
                  "ssm_decode_rows": 3, "ssm_prefill_rows": 512},
     "commit": {"tokens_out": 4}},
    {"dispatch": {"rows": "1@401;1@901;1@2001;1@512", "decode_rows": 4,
                  "prefill_rows": 0, "ssm_slots": 4, "ssm_fresh": 0,
                  "ssm_decode_rows": 4, "ssm_prefill_rows": 0},
     "commit": {"tokens_out": 4}}]
OPS = {"rpa.3 custom-call": 0.0004, "rpa.9 custom-call": 0.0004,
       "ssd.1 custom-call": 0.3, "ssd.44 custom-call": 0.1,
       "fusion.11": 0.3}


def test_each_reader_returns_its_number(monkeypatch):
    run = _run(monkeypatch, STEPS, OPS)
    got = {n.rsplit(".", 1)[0]: harness.read_layer_metric(n, run)
           for n in NAMES}
    assert all(v is not None for k, v in got.items()
               if k not in ("step_host_ms", "idle_named_pct")), got
    for name in ("serve_mfu_pct", "rpa_roofline", "ssd_roofline"):
        assert 0 < got[name] < 105, (name, got)
    rows = [(1, 400), (1, 900), (1, 2000), (512, 0), (1, 401), (1, 901),
            (1, 2001), (1, 512)]
    least = lambda f, b: max(f / tiny.PEAKS["bf16_flops_per_s"],  # noqa
                             b / tiny.PEAKS["hbm_bytes_per_s"])
    f, b = ssd.required(rows, 64, 64, 128, 4, 4352)
    assert got["ssd_roofline"] == pytest.approx(
        100 * least(36 * f, 36 * b) / 0.4)
    f, b = rpa.required(rows, 32, 8, 64)
    assert got["rpa_roofline"] == pytest.approx(
        100 * least(4 * f, 4 * b) / 0.0008)
    assert got["ssd_time_pct"] == pytest.approx(50.0)
    assert got["ssm_slots_live"] == 4.0
    cfg = run["cfg"]
    flops = 519 * gm.row_flops(cfg) \
        + 4 * gm.attention_flops_per_pair(cfg) * gm.visible_pairs(rows) \
        + 8 * 2 * gm.matmul_params(cfg)["head"]
    assert got["serve_mfu_pct"] == pytest.approx(
        100 * flops / tiny.PEAKS["bf16_flops_per_s"])
    assert got["engine_step_ms"] == pytest.approx(30.0)
    assert got["device_idle_pct"] == pytest.approx(20.0)
    assert harness.read_layer_metric("cached_prompt_pct", run) == 0.0


def test_the_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """A program without state rows (its dispatch spans carry no
    ``ssm_*``, no ``ssd`` call in its trace) and a run without a trace:
    None, never 0."""
    bare = [{"dispatch": {"rows": "8@0", "decode_rows": 0},
             "commit": {"tokens_out": 1}}]
    run = _run(monkeypatch, bare, {"rpa.3 custom-call": 0.001})
    for name in ("ssd_roofline", "ssd_time_pct", "ssm_slots_live"):
        assert harness.read_layer_metric(name + ".granite-chat", run) \
            is None, name
    run = _run(monkeypatch, STEPS, OPS, with_trace=False)
    run["traced"] = {}
    for name in ("serve_mfu_pct", "rpa_roofline", "ssd_roofline",
                 "ssd_time_pct", "ssm_slots_live", "device_idle_pct"):
        assert harness.read_layer_metric(name + ".granite-chat", run) \
            is None, name


# ----------------------------------------------- the cell, at a tiny size --
@pytest.fixture(scope="module")
def served():
    ctx = tiny.context(CELL, chat_mix(), cfg=CFG, seed=SEED, seconds=2.0)
    return harness.run_cell(ctx)


def test_the_tiny_cell_reads_nought_against_its_own_reference(served):
    line = tiny.result(CELL, served, TINY_LIMIT)
    assert line["correct"] is True, (line["compared"], served.notes)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = tiny.result(CELL, served, TINY_LIMIT, traced=True)["metrics"]
    assert traced["engine_step_ms.granite-chat"]["value"] > 0
    # prefixes are declined over state layers
    assert traced["cached_prompt_pct"]["value"] == 0.0
    # no trace on a CPU: the trace's readers return nothing, never 0
    for name in ("ssd_roofline", "ssd_time_pct", "ssm_slots_live",
                 "rpa_roofline", "serve_mfu_pct", "device_idle_pct"):
        assert name + ".granite-chat" not in traced


def _reread(served):
    from benchmark.kinds import open_loop
    run = served.run
    sample = open_loop.check_sample(run["records"], SEED,
                                    int(run["mix"]["check_requests"]))
    return open_loop.served_gaps(sample, SEED, run["cfg"], run["mix"],
                                 "exact", R.serve_logits)


_REAL_MAMBA = R.mamba


def _no_conv_state(a, w, z, mm):
    """The convolution reads only the position itself."""
    import jax.numpy as jnp
    keep = jnp.zeros_like(w["conv_w"]).at[-1].set(w["conv_w"][-1])
    return _REAL_MAMBA(a, dict(w, conv_w=keep), z, mm)


def test_a_planted_fault_fails_the_limits(served, monkeypatch):
    """The served tokens of the sound run read against a reference whose
    convolution forgets the inputs before each position: the distance the
    program would read against the sound reference had the fault been its
    own."""
    R._forward_fn.cache_clear()
    with monkeypatch.context() as m:
        m.setattr(R, "mamba", _no_conv_state)
        gaps = _reread(served)
    R._forward_fn.cache_clear()
    numbers = {"served_gap_max": float(gaps["served"].max()),
               "served_gap_mean": float(gaps["served"].mean())}
    ok, compared = harness.judge(numbers, TINY_LIMIT)
    assert ok is False, compared
