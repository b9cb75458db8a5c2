"""Model ``exaone_moe`` in the benchmark: the manifest's rules on the tree,
its configuration's arithmetic, its kernels' counts by hand, each new
reader on hand-built spans and ops (a number in range; nothing where the
program writes no such span), the kernel names of one traced drafting
step, and its cell through the whole run flow at a tiny size: a sound run
reads nought against its own reference and reports what its drafts came
to, the int8 control and planted faults read over the limit."""
import importlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import benchmark_tiny as tiny
import manifest_rules as rules
from benchmark import harness, spans, xplane
from benchmark import weights_exaone_moe as W
from benchmark.kernels import exaone_moe_model as em, moe_gmm, rpa, rpa_win
from benchmark.reference import exaone_moe as R
from exaone_tiny import CFG
from paddle_tpu.serving.engine import serving_metrics

CELL = "serve-kexaone-reason"
CONFIG = "benchmark/configs/k-exaone-236b-a23b-serve-ep8-l5.json"
#: the tiny float32 model against its own reference reads nought; the int8
#: control and the planted faults read tenths of a logit and more
TINY_LIMIT = {"served_gap_max": 0.05, "served_gap_mean": 0.001}
SEED = 13
NAMES = [m["name"] for m in harness.load_manifest()["per_layer"]
         if m.get("workloads") == [CELL]]


@pytest.fixture(scope="module", autouse=True)
def own_expert_rows():
    """``serving_moe_expert_rows_total`` is one family a process (see
    ``test_benchmark_smallthinker.py``)."""
    family = serving_metrics()["moe_rows"]
    family.clear()
    yield
    family.clear()


def reason_mix():
    mix = harness.load_json(harness.HERE, "traffic", "reason-768.json")
    mix.update(
        sessions_per_s=4.0, cycle_sessions=16, warmup_prompt=20,
        check_pad_to=256, check_positions=600, check_requests=8,
        trace_start_s=0.2, trace_seconds=0.5,
        prefix={"pool": 4, "share": 0.3,
                "tokens": {"dist": "fixed", "value": 16}},
        prompt_total={"dist": "lognormal", "median": 30, "sigma": 0.8,
                      "min": 6, "max": 90},
        suffix={"dist": "fixed", "value": 4, "min": 4},
        answer={"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
                "max": 48})
    return mix


# ------------------------------------------------------- the manifest --
def test_the_manifest_rules_pass_on_the_tree():
    manifest = harness.load_manifest()
    rules.check_all(manifest, harness.ROOT)
    conf, = [c for c in manifest["configs"]
             if c["name"] == "k-exaone-236b-a23b-serve-ep8-l5"]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["traffic"], cell["chips"]) == ("reason-768", 1)
    assert len(NAMES) == 19
    assert all(n.endswith(".kx-reason") for n in NAMES)
    for shared in ("serve_tokens_per_s", "cached_prompt_pct"):
        entry, = [m for m in manifest["end_to_end"] + manifest["per_layer"]
                  if m["name"] == shared]
        assert CELL in entry["workloads"]


def test_the_mix_is_the_issue_s():
    mix = harness.load_json(harness.HERE, "traffic", "reason-768.json")
    assert mix["prefix"] == {"pool": 4, "share": 0.3, "tokens": {
        "dist": "fixed", "value": 256}}
    assert mix["prompt_total"] == {"dist": "lognormal", "median": 384,
                                   "sigma": 0.8, "min": 64, "max": 1536}
    assert mix["answer"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.5, "min": 192, "max": 1536}
    assert (mix["asks_per_session"], mix["backlog"]) == (1, "cut")
    assert "rotate" not in mix
    assert mix["check_pad_to"] == 1536 + 1536
    assert mix["check_positions"] >= mix["check_requests"] * 1536
    # a 51 s window is offered no more sessions than the cycle holds
    assert mix["cycle_sessions"] >= 51 * mix["sessions_per_s"]
    # the traced span lies where the slots are full (from about the 25th
    # second on), inside a 45 s window
    assert mix["trace_start_s"] >= 30
    assert mix["trace_start_s"] + mix["trace_seconds"] <= 42


def test_the_configuration_holds_what_its_file_says():
    """The cut's arithmetic (ISSUE 33), in millions of parameters:
    attention 113.25; the dense layer 452.98; an expert 37.75; an expert
    layer 113.25 + router 0.79 + shared 37.75 + 16 x 37.75 = 755.76;
    embedding and head 117.96 each; the drafter 75.50 + an expert layer =
    831.26; held 4,543 M, 9.09 GB in bf16. K/V 4,096 B a token and layer:
    a page of 128 tokens is 1 MiB over the full group's two layers (layer 3
    and the drafter's) and 2 MiB over the window group's four."""
    cfg = harness.load_json(harness.ROOT, CONFIG)
    n, z = W.n_params(cfg), W.sizes(cfg)
    mil = lambda v: round(v / 1e6, 2)
    assert mil(n["attention"]) == 113.25
    assert mil(n["attention"] + n["dense_mlp"]) == 452.98
    assert mil(n["expert"]) == mil(n["shared"]) == 37.75
    assert mil(n["router"]) == 0.79 and mil(n["expert_layer"]) == 755.76
    assert mil(n["embed"]) == mil(n["head"]) == 117.96
    assert mil(n["mtp_proj"]) == 75.5 and mil(n["drafter"]) == 831.26
    assert round(n["held_total"] / 1e6) == 4543
    assert round(2 * n["held_total"] / 1e9, 2) == 9.09
    assert z["windows"] == (128, 128, 128, 0, 128) and z["dense"] == 1
    assert (z["experts"], len(z["held"]), z["top_k"]) == (128, 16, 8)
    assert z["vocab"] * 8 == cfg["published"]["vocab_size"]
    assert len(cfg["sliding_windows"]) == len(cfg["layer_types"]) == 48
    assert em.layer_kinds(cfg) == (2, 4)
    assert em.layer_kinds(cfg, drafter=False) == (1, 4)
    token = z["kv"] * z["hd"] * 2 * 2
    assert token == 4096
    eng = cfg["engine"]
    assert eng["draft_tokens"] == 1 and eng["block_size"] == 128
    assert 128 * token * 2 == 1 << 20 and 128 * token * 4 == 2 << 20
    assert set(eng["max_blocks"]) == {"full", "window"}
    pools = (eng["max_blocks"]["full"] + 1) * (1 << 20) \
        + (eng["max_blocks"]["window"] + 1) * (2 << 20)
    # weights and pools leave room for a step's activations on 16 GiB
    assert 12.5e9 < 2 * n["held_total"] + pools < 14.5e9
    mix = harness.load_json(harness.HERE, "traffic", "reason-768.json")
    assert eng["max_blocks_per_seq"] * 128 >= mix["check_pad_to"]
    # a row at context 1000: every key in the two full blocks, 128 in the
    # four window layers; the routers, shared experts and its expected
    # share of the held experts (8 x 16 / 128 = 1 a layer) in 5 expert
    # layers; the head twice
    m = em.matmul_params(cfg)
    want = 2 * (6 * m["attention"] + m["dense_mlp"]
                + 5 * (m["expert_fixed"] + m["expert"]) + m["mtp_proj"]) \
        + 4 * 64 * 128 * (2 * 1000 + 4 * 128) + 2 * 2 * m["head"]
    assert em.forward_flops_per_token(cfg, 1000) == want
    assert em.forward_flops_per_token(cfg, 1000, drafter=False) == \
        2 * (5 * m["attention"] + m["dense_mlp"]
             + 4 * (m["expert_fixed"] + m["expert"])) \
        + 4 * 64 * 128 * (1000 + 4 * 128) + 2 * m["head"]


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


def _run(monkeypatch, steps, ops, with_trace=True, drafts=True):
    cfg = harness.load_json(harness.ROOT, CONFIG)
    ev = spans.Events(ops={}, modules=[], host=sorted(
        _host(steps), key=lambda h: h[1]))
    monkeypatch.setattr(spans, "load", lambda path: ev)
    trace = xplane.Reduced(window_s=1.0, busy_s=0.8, n_devices=1,
                           device_ops=dict(ops),
                           op_counts={n: 1 for n in ops})
    stats = {"kv_groups": {
        "window": {"blocks": 640, "free": 200, "in_use": 400,
                   "reclaimable": 40, "window": 128, "layers": 4},
        "full": {"blocks": 2560, "free": 1280, "in_use": 1200,
                 "reclaimable": 80, "window": None, "layers": 2}}}
    if drafts:
        stats["drafts"] = {"drafted": 4000, "accepted": 1000,
                           "emitted": 5100, "decode_seqs": 4100,
                           "draft_tokens": 1}
    return {
        "kind": "open_loop", "cfg": cfg, "peaks": tiny.PEAKS,
        "window_s": 45.0, "xplane_path": "made-by-hand",
        "counters": {"steps": 1500.0, "prefix_hit_tokens": 300.0,
                     "prompt_tokens": 900.0},
        "e2e": {"itl_p95_ms": 47.0},
        "trace": trace if with_trace else None,
        "traced": {"span_s": 1.0, "rows": [], "counters": {
            "prompt_tokens": 512.0, "generated_tokens": 300.0}},
        "stats": stats}


STEPS = [
    {"dispatch": {"rows": "2@400;2@900;1@2000;512@0", "decode_rows": 3,
                  "draft_rows": 2, "draft_seqs": 2, "prefill_rows": 1,
                  "ahead": 1, "rpa_live": 300, "rpa_walked": 310,
                  "rpa_pages": 300, "rpa_pages_window": 30,
                  "rpa_pages_causal_window": 200},
     "commit": {"tokens_out": 4, "drafted": 2, "accepted": 1, "emitted": 4,
                "decode_seqs": 3, "moe_rows": 2600, "moe_max": 90,
                "moe_live": 80}},
    {"dispatch": {"rows": "2@402;2@901;2@2001;2@512", "decode_rows": 4,
                  "draft_rows": 4, "draft_seqs": 4, "prefill_rows": 0,
                  "ahead": 1, "rpa_live": 100, "rpa_walked": 105,
                  "rpa_pages": 100, "rpa_pages_window": 12,
                  "rpa_pages_causal_window": 60},
     "commit": {"tokens_out": 4, "drafted": 4, "accepted": 0, "emitted": 4,
                "decode_seqs": 4, "moe_rows": 40, "moe_max": 3,
                "moe_live": 30}}]
OPS = {"rpa.3 custom-call": 0.0004, "rpa.9 custom-call": 0.0004,
       "rpa_win.7 custom-call": 0.002, "ragged-dot-none.2 custom-call": 0.02,
       "fusion.11": 0.4}


def test_each_reader_returns_its_number(monkeypatch):
    run = _run(monkeypatch, STEPS, OPS)
    got = {n.rsplit(".", 1)[0]: harness.read_layer_metric(n, run)
           for n in NAMES}
    assert all(v is not None for k, v in got.items()
               if not k.startswith("moe_rows") and not k.startswith(
                   "moe_load") and k not in ("step_host_ms",
                                             "idle_named_pct")), got
    for name in ("serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "moe_gmm_roofline"):
        assert 0 < got[name] < 105, (name, got)
    assert got["mtp_accept_pct"] == pytest.approx(25.0)
    assert got["tokens_per_seq_step"] == pytest.approx(5100 / 4100)
    rows = [(2, 400), (2, 900), (1, 2000), (512, 0), (2, 402), (2, 901),
            (2, 2001), (2, 512)]
    assert got["draft_rows_pct"] == pytest.approx(
        100 * 6 / sum(n for n, _ in rows))
    assert got["step_ahead_pct"] == 100.0
    least = lambda f, b: max(f / tiny.PEAKS["bf16_flops_per_s"],
                             b / tiny.PEAKS["hbm_bytes_per_s"])
    f, b = rpa.required(rows, 64, 8, 128)
    assert got["rpa_roofline"] == pytest.approx(
        100 * least(2 * f, 2 * b) / 0.0008)
    f, b = rpa_win.required(rows, 64, 8, 128, 128)
    assert got["rpa_win_roofline"] == pytest.approx(
        100 * least(4 * f, 4 * b) / 0.002)
    f, b = moe_gmm.required(2640, 110, 6144, 2048)
    assert got["moe_gmm_roofline"] == pytest.approx(100 * least(f, b) / 0.02)
    cfg = run["cfg"]
    m = em.matmul_params(cfg)
    # heads: 7 decode rows + 6 drafts + 0 prompts' ends, and 7 kept guesses
    flops = 525 * em.row_flops(cfg) + 2640 * 2 * m["expert"] \
        + (13 + 7) * 2 * m["head"] + 4 * 64 * 128 * (
            2 * em.visible_pairs(rows) + 4 * em.visible_pairs(rows, 128))
    assert got["serve_mfu_pct"] == pytest.approx(
        100 * flops / tiny.PEAKS["bf16_flops_per_s"])
    assert got["full_pool_used_pct"] == pytest.approx(50.0)
    assert got["win_pool_used_pct"] == pytest.approx(100 * 440 / 640)
    assert got["rpa_live_step_pct"] == pytest.approx(100 * 400 / 415)
    assert got["win_keys_read_pct"] == pytest.approx(100 * 42 / 260)
    assert got["itl_p95_ms"] == 47.0
    assert harness.read_layer_metric("cached_prompt_pct", run) == 25.0
    assert got["engine_step_ms"] == pytest.approx(30.0)
    assert got["device_idle_pct"] == pytest.approx(20.0)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """A program that drafts nothing (its spans carry no ``draft_rows``,
    its stats no ``drafts``) and a run without a trace: None, never 0."""
    bare = [{"dispatch": {"rows": "8@0", "rpa_live": 3, "rpa_walked": 4,
                          "rpa_pages": 3, "decode_rows": 0},
             "commit": {"tokens_out": 1, "moe_rows": 10, "moe_live": 4}}]
    run = _run(monkeypatch, bare, OPS, drafts=False)
    run["stats"].pop("kv_groups")
    for name in ("mtp_accept_pct", "tokens_per_seq_step", "draft_rows_pct",
                 "serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "moe_gmm_roofline", "moe_rows_per_expert",
                 "moe_load_max_over_mean", "full_pool_used_pct",
                 "win_pool_used_pct", "step_ahead_pct", "win_keys_read_pct"):
        assert harness.read_layer_metric(name + ".kx-reason", run) is None, \
            name
    run["e2e"] = {}
    assert harness.read_layer_metric("itl_p95_ms.kx-reason", run) is None
    run = _run(monkeypatch, STEPS, OPS, with_trace=False)
    run["traced"] = {}
    for name in ("serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "moe_gmm_roofline", "device_idle_pct"):
        assert harness.read_layer_metric(name + ".kx-reason", run) is None, \
            name


def test_one_traced_drafting_step_names_its_kernels(monkeypatch):
    """A tiny drafting engine under the RPA reader traces, in its one step,
    two ``rpa`` calls (the full layer and the drafter's block) and four
    ``rpa_win`` calls (the window layers)."""
    from benchmark import sut_exaone_moe as sut
    kernel = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    names, real = [], kernel.pl.pallas_call

    def recording(*a, **kw):
        names.append(kw.get("name"))
        return real(*a, **kw)
    monkeypatch.setattr(kernel.pl, "pallas_call", recording)
    engine = sut.build_engine(CFG, SEED, {"attn_impl": "rpa"})
    engine.submit(list(range(1, 40)), max_new_tokens=4)
    engine.run_until_idle()
    assert engine.step_traces == 1
    assert sorted(names) == ["rpa"] * 2 + ["rpa_win"] * 4
    assert engine.stats()["drafts"]["drafted"] == 2


# ----------------------------------------------- the cell, at a tiny size --
@pytest.fixture(scope="module")
def served():
    ctx = tiny.context(CELL, reason_mix(), cfg=CFG, seed=SEED, seconds=2.5)
    return harness.run_cell(ctx)


def test_the_tiny_cell_reads_nought_against_its_own_reference(served):
    line = tiny.result(CELL, served, TINY_LIMIT)
    assert line["correct"] is True, (line["compared"], served.notes)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = tiny.result(CELL, served, TINY_LIMIT, traced=True)["metrics"]
    value = lambda n: traced[n + ".kx-reason"]["value"]
    assert 0 <= value("mtp_accept_pct") <= 100
    assert 1.0 <= value("tokens_per_seq_step") <= 2.0
    drafts = served.run["stats"]["drafts"]
    assert drafts["drafted"] > 100
    assert value("tokens_per_seq_step") == pytest.approx(
        1 + drafts["accepted"] / drafts["decode_seqs"])
    assert value("engine_step_ms") > 0
    assert value("itl_p95_ms") > 0
    assert 0 < traced["cached_prompt_pct"]["value"] < 100
    assert 0 < value("full_pool_used_pct") <= 100
    assert 0 < value("win_pool_used_pct") <= 100
    # 2 of 8 experts a token, half of them held: five blocks with the
    # drafter's
    assert value("moe_rows_per_expert") > 0
    assert value("moe_load_max_over_mean") >= 1
    # no trace on a CPU: the trace's readers return nothing, never 0
    for name in ("rpa_roofline", "rpa_win_roofline", "moe_gmm_roofline",
                 "serve_mfu_pct", "device_idle_pct", "step_host_ms",
                 "draft_rows_pct", "step_ahead_pct", "win_keys_read_pct"):
        assert name + ".kx-reason" not in traced


def _reread(served, mode="exact"):
    from benchmark.kinds import open_loop
    run = served.run
    sample = open_loop.check_sample(run["records"], SEED,
                                    int(run["mix"]["check_requests"]))
    return open_loop.served_gaps(sample, SEED, run["cfg"], run["mix"], mode,
                                 R.serve_logits)


def test_the_int8_control_fails_the_limits(served):
    gaps = _reread(served, "int8")
    assert float(gaps["control"].max()) > TINY_LIMIT["served_gap_max"]
    assert float(gaps["served"].max()) <= TINY_LIMIT["served_gap_max"]


def _no_bias(t, b, z):
    return _REAL_WEIGHTS(t, 0.0 * b, z)


_REAL_WEIGHTS = R.router_weights
_REAL_ATTENTION = R.attention


def _no_qk_norm(u, w, z, mm, pos, window, block=256):
    import jax.numpy as jnp
    ones = dict(w, ln_q=jnp.ones_like(w["ln_q"]), ln_k=jnp.ones_like(w["ln_k"]))
    return _REAL_ATTENTION(u, ones, z, mm, pos, window, block)


@pytest.mark.parametrize("fault", ["no_selection_bias", "qk_gains_of_one"])
def test_a_planted_fault_fails_the_limits(served, monkeypatch, fault):
    """The served tokens of the sound run read against a reference with a
    fault: the distance the program would read against the sound reference
    had the fault been its own."""
    R._forward_fn.cache_clear()
    with monkeypatch.context() as m:
        if fault == "no_selection_bias":
            m.setattr(R, "router_weights", _no_bias)
        else:
            m.setattr(R, "attention", _no_qk_norm)
        gaps = _reread(served)
    R._forward_fn.cache_clear()
    numbers = {"served_gap_max": float(gaps["served"].max()),
               "served_gap_mean": float(gaps["served"].mean())}
    ok, compared = harness.judge(numbers, TINY_LIMIT)
    assert ok is False, compared
