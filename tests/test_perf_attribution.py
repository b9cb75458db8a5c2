"""Performance-attribution layer (ISSUE 6): phase-level step attribution
with cost-analysis FLOPs, the bench.py --report regression gate over the
committed BENCH_r0*/MULTICHIP_r0* trajectory, and the docs-vs-registry
metric-family drift check (docs/OBSERVABILITY.md)."""
import importlib.util
import json
import os
import re

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_for_tests", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------- attribution table ------------------------------------------

class TestAttribution:
    @pytest.fixture(scope="class")
    def report(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.observability.attribution import \
            attribute_train_step
        cfg = LlamaConfig(
            vocab_size=2048, hidden_size=64, intermediate_size=128,
            num_hidden_layers=1, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            tie_word_embeddings=True)
        pt.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
        x = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (2, 32)).astype(np.int64)
        return attribute_train_step(model, opt, x, steps=2, warmup=1,
                                    reps=2, data_time_s=0.003)

    def test_phases_sum_to_step_time(self, report):
        # the acceptance bound: phases explain the measured step within 5%
        assert report.check(0.05), (report.sum_seconds,
                                    report.step_time_s)
        assert set(report.phases) == {
            "data", "embedding_layers", "loss_head", "optimizer",
            "exposed_collective"}

    def test_loss_head_and_optimizer_carry_time(self, report):
        # this geometry's vocab matmul + CE and the AdamW update are
        # real costs: the glue the full-vs-layer MFU gap hides in
        assert report.phases["loss_head"]["seconds"] > 0
        assert report.phases["optimizer"]["seconds"] > 0
        assert report.glue_share() > 0

    def test_flops_from_cost_analysis(self, report):
        fl_layers = report.phases["embedding_layers"]["flops"]
        fl_head = report.phases["loss_head"]["flops"]
        assert fl_layers and fl_layers > 0
        # loss head adds the [T, d]x[d, V] matmul fwd+bwd: ~6*T*d*V
        assert fl_head == pytest.approx(6 * 2 * 32 * 64 * 2048, rel=0.5)
        assert report.total_flops == pytest.approx(fl_layers + fl_head)

    def test_data_phase_passthrough_and_table(self, report):
        assert report.phases["data"]["seconds"] == pytest.approx(0.003)
        table = report.table()
        assert "loss_head" in table and "step(measured)" in table
        doc = report.to_json()
        json.dumps(doc)
        assert doc["phases"]["embedding_layers"]["share_pct"] > 0

    def test_frozen_params_attribution(self):
        # grads must cover only the TRAIN subset: with a frozen backbone
        # chunk, differentiating frozen params too would inflate t_grad
        # and clamp the optimizer phase to ~0
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.observability.attribution import \
            attribute_train_step
        cfg = LlamaConfig(
            vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=1, max_position_embeddings=32)
        pt.seed(0)
        model = LlamaForCausalLM(cfg)
        for p in model.model.embed_tokens.parameters():
            p.stop_gradient = True
        trainable = [p for p in model.parameters() if not p.stop_gradient]
        assert len(trainable) < len(list(model.parameters()))
        opt = pt.optimizer.AdamW(learning_rate=1e-4, parameters=trainable)
        x = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (2, 16)).astype(np.int64)
        from paddle_tpu.observability.metrics import MetricsRegistry
        rep = attribute_train_step(model, opt, x, steps=2, warmup=1,
                                   reps=1, registry=MetricsRegistry())
        assert rep.check(0.05)
        assert rep.phases["optimizer"]["seconds"] > 0

    def test_registry_gauges_published(self, report):
        from paddle_tpu.observability import get_registry
        g = get_registry().get("attribution_phase_seconds")
        assert g is not None
        assert g.value(phase="loss_head") == pytest.approx(
            report.phases["loss_head"]["seconds"])
        assert get_registry().get("attribution_step_seconds").value() > 0


# ---------------- bench.py --report gate -------------------------------------

class TestBenchReportGate:
    @pytest.fixture(scope="class")
    def bench(self):
        return _bench()

    #: a driver-round record the tests write themselves (the gate reads
    #: whatever BENCH_r*.json its --baseline-dir holds; no committed
    #: record is needed to test it)
    ROUND = {"rc": 0, "parsed": {
        "llama_full_train_step_mfu_bf16": 60.0, "tokens_per_sec": 25000.0,
        "step_ms": 300.0, "spread_pct_of_mean": 1.0,
        "layer_mfu_pct": 65.0, "device": "TPU v5 lite"}}
    MULTICHIP = {"n_devices": 8, "rc": 0, "ok": True, "tail":
                 "dryrun_multichip(8): dpxmp(dp=4,mp=2) loss=5.5567 | "
                 "dp-parity |5.55671-5.55671|<tol | pp(stages=4,v=2) "
                 "loss=1.2698 | zero(p_g_os) |1.15677-1.15677|<tol | "
                 "ep(experts=8) ok\n"}

    @pytest.fixture(scope="class")
    def rounds(self, tmp_path_factory):
        d = tmp_path_factory.mktemp("rounds")
        (d / "BENCH_r05.json").write_text(json.dumps(self.ROUND))
        (d / "MULTICHIP_r05.json").write_text(json.dumps(self.MULTICHIP))
        return str(d)

    @pytest.fixture(scope="class")
    def baseline(self, bench, rounds):
        name, metrics = bench.report_baseline(rounds)
        assert name == "BENCH_r05.json" and metrics
        return metrics

    def test_baseline_extraction(self, baseline):
        assert baseline["llama_full_train_step_mfu_bf16"] == \
            pytest.approx(60.0)
        assert baseline["step_ms"] == pytest.approx(300.0)

    def test_equal_run_passes(self, bench, baseline, rounds, tmp_path):
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps({"parsed": baseline}))
        rc = bench.bench_report(["--report", "--current", str(cur),
                                 "--baseline-dir", rounds])
        assert rc == 0

    @pytest.mark.parametrize("doctor", [
        {"llama_full_train_step_mfu_bf16": 0.9},   # MFU down 10%
        {"step_ms": 1.2},                           # step 20% slower
        {"tokens_per_sec": 0.8},
        {"spread_pct_of_mean": 4.0},                # stability blown
    ])
    def test_doctored_regression_fails(self, bench, baseline, rounds,
                                       tmp_path, doctor):
        bad = dict(baseline)
        for k, f in doctor.items():
            bad[k] = bad[k] * f
        cur = tmp_path / "bad.json"
        cur.write_text(json.dumps({"parsed": bad}))
        rc = bench.bench_report(["--report", "--current", str(cur),
                                 "--baseline-dir", rounds])
        assert rc == 1

    def test_improvement_passes(self, bench, baseline, rounds, tmp_path):
        good = dict(baseline)
        good["llama_full_train_step_mfu_bf16"] *= 1.1  # faster is fine
        good["step_ms"] *= 0.9
        cur = tmp_path / "good.json"
        cur.write_text(json.dumps({"parsed": good}))
        assert bench.bench_report(["--report", "--current", str(cur),
                                   "--baseline-dir", rounds]) == 0

    def test_tolerance_is_configurable(self, bench, baseline, rounds, tmp_path):
        near = dict(baseline)
        near["step_ms"] *= 1.04  # 4% slower
        cur = tmp_path / "near.json"
        cur.write_text(json.dumps({"parsed": near}))
        assert bench.bench_report(
            ["--report", "--current", str(cur), "--baseline-dir", rounds,
             "--tolerance", "5"]) == 0
        assert bench.bench_report(
            ["--report", "--current", str(cur), "--baseline-dir", rounds,
             "--tolerance", "2"]) == 1

    def test_crashed_current_run_fails_gate(self, bench, baseline,
                                            rounds, tmp_path):
        # a crashed bench's partial numbers are not proof of no
        # regression — rc != 0 fails regardless of the numbers
        cur = tmp_path / "crashed.json"
        cur.write_text(json.dumps({"rc": 1, "parsed": dict(baseline)}))
        rc = bench.bench_report(["--report", "--current", str(cur),
                                 "--baseline-dir", rounds])
        assert rc == 1

    def test_baseline_skips_metricless_round(self, bench, tmp_path):
        # a newer round with only bookkeeping numerics (rc) or a null
        # headline is not a usable baseline — fall back to the previous
        (tmp_path / "BENCH_r01.json").write_text(
            json.dumps({"rc": 0, "parsed": {"step_ms": 100.0}}))
        (tmp_path / "BENCH_r02.json").write_text(
            json.dumps({"rc": 0,
                        "tail": '{"metric": "mfu", "value": null}'}))
        name, base = bench.report_baseline(str(tmp_path))
        assert name == "BENCH_r01.json"
        assert base == {"step_ms": 100.0}

    def test_baseline_orders_rounds_numerically(self, bench, tmp_path):
        # r10 must beat r09 — lexicographic file order would pin the
        # gate to r09 forever once double-digit rounds land
        for n, ms in ((9, 300.0), (10, 200.0)):
            (tmp_path / f"BENCH_r{n:02d}.json").write_text(
                json.dumps({"rc": 0, "parsed": {"step_ms": ms}}))
        (tmp_path / "BENCH_r2.json").write_text(
            json.dumps({"rc": 0, "parsed": {"step_ms": 900.0}}))
        name, base = bench.report_baseline(str(tmp_path))
        assert name == "BENCH_r10.json"
        assert base["step_ms"] == 200.0

    def test_missing_metrics_skip_unless_strict(self, bench, rounds,
                                                tmp_path):
        cur = tmp_path / "cpu.json"
        cur.write_text(json.dumps(
            {"parsed": {"tokens_per_sec_cpu_smoke": 123.0}}))
        argv = ["--report", "--current", str(cur), "--baseline-dir", rounds]
        assert bench.bench_report(argv) == 0            # visible but soft
        assert bench.bench_report(argv + ["--strict"]) == 1

    def test_multichip_coverage_gate(self, bench, rounds):
        mc = self.MULTICHIP
        ok = bench.report_multichip(rounds, mc)
        assert ok["status"] == "ok"
        shrunk = dict(mc)
        shrunk["tail"] = mc["tail"].split("| zero")[0]
        bad = bench.report_multichip(rounds, shrunk)
        assert bad["status"] == "fail"
        assert "zero" in bad["missing_segments"]

    def test_emit_metrics_carries_exposure_families(self, bench,
                                                    tmp_path):
        # acceptance: comm_exposed/overlapped appear in --emit-metrics
        out = tmp_path / "m.json"
        bench.emit_metrics({"x": 1.0}, str(out))
        doc = json.load(open(out))
        assert "comm_exposed_seconds_total" in doc
        assert "comm_overlapped_seconds_total" in doc
        assert "bench_result" in doc


# ---------------- docs <-> registry drift ------------------------------------

#: family-name prefixes owned by this framework's telemetry
_FAMILY_PREFIXES = ("comm_", "train_", "serving_", "ckpt_",
                    "resilience_", "data_", "loader_", "attribution_",
                    "hbm_", "fleet_", "goodput_", "job_", "numerics_",
                    "quantization_", "python_")

#: backticked doc tokens that look like families but are not registry
#: metrics: `comm_bytes` is the chrome-trace counter-track name,
#: `comm_scope` an API; the two `serving_*` names are bench.py --serve
#: report-gate headlines (stdout {"metric","value"} lines gated by
#: --report, ISSUE 8) — percentile aggregates of the registry's
#: serving_ttft_seconds / serving_tokens_total families, not families
#: themselves
_NON_FAMILY_DOC_TOKENS = {"comm_bytes", "comm_scope", "comm_event",
                          "comm_totals", "data_time_s",
                          # fleet/goodput non-families (ISSUE 13):
                          # /healthz + heartbeat record fields and
                          # bench.py --chaos output keys, not registry
                          # metric families
                          "job_id", "goodput_fraction", "goodput_bins",
                          "goodput_wall_coverage", "goodput_restart_s",
                          "goodput_incarnations",
                          # goodput bin names / heartbeat record fields
                          # (docs backtick them; they are not families)
                          "data_stall", "ckpt_s", "hbm_in_use",
                          "serving_p99_ttft_seconds",
                          "serving_decode_tokens_per_sec",
                          # bench.py --serve shared-prefix report-gate
                          # headlines (ISSUE 15, docs/SERVING.md) —
                          # stdout {"metric","value"} lines, not
                          # registry families
                          "serving_prefix_cache_hit_rate",
                          "serving_shared_prefix_speedup",
                          "serving_cached_p99_ttft_seconds",
                          "serving_cold_p99_ttft_seconds",
                          # bench.py --serve --replicas N fleet
                          # report-gate headlines (ISSUE 17,
                          # docs/SERVING.md#serving-fleet) — stdout
                          # {"metric","value"} lines, not registry
                          # families
                          "serving_fleet_tokens_per_sec",
                          "serving_fleet_scaling_efficiency",
                          # commplan geometry label (ISSUE 15,
                          # docs/SERVING.md), not a metric family
                          "serving_mp2",
                          # bench.py --audit report-gate headlines
                          # (docs/ANALYSIS.md), not registry families
                          "train_step_allreduce_count",
                          "train_step_undonated_bytes",
                          "train_step_largest_intermediate_bytes",
                          # bench.py --audit runtime-memory headline
                          # (ISSUE 11, docs/ANALYSIS.md) — a report-gate
                          # stdout line, not a registry family
                          "train_step_peak_hbm_bytes",
                          # per-axis comm-plan headline family
                          # (docs/ANALYSIS.md Prong 3) — bench.py
                          # --audit report-gate stdout lines, not
                          # registry families
                          "train_step_comm_bytes_dp",
                          # HBM-ledger owner names (the {owner} label
                          # values of hbm_bytes, docs/OBSERVABILITY.md
                          # #memory), not families themselves
                          "serving_params", "data_prefetch",
                          # bench.py --numerics report-gate headline
                          # (ISSUE 14) — a stdout {"metric","value"}
                          # line, not a registry family
                          "numerics_step_overhead_frac",
                          # bench.py --serve ledger-cost headline
                          # (ISSUE 16) — a report-gate stdout line, not
                          # a registry family
                          "serving_request_ledger_overhead_frac",
                          # bench.py --serve quantization/multi-tenant
                          # headlines (ISSUE 20, docs/QUANTIZATION.md) —
                          # report-gate stdout lines, not registry
                          # families
                          "serving_int8_tokens_per_sec",
                          "serving_kv_quant_max_batch",
                          "serving_adapters_served",
                          # commplan geometry label (ISSUE 20), not a
                          # metric family
                          "serving_mp2_int8"}


def _documented_families():
    """Every metric family name mentioned in docs/*.md + README.md.
    Handles `name{label}` / `name{label="v"}` suffixes and
    `a_{x,y}_b` brace alternations."""
    found = set()
    doc_paths = [os.path.join(REPO, "README.md")] + [
        os.path.join(REPO, "docs", f)
        for f in os.listdir(os.path.join(REPO, "docs"))
        if f.endswith(".md")]
    for path in doc_paths:
        with open(path) as f:
            text = f.read()
        for token in re.findall(r"`([^`\n]+)`", text):
            if not re.match(r"^[a-z][a-z0-9_{},=\"]*$", token):
                continue
            # strip a trailing label-set: family{kind} / family{kind="x"}
            m = re.match(r"^([a-z][a-z0-9_]*)\{[^}]*\}$", token)
            names = [m.group(1)] if m else None
            if names is None and "{" in token:
                # alternation: train_step_{data,compute}_seconds
                m = re.match(r"^([a-z0-9_]*)\{([a-z0-9_,]+)\}([a-z0-9_]*)$",
                             token)
                if not m:
                    continue
                names = [m.group(1) + alt + m.group(3)
                         for alt in m.group(2).split(",")]
            if names is None:
                names = [token]
            for name in names:
                if name.startswith(_FAMILY_PREFIXES) and \
                        name not in _NON_FAMILY_DOC_TOKENS:
                    found.add(name)
    return found


def _registered_families():
    """Instantiate every subsystem's metric accessor, then read the
    default registry — "exists in the registry after importing the
    instrumented modules" per the docs-drift contract."""
    from paddle_tpu.checkpoint.writer import ckpt_metrics
    from paddle_tpu.data.metrics import data_metrics
    from paddle_tpu.io.dataloader import loader_metrics
    from paddle_tpu.observability import StepTimer, get_registry
    from paddle_tpu.observability.attribution import attribution_metrics
    from paddle_tpu.observability.fleet import fleet_metrics
    from paddle_tpu.observability.goodput import goodput_metrics
    from paddle_tpu.observability.memory import memory_metrics
    from paddle_tpu.observability.numerics import numerics_metrics
    from paddle_tpu.observability.requests import request_metrics
    from paddle_tpu.observability.slo import slo_metrics
    from paddle_tpu.profiler import trace_gc
    from paddle_tpu.resilience.counters import (
        nonfinite_counter, preemption_counter, rollback_counter,
        watchdog_metrics)
    from paddle_tpu.quantization.weight_only import quantization_metrics
    from paddle_tpu.serving.engine import serving_metrics
    from paddle_tpu.serving.fleet.router import router_metrics

    StepTimer(peak=0)
    ckpt_metrics()
    data_metrics()
    loader_metrics()
    attribution_metrics()
    fleet_metrics()
    goodput_metrics()
    memory_metrics()
    numerics_metrics()
    serving_metrics()
    router_metrics()
    quantization_metrics()
    request_metrics()
    slo_metrics()
    nonfinite_counter(), rollback_counter(), preemption_counter()
    watchdog_metrics()
    trace_gc()
    return {n for n in get_registry().names()
            if n.startswith(_FAMILY_PREFIXES)}


class TestDocsMetricDrift:
    """Doc/metric skew crept across five PRs; this pins both directions."""

    def test_every_registered_family_is_documented(self):
        missing = _registered_families() - _documented_families()
        assert not missing, (
            f"metric families registered in code but absent from "
            f"docs/*.md: {sorted(missing)} — add them to the family "
            f"index in docs/OBSERVABILITY.md")

    def test_every_documented_family_is_registered(self):
        ghosts = _documented_families() - _registered_families()
        assert not ghosts, (
            f"metric families documented in docs/*.md but never "
            f"registered by the instrumented modules: {sorted(ghosts)} — "
            f"fix the doc or the registration")
