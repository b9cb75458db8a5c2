"""Model ``smallthinker`` in the benchmark: the manifest's rules on the
tree, the reference against the tiny program, its kernels' counts by hand,
its configuration's arithmetic, each new reader on hand-built spans and
ops (a number in range; nothing where the program writes no such span),
the two kernel names in one traced step, and its cell through the whole run
flow at a tiny size: a sound run reads nought against its own reference,
the int8 control and a planted router fault read over the limit."""
import importlib

import numpy as np
import pytest

import benchmark_tiny as tiny
import manifest_rules as rules
from benchmark import harness, spans, xplane
from benchmark import weights_smallthinker as W
from benchmark.kernels import moe_gmm, rpa, rpa_win, smallthinker_model as sm
from benchmark.reference import smallthinker as R
from paddle_tpu.serving.engine import serving_metrics

CELL = "serve-smallthinker-mixed"
CONFIG = "benchmark/configs/smallthinker-21b-a3b-serve-l12.json"
CFG = dict(
    model="smallthinker", hidden_size=64, num_hidden_layers=4,
    num_attention_heads=7, num_key_value_heads=1, head_dim=16,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, vocab_size=128,
    sliding_window_size=32, sliding_window_layout=[0, 1, 1, 1] * 2,
    rope_layout=[0, 1, 1, 1] * 2, rope_theta=10000.0, rms_norm_eps=1e-6,
    max_position_embeddings=256, initializer_range=0.1, dtype="float32",
    engine=dict(max_batch=4, max_blocks={"full": 96, "window": 48},
                block_size=8, prefill_chunk=16, max_blocks_per_seq=32))
#: the tiny float32 model against its own reference reads nought (the same
#: float32 weights on both sides); the int8 control reads 0.2-1.4 and a
#: router that scores with a sigmoid 0.1-0.9 over some 200 served tokens
TINY_LIMIT = {"served_gap_max": 0.05, "served_gap_mean": 0.001}
SEED = 11
NAMES = [m["name"] for m in harness.load_manifest()["per_layer"]
         if m.get("workloads") == [CELL]]


@pytest.fixture(scope="module", autouse=True)
def own_expert_rows():
    """``serving_moe_expert_rows_total`` is one family a process, and a test
    worker runs several files in one: this file's engines must neither
    read another model's rows nor leave theirs behind (the readers of a
    cell's expert load sum the whole family)."""
    family = serving_metrics()["moe_rows"]
    family.clear()
    yield
    family.clear()


def mixed_mix():
    mix = harness.load_json(harness.HERE, "traffic", "mixed-16k.json")
    mix.update(
        sessions_per_s=3.0, cycle_sessions=16, warmup_prompt=20,
        check_pad_to=256, trace_start_s=0.2, trace_seconds=0.5,
        ask_gap_s={"dist": "uniform", "min": 0.1, "max": 0.3},
        prefix={"pool": 0, "share": 0.5,
                "tokens": {"dist": "lognormal", "median": 90, "sigma": 0.4,
                           "min": 48, "max": 160}},
        suffix={"dist": "lognormal", "median": 10, "sigma": 0.8, "min": 3,
                "max": 30},
        answer={"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 4,
                "max": 40},
        check_requests=12, check_positions=600)
    return mix


# ------------------------------------------------------- the manifest --
def test_the_manifest_rules_pass_on_the_tree():
    manifest = harness.load_manifest()
    rules.check_all(manifest, harness.ROOT)
    conf, = [c for c in manifest["configs"]
             if c["name"] == "smallthinker-21b-a3b-serve-l12"]
    assert conf["reduced"] == ["num_hidden_layers"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["traffic"], cell["chips"]) == ("mixed-16k", 1)
    assert len(NAMES) == 14
    for shared in ("serve_tokens_per_s", "cached_prompt_pct"):
        entry, = [m for m in manifest["end_to_end"] + manifest["per_layer"]
                  if m["name"] == shared]
        assert entry["workloads"][-1] == CELL


def test_the_mix_is_the_issue_s():
    mix = harness.load_json(harness.HERE, "traffic", "mixed-16k.json")
    assert mix["prefix"] == {"pool": 0, "share": 0.5, "tokens": {
        "dist": "lognormal", "median": 8192, "sigma": 0.4, "min": 4096,
        "max": 15360}}
    assert mix["suffix"]["max"] + mix["answer"]["max"] + 15360 == 16384 \
        == mix["check_pad_to"]
    assert (mix["asks_per_session"], mix["backlog"]) == (3, "cut")
    assert "rotate" not in mix
    # a 51 s window is offered no more sessions than the cycle holds
    assert mix["cycle_sessions"] >= 51 * mix["sessions_per_s"]
    assert 12 <= mix["trace_seconds"] <= 20


def test_the_configuration_holds_what_its_file_says():
    """The cut's arithmetic (ISSUE 31): 20.97 M attention parameters a
    layer, 0.16 M in the router, 5.898 M an expert and 64 of them, 388.96 M
    in the embedding and in the head: 5,561 M, 11.12 GB in bf16; 3 full and
    9 window layers; a page of 128 tokens 256 KiB a layer."""
    cfg = harness.load_json(harness.ROOT, CONFIG)
    n, z = W.n_params(cfg), W.sizes(cfg)
    assert round(n["attention"] / 1e6, 2) == 20.97
    assert round(n["router"] / 1e6, 2) == 0.16
    assert round(n["expert"] / 1e6, 3) == 5.898
    assert round(n["embed"] / 1e6, 2) == round(n["head"] / 1e6, 2) == 388.96
    assert round(n["total"] / 1e6) == 5561
    assert round(2 * n["total"] / 1e9, 2) == 11.12
    assert sm.layer_kinds(cfg) == (3, 9)
    assert z["windowed"][:5] == (False, True, True, True, False)
    assert z["rotary"] == z["windowed"] and z["experts"] == 64
    assert z["kv"] * 128 * z["hd"] * 2 * 2 == 256 * 1024
    eng = cfg["engine"]
    assert eng["max_blocks_per_seq"] * eng["block_size"] == 16384 \
        == cfg["max_position_embeddings"]
    assert set(eng["max_blocks"]) == {"full", "window"}
    # a token at context 5000: every key in a full layer, 4096 in a window
    m = sm.matmul_params(cfg)
    want = 12 * 2 * (m["attention"] + m["router"] + 6 * m["expert"]) \
        + 4 * 28 * 128 * (3 * 5000 + 9 * 4096) + 2 * m["head"]
    assert sm.forward_flops_per_token(cfg, 5000) == want


# ------------------------------------------------------ counts, by hand --
def test_visible_pairs_by_hand():
    """A chunk of 4 on 10 cached keys and a decode row on 7. No window: 58
    pairs. Window 12: the chunk's tokens see 11, 12, 12, 12 keys (the last
    two would see 13 and 14), the decode row 8."""
    rows = [(4, 10), (1, 7)]
    assert sm.visible_pairs(rows) == 58
    assert sm.visible_pairs(rows, 12) == 11 + 12 + 12 + 12 + 8
    assert sm.visible_pairs(rows, 100) == 58
    assert sm.visible_pairs([(3, 0)], 2) == 1 + 2 + 2


def test_rpa_win_counts_by_hand():
    """The same rows under a window of 12, 28 query heads on 4 KV heads of
    128: QK^T and PV a visible pair and head; the K and V rows any token
    of a row can see (the chunk: keys 0-13, all 14; the decode row: 8), q
    and the output moved once, 2 bytes a number."""
    flops, nbytes = rpa_win.required([(4, 10), (1, 7)], 28, 4, 128, 12)
    assert flops == 4 * 28 * 128 * 55
    moved = 2 * 4 * 128 * (14 + 8) + 2 * (4 + 1) * 28 * 128
    assert nbytes == 2 * moved
    # a decode row deep in a document reads its window, not its context
    _, deep = rpa_win.required([(1, 9000)], 28, 4, 128, 4096)
    assert deep == 2 * (2 * 4 * 128 * 4096 + 2 * 28 * 128)
    assert rpa_win.TRACE_PATTERN == r"^rpa_win\S* custom-call"
    import re
    assert re.search(rpa_win.FULL_TRACE_PATTERN, "rpa.12 custom-call")
    assert re.search(rpa_win.FULL_TRACE_PATTERN, "rpa custom-call")
    for other in ("rpa_win.3 custom-call", "rpa_mla.1 custom-call"):
        assert not re.search(rpa_win.FULL_TRACE_PATTERN, other)
        assert re.search(rpa.TRACE_PATTERN, other)   # the older pattern


def test_moe_gmm_counts_by_hand():
    """600 sorted rows on 40 (layer, expert) pairs at 2560 x 768: three
    products a row; the three matrices of each pair read once, a row's
    input read twice and its output written once 2560 wide, the gated
    activation's two factors written and their product read 768 wide."""
    flops, nbytes = moe_gmm.required(600, 40, 2560, 768)
    assert flops == 2 * 3 * 2560 * 768 * 600
    assert nbytes == 2 * (3 * 2560 * 768 * 40 + 600 * (3 * 2560 + 3 * 768))
    import re
    assert re.search(moe_gmm.TRACE_PATTERN, "ragged-dot-none.7 custom-call")
    assert not re.search(moe_gmm.TRACE_PATTERN, "fusion.7")


# ------------------------------------- the reference against the program --
def test_the_reference_agrees_with_the_tiny_program_s_own_forward():
    """``model(ids)`` (no cache, plain masked attention) against the
    reference at every position of two rows five windows long: float32
    over the same weights, 2e-5 absolute on logits of magnitude 1-3."""
    from benchmark import sut_smallthinker as sut
    import paddle_tpu as pt
    model = sut.build_model(CFG, SEED, "float32")
    model.eval()
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 128, (2, 160))
    got = np.asarray(model(pt.to_tensor(tokens.astype(np.int32))).data)
    rows, cols = np.repeat([0, 1], 160), np.tile(np.arange(160), 2)
    want, margins = R.forward_at(SEED, CFG, tokens, rows, cols,
                                 weight_dtype="float32")
    np.testing.assert_allclose(got.reshape(320, -1), np.asarray(want),
                               atol=2e-5)
    assert margins.shape == (4, 320) and float(margins.min()) >= 0


def test_margin_by_hand():
    """Five experts, two a token. Token 0: logits 3.0 and 2.0
    chosen, the best left out 1.5: 0.5 from the cut, over the logits'
    deviation. Token 1: 2.0 and 1.0 chosen, 0.9 left out: 0.1."""
    t = np.array([[3.0, 2.0, 1.5, 0.25, -1.0],
                  [0.0, -1.0, 1.0, 0.9, 2.0]], np.float32)
    got = np.asarray(R.margin(t, {"top_k": 2}))
    np.testing.assert_allclose(got, [0.5 / t[0].std(), 0.1 / t[1].std()],
                               rtol=1e-5)


def test_an_undecided_position_is_answered_with_equal_logits(capsys):
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, 128, (2, 64))
    rows, cols = [0] * 24 + [1] * 24, list(range(40, 64)) * 2
    plain, margins = (np.asarray(a) for a in R.forward_at(
        SEED, CFG, tokens, rows, cols, weight_dtype="float32"))
    least = margins.min(0)
    eps = float(np.sort(least)[12])              # a dozen lie under it
    cfg = dict(CFG, reference={"undecided_margin": eps})
    got = np.asarray(R.serve_logits(SEED, cfg, tokens, rows, cols,
                                    weight_dtype="float32"))
    assert "12 of 48 positions undecided" in capsys.readouterr().out
    under = least < eps
    assert (got[under] == 0).all()
    np.testing.assert_array_equal(got[~under], plain[~under])
    low = np.asarray(R.serve_logits(SEED, cfg, tokens, rows, cols, "int8",
                                    weight_dtype="float32"))
    assert (low.max(-1) > low.min(-1)).all()     # the control is not masked
    assert not np.allclose(low, plain, atol=1e-3)


# ----------------------------------------------- the readers, by hand --
def _host(steps):
    """A host plane of whole steps, 1 ms each: a step is ``{"dispatch":
    args, "commit": args}``."""
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
    rows = [tuple(int(v) for v in r.split("@"))
            for s in steps for k, v in s["dispatch"].items()
            if k == "rows" or k.startswith("rows_") for r in v.split(";")]
    return {
        "kind": "open_loop", "cfg": cfg, "peaks": tiny.PEAKS,
        "window_s": 45.0, "xplane_path": "made-by-hand",
        "counters": {"steps": 900.0},
        "trace": trace if with_trace else None,
        "traced": {"span_s": 1.0, "rows": rows, "counters": {
            "prompt_tokens": 2048.0, "generated_tokens": 40.0}},
        "stats": {"kv_groups": {
            "full": {"blocks": 1536, "free": 500, "in_use": 800,
                     "reclaimable": 236, "window": None, "layers": 3},
            "window": {"blocks": 896, "free": 96, "in_use": 600,
                       "reclaimable": 200, "window": 4096, "layers": 9}}}}


STEPS = [
    {"dispatch": {"rows": "1024@8192;1@300;1@9000", "rpa_live": 3000,
                  "rpa_walked": 3004, "rpa_pages": 4400,
                  "rpa_pages_full": 4400, "rpa_pages_window": 2300,
                  "rpa_pages_causal_window": 4400},
     "commit": {"tokens_out": 2, "moe_rows": 6156, "moe_max": 140,
                "moe_live": 768}},
    {"dispatch": {"rows": "1024@0;1@301;1@9001", "rpa_live": 600,
                  "rpa_walked": 601, "rpa_pages": 700,
                  "rpa_pages_full": 700, "rpa_pages_window": 650,
                  "rpa_pages_causal_window": 700},
     "commit": {"tokens_out": 3, "moe_rows": 6156, "moe_max": 120,
                "moe_live": 760}}]
OPS = {"rpa.3 custom-call": 0.012, "rpa.4 custom-call": 0.012,
       "rpa_win.7 custom-call": 0.05, "ragged-dot-none.2 custom-call": 0.03,
       "fusion.11": 0.4}


def test_each_trace_reader_returns_a_share_in_range(monkeypatch):
    run = _run(monkeypatch, STEPS, OPS)
    got = {n: harness.read_layer_metric(n, run) for n in NAMES}
    for name in ("serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "moe_gmm_roofline"):
        assert 0 < got[name + ".st-mixed"] < 105, (name, got)
    rows = run["traced"]["rows"]
    least = lambda f, b: max(f / tiny.PEAKS["bf16_flops_per_s"],
                             b / tiny.PEAKS["hbm_bytes_per_s"])
    f, b = rpa.required(rows, 28, 4, 128)
    assert got["rpa_roofline.st-mixed"] == pytest.approx(
        100 * least(3 * f, 3 * b) / 0.024)
    f, b = rpa_win.required(rows, 28, 4, 128, 4096)
    assert got["rpa_win_roofline.st-mixed"] == pytest.approx(
        100 * least(9 * f, 9 * b) / 0.05)
    f, b = moe_gmm.required(2 * 6156, 768 + 760, 2560, 768)
    assert got["moe_gmm_roofline.st-mixed"] == pytest.approx(
        100 * least(f, b) / 0.03)
    assert got["win_keys_read_pct.st-mixed"] == pytest.approx(
        100 * 2950 / 5100)
    assert got["full_pool_used_pct.st-mixed"] == pytest.approx(
        100 * 1036 / 1536)
    assert got["win_pool_used_pct.st-mixed"] == pytest.approx(
        100 * 800 / 896)
    assert got["rpa_live_step_pct.st-mixed"] == pytest.approx(
        100 * 3600 / 3605)
    assert got["engine_step_ms.st-mixed"] == pytest.approx(50.0)
    assert got["device_idle_pct.st-mixed"] == pytest.approx(20.0)


def test_a_step_s_rows_are_read_across_their_arguments(monkeypatch):
    """64 decode rows and two chunks do not fit the 256 characters a trace
    keeps of one value: the engine writes ``rows``, ``rows_1``, ... of
    whole rows each, and the reader takes them in turn."""
    from benchmark.layer_metrics import _smallthinker as st
    from paddle_tpu.serving.engine import _row_args
    rows = [(1, 9000 + i) for i in range(64)] + [(2048, 4096), (17, 0)]
    args = _row_args(f"{n}@{c}" for n, c in rows)
    assert list(args) == ["rows", "rows_1"]
    assert all(len(v) <= 250 for v in args.values())
    assert ";".join(args.values()) == ";".join(f"{n}@{c}" for n, c in rows)
    assert _row_args(f"{n}@{c}" for n, c in rows[:20]) == {
        "rows": ";".join(f"1@{9000 + i}" for i in range(20))}
    run = _run(monkeypatch, [{"dispatch": dict(args), "commit": {}}], {})
    assert st.span_steps(run)[1]["rows"] == rows
    assert st.span_rows(run) == rows


def test_the_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """The parent's spans (no layer groups, no experts' rows on this
    model) and a trace without the kernels: None, never 0."""
    bare = [{"dispatch": {"rows": "8@0", "rpa_live": 3, "rpa_walked": 4,
                          "rpa_pages": 3}, "commit": {"tokens_out": 1}}]
    run = _run(monkeypatch, bare, {"fusion.1": 0.5})
    run["stats"] = {"kv_blocks_free": 3}
    for name in ("serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "moe_gmm_roofline", "win_keys_read_pct",
                 "full_pool_used_pct", "win_pool_used_pct"):
        assert harness.read_layer_metric(name + ".st-mixed", run) is None, name
    run = _run(monkeypatch, STEPS, OPS, with_trace=False)
    run["traced"] = {}
    for name in ("serve_mfu_pct", "rpa_roofline", "rpa_win_roofline",
                 "moe_gmm_roofline", "device_idle_pct"):
        assert harness.read_layer_metric(name + ".st-mixed", run) is None, name


def test_one_traced_step_names_rpa_and_rpa_win(monkeypatch):
    """A tiny engine under the RPA reader traces, in its one step, one
    ``rpa`` call (the full layer) and three ``rpa_win`` calls (the window
    layers): the names the device trace shows the two groups under."""
    from benchmark import sut_smallthinker as sut
    kernel = importlib.import_module(
        "paddle_tpu.ops.pallas.ragged_paged_attention")
    names, real = [], kernel.pl.pallas_call

    def recording(*a, **kw):
        names.append(kw.get("name"))
        return real(*a, **kw)
    monkeypatch.setattr(kernel.pl, "pallas_call", recording)
    engine = sut.build_engine(CFG, SEED, {"attn_impl": "rpa"})
    engine.submit(list(range(1, 60)), max_new_tokens=3)
    engine.run_until_idle()
    assert engine.step_traces == 1
    assert sorted(names) == ["rpa", "rpa_win", "rpa_win", "rpa_win"]


# ----------------------------------------------- the cell, at a tiny size --
@pytest.fixture(scope="module")
def served():
    ctx = tiny.context(CELL, mixed_mix(), cfg=CFG, seed=SEED, seconds=2.5)
    return harness.run_cell(ctx)


def test_the_tiny_cell_reads_nought_against_its_own_reference(served):
    line = tiny.result(CELL, served, TINY_LIMIT)
    assert line["correct"] is True, (line["compared"], served.notes)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = tiny.result(CELL, served, TINY_LIMIT, traced=True)["metrics"]
    assert traced["cached_prompt_pct"]["value"] > 10
    assert traced["engine_step_ms.st-mixed"]["value"] > 0
    assert 0 < traced["full_pool_used_pct.st-mixed"]["value"] <= 100
    assert 0 < traced["win_pool_used_pct.st-mixed"]["value"] <= 100
    # 2 of 8 experts a token, all held: a quarter of a step's rows each
    assert traced["moe_rows_per_expert.st-mixed"]["value"] > 0
    assert traced["moe_load_max_over_mean.st-mixed"]["value"] >= 1
    # no trace on a CPU: the trace's readers return nothing, never 0
    for name in ("rpa_roofline", "rpa_win_roofline", "moe_gmm_roofline",
                 "serve_mfu_pct", "device_idle_pct", "step_host_ms",
                 "win_keys_read_pct"):
        assert name + ".st-mixed" not in traced


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


def _sigmoid_weights(t, z):
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(t)
    top_s, top_i = jax.lax.top_k(s, z["top_k"])
    w = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[jnp.arange(t.shape[0])[:, None], top_i].set(w)


def test_a_planted_router_fault_fails_the_limits(served, monkeypatch):
    """The served tokens of the sound run read against a reference whose
    router weighs the chosen by sigmoid scores: the distance the program
    would read against the sound reference had the fault been its own."""
    R._forward_fn.cache_clear()
    with monkeypatch.context() as m:
        m.setattr(R, "router_weights", _sigmoid_weights)
        gaps = _reread(served)
    R._forward_fn.cache_clear()
    numbers = {"served_gap_max": float(gaps["served"].max()),
               "served_gap_mean": float(gaps["served"].mean())}
    ok, compared = harness.judge(numbers, TINY_LIMIT)
    assert ok is False, compared
