"""Model ``pangu_ultra_moe`` in the benchmark: its kernel's counts by hand,
its configuration's arithmetic, and its cell through the whole run flow at
a tiny size (``harness.run_cell``): a sound run reads nought against its
own reference, and the reference with a fault planted in the router reads
far over the limit at the same prompts and tokens."""
import pytest

import benchmark_tiny as tiny
from benchmark import harness
from benchmark.kernels import pangu_model, rpa_mla
from benchmark.reference import pangu_ultra_moe as R

CELL = "serve-pangu-docs"
CFG = dict(
    model="pangu_ultra_moe", hidden_size=64, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=4,
    published={"n_routed_experts": 8}, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    vocab_size=128, num_hidden_layers=3, first_k_dense_replace=1,
    rope_theta=25600000.0, rms_norm_eps=1e-5, initializer_range=0.15,
    max_position_embeddings=512, dtype="float32",
    engine=dict(max_batch=4, max_blocks=64, block_size=8, prefill_chunk=16,
                max_blocks_per_seq=32))
#: the tiny float32 model against its own reference reads nought (both hold
#: the same float32 weights). Its weights are N(0, 0.15) so that the router's
#: logits are of the cell's size (0.15 * sqrt(64) = 1.2; the cell's 0.02 *
#: sqrt(7680) = 1.75): over some 230 served tokens the planted faults then
#: read 1.05-1.88 (mean 0.016-0.126) and the int8 control 0.67-1.90 (mean
#: 0.008-0.012), on two seeds and whichever requests the sample draws
TINY_LIMIT = {"served_gap_max": 0.05, "served_gap_mean": 0.001}


def docs_mix():
    mix = harness.load_json(harness.HERE, "traffic", "docs-16k.json")
    mix.update(
        sessions_per_s=3.0, warmup_prompt=20, check_pad_to=256,
        trace_start_s=0.2, trace_seconds=0.5,
        ask_gap_s={"dist": "uniform", "min": 0.1, "max": 0.3},
        prefix={"pool": 0, "share": 1.0,
                "tokens": {"dist": "lognormal", "median": 60, "sigma": 0.4,
                           "min": 30, "max": 120}},
        suffix={"dist": "uniform", "min": 4, "max": 12},
        answer={"dist": "uniform", "min": 8, "max": 32},
        check_requests=12, check_positions=400)
    return mix


SEED = 11


# ------------------------------------------------------ counts, by hand --
def test_rpa_mla_counts_by_hand_on_two_rows():
    """A chunk of 4 tokens on 10 cached rows and a decode row on 7: the
    pairs each may see, 2 operations a multiply-add over the 576-wide score
    and the 512-wide output a head; the rows' pages, the queries and the
    outputs moved once, 2 bytes a number."""
    heads, kd, vd = 128, 576, 512
    flops, nbytes = rpa_mla.required([(4, 10), (1, 7)], heads, kd, vd)
    pairs = (4 * 10 + (1 + 2 + 3 + 4)) + (1 * 7 + 1)
    assert pairs == 58
    assert flops == 2 * heads * (kd + vd) * pairs == 16_154_624
    moved = kd * (14 + 8) + (4 + 1) * heads * (kd + vd)
    assert nbytes == 2 * moved == 2 * 708_992
    assert rpa_mla.TRACE_PATTERN == r"^rpa_mla\S* custom-call"


def test_the_configuration_holds_what_its_file_says():
    """The cut's arithmetic (ISSUE 27): 196.6 M attention parameters a
    layer, a 621.2 M dense layer, a 1,000.7 M expert layer with 16 experts
    held, 147.5 M in the embedding and in the head: 9.84 GB in bf16; a
    latent row of 576 numbers."""
    from benchmark import weights_pangu as W
    cfg = harness.load_json(
        harness.ROOT, "benchmark/configs/"
        "openpangu-ultra-moe-718b-serve-ep16-l5.json")
    n, z = W.n_params(cfg), W.sizes(cfg)
    assert round(n["attention"] / 1e6, 1) == 196.6
    assert round((n["attention"] + n["dense_mlp"]) / 1e6, 1) == 621.2
    assert round((n["attention"] + n["shared"] + n["router"]
                  + 16 * n["expert"]) / 1e6, 1) == 1000.7
    assert round(n["embed"] / 1e6, 1) == round(n["head"] / 1e6, 1) == 147.5
    assert round(2 * n["held_total"] / 1e9, 2) == 9.84
    assert z["kv_rank"] + z["rope"] == 576 and z["experts"] == 256
    assert z["held"] == tuple(range(16))
    m = pangu_model.matmul_params(cfg)
    assert pangu_model.attention_flops_per_pair(cfg) == 2 * 128 * 320
    # a token at context 0 with the expected half a row on held experts
    want = 5 * 2 * m["attention"] + 2 * m["dense_mlp"] + 4 * 2 * (
        m["expert_fixed"] + 0.5 * m["expert"]) + 2 * m["head"]
    assert pangu_model.forward_flops_per_token(cfg, 0) == want


# ----------------------------------------------- the cell, at a tiny size --
@pytest.fixture(scope="module")
def served():
    """One sound tiny run of the cell; the faults re-read its sample."""
    ctx = tiny.context(CELL, docs_mix(), cfg=CFG, seed=SEED, seconds=2.5)
    return harness.run_cell(ctx)


def test_the_tiny_cell_reads_nought_against_its_own_reference(served):
    line = tiny.result(CELL, served, TINY_LIMIT)
    assert line["correct"] is True, (line["compared"], served.notes)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = tiny.result(CELL, served, TINY_LIMIT, traced=True)["metrics"]
    assert traced["cached_prompt_pct"]["value"] > 20
    assert traced["engine_step_ms.pangu-docs"]["value"] > 0
    assert 0 < traced["latent_pool_used_pct.pangu-docs"]["value"] <= 100
    # 2 of 8 experts a token, 4 held: half a row a token on held experts,
    # spread over 4 experts
    assert traced["moe_rows_per_expert.pangu-docs"]["value"] > 0
    assert traced["moe_load_max_over_mean.pangu-docs"]["value"] >= 1
    # no trace on a CPU: the trace's readers return nothing, never 0
    for name in ("mla_rpa_roofline", "serve_mfu_pct", "device_idle_pct",
                 "step_host_ms"):
        assert name + ".pangu-docs" not in traced


def _softmax_scores(t, z):
    import jax
    import jax.numpy as jnp
    s = jax.nn.softmax(t, -1)
    top_s, top_i = jax.lax.top_k(s, z["top_k"])
    w = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20) * z["scaling"]
    return jnp.zeros_like(s).at[jnp.arange(t.shape[0])[:, None], top_i].set(w)


_SOUND_ROUTER = R.router_weights


def _not_normalised(t, z):
    return _SOUND_ROUTER(t, dict(z, norm_topk=False))


def _no_scaling(t, z):
    return _SOUND_ROUTER(t, dict(z, scaling=1.0))


@pytest.mark.parametrize("fault", [_softmax_scores, _not_normalised,
                                   _no_scaling],
                         ids=["softmax_scores", "weights_not_normalised",
                              "scaling_factor_left_out"])
def test_a_planted_router_fault_fails_the_limits(served, fault, monkeypatch):
    """The served tokens of the sound run, read against a reference whose
    router is at fault: the same distance the program would read against
    the sound reference had the fault been its own."""
    from benchmark.kinds import open_loop
    R._serve_logits_fn.cache_clear()
    with monkeypatch.context() as m:
        m.setattr(R, "router_weights", fault)
        run = served.run
        sample = open_loop.check_sample(run["records"], SEED,
                                        int(run["mix"]["check_requests"]))
        gaps = open_loop.served_gaps(sample, SEED, run["cfg"],
                                     run["mix"], "exact", R.serve_logits)
    R._serve_logits_fn.cache_clear()
    numbers = {"served_gap_max": float(gaps["served"].max()),
               "served_gap_mean": float(gaps["served"].mean())}
    ok, compared = harness.judge(numbers, TINY_LIMIT)
    assert ok is False, compared
    assert numbers["served_gap_max"] > 10 * TINY_LIMIT["served_gap_max"]
    assert numbers["served_gap_mean"] > TINY_LIMIT["served_gap_mean"]


def test_the_int8_control_fails_the_limits(served):
    from benchmark.kinds import open_loop
    run = served.run
    sample = open_loop.check_sample(run["records"], SEED,
                                    int(run["mix"]["check_requests"]))
    gaps = open_loop.served_gaps(sample, SEED, run["cfg"], run["mix"],
                                 "int8", R.serve_logits)
    assert float(gaps["control"].max()) > TINY_LIMIT["served_gap_max"]
    assert float(gaps["served"].max()) <= TINY_LIMIT["served_gap_max"]


# ------------------------------------------------- undecided positions --
def test_held_margin_by_hand():
    """Five experts, two a token, experts 1 and 3 held. Token 0 chooses
    experts 0 and 1 (logits 3.0, 2.0; the best left out is 1.5): held 1 is
    chosen 0.5 above the cut, held 3 (0.25) lies 1.75 below the last one
    chosen. Token 1 chooses experts 4 and 2: held 3 (0.9) lies 0.1 below
    expert 2 (1.0), held 1 lies 2.0 below."""
    import numpy as np
    t = np.array([[3.0, 2.0, 1.5, 0.25, -1.0],
                  [0.0, -1.0, 1.0, 0.9, 2.0]], np.float32)
    margin, chosen = R.held_margin(t, {"top_k": 2, "held": (1, 3)})
    np.testing.assert_allclose(np.asarray(margin), [0.5, 0.1], atol=1e-6)
    assert np.asarray(chosen).tolist() == [[True, False], [False, False]]


def test_an_undecided_position_is_answered_with_equal_logits(capsys):
    """With ``reference.undecided_margin`` set, exactly the positions whose
    least margin over the expert layers lies under it come back as rows of
    equal logits (any served token reads a gap of nought there), the others
    untouched; the int8 control is never masked; without the key, nothing
    is."""
    import numpy as np
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, CFG["vocab_size"], (2, 64))
    rows, cols = [0] * 24 + [1] * 24, list(range(40, 64)) * 2
    plain, margin, chosen = (np.asarray(a) for a in R.forward_at(
        SEED, CFG, tokens, rows, cols))
    assert margin.shape == (2, 48) and chosen.shape == (2, 48, 4)
    least = margin.min(0)
    eps = float(np.sort(least)[12])              # a dozen lie under it
    cfg = dict(CFG, reference={"undecided_margin": eps})
    got = np.asarray(R.serve_logits(SEED, cfg, tokens, rows, cols))
    assert "12 of 48 positions undecided" in capsys.readouterr().out
    under = least < eps
    assert under.sum() == 12
    assert (got[under] == 0).all()
    np.testing.assert_array_equal(got[~under], plain[~under])
    np.testing.assert_array_equal(
        np.asarray(R.serve_logits(SEED, CFG, tokens, rows, cols)), plain)
    low = np.asarray(R.serve_logits(SEED, cfg, tokens, rows, cols, "int8"))
    assert (low.max(-1) > low.min(-1)).all()


def test_the_int8_control_rounds_the_router_too():
    """Every product of the model is one precision down in the control:
    the router's logits differ from the exact ones as well."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference.mistral import linear
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((9, 64)), jnp.float32)
    w = {"router": jnp.asarray(rng.standard_normal((64, 8)) * 0.15,
                               jnp.float32)}
    z = dict(R.W.sizes(CFG), shared=0)
    none = lambda e: {n: jnp.zeros((64, 32) if n != "e_down" else (32, 64))
                      for n in ("e_gate", "e_up", "e_down")}
    exact = R.expert_layer(h, w, z, linear("exact"), none)[1][0]
    low = R.expert_layer(h, w, z, linear("int8"), none)[1][0]
    assert not np.allclose(np.asarray(exact), np.asarray(low), atol=1e-5)
