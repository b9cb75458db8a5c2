"""``models/exaone_moe.py`` against the plain reference
(``benchmark/reference/exaone_moe.py``) over the same seeded float32
weights: the full forward, the drafter's logits, the served logits through
chunked prefill and then decode under either reader, the drafted engine's
tokens against the reference's greedy decoding and its count of accepted
drafts against the reference's own; the eight shares of an expert layer add
up to the uncut layer; and planted faults each fail."""
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import sut_exaone_moe as sut
from benchmark import weights_exaone_moe as W
from benchmark.reference import exaone_moe as R
from exaone_tiny import CFG
from paddle_tpu.serving import ServingEngine
from serving_probe import keep_logits

SEED = 7
ATOL = 5e-5          # float32 on both sides, logits of magnitude 1-3


@pytest.fixture(scope="module", autouse=True)
def own_expert_rows():
    """``serving_moe_expert_rows_total`` is one family a process, and a test
    worker runs several files in one: this file's engines must neither read
    another model's rows nor leave theirs behind (see test_smallthinker.py)."""
    from paddle_tpu.serving.engine import serving_metrics
    family = serving_metrics()["moe_rows"]
    family.clear()
    yield
    family.clear()


@pytest.fixture(scope="module")
def model():
    m = sut.build_model(CFG, SEED, "float32")
    m.eval()
    return m


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(1, 128, (2, 96))


def _everywhere(tokens, upto=None):
    n = tokens.shape[1] if upto is None else upto
    return (np.repeat(np.arange(len(tokens)), n),
            np.tile(np.arange(n), len(tokens)))


def _program_logits(model, tokens):
    return np.asarray(model(pt.to_tensor(tokens.astype(np.int32))).data)


def _program_draft_logits(model, tokens):
    ids = pt.to_tensor(tokens.astype(np.int32))
    _, resid = model.model(ids, keep_residual=True)
    nxt = pt.to_tensor(np.roll(tokens, -1, axis=1).astype(np.int32))
    return np.asarray(model._logits(model.draft(resid, nxt)).data)


def test_the_full_forward_agrees_with_the_reference(model, tokens):
    rows, cols = _everywhere(tokens)
    want, margin, chosen = R.forward_at(SEED, CFG, tokens, rows, cols,
                                        weight_dtype="float32")
    got = _program_logits(model, tokens).reshape(len(rows), -1)
    np.testing.assert_allclose(got, np.asarray(want), atol=ATOL)
    assert margin.shape == (4, len(rows)) and float(margin.min()) >= 0
    assert chosen.shape == (4, len(rows), 4)


def test_the_drafter_s_logits_agree_with_the_reference(model, tokens):
    rows, cols = _everywhere(tokens, upto=tokens.shape[1] - 1)
    want, margin, _ = R.forward_at(SEED, CFG, tokens, rows, cols,
                                   weight_dtype="float32", drafter=True)
    got = _program_draft_logits(model, tokens)[:, :-1].reshape(len(rows), -1)
    np.testing.assert_allclose(got, np.asarray(want), atol=ATOL)
    assert margin.shape == (1, len(rows))
    # it is another function than the model's own next-token logits
    own = _program_logits(model, tokens)[:, :-1].reshape(len(rows), -1)
    assert np.abs(own - got).max() > 0.1


def _engine(model, drafts, **kw):
    eng = dict(CFG["engine"], draft_tokens=drafts, prefix_cache=False)
    eng.update(kw)
    return ServingEngine(model, **eng)


@pytest.mark.parametrize("impl", ["gather", "rpa"])
def test_served_logits_agree_through_prefill_then_decode(model, impl):
    """Prompts longer than a chunk and than three windows, then 12 decode
    steps: every sampled token's logits against the reference's at that
    position of the served sequence."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 128, n).tolist() for n in (7, 41, 70)]
    engine = _engine(model, 0, attn_impl=impl)
    kept = keep_logits(engine)
    handles = [engine.submit(p, max_new_tokens=12) for p in prompts]
    engine.run_until_idle()
    engine.cache.assert_no_leaks()
    assert engine.step_traces == 1
    for p, h in zip(prompts, handles):
        full = np.zeros((1, 96), np.int64)
        seq = p + h.token_ids
        full[0, :len(seq)] = seq
        cols = list(range(len(p) - 1, len(seq) - 1))
        want, _, _ = R.forward_at(SEED, CFG, full, [0] * len(cols), cols,
                                  weight_dtype="float32")
        np.testing.assert_allclose(np.stack(kept[h.req_id]),
                                   np.asarray(want), atol=ATOL)


@pytest.mark.parametrize("impl", ["gather", "rpa"])
def test_drafted_serving_is_the_reference_s_greedy_decoding(model, impl):
    """With ``draft_tokens=1`` the served tokens are the reference's greedy
    tokens, and the engine verified and accepted as many drafts as the
    reference's own drafter would have had accepted on those sequences."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 128, n).tolist() for n in (5, 23, 40)]
    engine = _engine(model, 1, attn_impl=impl)
    handles = [engine.submit(p, max_new_tokens=14) for p in prompts]
    engine.run_until_idle()
    engine.cache.assert_no_leaks()
    assert engine.step_traces == 1
    drafted = accepted = 0
    for p, h in zip(prompts, handles):
        assert h.token_ids == R.greedy(SEED, CFG, p, 14, pad_to=64,
                                       weight_dtype="float32")
        seq = np.zeros((1, 64), np.int64)
        seq[0, :len(p) + 14] = p + h.token_ids
        cols = list(range(len(p) + 13))
        guess, _, _ = R.forward_at(SEED, CFG, seq, [0] * len(cols), cols,
                                   weight_dtype="float32", drafter=True)
        d, a = R.accepted_drafts(seq[0, :len(p) + 14], len(p),
                                 np.asarray(guess).argmax(-1))
        drafted, accepted = drafted + d, accepted + a
    got = engine.stats()["drafts"]
    assert (got["drafted"], got["accepted"]) == (drafted, accepted)
    assert drafted == 3 * 12      # 13 steps a sequence, the last undrafted
    assert got["emitted"] == 3 * 13 and got["decode_seqs"] == 39 - accepted


def test_accepted_drafts_by_hand():
    """Prompt of 2, tokens 5 6 | 7 8 9 10 11. The step at 7 (n=2) verifies
    guesses[1] against 8: right, so 8 and 9 are out and the step at 9
    verifies guesses[3] against 10: wrong; the step at 10 has one token to
    come and drafts nothing."""
    tokens = [5, 6, 7, 8, 9, 10, 11]
    assert R.accepted_drafts(tokens, 2, [0, 8, 0, 99, 0, 0]) == (2, 1)
    # every guess right: two steps of two tokens and nothing left to draft
    assert R.accepted_drafts(tokens, 2, [7, 8, 9, 10, 11, 0]) == (2, 2)
    # every guess wrong: four steps with two tokens to come, none accepted
    assert R.accepted_drafts(tokens, 2, [0] * 6) == (3, 0)
    assert R.accepted_drafts(tokens[:3], 2, [0, 0]) == (0, 0)


# ----------------------------------------------- the shares add up --
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Reference: the routed parts of the eight shares of an expert layer
    (one expert each here) and the shared expert counted once are the
    uncut layer. Program: ``HeldExpertsLayer`` told each share in turn."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.fleet import HeldExpertsLayer
    whole = dict(CFG, num_experts=8, published={"num_experts": 8},
                 held_experts=list(range(8)))
    z, key = W.sizes(whole), W.seed_key(SEED)
    w = {k: v.astype(jnp.float32) for k, v in W.layer_leaves(
        key, 2, whole, "float32", False, experts=False).items()}
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64), jnp.float32)
    get = R._expert_weights(key, 2, whole, "float32")
    mm = R.linear("exact")
    uncut, _ = R.expert_layer(h, w, z, mm, get)
    parts = sum(R.expert_layer(h, w, z, mm, get, held=[e], shared=False)[0]
                for e in range(8))
    shared, _ = R.expert_layer(h, w, z, mm, get, held=[0], shared=True)
    shared = shared - R.expert_layer(h, w, z, mm, get, held=[0],
                                     shared=False)[0]
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(uncut),
                               atol=1e-5)
    # the program's layer, a share at a time, against the reference's part
    stacks = W.layer_leaves(key, 2, whole, "float32", False)
    for share in ([0, 1], [6, 7], [3]):
        layer = HeldExpertsLayer(64, 32, 8, 2, held=share,
                                 routed_scaling_factor=2.5,
                                 selection_bias=True)
        layer.router.set_value(stacks["router"])
        layer.router_bias.set_value(stacks["router_bias"])
        for name, leaf in (("w_gate", "e_gate"), ("w_up", "e_up"),
                           ("w_down", "e_down")):
            getattr(layer, name).set_value(stacks[leaf][np.asarray(share)])
        want, _ = R.expert_layer(h, w, z, mm, get, held=share, shared=False)
        np.testing.assert_allclose(np.asarray(layer(pt.to_tensor(h)).data),
                                   np.asarray(want), atol=1e-5)


def test_rows_the_grouped_product_leaves_undefined_are_never_read(
        monkeypatch):
    """The grouped products (``ops/pallas/moe_gmm.py``) define only the
    rows of their groups. On a TPU the rest is whatever the buffer held
    (found on a TPU v5e: NaN at some step widths, and a weight of
    nought times NaN is NaN): the rows past the last group, an absent
    expert's, and the padding between groups that aligns each to a row
    tile. Planted here as NaN, the layer's output must not change."""
    import jax.numpy as jnp
    from paddle_tpu.distributed.fleet import HeldExpertsLayer
    from paddle_tpu.ops.pallas import moe_gmm
    pt.seed(3)
    layer = HeldExpertsLayer(32, 16, 8, 2, held=[1, 4, 6])
    x = pt.to_tensor(np.random.default_rng(0).normal(
        size=(2, 9, 32)).astype(np.float32))
    mask = pt.to_tensor(np.arange(18).reshape(2, 9) < 15)
    want = np.asarray(layer(x, token_mask=mask).data)
    real = {name: getattr(moe_gmm, name)
            for name in ("layout", "gate_up", "down")}
    groups = {}

    def layout(slot, n, tm):
        sizes, starts, tiles, dest = real["layout"](slot, n, tm)
        groups.update(sizes=sizes, starts=starts)
        return sizes, starts, tiles, dest

    def poisoned(name):
        def product(*args, **kw):
            out = real[name](*args, **kw)
            row = jnp.arange(out.shape[0])[:, None]
            live = ((row >= groups["starts"])
                    & (row < groups["starts"] + groups["sizes"])).any(-1)
            assert not live.all()          # padding between groups, at least
            return jnp.where(live[:, None], out, jnp.nan)
        return product
    monkeypatch.setattr(moe_gmm, "layout", layout)
    monkeypatch.setattr(moe_gmm, "gate_up", poisoned("gate_up"))
    monkeypatch.setattr(moe_gmm, "down", poisoned("down"))
    got = np.asarray(layer(x, token_mask=mask).data)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


def test_the_selection_bias_moves_the_choice_and_not_the_weight():
    """Three experts, one a token: scores sigmoid(0) = 0.5 and
    sigmoid(-1) = 0.27; a bias of 0.5 on the second makes it the choice,
    and its weight is still its score (normalised: the scaling factor)."""
    t = np.array([[0.0, -1.0, -3.0]], np.float32)
    z = {"top_k": 1, "scaling": 2.5, "norm_topk": False}
    plain = np.asarray(R.router_weights(t, np.zeros(3, np.float32), z))
    biased = np.asarray(R.router_weights(
        t, np.array([0, 0.5, 0], np.float32), z))
    assert plain[0].argmax() == 0 and biased[0].argmax() == 1
    np.testing.assert_allclose(biased[0, 1], 2.5 / (1 + np.e), rtol=1e-6)


# ----------------------------------------------------- planted faults --
def _rotate_everywhere(u, w, z, mm, pos, window, block=256):
    # a full layer given the window layers' rotation (its mask kept)
    import jax.numpy as jnp
    wide = jnp.where(window > 0, window, 1 << 20)
    return _REAL_ATTENTION(u, w, z, mm, pos, wide, block)


_REAL_ATTENTION = R.attention


def _window_off_by_one(u, w, z, mm, pos, window, block=256):
    import jax.numpy as jnp
    return _REAL_ATTENTION(u, w, z, mm, pos,
                           jnp.where(window > 0, window + 1, 0), block)


@pytest.mark.parametrize("fault", ["rope_in_full_layers",
                                   "window_off_by_one",
                                   "drafter_fed_this_token"])
def test_a_planted_fault_fails(model, tokens, monkeypatch, fault):
    """The sound program against a reference with the fault: the distance
    the program would read against the sound reference had the fault been
    its own. Each is far over the tolerance of the sound comparison."""
    rows, cols = _everywhere(tokens, upto=tokens.shape[1] - 1)
    drafter = fault == "drafter_fed_this_token"
    R._forward_fn.cache_clear()
    with monkeypatch.context() as m:
        if fault == "rope_in_full_layers":
            m.setattr(R, "attention", _rotate_everywhere)
        elif fault == "window_off_by_one":
            m.setattr(R, "attention", _window_off_by_one)
        else:
            m.setattr(R, "next_tokens", lambda t: t)
        want, _, _ = R.forward_at(SEED, CFG, tokens, rows, cols,
                                  weight_dtype="float32", drafter=drafter)
    R._forward_fn.cache_clear()
    got = (_program_draft_logits if drafter else _program_logits)(
        model, tokens)[:, :-1].reshape(len(rows), -1)
    assert np.abs(got - np.asarray(want)).max() > 100 * ATOL
