"""Phi-4-mini-flash (``models/phi4flash.py``) through the serving engine
against the plain reference (``benchmark/reference/phi4flash.py``), at a
tiny size on the CPU with seeded weights: eight layers of every kind of the
published map (``tests/phi4flash_tiny.py``: Mamba, window, Mamba, window,
Mamba with the memory, full, GMU, cross). Prefill over several chunks then
decode, decode rows beside another sequence's chunks, the selective scan in
interpret mode, the cross-decoder on the sampling rows against every row
through it, the window's edge, the pair-head pages against the published
differential form, what is refused, and planted faults that must fail.

**Tolerance.** Both sides are float32 over the same weights (drawn at
``initializer_range`` 0.2, so that a mixer's share of the residual stream
shows in the logits), the state rows held in float32: 2e-5 absolute on
logits of a few units (seen: 9e-6; the sums run in another order). The
planted faults move a logit by 1e-2 and more."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import sut_phi4flash as sut
from benchmark import weights_phi4flash as W
from benchmark.reference import phi4flash as ref
from phi4flash_tiny import CFG
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import serving_metrics
from serving_probe import keep_logits

TOL = 2e-5
FAULT = 1e-2
SEED = 7
#: the reference's one call shape: prompts and answers of every test fit
ROWS, COLS, POSITIONS = 5, 128, 128
ENGINE = dict(CFG["engine"])
VOCAB = CFG["vocab_size"]


@pytest.fixture(scope="module")
def model():
    m = sut.build_model(CFG, SEED, "float32")
    m.eval()
    return m


def engine(model, **kw):
    return ServingEngine(model, **{**ENGINE, **kw})


def serve(eng, asks):
    """Run ``asks`` ``[(prompt, max_new)]`` to the end; returns the handles
    and, a request, the logits row behind each of its sampled tokens."""
    rows = keep_logits(eng)
    handles = [eng.submit(p, max_new_tokens=n) for p, n in asks]
    eng.run_until_idle()
    return handles, [np.stack(rows[h.req_id]) for h in handles]


def gaps(handles, rows, asks, cfg=CFG, cols=COLS):
    """The largest distance of each request's served logits from the
    reference's full forward of its prompt and tokens (one call, of one
    shape for every test: ``ROWS`` rows of ``cols``)."""
    outs = [h.result()["token_ids"] for h in handles]
    tokens = np.zeros((ROWS, cols), np.int32)
    r, c = [], []
    for i, ((p, _), o) in enumerate(zip(asks, outs)):
        full = list(p) + o[:-1]
        tokens[i, :len(full)] = full
        r += [i] * len(o)
        c += [len(p) - 1 + j for j in range(len(o))]
    n = len(c)
    r, c = r + [0] * (POSITIONS - n), c + [0] * (POSITIONS - n)
    want = np.asarray(ref.serve_logits(SEED, cfg, tokens, r, c))[:n]
    got = np.concatenate(rows)
    out, at = [], 0
    for o in outs:
        out.append(float(np.abs(got[at:at + len(o)]
                                - want[at:at + len(o)]).max()))
        at += len(o)
    return out


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).tolist() for n in lengths]


def test_chunks_then_decode_beside_other_chunks_agree_with_the_reference(
        model):
    """A prompt of 50 (four chunks of 16) decodes while a later prompt of
    37 prefills in its chunks: decode rows of one slot and another's chunk
    share steps; the cross layers keep no pages and the GMUs nothing."""
    asks = list(zip(prompts(50, 9, 37), (12, 20, 8)))
    eng = engine(model)
    handles, rows = serve(eng, asks)
    assert max(gaps(handles, rows, asks)) < TOL
    assert eng.step_traces == 1
    st = eng.stats()
    assert sorted(st["kv_groups"]) == ["full", "window"]
    assert st["kv_groups"]["window"]["layers"] == 2
    assert st["kv_groups"]["full"]["layers"] == 1
    assert st["state_group"]["layers"] == 3
    assert st["state_group"]["ssm"] == (64, 1, 16)
    assert eng.read_layers == [7] and eng.cross_from == 6
    for i in (6, 7):                    # GMU and cross: no pool of their own
        assert eng.cache.k_pools[i] is None and eng.cache.v_pools[i] is None
    pools = [p for p in eng.cache.k_pools + eng.cache.v_pools
             if p is not None]
    assert len({id(p) for p in pools}) == len(pools) == 12
    eng.cache.assert_no_leaks()


def test_the_scan_kernel_serves_the_reference_s_logits(model, monkeypatch):
    """Served with the selective-scan kernel itself (in interpret mode):
    four short prompts that decode for most of their steps and a prompt
    over several chunks; each Mamba layer's state pool is ``[max_batch +
    1, N, D]``."""
    from paddle_tpu.ops.pallas import selective_scan
    monkeypatch.setattr(selective_scan, "_mode", lambda: "interpret")
    eng = engine(model)
    for i in eng.cache.state_group.layers:
        assert eng.cache.k_pools[i].shape == (ENGINE["max_batch"] + 1, 16,
                                              64)
    asks = list(zip(prompts(6, 41, 5, 12, seed=4), (14, 10, 18, 16)))
    handles, rows = serve(eng, asks)
    assert max(gaps(handles, rows, asks)) < TOL
    eng.cache.assert_no_leaks()


def test_the_cross_decoder_on_the_sampling_rows_is_every_row_through_it(
        model, monkeypatch):
    """The same prompts served by the step that runs layers 6-7 on each
    entry's sampling row and by one that runs every packed row through
    them (the model stating no cross-decoder): equal logits; the rows
    counter's ``cross`` part is the sampled tokens in the first, every
    row in the second."""
    asks = list(zip(prompts(45, 7, 30, seed=6), (9, 15, 6)))
    counter = serving_metrics()["rows"]

    def run(skip):
        if not skip:
            monkeypatch.setattr(model, "cross_decoder_start", lambda: None)
        before = {p: counter.value(part=p) for p in ("self", "cross")}
        eng = engine(model)
        leaf, spans = eng._leaf, []

        def record(name, step, **args):
            if name == "serving.dispatch":
                spans.append(args)
            return leaf(name, step, **args)
        eng._leaf = record
        handles, rows = serve(eng, asks)
        monkeypatch.undo()
        return eng, rows, {p: counter.value(part=p) - before[p]
                           for p in before}, spans
    eng, skip_rows, skip_n, spans = run(True)
    _, all_rows, all_n, all_spans = run(False)
    for a, b in zip(skip_rows, all_rows):
        np.testing.assert_allclose(a, b, atol=TOL)
    sampled = sum(n for _, n in asks)
    prompt = sum(len(p) for p, _ in asks)
    assert skip_n == {"self": prompt + sampled - len(asks), "cross": sampled}
    assert all_n == {"self": skip_n["self"], "cross": skip_n["self"]}
    assert eng.cross_tokens == 8    # 4 slots, the q tile of 8
    # the dispatch spans: a cross row a sampled token, which reads the keys
    # up to its own position
    assert sum(a["cross_rows"] for a in spans) == sampled
    assert sum(a["cross_keys"] for a in spans) == sum(
        len(p) + j for p, n in asks for j in range(n))
    assert not any("cross_rows" in a for a in all_spans)


def test_the_window_s_edge(monkeypatch):
    """A window of 32 over prompts of 31, 32 and 40, each decoding past
    position 32: a query sees the key 31 positions back and not the one 32
    back. The served logits are the reference's under the same window, and
    not under one a key shorter or a key longer."""
    cfg = dict(CFG, sliding_window=32)
    m = sut.build_model(cfg, SEED, "float32")
    asks = list(zip(prompts(31, 32, 40, seed=8), (4, 4, 4)))
    handles, rows = serve(engine(m), asks)
    assert max(gaps(handles, rows, asks, cfg)) < TOL
    for off_by_one in (31, 33):
        assert min(gaps(handles, rows, asks,
                        dict(cfg, sliding_window=off_by_one))) > FAULT


def test_the_pair_head_pages_are_the_published_differential_form():
    """Queries ``[q1 | 0]`` and ``[0 | q2]`` of width 2 hd over pages of
    pair heads ``[k1 | k2]``, ``[v1 | v2]`` (the engine's read) give the
    published form's ``(A_1 - lam A_2) [v1 | v2]`` and its subln."""
    from paddle_tpu.models.phi4flash import lambda_init, subln
    rng = np.random.default_rng(0)
    L, H, G, hd, depth = 24, 4, 2, 8, 3
    q = rng.normal(size=(L, H, 2, hd)).astype(np.float32)
    k = rng.normal(size=(L, G, 2, hd)).astype(np.float32)
    v = rng.normal(size=(L, G, 2, hd)).astype(np.float32)
    w = {n: jnp.asarray(0.3 * rng.normal(size=hd), jnp.float32)
         for n in ("lq1", "lk1", "lq2", "lk2")}
    w["subln"] = jnp.asarray(1 + 0.1 * rng.normal(size=2 * hd), jnp.float32)
    z = {"eps": 1e-5}
    want = ref.diff_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              w, z, depth, block=L)
    zero = np.zeros_like(q[:, :, 0])
    wide = np.stack([np.concatenate([q[:, :, 0], zero], -1),
                     np.concatenate([zero, q[:, :, 1]], -1)], 2)
    wide = wide.reshape(L, 2 * H, 2 * hd)           # 2H heads over G pairs
    kp, vp = k.reshape(L, G, 2 * hd), v.reshape(L, G, 2 * hd)
    s = np.einsum("tgrh,lgh->tgrl", wide.reshape(L, G, 2 * H // G, 2 * hd),
                  kp) / np.sqrt(hd)
    s = np.where(np.tril(np.ones((L, L), bool))[:, None, None], s, -np.inf)
    out = np.einsum("tgrl,lgh->tgrh", jax.nn.softmax(s, -1), vp)
    out = out.reshape(L, H, 2, 2 * hd)
    lam = np.exp(np.sum(w["lq1"] * w["lk1"])) \
        - np.exp(np.sum(w["lq2"] * w["lk2"])) + lambda_init(depth)
    got = subln(jnp.asarray(out[:, :, 0] - lam * out[:, :, 1]), w["subln"],
                1e-5) * (1 - lambda_init(depth))
    np.testing.assert_allclose(np.asarray(got).reshape(L, -1),
                               np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("kw, what", [
    ({"draft_tokens": 1}, "draft_tokens"),
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"mesh": object()}, "mesh="),
])
def test_what_state_and_shared_pages_refuse(model, kw, what):
    with pytest.raises(NotImplementedError) as e:
        engine(model, **kw)
    assert "state layers [0, 2, 4]" in str(e.value) and what in str(e.value)


def test_lora_slots_and_block_transfer_are_refused(model, monkeypatch):
    eng = engine(model)
    for call in (lambda: eng.export_kv_blocks([b"x" * 16]),
                 lambda: eng.import_kv_blocks([]),
                 lambda: eng.cache.copy_block(1, 2)):
        with pytest.raises(NotImplementedError):
            call()
    monkeypatch.setattr(model, "_lora_slots", 2, raising=False)
    with pytest.raises(NotImplementedError, match="LoRA slots"):
        engine(model)


def test_a_prompt_behind_a_registered_prefix_is_declined(model):
    first, tail = prompts(40, 9, seed=2)
    asks = [(first, 4), (first[:32] + tail, 6)]
    eng = engine(model)
    h0, r0 = serve(eng, asks[:1])
    h1, r1 = serve(eng, asks[1:])
    assert max(gaps(h0 + h1, r0 + r1, asks)) < TOL
    pc = eng.stats()["prefix_cache"]
    assert pc["hits"] == 0 and pc["declined"] == 1
    eng.cache.assert_no_leaks()


# ------------------------------------------------------------ faults --
FAULT_ASKS = list(zip(prompts(40, 12, seed=3), (6, 8)))


def _memory_from_two_layers_early(model, monkeypatch):
    """The GMUs gate layer 2's Mamba output instead of layer 4's."""
    layers = model.model.layers
    kept = {}

    def wrap(layer, fn):
        monkeypatch.setattr(layer, "forward", fn, raising=False)
    early, memory = layers[2], layers[4]
    sound_early, sound_memory = early.forward, memory.forward

    def stash(x, cache, mem=None):
        out = sound_early(x, cache, mem)
        kept["m"] = out[2]
        return out

    def swap(x, cache, mem=None):
        out, c, _ = sound_memory(x, cache, mem)
        return out, c, kept["m"]
    wrap(early, stash)
    wrap(memory, swap)


def _lambda_init_a_layer_deeper(model, monkeypatch):
    from paddle_tpu.models import phi4flash
    for i, layer in enumerate(model.model.layers):
        if hasattr(layer.mixer, "lambda_init"):
            monkeypatch.setattr(layer.mixer, "lambda_init",
                                phi4flash.lambda_init(i + 1))


def _no_subln(model, monkeypatch):
    from paddle_tpu.models import phi4flash
    monkeypatch.setattr(phi4flash, "subln", lambda o, gain, eps: o * gain)


def _state_not_carried(model, monkeypatch):
    """Every step starts its sequences from zero state."""
    from paddle_tpu.ops.pallas import selective_scan
    sound = selective_scan.selective_scan_update

    def forgetful(state, u, dt, A, B, C, rows, starts, lens, fresh, n_live):
        return sound(state, u, dt, A, B, C, rows, starts, lens,
                     fresh * 0 + 1, n_live)
    monkeypatch.setattr(selective_scan, "selective_scan_update", forgetful)


@pytest.mark.parametrize("plant", [
    _memory_from_two_layers_early, _lambda_init_a_layer_deeper, _no_subln,
    _state_not_carried], ids=lambda f: f.__name__.strip("_"))
def test_a_planted_fault_in_the_program_fails_the_comparison(
        monkeypatch, plant):
    m = sut.build_model(CFG, SEED, "float32")
    plant(m, monkeypatch)
    handles, rows = serve(engine(m), FAULT_ASKS)
    assert max(gaps(handles, rows, FAULT_ASKS)) > FAULT


def test_cross_layers_reading_their_own_k_v_fail_the_comparison(
        model, monkeypatch):
    """The sound program against a reference whose cross layers read the
    K/V of their own input (through the full layer's projections) in
    place of the full layer's."""
    def own(u, kv, w_full, z, mm):
        L, G, hd = u.shape[0], z["kv"] // 2, z["hd"]
        qkv = mm(u, w_full["wqkv"]) + w_full["bqkv"]
        q = z["heads"] * hd
        return (qkv[:, q:q + G * 2 * hd].reshape(L, G, 2, hd),
                qkv[:, q + G * 2 * hd:].reshape(L, G, 2, hd))
    handles, rows = serve(engine(model), FAULT_ASKS)
    ref._forward_fn.cache_clear()
    monkeypatch.setattr(ref, "cross_kv", own)
    try:
        assert max(gaps(handles, rows, FAULT_ASKS)) > FAULT
    finally:
        ref._forward_fn.cache_clear()


def test_the_tiny_configuration_is_every_kind():
    assert W.sizes(CFG)["kinds"] == ("mamba", "window", "mamba", "window",
                                     "mamba", "full", "gmu", "cross")
