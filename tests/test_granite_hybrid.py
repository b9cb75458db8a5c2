"""Granite 4.0-H (``models/granite_hybrid.py``) through the serving engine
against the plain reference (``benchmark/reference/granite_hybrid.py``), at
a tiny size on the CPU with seeded weights: two Mamba-2 layers about one
attention layer (``tests/granite_tiny.py``). Prefill over several chunks
then decode, decode rows beside another sequence's chunks, a slot reused
after a finish and a preempted sequence served again (both start from zero
state), a prompt behind a registered prefix declined and recomputed, what
is refused over state layers, and a planted fault that must fail.

**Tolerance.** Both sides are float32 over the same weights (drawn at
``initializer_range`` 0.2, so that a mixer's share of the residual stream
shows in the logits), the state rows held in float32: 2e-5 absolute on
logits (seen: 1.2e-7). The planted fault moves a logit by 2.3e-2."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from benchmark import sut_granite_hybrid as sut
from benchmark.reference import granite_hybrid as ref
from granite_tiny import CFG
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import serving_metrics
from serving_probe import keep_logits

TOL = 2e-5
SEED = 5
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


def gaps(handles, rows, asks):
    """The largest distance of each request's served logits from the
    reference's full forward of its prompt and tokens (one call, of one
    shape for every test: ``ROWS`` rows of ``COLS``, one compile)."""
    outs = [h.result()["token_ids"] for h in handles]
    tokens = np.zeros((ROWS, COLS), np.int32)
    r, c = [], []
    for i, ((p, _), o) in enumerate(zip(asks, outs)):
        full = list(p) + o[:-1]
        tokens[i, :len(full)] = full
        r += [i] * len(o)
        c += [len(p) - 1 + j for j in range(len(o))]
    n = len(c)
    r, c = r + [0] * (POSITIONS - n), c + [0] * (POSITIONS - n)
    want = np.asarray(ref.serve_logits(SEED, CFG, tokens, r, c))[:n]
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
    share steps."""
    asks = list(zip(prompts(50, 9, 37), (12, 20, 8)))
    eng = engine(model)
    handles, rows = serve(eng, asks)
    assert max(gaps(handles, rows, asks)) < TOL
    assert eng.step_traces == 1
    st = eng.stats()
    assert st["state_group"]["layers"] == 2
    assert st["state_group"]["rows"] == ENGINE["max_batch"] + 1
    assert eng.cache.pool_bytes()["state"] > 0
    # a pool a layer: the step donates each, and one buffer may not be
    # donated twice
    pools = [p for p in eng.cache.k_pools + eng.cache.v_pools]
    assert len({id(p) for p in pools}) == len(pools)
    eng.cache.assert_no_leaks()


def test_state_pools_are_laid_out_as_the_kernel_reads_them(
        model, monkeypatch):
    """Each state layer's recurrent-state pool is ``[max_batch + 1, N,
    H*P]`` (the state width on sublanes, heads along lanes), the layout
    ``ops/pallas/ssd.py`` takes; served with the kernel itself (in
    interpret mode), four short prompts that decode for most of their
    steps still give the reference's logits."""
    from paddle_tpu.ops.pallas import ssd
    monkeypatch.setattr(ssd, "_mode", lambda: "interpret")
    H, P, N = (CFG["mamba_n_heads"], CFG["mamba_d_head"],
               CFG["mamba_d_state"])
    eng = engine(model)
    for i in eng.cache.state_group.layers:
        assert eng.cache.k_pools[i].shape == (ENGINE["max_batch"] + 1, N,
                                              H * P)
    asks = list(zip(prompts(6, 9, 5, 12, seed=4), (24, 20, 28, 16)))
    handles, rows = serve(eng, asks)
    assert max(gaps(handles, rows, asks)) < TOL
    eng.cache.assert_no_leaks()


def test_a_reused_slot_and_a_preempted_sequence_start_from_zero_state(
        model):
    """Two slots for five requests (each slot serves several sequences in
    turn) over a pool of 9 pages of 8 that three of them cannot hold at
    once: a preempted sequence prefills again from position 0, its state
    row rewritten from zero."""
    asks = list(zip(prompts(30, 21, 14, 26, 11, seed=1), (20, 14, 9, 16, 6)))
    eng = engine(model, max_batch=2, max_blocks=9)
    handles, rows = serve(eng, asks)
    assert max(gaps(handles, rows, asks)) < TOL
    assert eng.stats()["preemptions"] > 0
    eng.cache.assert_no_leaks()


def test_a_prompt_behind_a_registered_prefix_is_declined(model):
    """The second prompt shares the first's first 32 tokens (four full
    pages, registered once the first was served): no page is reused, the
    decline is counted, and its logits are still the reference's."""
    first, tail = prompts(40, 9, seed=2)
    asks = [(first, 4), (first[:32] + tail, 6)]
    declined = serving_metrics()["prefix_declined"]
    before = declined.value(reason="state")
    eng = engine(model)
    h0, r0 = serve(eng, asks[:1])
    h1, r1 = serve(eng, asks[1:])
    assert max(gaps(h0 + h1, r0 + r1, asks)) < TOL
    assert h1[0]._req.cached_prompt_tokens == 0
    pc = eng.stats()["prefix_cache"]
    assert pc["hits"] == 0 and pc["declined"] == 1
    assert pc["declined_tokens"] == 32
    assert declined.value(reason="state") - before == 1
    eng.cache.assert_no_leaks()


@pytest.mark.parametrize("kw, what", [
    ({"draft_tokens": 1}, "draft_tokens"),
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"mesh": object()}, "mesh="),
])
def test_what_state_layers_refuse(model, kw, what):
    with pytest.raises(NotImplementedError) as e:
        engine(model, **kw)
    assert "state layers [0, 2]" in str(e.value) and what in str(e.value)


def test_lora_slots_and_block_transfer_are_refused(model, monkeypatch):
    eng = engine(model)
    for call in (lambda: eng.export_kv_blocks([b"x" * 16]),
                 lambda: eng.import_kv_blocks([]),
                 lambda: eng.cache.copy_block(1, 2)):
        with pytest.raises(NotImplementedError, match=r"layers \[0, 2\]"):
            call()
    monkeypatch.setattr(model, "_lora_slots", 2, raising=False)
    with pytest.raises(NotImplementedError, match="LoRA slots"):
        engine(model)


def test_a_state_not_carried_between_steps_fails_the_comparison(
        model, monkeypatch):
    """The program with every step starting its sequences from zero state
    (the state rows written, never read) against the sound reference: a
    prompt over several chunks reads over 1e-2."""
    from paddle_tpu.ops.pallas import ssd
    sound = ssd.ssd_update

    def forgetful(state, xd, a, B, C, rows, starts, lens, fresh, n_live):
        return sound(state, xd, a, B, C, rows, starts, lens,
                     fresh * 0 + 1, n_live)
    monkeypatch.setattr(ssd, "ssd_update", forgetful)
    asks = list(zip(prompts(40, seed=3), (6,)))
    handles, rows = serve(engine(model), asks)
    assert max(gaps(handles, rows, asks)) > 1e-2
