"""SmallThinker (``models/smallthinker.py``) through the serving engine
against the plain reference (``benchmark/reference/smallthinker.py``), at a
tiny size on the CPU with seeded weights, the norm gains drawn too (ISSUE
31): chunked prefill and decode over a context three windows long, a
prefix-cache hit after window pages were released and evicted, the expert
layer's new options against the equations, the two cache groups'
allocator properties, and four planted faults that must fail.

**Tolerance.** Both sides are float32 over the same weights: 2e-5 absolute
on logits of magnitude 1-3 (seen: 1.4e-6; a float32 softmax over a hundred
keys and four layers of accumulation reorderings stay under 1e-5). Every
planted fault moves a logit by over 1e-2, five hundred times the
tolerance."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from benchmark import sut_smallthinker as sut
from benchmark.reference import smallthinker as ref
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import serving_metrics
from serving_probe import keep_logits

TOL = 2e-5
SEED = 3
#: window 32 in pages of 8 under a chunk of 16: pages are released, and a
#: chunk straddles the window's edge
CFG = dict(
    model="smallthinker", hidden_size=64, num_hidden_layers=4,
    num_attention_heads=7, num_key_value_heads=1, head_dim=16,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, vocab_size=128,
    sliding_window_size=32, sliding_window_layout=[0, 1, 1, 1] * 2,
    rope_layout=[0, 1, 1, 1] * 2, rope_theta=10000.0, rms_norm_eps=1e-6,
    max_position_embeddings=256, initializer_range=0.1, dtype="float32")
ENGINE = dict(max_batch=4, max_blocks={"full": 48, "window": 24},
              block_size=8, prefill_chunk=16)


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


def reference_logits(prompt, generated, cfg=CFG):
    """The reference's logits at the positions that produced ``generated``:
    one full forward of prompt + generated, no cache."""
    full = list(prompt) + list(generated[:-1])
    L = -(-len(full) // 32) * 32
    tokens = np.zeros((1, L), np.int32)
    tokens[0, :len(full)] = full
    cols = [len(prompt) - 1 + j for j in range(len(generated))]
    logits, _ = ref.forward_at(SEED, cfg, tokens, [0] * len(cols), cols,
                               weight_dtype="float32")
    return np.asarray(logits)


def gap(eng_rows, prompt, handle, cfg=CFG):
    return float(np.abs(eng_rows - reference_logits(
        prompt, handle.result()["token_ids"], cfg)).max())


# ------------------------------------ (a) chunked prefill, then decode --
@pytest.mark.parametrize("impl", ["gather", "rpa"])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(model, impl):
    rng = np.random.default_rng(0)
    long = rng.integers(1, 128, 100).tolist()    # over three windows
    short = rng.integers(1, 128, 21).tolist()
    released = serving_metrics()["kv_released"]
    before = released.value(group="window")
    eng = engine(model, attn_impl=impl)
    handles, rows = serve(eng, [(long, 20), (short, 12)])
    assert gap(rows[0], long, handles[0]) < TOL
    assert gap(rows[1], short, handles[1]) < TOL
    # 120 tokens are 15 pages; the window group gave back all but the last
    # 32 keys' as it went, the full group none
    assert released.value(group="window") - before >= 10
    assert released.value(group="full") == 0
    assert eng.step_traces == 1
    groups = eng.stats()["kv_groups"]
    assert list(groups) == ["full", "window"]
    assert groups["full"]["layers"] == 1 and groups["window"]["layers"] == 3
    eng.cache.assert_no_leaks()


# ------------------------------------- (b) a hit after released pages --
@pytest.mark.parametrize("impl", ["gather", "rpa"])
def test_a_prefix_hit_after_window_pages_were_released_and_evicted(
        model, impl):
    """The document's second ask matches all 12 of its blocks: the full
    group still holds every one, the window group only the last four (its
    pool of 10 pages evicted the early ones the first ask released), which
    is all the next token can see. The early window entries of the new
    sequence's table are null."""
    rng = np.random.default_rng(1)
    doc = rng.integers(1, 128, 96).tolist()
    first = doc + rng.integers(1, 128, 7).tolist()
    second = doc + rng.integers(1, 128, 9).tolist()
    eng = engine(model, attn_impl=impl,
                 max_blocks={"full": 48, "window": 10})
    (h1,), (rows1,) = serve(eng, [(first, 6)])
    assert gap(rows1, first, h1) < TOL
    win = eng.cache.groups[1]
    assert win.prefix_cache.evictions >= 3       # early pages are gone
    assert all(win.prefix_cache.lookup(d) is None
               for d in list(eng.cache.groups[0].prefix_cache._index)[:3])
    (h2,), (rows2,) = serve(eng, [(second, 8)])
    assert h2._req.cached_prompt_tokens == 96
    assert gap(rows2, second, h2) < TOL
    assert eng.cache.groups[0].prefix_cache.hits == 1
    eng.cache.assert_no_leaks()


def test_a_prefix_the_window_group_lost_is_matched_only_as_far_as_it_holds(
        model):
    """Evict the window group's copy of the document's LAST blocks: the
    match falls back to the longest prefix whose last window the group
    still holds (here none: the whole prompt prefills)."""
    rng = np.random.default_rng(2)
    doc = rng.integers(1, 128, 64).tolist()
    eng = engine(model, attn_impl="gather")
    serve(eng, [(doc + [5, 6, 7], 2)])
    win = eng.cache.groups[1]
    taken = win.allocator.allocate(win.allocator.capacity)   # evicts all
    win.allocator.free(taken)
    (h,), (rows,) = serve(eng, [(doc + [9, 9, 9, 9], 3)])
    assert h._req.cached_prompt_tokens == 0
    assert gap(rows, doc + [9, 9, 9, 9], h) < TOL


# --------------------------------------------- (f) allocator properties --
def test_a_sequence_never_holds_more_window_pages_than_the_bound(model):
    """``ceil((window + prefill_chunk) / block_size) + 1`` = 7 pages a
    sequence in the window group while a step runs, under preemption
    (the full pool is tight) and to the end; nothing leaks in either
    group."""
    rng = np.random.default_rng(4)
    eng = engine(model, attn_impl="gather",
                 max_blocks={"full": 30, "window": 16})
    bound = -(-(32 + 16) // 8) + 1
    assert eng.cache.groups[1].max_pages_held(16, 32) == bound == 7
    asks = [(rng.integers(1, 128, n).tolist(), 10) for n in (90, 70, 50, 30)]
    handles = [eng.submit(p, max_new_tokens=n) for p, n in asks]
    most, run = [0, 0], eng._run_unified

    def holdings(when):
        for seq in eng.scheduler.slotted():
            full, win = seq.tables
            assert len(full) == len(win)
            assert all(b != 0 for b in full)
            held = sum(1 for b in win if b != 0)
            # the released pages are a prefix of the table
            assert win == [0] * (len(win) - held) + win[len(win) - held:]
            most[when] = max(most[when], held)

    def checked(*planned):             # planned and allocated, not yet run
        holdings(0)
        return run(*planned)
    eng._run_unified = checked
    while eng.has_pending():
        eng.step()
        holdings(1)
    # inside a step the bound; between steps what the next token can see
    assert most[1] == -(-32 // 8) + 1
    most = most[0]
    assert most == bound
    assert eng.scheduler.num_preemptions > 0
    assert all(len(h.result()["token_ids"]) == 10 for h in handles)
    eng.cache.assert_no_leaks()
    assert all(g.allocator.blocks_in_use() == 0 for g in eng.cache.groups)


def test_headroom_is_the_tightest_groups_and_block_seconds_every_groups(
        model):
    """Two requests of 40 tokens fill a window pool of 10 pages while the
    full pool of 48 stays over three quarters free: admission stalls on
    the window pool, so that is the headroom the router and load shedding
    must read; the occupancy integral bills both groups' pages."""
    eng = engine(model, attn_impl="gather",
                 max_blocks={"full": 48, "window": 10}, prefix_cache=False)
    rng = np.random.default_rng(5)
    for _ in range(2):
        eng.submit(rng.integers(1, 128, 40).tolist(), max_new_tokens=2)
    full, win = eng.cache.groups
    least = 1.0
    while eng.has_pending():
        eng.step()
        st, shares = eng.stats(), [sum(g.fractions()) for g in (full, win)]
        assert st["kv_headroom"] == round(min(shares), 4)
        assert st["kv_headroom"] == round(
            st["kv_free_fraction"] + st["kv_reclaimable_fraction"], 4)
        assert serving_metrics()["kv_headroom"].value() == min(shares)
        assert st["kv_blocks_free"] == full.allocator.num_free()
        if shares[1] < least:
            least, full_then = shares[1], shares[0]
    assert least <= 0.2 and full_then >= 0.75
    eng.run_until_idle()
    eng.cache.assert_no_leaks()
    st = eng.stats()
    assert st["kv_headroom"] == 1.0
    both = sum(g.allocator.block_seconds_total() for g in eng.cache.groups)
    assert st["kv_block_seconds_total"] == round(both, 4)
    assert both > full.allocator.block_seconds_total() > 0


def test_a_multi_group_cache_refuses_what_addresses_one_block_id(model):
    eng = engine(model)
    with pytest.raises(NotImplementedError, match="layer groups"):
        eng.export_kv_blocks([b"x" * 16])
    with pytest.raises(NotImplementedError, match="layer groups"):
        eng.import_kv_blocks([])
    with pytest.raises(NotImplementedError, match="layer groups"):
        eng.cache.copy_block(1, 2)
    with pytest.raises(ValueError, match="int8 KV"):
        engine(model, kv_dtype="int8")
    with pytest.raises(ValueError, match="names groups"):
        engine(model, max_blocks={"full": 8})


def test_what_no_model_has_yet_is_refused_not_guessed():
    """Two groups of one kind, and latent pages under a window."""
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.serving import PagedKVCache
    kv = pa.LayerCacheSpec.kv
    with pytest.raises(NotImplementedError, match="named 'window'"):
        PagedKVCache(2, 4, 8, [kv(1, 16, window=32), kv(1, 16, window=64)])
    none = [None] * 8
    with pytest.raises(NotImplementedError, match="under a window"):
        pa.attend(pa.RaggedLayerCache(None, None, *none, window=32),
                  None, None, value_cols=16)


def test_a_one_group_cache_counts_as_it_did_on_a_recorded_schedule():
    """A Llama engine (one spec, one group) on a fixed schedule of shared
    prefixes, preemption and eviction: its counters equal those the parent
    commit (3ec5d86) gave for the same schedule, recorded there."""
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128))
    eng = ServingEngine(m, max_batch=3, max_blocks=14, block_size=4,
                        prefill_chunk=8, attn_impl="gather")
    rng = np.random.default_rng(7)
    pre = rng.integers(1, 96, 16).tolist()
    asks = [pre + rng.integers(1, 96, k).tolist() for k in (5, 9, 3, 12, 0)]
    for p in asks[:3]:
        eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    for p in asks[3:] + [asks[0]]:
        eng.submit(p, max_new_tokens=5)
    eng.run_until_idle()
    st = eng.stats()
    got = {k: st[k] for k in ("kv_blocks_in_use", "kv_blocks_free",
                              "kv_blocks_reclaimable", "preemptions")}
    got.update({k: st["prefix_cache"][k] for k in
                ("lookups", "hits", "evictions", "hit_tokens", "entries")})
    got["steps"] = eng._decode_steps
    assert "kv_groups" not in st
    assert got == RECORDED, got
    eng.cache.assert_no_leaks()


#: the parent's run of this schedule (its tree unpacked beside this one)
RECORDED = {"kv_blocks_in_use": 0, "kv_blocks_free": 2,
            "kv_blocks_reclaimable": 12, "preemptions": 4, "lookups": 10,
            "hits": 7, "evictions": 6, "hit_tokens": 127, "entries": 12,
            "steps": 18}


# --------------------------------------------------- (e) the expert layer --
def _experts_by_the_equations(x, r_in, router, wg, wu, wd, k, held):
    t = r_in @ router
    out = np.zeros_like(x)
    for i in range(x.shape[0]):
        top = np.argsort(-t[i], kind="stable")[:k]
        w = np.exp(t[i, top] - t[i, top].max())
        w /= w.sum()
        for e, we in zip(top, w):
            if e in held:
                j = held.index(e)
                out[i] += we * ((np.maximum(x[i] @ wg[j], 0)
                                 * (x[i] @ wu[j])) @ wd[j])
    return out


def test_softmax_relu_router_input_against_the_equations():
    import paddle_tpu as pt
    from paddle_tpu.distributed.fleet import HeldExpertsLayer
    pt.seed(5)
    rng = np.random.default_rng(5)
    d, f, E, k = 16, 8, 8, 3
    whole = HeldExpertsLayer(d, f, E, k, init_std=0.5, score="softmax",
                             activation="relu")
    x = rng.standard_normal((2, 5, d)).astype(np.float32)
    r = rng.standard_normal((2, 5, d)).astype(np.float32)
    leaves = [np.asarray(p.data) for p in (whole.router, whole.w_gate,
                                           whole.w_up, whole.w_down)]
    want = _experts_by_the_equations(x.reshape(10, d), r.reshape(10, d),
                                     *leaves, k, list(range(E)))
    got = np.asarray(whole(pt.to_tensor(x), router_input=pt.to_tensor(r)).data)
    np.testing.assert_allclose(got.reshape(10, d), want, atol=1e-5)
    assert int(np.asarray(whole.last_rows.data).sum()) == 10 * k
    # routed from its own input where none is given
    own = np.asarray(whole(pt.to_tensor(x)).data).reshape(10, d)
    np.testing.assert_allclose(own, _experts_by_the_equations(
        x.reshape(10, d), x.reshape(10, d), *leaves, k, list(range(E))),
        atol=1e-5)
    # the shares of a deployment add up to the whole (PR 27's test, with
    # the new options): each share normalises over all k chosen
    parts = np.zeros_like(got)
    for held in ((0, 1, 2), (3, 4), (5, 6, 7)):
        share = HeldExpertsLayer(d, f, E, k, held=held, score="softmax",
                                 activation="relu")
        share.router.set_value(whole.router.data)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share, name).set_value(
                getattr(whole, name).data[np.asarray(held)])
        parts += np.asarray(share(pt.to_tensor(x),
                                  router_input=pt.to_tensor(r)).data)
    np.testing.assert_allclose(parts, got, atol=1e-5)
    with pytest.raises(ValueError):
        HeldExpertsLayer(d, f, E, k, score="tanh")
    with pytest.raises(ValueError):
        HeldExpertsLayer(d, f, E, k, activation="gelu")


# ----------------------------------------------------- planted faults --
def _one_request(model):
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, 128, 80).tolist()
    kw = {} if len(set(model.kv_cache_spec())) > 1 else {"max_blocks": 48}
    (h,), (rows,) = serve(engine(model, attn_impl="gather", **kw),
                          [(prompt, 6)])
    return gap(rows, prompt, h)


def _no_window(m):
    bad = sut.build_model(dict(CFG, sliding_window_layout=[0] * 8), SEED,
                          "float32")
    bad.eval()
    return bad


def _rope_in_full_layers(m):
    bad = sut.build_model(dict(CFG, rope_layout=[1] * 8), SEED, "float32")
    bad.eval()
    return bad


def _sigmoid_scores(m):
    for layer in m.model.layers:
        layer.mlp.score = "sigmoid"
    return m


def _router_reads_the_normed_input(m):
    for layer in m.model.layers:
        def forward(x, token_mask=None, router_input=None, *, layer=layer,
                    sound=layer.mlp.forward):
            return sound(x, token_mask=token_mask,
                         router_input=layer.input_layernorm(router_input))
        layer.mlp.forward = forward
    return m


@pytest.mark.parametrize("fault", [
    _no_window, _rope_in_full_layers, _sigmoid_scores,
    _router_reads_the_normed_input], ids=lambda f: f.__name__.strip("_"))
def test_a_planted_fault_fails_the_comparison(fault):
    """The program with one fault, served the same way, against the sound
    reference: each reads over 1e-2 where the sound program reads under
    2e-5 (``test_prefill_in_chunks_...``)."""
    m = sut.build_model(CFG, SEED, "float32")
    m.eval()
    assert _one_request(fault(m)) > 1e-2
