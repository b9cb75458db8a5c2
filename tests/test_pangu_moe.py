"""The latent-attention, sandwich-norm, held-experts model
(``models/pangu_moe.py``) at a tiny size on the CPU: against the plain
float32 reference (``benchmark/reference/pangu_ultra_moe.py``, which
imports nothing of ``paddle_tpu``) on the same seeded weights, through the
serving engine's normal path (scheduler, paged latent pool, prefix cache,
the one compiled step), and the parts it brought: the ``rpa_mla`` kernel
form, the dropless held-experts layer with its share test, the cache spec.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark import sut_pangu
from benchmark import weights_pangu as W
from benchmark.reference import pangu_ultra_moe as R
from benchmark.reference.mistral import linear
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.fleet import HeldExpertsLayer
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    build_step_maps, ragged_paged_attention, rpa_max_items, rpa_run_pages)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import PagedKVCache
from serving_probe import keep_logits

SEED = 5
#: hidden 64, 4 heads of 16 + 8 (values 16), ranks 32 / 16, 8 experts 2 a
#: token, 1 shared, 1 dense + 2 expert layers; all 8 experts held
CFG = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2.5,
    norm_topk_prob=True, vocab_size=128, num_hidden_layers=3,
    first_k_dense_replace=1, rope_theta=25600000.0, rms_norm_eps=1e-5,
    initializer_range=0.02, max_position_embeddings=256, dtype="float32",
    engine=dict(max_batch=4, max_blocks=48, block_size=8, prefill_chunk=16,
                max_blocks_per_seq=24))


def share_cfg(held):
    """``CFG`` as the chip that holds the experts ``held`` of 8."""
    return dict(CFG, n_routed_experts=len(held), held_experts=list(held),
                published={"n_routed_experts": 8})


def ref_logits(cfg, row, cols):
    arr = np.zeros((1, 128), np.int32)
    arr[0, :len(row)] = row
    return np.asarray(R.serve_logits(SEED, cfg, arr, [0] * len(cols), cols))


@pytest.fixture(scope="module")
def model():
    m = sut_pangu.build_model(CFG, SEED, "float32")
    m.eval()
    return m


# ------------------------------------------------------------ the forward --
def test_full_forward_matches_the_reference(model):
    ids = np.random.default_rng(0).integers(1, 128, (2, 40))
    got = np.asarray(model(pt.to_tensor(ids)).data)
    for b in range(2):
        want = ref_logits(CFG, ids[b].tolist(), list(range(40)))
        np.testing.assert_allclose(got[b], want, atol=2e-5)


def _served_logits(engine, prompt, n):
    """Greedy tokens of one request and the logits row behind each."""
    kept = keep_logits(engine)
    h = engine.submit(prompt, max_new_tokens=n, temperature=0.0)
    engine.run_until_idle()
    return h, np.stack(kept[h.req_id])


@pytest.mark.parametrize("impl", ["gather", "rpa"])
def test_engine_prefill_prefix_hit_and_decode_match_the_reference(model, impl):
    """Chunked prefill (3 chunks), decode through the latent pool, and a
    second ask that finds the document's pages in the prefix cache: every
    served logits row equals the reference's full forward at that place."""
    eng = ServingEngine(model, attn_impl=impl, **CFG["engine"])
    rng = np.random.default_rng(1)
    doc = rng.integers(1, 128, 40).tolist()
    for ask, cached in ((0, 0), (1, 40)):
        prompt = doc + rng.integers(1, 128, 5).tolist()
        h, rows = _served_logits(eng, prompt, 6)
        assert h._req.cached_prompt_tokens == cached
        toks = h.token_ids
        cols = [len(prompt) - 1 + j for j in range(len(toks))]
        want = ref_logits(CFG, prompt + toks[:-1], cols)
        np.testing.assert_allclose(rows, want, atol=5e-5)
    st = eng.stats()
    assert st["prefix_cache"]["hit_tokens"] == 40
    assert st["step_compiles"] == eng.step_traces == 1


def test_one_compile_across_mixes_and_the_rows_reach_span_and_registry(model):
    """Chunks alone, decode rows alone and both in one step run the one
    executable; the step hands back the rows each held expert took."""
    from paddle_tpu.serving.engine import serving_metrics
    eng = ServingEngine(model, attn_impl="rpa", **CFG["engine"])
    fam = serving_metrics()["moe_rows"]
    before = fam.total()
    rng = np.random.default_rng(2)
    hs = [eng.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=m,
                     temperature=0.0)
          for n, m in ((37, 9), (5, 12), (20, 3))]
    eng.run_until_idle()
    late = eng.submit(rng.integers(1, 128, 50).tolist(), max_new_tokens=4,
                      temperature=0.0)
    eng.run_until_idle()
    assert all(h.result()["finish_reason"] for h in hs + [late])
    assert eng.step_traces == 1
    # 2 of 8 experts a token, all 8 held, 2 expert layers: 4 rows a token
    # processed (prompt tokens and decode steps; the last sampled token of
    # a request is never fed back)
    fed = sum(n + m - 1 for n, m in ((37, 9), (5, 12), (20, 3), (50, 4)))
    assert fam.total() - before == 4 * fed
    assert eng.cache.pool_bytes()["kv"] == 0 \
        and eng.cache.pool_bytes()["latent"] > 0


# ------------------------------------------------------------- the kernel --
def _latent_case(rng, seqs, block_size=8, heads=4, kd=24, vd=16, tile_q=8,
                 mbps=6, pool_blocks=24, pad_tiles=1):
    """A token-packed step over a latent pool: ``seqs`` rows of
    ``(new, context)``, the pool already holding every row (the step's
    own included), padding tokens and a padding tile at the end."""
    max_seqs = len(seqs) + 1
    total = sum(n for n, _ in seqs)
    T = (-(-total // tile_q) + pad_tiles) * tile_q
    bt = np.zeros((max_seqs + 1, mbps), np.int32)
    pool = np.zeros((pool_blocks + 1, 1, block_size, kd), np.float32)
    nxt, kv_lens, lat = 1, [], []
    for s, (n, c) in enumerate(seqs):
        kv = n + c
        kv_lens.append(kv)
        npg = -(-kv // block_size)
        bt[s, :npg] = np.arange(nxt, nxt + npg)
        rows = rng.standard_normal((kv, kd)).astype(np.float32)
        for p in range(kv):
            pool[bt[s, p // block_size], 0, p % block_size] = rows[p]
        lat.append(rows)
        nxt += npg
    cu = np.zeros(max_seqs + 2, np.int32)
    cu[1:len(seqs) + 1] = np.cumsum([n for n, _ in seqs])
    cu[len(seqs) + 1:] = total
    ctx = np.zeros(max_seqs + 1, np.int32)
    ctx[:len(seqs)] = [c for _, c in seqs]
    sid = np.full(T, max_seqs, np.int32)
    pos = np.zeros(T, np.int32)
    off = 0
    for s, (n, c) in enumerate(seqs):
        sid[off:off + n] = s
        pos[off:off + n] = c + np.arange(n)
        off += n
    q = rng.standard_normal((T, heads, kd)).astype(np.float32)
    # 2: an item is a run of pages (the latent pool's value width)
    run = rpa_run_pages(block_size, kd, vd, 4, latent=True)
    maps = build_step_maps(
        cu[:len(seqs) + 1], kv_lens, total_tokens=T, tile_q=tile_q,
        block_size=block_size, max_seqs=max_seqs, run_pages=run,
        max_items=rpa_max_items(T // tile_q, max_seqs, mbps, run))
    return dict(q=q, pool=pool, bt=bt, cu=cu, ctx=ctx, sid=sid, pos=pos,
                maps=maps, lat=lat, total=total, vd=vd, seqs=seqs)


def _expanded_oracle(case, w_uv, scale):
    """Attention in the expanded form on the same numbers: with ``q' =
    [W_UK^T q_nope | q_rope]`` given, each head's keys are ``[W_UK c |
    k_rope]``... regrouped, that is ``q'.row``; the oracle forms the per
    head values ``W_UV c`` and attends token by token in plain numpy."""
    out, off = [], 0
    for s, (n, c) in enumerate(case["seqs"]):
        rows = case["lat"][s]
        for i in range(n):
            seen = rows[:c + i + 1]
            qi = case["q"][off + i]                       # [heads, kd]
            sc = (qi @ seen.T) * scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            vals = np.einsum("lc,chv->lhv", seen[:, :case["vd"]], w_uv)
            out.append(np.einsum("hl,lhv->hv", p, vals))
        off += n
    return np.stack(out)


@pytest.mark.parametrize("seqs", [
    [(11, 5), (1, 19), (1, 0), (1, 33)],      # a chunk with decode rows
    [(1, 7), (1, 8), (1, 40)],                # decode rows alone
    [(20, 0)],                                # a first chunk, three tiles
], ids=["chunk_and_decode", "decode_only", "first_chunk"])
def test_rpa_mla_interpret_matches_expanded_attention(seqs):
    rng = np.random.default_rng(3)
    case = _latent_case(rng, seqs)
    heads, vd = 4, case["vd"]
    w_uv = rng.standard_normal((vd, heads, 6)).astype(np.float32)
    scale = 0.3
    args = [jnp.asarray(case[k]) for k in ("bt", "cu", "ctx")]
    u = ragged_paged_attention(
        jnp.asarray(case["q"]), jnp.asarray(case["pool"]), None, *args,
        case["maps"].step_seq, case["maps"].step_blk, case["maps"].step_tile,
        sm_scale=scale, value_cols=vd)
    assert u.shape == (case["q"].shape[0], heads, vd)
    got = np.einsum("thc,chv->thv", np.asarray(u), w_uv)
    want = _expanded_oracle(case, w_uv, scale)
    n = case["total"]
    np.testing.assert_allclose(got[:n], want, atol=1e-4, rtol=1e-4)
    assert not np.asarray(u)[n:].any()          # padding rows are exactly 0
    gathered = pa.ragged_latent_gather_attention(
        jnp.asarray(case["q"]), jnp.asarray(case["pool"]),
        jnp.asarray(case["bt"]), jnp.asarray(case["sid"]),
        jnp.asarray(case["pos"]), value_cols=vd, scale=scale)
    np.testing.assert_allclose(np.asarray(gathered)[:n], np.asarray(u)[:n],
                               atol=1e-4, rtol=1e-4)


def test_latent_step_writes_rows_then_reads_them():
    """``attend`` over a latent pool: the step's own rows land in the
    pool at their pages (padding in the null block) before the read; a
    K/V cache, or values beside a latent pool, are refused."""
    rng = np.random.default_rng(4)
    case = _latent_case(rng, [(5, 3), (1, 9)])
    new = np.zeros((case["q"].shape[0], 24), np.float32)
    pool = case["pool"].copy()
    off = 0
    for s, (n, c) in enumerate(case["seqs"]):
        new[off:off + n] = case["lat"][s][c:c + n]
        for p in range(c, c + n):                # not yet in the pool
            pool[case["bt"][s, p // 8], 0, p % 8] = 0
        off += n
    meta = [jnp.asarray(case[k]) for k in ("bt", "cu", "ctx", "sid", "pos")] \
        + list(case["maps"][:3])
    for impl in ("rpa", "gather"):
        cache = pa.RaggedLayerCache(jnp.asarray(pool), None, *meta, impl=impl)
        u, cache2 = pa.attend(cache, jnp.asarray(case["q"]), jnp.asarray(new),
                              value_cols=16, scale=0.3)
        (pool2,) = cache2.pools()
        np.testing.assert_array_equal(np.asarray(pool2)[1:],
                                      case["pool"][1:])
        want = _expanded_oracle(case, np.eye(16)[:, None, :]
                                .repeat(4, 1).astype(np.float32), 0.3)
        np.testing.assert_allclose(np.asarray(u)[:6], want, atol=1e-4,
                                   rtol=1e-4)
    rows = jnp.asarray(new)
    with pytest.raises(NotImplementedError, match="latent pool"):
        pa.attend(cache, jnp.asarray(case["q"]), rows, rows, value_cols=16)
    with pytest.raises(NotImplementedError, match="latent pool"):
        pa.attend(cache._replace(v_pool=cache.k_pool),
                  jnp.asarray(case["q"]), rows, value_cols=16)


# ---------------------------------------------------- the held experts --
def _program_layer(cfg, layer_idx=1):
    """The program's expert layer and shared expert with layer
    ``layer_idx``'s seeded weights of ``cfg``'s share."""
    z = W.sizes(cfg)
    leaves = W.layer_leaves(W.seed_key(SEED), layer_idx, cfg, "float32",
                            dense=False)
    layer = HeldExpertsLayer(
        z["d"], z["moe_ffn"], z["experts"], z["top_k"], held=z["held"],
        routed_scaling_factor=z["scaling"], norm_topk_prob=z["norm_topk"])
    for name, leaf in (("router", "router"), ("w_gate", "e_gate"),
                       ("w_up", "e_up"), ("w_down", "e_down")):
        getattr(layer, name)._data = leaves[leaf]
    return layer, leaves


def _reference_layer(cfg, h, shared, layer_idx=1):
    z, key = W.sizes(cfg), W.seed_key(SEED)
    w = W.layer_leaves(key, layer_idx, cfg, "float32", dense=False,
                       experts=False)

    def expert_weights(e):
        return {n: W.expert_leaf(key, layer_idx, n, e, cfg, "float32")
                for n in ("e_gate", "e_up", "e_down")}
    with jax.default_matmul_precision("highest"):
        return np.asarray(R.expert_layer(jnp.asarray(h), w, z,
                                         linear("exact"), expert_weights,
                                         shared=shared)[0])


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of all 4 shares of 8 experts,
    with the shared expert counted once, equal the uncut reference's
    layer; each share's part equals the reference given that share."""
    h = np.random.default_rng(5).standard_normal((37, 64)).astype(np.float32)
    whole = _reference_layer(share_cfg(range(8)), h, shared=True)
    total = _reference_layer(share_cfg(range(8)), h, shared=True) \
        - _reference_layer(share_cfg(range(8)), h, shared=False)  # shared
    rows = 0
    for j in range(4):
        cfg = share_cfg((2 * j, 2 * j + 1))
        layer, _ = _program_layer(cfg)
        part = np.asarray(layer(Tensor(jnp.asarray(h))).data)
        np.testing.assert_allclose(
            part, _reference_layer(cfg, h, shared=False), atol=2e-6)
        rows += int(np.asarray(layer.last_rows.data).sum())
        total = total + part
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert rows == 37 * 2             # every assignment computed once


def test_a_tokens_output_does_not_depend_on_the_rest_of_the_step():
    """Dropless: no capacity, so a token's routed output is the same alone,
    among other tokens, and among tokens that all crowd its experts."""
    layer, _ = _program_layer(share_cfg((0, 1, 2)))
    rng = np.random.default_rng(6)
    tok = rng.standard_normal((1, 64)).astype(np.float32)
    alone = np.asarray(layer(Tensor(jnp.asarray(tok))).data)[0]
    crowd = np.concatenate([rng.standard_normal((90, 64)), tok,
                            np.repeat(tok, 40, 0) * 1.001]).astype(np.float32)
    among = np.asarray(layer(Tensor(jnp.asarray(crowd))).data)[90]
    np.testing.assert_allclose(among, alone, atol=1e-6)
    mask = np.ones(131, bool)
    mask[:90] = False                 # padding chooses no expert
    masked = layer(Tensor(jnp.asarray(crowd)),
                   token_mask=Tensor(jnp.asarray(mask)))
    np.testing.assert_allclose(np.asarray(masked.data)[90], alone, atol=1e-6)
    assert not np.asarray(masked.data)[:90].any()


# ------------------------------------------------------- the cache spec --
def test_latent_pages_copy_export_import_and_are_reused():
    spec = pa.LayerCacheSpec(1, 24, None, 16)
    a = PagedKVCache(2, 6, 4, spec, prefix_cache=True)
    assert a.v_pools == (None, None) and a.k_pools[0].shape == (7, 1, 4, 24)
    assert a.pool_bytes() == {"latent": 2 * 7 * 4 * 24 * 4, "kv": 0}
    rows = np.random.default_rng(7).standard_normal((2, 1, 4, 24)) \
        .astype(np.float32)
    (b1,) = a.groups[0].allocator.allocate(1)
    a.import_block(b1, rows)
    (b2,) = a.groups[0].allocator.allocate(1)
    a.copy_block(b1, b2)
    k, v = a.export_block(b2)
    assert v is None
    np.testing.assert_array_equal(k, rows)
    other = PagedKVCache(2, 6, 4, spec)
    (b3,) = other.groups[0].allocator.allocate(1)
    other.import_block(b3, k, v)
    np.testing.assert_array_equal(np.asarray(other.k_pools[1][b3]), rows[1])


def test_latent_blocks_move_between_engines(model):
    """Export the document's pages from one engine, import them into a
    second: its first ask of that document hits the prefix cache and
    serves the tokens the first engine served."""
    from paddle_tpu.serving.kv_cache import chain_hash
    kw = CFG["engine"]
    a = ServingEngine(model, **kw)
    b = ServingEngine(model, **kw)
    rng = np.random.default_rng(8)
    prompt = rng.integers(1, 128, 37).tolist()
    ha = a.submit(prompt, max_new_tokens=5, temperature=0.0)
    a.run_until_idle()
    digests, parent = [], None
    for i in range(len(prompt) // kw["block_size"]):
        parent = chain_hash(parent, prompt[i * 8:(i + 1) * 8])
        digests.append(parent)
    records = a.export_kv_blocks(digests)
    assert len(records) == 4 and records[0][2] is None
    assert b.import_kv_blocks(records) == 4
    hb = b.submit(prompt, max_new_tokens=5, temperature=0.0)
    b.run_until_idle()
    assert hb.token_ids == ha.token_ids
    assert b.stats()["prefix_cache"]["hit_tokens"] == 32


def test_llama_and_moe_engines_build_their_pools_from_the_spec():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.moe import MoeConfig, MoeForCausalLM
    for m, n_kv, hd in (
            (LlamaForCausalLM(LlamaConfig.tiny()), 2, 16),
            (MoeForCausalLM(MoeConfig.tiny()), 2, 16)):
        spec = m.kv_cache_spec()
        assert spec == pa.LayerCacheSpec.kv(n_kv, hd)
        eng = ServingEngine(m, max_batch=2, max_blocks=8, block_size=4,
                            prefill_chunk=4)
        assert [g.spec for g in eng.cache.groups] == [spec]
        assert all(p.shape == (9, n_kv, 4, hd)
                   for p in eng.cache.k_pools + eng.cache.v_pools)
        assert eng.cache.pool_bytes()["latent"] == 0

    silent = LlamaForCausalLM(LlamaConfig.tiny())
    silent.kv_cache_spec = None       # a model that states no spec
    with pytest.raises(TypeError, match="kv_cache_spec"):
        ServingEngine(silent)
