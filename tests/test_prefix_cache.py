"""Block-granular prefix cache + tensor-parallel serving (ISSUE 15).

Coverage contract: BlockAllocator reclaimable-tier invariants (park on
last free, LRU eviction order + index callback, resurrection via
``reuse_cached``, capacity accounting incl. ``assert_no_leaks``),
chain-hash semantics, PrefixCache match/register incl. the
fully-cached ``len−1`` COW cap, and engine integration — shared-prefix
greedy streams bit-identical cache-on vs cache-off (the cache-off
engine is the parity oracle), copy-on-write divergence, abort while a
cached block is shared live, preemption re-admitting THROUGH the cache
(recompute == uncached tail only), and mp=2 tensor-parallel token
parity against the single-device stream over the CPU 8-virtual-device
mesh (tests/conftest.py forces ``--xla_force_host_platform_device_count=8``).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import (BlockAllocator,
                                         chain_hash)


def _tiny(seed=0, tensor_parallel=False):
    pt.seed(seed)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=True,
        tensor_parallel=tensor_parallel))
    m.eval()
    return m


def _eager_continuation(model, prompt, max_new_tokens):
    out = model.generate(pt.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=max_new_tokens,
                         temperature=0.0).numpy()[0]
    return [int(t) for t in out[len(prompt):]]


# ---------------- chain hashing ----------------------------------------------
def test_chain_hash_commits_to_whole_prefix():
    h1 = chain_hash(None, [1, 2, 3, 4])
    assert h1 == chain_hash(None, [1, 2, 3, 4]) and len(h1) == 16
    assert h1 != chain_hash(None, [1, 2, 3, 5])
    # same block content under a different parent → different digest:
    # a block's identity includes every token before it
    assert chain_hash(h1, [5, 6]) != chain_hash(chain_hash(None, [9]),
                                                [5, 6])


# ---------------- allocator reclaimable tier ---------------------------------
def test_reclaimable_park_resurrect_and_accounting():
    a = BlockAllocator(4)
    b1, b2 = a.allocate(2)
    a.mark_cached(b1, b"k1")
    a.free([b1])                       # cached: parks, doesn't free
    a.free([b2])                       # uncached: straight to free list
    assert a.num_reclaimable() == 1 and a.num_free() == 3
    assert a.blocks_in_use() == 0
    assert a.can_allocate(4)           # reclaimable counts as capacity
    a.assert_no_leaks()                # parked blocks are accounted
    # resurrection: a parked block comes back live at refcount 1
    assert a.reuse_cached(b1)
    assert a.refcount(b1) == 1 and a.num_reclaimable() == 0
    # live cached block shares by incref through the same API
    assert a.reuse_cached(b1) and a.refcount(b1) == 2
    a.free([b1]), a.free([b1])
    a.assert_no_leaks()


def test_reclaimable_lru_eviction_order_and_callback():
    a = BlockAllocator(3)
    evicted = []
    a._evict_cb = lambda b, k: evicted.append((b, k))
    blocks = a.allocate(3)
    for i, b in enumerate(blocks):
        a.mark_cached(b, bytes([i]) * 16)
    a.free([blocks[0]])                # parked first → LRU-oldest
    a.free([blocks[2]])
    a.free([blocks[1]])
    got = a.allocate(2)                # free list empty: must evict
    assert evicted == [(blocks[0], bytes([0]) * 16),
                       (blocks[2], bytes([2]) * 16)]   # LRU order
    assert not a.is_cached(blocks[0]) and a.is_cached(blocks[1])
    assert a.reuse_cached(blocks[0]) is False   # evicted: gone
    a.free(got)            # blocks[1] is already parked at refcount 0
    a.assert_no_leaks()


# ---------------- PrefixCache unit -------------------------------------------
def test_prefix_cache_match_register_and_cow_cap():
    from paddle_tpu.ops.paged_attention import LayerCacheSpec
    from paddle_tpu.serving import PagedKVCache
    cache = PagedKVCache(num_layers=1, num_blocks=8, block_size=4,
                         spec=LayerCacheSpec.kv(1, 4), prefix_cache=True)
    (g,) = cache.groups
    a, pc = g.allocator, g.prefix_cache
    blocks = a.allocate(2)
    d0 = chain_hash(None, [1, 2, 3, 4])
    d1 = chain_hash(d0, [5, 6, 7, 8])
    pc.register(d0, blocks[0])
    pc.register(d1, blocks[1])
    a.free(blocks)                     # registered → both park
    # partial tail: only full, chain-linked blocks match
    got, digests = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 9])
    assert got == [blocks] and digests == [d0, d1]
    assert a.refcount(blocks[0]) == 1  # match CLAIMS the blocks
    a.free(blocks)
    # divergence in the second block stops the walk after the first
    (got2,), _ = cache.match([1, 2, 3, 4, 9, 9, 9, 9, 1])
    assert got2 == [blocks[0]]
    a.free(got2)
    assert pc.stats()["lookups"] == 2 and pc.stats()["hits"] == 2
    a.assert_no_leaks()


# ---------------- engine integration -----------------------------------------
BS = 4


@pytest.fixture(scope="module")
def model():
    return _tiny(11)


@pytest.fixture(scope="module")
def eng_on(model):
    return ServingEngine(model, max_batch=4, max_blocks=32, block_size=BS,
                         prefill_chunk=4, prefix_cache=True)


def test_shared_prefix_bit_parity_cache_on_vs_off(model, eng_on):
    """The tentpole parity oracle: identical greedy streams with the
    cache on and off over shared-prefix traffic, with the cache-on run
    actually hitting."""
    eng_off = ServingEngine(model, max_batch=4, max_blocks=32,
                            block_size=BS, prefill_chunk=4,
                            prefix_cache=False)
    assert eng_off.stats()["prefix_cache"] is None
    rng = np.random.RandomState(0)
    pfx = [int(t) for t in rng.randint(1, 128, 12)]
    prompts = [pfx + [int(t) for t in rng.randint(1, 128, n)]
               for n in (3, 5, 2)]
    streams = {}
    for name, eng in (("on", eng_on), ("off", eng_off)):
        # first request runs alone so its blocks COMMIT before the rest
        # admit (registration happens after the step that writes a
        # block's last token) — the bench's warmup, in miniature
        h0 = eng.submit(prompts[0], max_new_tokens=6)
        eng.run_until_idle()
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        eng.run_until_idle()
        streams[name] = [h.result(30)["token_ids"]
                         for h in [h0] + handles]
        eng.cache.assert_no_leaks()
    assert streams["on"] == streams["off"]
    pc = eng_on.stats()["prefix_cache"]
    assert pc["hits"] >= 2 and pc["hit_tokens"] >= 2 * 12
    # headroom splits: free + reclaimable == allocatable headroom
    st = eng_on.stats()
    assert st["kv_headroom"] == pytest.approx(
        st["kv_free_fraction"] + st["kv_reclaimable_fraction"])
    assert st["kv_blocks_reclaimable"] > 0     # warm cache parked


def test_fully_cached_prompt_cow_lifecycle(model, eng_on):
    """Resubmitting an identical block-aligned prompt is the COW
    corner: every token is cached, the cap re-prefills exactly one, and
    the copied block is private (stream still bit-exact)."""
    rng = np.random.RandomState(1)
    prompt = [int(t) for t in rng.randint(1, 128, 3 * BS)]  # aligned
    base = _eager_continuation(model, prompt, 5)
    h1 = eng_on.submit(prompt, max_new_tokens=5)
    eng_on.run_until_idle()
    assert h1.result(30)["token_ids"] == base
    h2 = eng_on.submit(prompt, max_new_tokens=5)
    eng_on.run_until_idle()
    assert h2.result(30)["token_ids"] == base
    r = h2._req
    assert r.cached_tokens_total == len(prompt) - 1   # the len−1 cap
    assert r.prefilled_tokens == \
        r.admitted_pending_total - r.cached_tokens_total
    assert r.cow_src is None                          # copy released
    eng_on.cache.assert_no_leaks()


def test_mid_block_divergence_matches_cold_runs(model, eng_on):
    """Two prompts sharing two full blocks then diverging inside the
    third: the chain hash stops the match at the shared boundary and
    both streams equal their solo cold baselines."""
    rng = np.random.RandomState(2)
    pfx = [int(t) for t in rng.randint(1, 128, 2 * BS)]
    pa = pfx + [int(t) for t in rng.randint(1, 128, 3)]
    pb = pfx + [int(t) for t in rng.randint(1, 128, 3)]
    assert pa[2 * BS:] != pb[2 * BS:]
    ha = eng_on.submit(pa, max_new_tokens=4)
    eng_on.run_until_idle()
    hb = eng_on.submit(pb, max_new_tokens=4)
    eng_on.run_until_idle()
    assert ha.result(30)["token_ids"] == _eager_continuation(model, pa, 4)
    assert hb.result(30)["token_ids"] == _eager_continuation(model, pb, 4)
    # b matched exactly the shared full blocks, recomputed its own tail
    assert hb._req.cached_tokens_total == 2 * BS
    eng_on.cache.assert_no_leaks()


def test_abort_while_cached_block_shared(model, eng_on):
    """Aborting one of two requests sharing cached blocks must drop only
    its references: the survivor finishes bit-exact and the blocks
    return to the reclaimable tier, not the free list."""
    rng = np.random.RandomState(3)
    pfx = [int(t) for t in rng.randint(1, 128, 3 * BS)]
    warm = eng_on.submit(pfx + [1], max_new_tokens=2)
    eng_on.run_until_idle()
    warm.result(30)
    hb = eng_on.submit(pfx + [5, 6], max_new_tokens=4)
    hc = eng_on.submit(pfx + [7, 8], max_new_tokens=4)
    # admit both (no model step yet): they claim the same cached blocks
    eng_on.scheduler._admit()
    shared = hb._req.tables[0][:3]
    assert shared and shared == hc._req.tables[0][:3]
    alloc = eng_on.cache.groups[0].allocator
    assert all(alloc.refcount(b) == 2 for b in shared)
    assert eng_on.abort(hb.req_id, reason="test")
    assert all(alloc.refcount(b) == 1 for b in shared)  # survivor holds
    eng_on.run_until_idle()
    assert hc.result(30)["token_ids"] == \
        _eager_continuation(model, pfx + [7, 8], 4)
    assert all(alloc.is_cached(b) for b in shared)      # parked again
    alloc.assert_no_leaks()


def test_preemption_readmits_through_cache(model):
    """Deterministic preempt→readmit: the committed blocks park, the
    readmission match claims them back, and the recompute prefills
    ONLY the uncached tail (the ISSUE 15 preemption satellite, in
    isolation from victim-selection timing)."""
    eng = ServingEngine(model, max_batch=2, max_blocks=32, block_size=BS,
                        prefill_chunk=4, prefix_cache=True)
    rng = np.random.RandomState(4)
    prompt = [int(t) for t in rng.randint(1, 128, 10)]
    h = eng.submit(prompt, max_new_tokens=8)
    while len(h._req.generated) < 4:
        assert eng.step()
    committed = h._req.committed_blocks
    assert committed >= 3                    # 12+ tokens committed
    eng.scheduler.preempt(h._req)
    assert eng.cache.groups[0].allocator.num_reclaimable() >= committed
    eng.run_until_idle()
    assert h.result(30)["token_ids"] == \
        _eager_continuation(model, prompt, 8)
    r = h._req
    assert r.preemptions == 1
    assert r.cached_tokens_total == committed * BS   # tail-only recompute
    assert r.prefilled_tokens == \
        r.admitted_pending_total - r.cached_tokens_total
    eng.cache.assert_no_leaks()


def test_tensor_parallel_mp2_token_parity():
    """mp=2 over two of the 8 CPU virtual devices: Megatron-sharded
    weights + KV pools, ONE compiled SPMD step, greedy stream
    bit-identical to the single-device (eager) stream."""
    import jax

    from paddle_tpu.distributed import get_mesh, init_mesh, set_mesh

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    prev = get_mesh()
    try:
        # the model must be BUILT under the mesh: the Megatron layers
        # stamp their sharding specs against it at construction
        mesh = init_mesh({"mp": 2}, devices=jax.devices()[:2])
        model = _tiny(12, tensor_parallel=True)
        eng = ServingEngine(model, max_batch=2, max_blocks=16,
                            block_size=BS, prefill_chunk=4, mesh=mesh)
        assert eng.stats()["tensor_parallel"] == 2
        rng = np.random.RandomState(5)
        prompts = [[int(t) for t in rng.randint(1, 128, n)]
                   for n in (9, 6)]
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle()
        for h, p in zip(handles, prompts):
            assert h.result(60)["token_ids"] == \
                _eager_continuation(model, p, 6)
        assert eng.step_traces == 1
        eng.cache.assert_no_leaks()
    finally:
        set_mesh(prev)


@pytest.mark.parametrize("engine_kw", [
    {"attn_impl": "gather"}, {"attn_impl": "rpa"}, {"kv_dtype": "int8"}],
    ids=["gather", "rpa", "int8_kv"])
def test_tensor_parallel_step_gathers_no_pool(engine_kw):
    """Under an mp mesh each shard writes the step's rows into its own
    heads of its own pool shard: the compiled step holds no all-gather
    whose result has a pool's (or a scale pool's) full shape. The flat
    view of a pool merges the sharded head axis; written under GSPMD
    alone, every pool would be gathered to be reshaped."""
    import re

    import jax

    from paddle_tpu.distributed import get_mesh, init_mesh, set_mesh

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    prev = get_mesh()
    try:
        mesh = init_mesh({"mp": 2}, devices=jax.devices()[:2])
        eng = ServingEngine(_tiny(12, tensor_parallel=True), max_batch=2,
                            max_blocks=16, block_size=BS, prefill_chunk=4,
                            mesh=mesh, **engine_kw)
        text = eng.compiled_hlo()
    finally:
        set_mesh(prev)
    pools = eng.cache.k_pools + eng.cache.k_scales
    assert all(p.sharding.spec[1] == "mp" for p in pools)
    full = {",".join(map(str, p.shape)) for p in pools}
    gathered = re.findall(r"= \w+\[([\d,]+)\]\S* all-gather(?:-start)?\(",
                          text)
    assert not full & set(gathered), sorted(full & set(gathered))
