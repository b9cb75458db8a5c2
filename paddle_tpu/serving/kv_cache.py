"""Block-paged KV-cache manager for the serving engine.

Three parts:

* :class:`BlockAllocator` — host-side accounting over a fixed pool of
  ``num_blocks`` token blocks: a free list, per-block refcounts
  (refcounting keeps the door open for prefix sharing / request forks —
  a shared block is freed only when its last holder drops it), an LRU
  **reclaimable tier** for prefix-cached blocks whose refcount dropped
  to zero (they keep their contents and are evicted only when the free
  list runs dry), and leak assertions. Physical **block 0 is reserved
  as the null block** (see ``ops/paged_attention.py``) and is never
  handed out.

* :class:`PrefixCache` — the block-granular prefix index (ISSUE 15):
  every *full* ``block_size``-aligned chunk of a sequence's cached
  token stream is chain-hashed (``h_i = blake2b(h_{i-1} || tokens_i)``,
  so a block's digest commits to its entire prefix) and mapped to the
  committed physical block. Admission matches the longest registered
  prefix and increfs the matched blocks into the new sequence's table;
  only the uncached tail prefills. Registered blocks are IMMUTABLE —
  the engine only registers a block after the step that wrote its last
  token ran, and sequence writes land strictly beyond ``num_cached``,
  so an index entry stays valid until the allocator evicts the block.

* :class:`PagedKVCache` — the device state, built from the layer spec
  the served model states (``ops.paged_attention.LayerCacheSpec``:
  heads, key width, value width, or "values are the first columns of
  the key page"): per layer one ``[num_blocks + 1, kv_heads, block_size,
  key_dim]`` K pool and, unless the layer's page is a latent one, a V
  pool of ``value_dim`` (the +1 row is the null block at physical index
  0; the layout is ``ops/paged_attention.py``'s), threaded
  functionally through the engine's compiled step (the jitted function
  takes the pools as inputs and returns the updated ones — nothing is
  mutated in place, so the executable never recompiles), plus the
  allocator, the block-table padding helper, the copy-on-write block
  copy (one jitted program, physical src/dst are traced scalars) and
  the optional ``mp``-axis pool sharding for tensor-parallel serving.
  A model may state a spec a layer: layers of equal spec form a
  :class:`CacheGroup` with its own allocator, prefix index and pool size
  (window layers beside full ones, docs/SERVING.md "Layer groups").
  A state-space layer states a
  :class:`~paddle_tpu.ops.paged_attention.StateCacheSpec` instead: such
  layers form the :class:`StateGroup`, one row a serving slot and no
  pages (docs/SERVING.md "State layers"). A layer that states a
  :class:`~paddle_tpu.ops.paged_attention.ReadCacheSpec` keeps nothing:
  it reads another layer's pool through that layer's group table
  (docs/SERVING.md "Layers that read another layer's pages").

Sizing math (docs/SERVING.md): a request of total length ``T`` (prompt +
generated) holds ``ceil(T / block_size)`` blocks, so worst-case pool
demand for ``B`` concurrent requests of max total length ``T_max`` is
``B * ceil(T_max / block_size)`` blocks; internal fragmentation is at
most ``block_size - 1`` tokens per sequence instead of the
``T_max - T`` of a contiguous worst-case layout. With the prefix cache
on, refcount-0 cached blocks additionally occupy otherwise-free blocks
— they are *reclaimable* capacity, not pressure: ``can_allocate``
counts them and ``allocate`` evicts LRU-first before failing.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import ReadCacheSpec, StateCacheSpec, tokens_a_row

__all__ = ["BlockAllocator", "CacheGroup", "PagedKVCache", "PrefixCache",
           "StateGroup", "chain_hash"]

#: physical block id reserved as the write-off target for padding
NULL_BLOCK = 0

#: chain seed for the first block's digest (no parent)
_HASH_SEED = b"\x00" * 16


def chain_hash(parent: Optional[bytes], tokens: Sequence[int]) -> bytes:
    """Digest of one full token block, chained to its prefix: two blocks
    collide only if their entire token prefixes agree (16-byte blake2b —
    keyed content addressing, not cryptographic auth)."""
    h = hashlib.blake2b(parent or _HASH_SEED, digest_size=16)
    h.update(np.asarray(tokens, dtype=np.int64).tobytes())
    return h.digest()


class BlockAllocator:
    """Refcounted free-list allocator over block ids ``1..num_blocks``
    with an LRU reclaimable tier for prefix-cached refcount-0 blocks."""

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError("need at least one allocatable block")
        self.num_blocks = num_blocks
        self._lock = threading.Lock()
        # ids 1..num_blocks (0 is the null block); popped from the end
        self._free: List[int] = list(range(num_blocks, 0, -1))
        self._refcount: Dict[int, int] = {}
        # block-seconds occupancy integral: bill the PREVIOUS holding
        # level for each elapsed interval at every occupancy transition
        # (left-continuous — the exact pool-level cost the per-request
        # ledger approximates at step granularity)
        self._occ_t = time.monotonic()
        self._occ_seconds = 0.0
        # refcount-0 blocks still holding registered prefix-cache
        # contents, LRU order (oldest first — the eviction order)
        self._reclaimable: "OrderedDict[int, bytes]" = OrderedDict()
        # block id -> prefix digest for every REGISTERED block (live or
        # parked); registration survives free/park until eviction
        self._cached_key: Dict[int, bytes] = {}
        #: called (block_id, key) under the allocator lock when an LRU
        #: reclaimable block is repurposed — the PrefixCache drops its
        #: index entry here (must not re-enter the allocator)
        self._evict_cb: Optional[Callable[[int, bytes], None]] = None

    def _occ_tick_locked(self, now: Optional[float] = None):
        """Accrue block-seconds at the current holding level (lock
        held; called BEFORE any occupancy mutation)."""
        now = time.monotonic() if now is None else now
        dt = now - self._occ_t
        if dt > 0:
            self._occ_seconds += len(self._refcount) * dt
            self._occ_t = now

    def block_seconds_total(self) -> float:
        """Cumulative pool occupancy integral (blocks held by live
        sequences x seconds held) since construction."""
        with self._lock:
            self._occ_tick_locked()
            return self._occ_seconds

    @property
    def capacity(self) -> int:
        return self.num_blocks

    def num_free(self) -> int:
        with self._lock:
            return len(self._free)

    def num_reclaimable(self) -> int:
        with self._lock:
            return len(self._reclaimable)

    def blocks_in_use(self) -> int:
        with self._lock:
            return len(self._refcount)

    def can_allocate(self, n: int) -> bool:
        """Reclaimable blocks count as capacity: they are evicted before
        an allocation is allowed to fail."""
        with self._lock:
            return len(self._free) + len(self._reclaimable) >= n

    def allocate(self, n: int = 1) -> List[int]:
        """``n`` fresh blocks at refcount 1; raises ``MemoryError`` when
        the pool can't cover the request (callers preempt on that).
        Free-list blocks go first; then LRU reclaimable cached blocks
        are evicted (their prefix-index entries invalidated via the
        eviction callback) — a cache entry is never worth failing an
        allocation for."""
        with self._lock:
            self._occ_tick_locked()
            if len(self._free) + len(self._reclaimable) < n:
                raise MemoryError(
                    f"KV block pool exhausted: need {n}, free "
                    f"{len(self._free)}+{len(self._reclaimable)} "
                    f"reclaimable /{self.num_blocks}")
            out = []
            for _ in range(n):
                if self._free:
                    b = self._free.pop()
                else:
                    b, key = self._reclaimable.popitem(last=False)
                    del self._cached_key[b]
                    if self._evict_cb is not None:
                        self._evict_cb(b, key)
                self._refcount[b] = 1
                out.append(b)
            return out

    def incref(self, block_id: int):
        with self._lock:
            if block_id not in self._refcount:
                raise ValueError(f"block {block_id} is not allocated")
            self._refcount[block_id] += 1

    def free(self, block_ids: Sequence[int]):
        """Drop one reference per id. At refcount 0 a registered
        (prefix-cached) block PARKS in the reclaimable tier — contents
        kept, evictable LRU — while an unregistered block returns to
        the free list."""
        with self._lock:
            self._occ_tick_locked()
            for b in block_ids:
                rc = self._refcount.get(b)
                if rc is None:
                    raise ValueError(f"double free of block {b}")
                if rc == 1:
                    del self._refcount[b]
                    key = self._cached_key.get(b)
                    if key is not None:
                        self._reclaimable[b] = key  # MRU end
                    else:
                        self._free.append(b)
                else:
                    self._refcount[b] = rc - 1

    def refcount(self, block_id: int) -> int:
        with self._lock:
            return self._refcount.get(block_id, 0)

    # -- prefix-cache hooks ------------------------------------------------
    def mark_cached(self, block_id: int, key: bytes):
        """Register a LIVE block as prefix-cache backed: when its
        refcount later hits 0 it parks as reclaimable instead of
        returning to the free list."""
        with self._lock:
            if block_id not in self._refcount:
                raise ValueError(
                    f"block {block_id} is not allocated (cannot cache)")
            self._cached_key[block_id] = key

    def reuse_cached(self, block_id: int) -> bool:
        """Claim one reference on a registered block for a cache hit:
        incref a live holder, or resurrect a parked reclaimable block at
        refcount 1. False when the block was already evicted (the
        caller treats the walk as a miss from here on)."""
        with self._lock:
            self._occ_tick_locked()
            if block_id not in self._cached_key:
                return False  # evicted (and possibly reallocated)
            if block_id in self._refcount:
                self._refcount[block_id] += 1
                return True
            if block_id in self._reclaimable:
                del self._reclaimable[block_id]
                self._refcount[block_id] = 1
                return True
            return False

    def is_cached(self, block_id: int) -> bool:
        with self._lock:
            return block_id in self._cached_key

    def assert_no_leaks(self):
        """Every block is back in the pool (end-of-drain invariant).
        Parked reclaimable blocks are NOT leaks — they are evictable
        capacity — but every block must be accounted for exactly once."""
        with self._lock:
            leaked = sorted(self._refcount)
            if leaked:
                raise AssertionError(
                    f"{len(leaked)} KV blocks leaked: {leaked[:16]}")
            total = len(self._free) + len(self._reclaimable)
            if total != self.num_blocks:
                raise AssertionError(
                    f"pool accounting broke: {len(self._free)} free + "
                    f"{len(self._reclaimable)} reclaimable != "
                    f"{self.num_blocks}")


class PrefixCache:
    """Hash index over committed full KV blocks (ISSUE 15).

    :meth:`PagedKVCache.match` walks the chain hashes of a prompt's full
    blocks and CLAIMS every hit (``claim``: incref / resurrect through
    the allocator);
    ``register`` is called by the engine's post-step commit pass — only
    for blocks whose final token the executed step wrote, so an indexed
    block is always immutable. Counters are cumulative; the engine
    publishes deltas into the ``serving_prefix_cache_*`` metric
    families."""

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        self._index: Dict[bytes, int] = {}   # digest -> physical block
        self.lookups = 0
        self.hits = 0
        self.evictions = 0
        self.hit_tokens = 0      # prompt tokens served from the cache
        #: lookups that found a registered prefix and were refused it, and
        #: the tokens they would have reused: a cache with state layers
        #: has no state at a block's boundary (``PagedKVCache.match``)
        self.declined = 0
        self.declined_tokens = 0
        allocator._evict_cb = self._on_evict

    def __len__(self) -> int:
        return len(self._index)

    def _on_evict(self, block_id: int, key: bytes):
        # under the allocator lock — dict surgery only
        if self._index.get(key) == block_id:
            del self._index[key]
        self.evictions += 1

    def lookup(self, digest: bytes) -> Optional[int]:
        return self._index.get(digest)

    def claim(self, digest: bytes) -> Optional[int]:
        """The block registered under ``digest`` with one reference taken
        on it (incref, or a parked block resurrected), the caller's to
        free; None where nothing is registered or the block is gone."""
        b = self._index.get(digest)
        if b is None or not self.allocator.reuse_cached(b):
            if b is not None:
                # index raced an eviction path — drop the stale entry
                self._index.pop(digest, None)
            return None
        return b

    def register(self, digest: bytes, block_id: int):
        """Index a completed full block. First writer wins: duplicate
        content keeps the existing entry and the caller's block simply
        stays a plain (uncached) block."""
        if digest in self._index:
            return
        self.allocator.mark_cached(block_id, digest)
        self._index[digest] = block_id

    def stats(self) -> dict:
        return {
            "lookups": self.lookups,
            "hits": self.hits,
            "evictions": self.evictions,
            "hit_tokens": self.hit_tokens,
            "declined": self.declined,
            "declined_tokens": self.declined_tokens,
            "entries": len(self._index),
        }

    #: bytes of each digest kept in the router-facing sketch — 8 bytes
    #: (64 bits) keeps accidental cross-replica collisions negligible at
    #: any realistic index size while shrinking the wire payload 2x
    SKETCH_PREFIX_BYTES = 8

    def sketch(self, limit: int = 4096) -> List[str]:
        """Compact content summary of the index for the fleet router:
        the hex-truncated digest of every registered block (chain hashes
        commit to their whole prefix, so digest-set intersection IS
        prefix overlap). Capped at ``limit`` entries — a partial sketch
        only costs affinity accuracy, never correctness, because the
        router treats it as a routing hint and admission re-walks the
        real index."""
        n = self.SKETCH_PREFIX_BYTES
        keys = list(self._index.keys())[:limit]
        return [d[:n].hex() for d in keys]


class CacheGroup:
    """The layers of one :class:`~paddle_tpu.ops.paged_attention.LayerCacheSpec`:
    their pools share a size, an allocator, a prefix index and, a
    sequence, one block-table row (docs/SERVING.md "Layer groups")."""

    def __init__(self, name: str, spec, layers: Sequence[int],
                 num_blocks: int, block_size: int, prefix_cache: bool):
        self.name, self.spec, self.layers = name, spec, tuple(layers)
        self.num_blocks = int(num_blocks)
        self.block_size = block_size
        self.allocator = BlockAllocator(self.num_blocks)
        self.prefix_cache = (PrefixCache(self.allocator, block_size)
                             if prefix_cache else None)

    @property
    def window(self):
        return self.spec.window

    def first_visible_page(self, kv_len: int) -> int:
        """The first page that holds a key the token at position
        ``kv_len`` (the next to come once ``kv_len`` are cached) can see:
        0 without a window. Every page before it is dead to the sequence."""
        if self.window is None:
            return 0
        return max(0, kv_len - self.window + 1) // self.block_size

    def max_pages_held(self, new_tokens: int, table_width: int) -> int:
        """Most pages one sequence holds in this group while a step
        writes ``new_tokens`` of it: the table's width, or under a window
        ``ceil((window + new_tokens) / block_size) + 1``."""
        if self.window is None:
            return table_width
        return min(table_width,
                   -(-(self.window + new_tokens) // self.block_size) + 1)

    def usage(self) -> dict:
        a = self.allocator
        return {"blocks": self.num_blocks, "in_use": a.blocks_in_use(),
                "free": a.num_free(), "reclaimable": a.num_reclaimable(),
                "layers": len(self.layers), "window": self.window}

    def fractions(self) -> Tuple[float, float]:
        """``(free, reclaimable)`` as shares of the group's pool."""
        a = self.allocator
        cap = max(a.capacity, 1)
        return a.num_free() / cap, a.num_reclaimable() / cap


class StateGroup:
    """The state-space layers of a cache: one
    :class:`~paddle_tpu.ops.paged_attention.StateCacheSpec` and, a layer,
    a pool of ``rows`` state rows (a serving slot's is ``slot + 1``; row 0
    is the null row). No allocator, no block table, no prefix index: a
    sequence's row is its slot's from admission to release, and a step that
    starts a sequence at position 0 starts it from zero state."""

    def __init__(self, spec, layers: Sequence[int], rows: int):
        self.spec, self.layers, self.rows = spec, tuple(layers), int(rows)

    def usage(self) -> dict:
        return {"rows": self.rows, "layers": len(self.layers),
                "conv": tuple(self.spec.conv), "ssm": tuple(self.spec.ssm)}


def _group_layers(specs):
    """Layers of equal spec, in order of first appearance:
    ``[(name, spec, [layer, ...])]``. A group is named for what sets it
    apart: ``window``, ``latent``, else ``full``. State layers and layers
    that read another's pages form no paged group."""
    groups = []
    for i, sp in enumerate(specs):
        if sp is None or isinstance(sp, (StateCacheSpec, ReadCacheSpec)):
            continue
        for g in groups:
            if g[1] == sp:
                g[2].append(i)
                break
        else:
            name = "window" if sp.window is not None else \
                "latent" if sp.latent else "full"
            if any(g[0] == name for g in groups):
                raise NotImplementedError(
                    f"two layer groups named {name!r}: layer {i} states "
                    f"{sp}, an earlier one "
                    f"{next(g[1] for g in groups if g[0] == name)}")
            groups.append((name, sp, [i]))
    return groups


class PagedKVCache:
    """Per-layer block pools + the allocators + table-shaping helpers.

    What a model must state: ``spec``, the
    :class:`~paddle_tpu.ops.paged_attention.LayerCacheSpec` of its
    attention layers (``model.kv_cache_spec()``): how many heads a page
    holds, how wide a key row is, and either how wide a value row is or
    that the values are the first ``value_cols`` columns of the key page
    (a latent page: there is no V pool and ``v_pools`` holds None a
    layer). One spec stands for every layer; a list states one a layer,
    and layers of equal spec form a :class:`CacheGroup` (``groups``, in
    order of first appearance): its own pools' size, allocator and prefix
    index. ``num_blocks`` is a number (every group's) or a mapping by
    group name. Allocators, prefix indexes and specs are the groups': a
    model of one spec has a list of one. Layers that state a
    ``StateCacheSpec`` form :attr:`state_group` (None without them): their
    ``k_pools`` entry is the recurrent state's pool and their ``v_pools``
    entry the convolution's, ``state_rows`` rows each. A layer that states
    a ``ReadCacheSpec`` has None in both: :attr:`source_of_layer` names
    the paged layer whose pools and group table it reads. A layer whose
    spec is None keeps nothing and reads nothing (its mixer holds no
    state: a gated memory unit)."""

    def __init__(self, num_layers: int, num_blocks, block_size: int,
                 spec, max_blocks_per_seq: Optional[int] = None,
                 dtype=jnp.float32, prefix_cache: bool = False,
                 kv_dtype: Optional[str] = None,
                 state_rows: Optional[int] = None):
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype={kv_dtype!r} (want None or 'int8')")
        specs = list(spec) if isinstance(spec, (list, tuple)) \
            and not hasattr(spec, "kv_heads") else [spec] * num_layers
        if len(specs) != num_layers:
            raise ValueError(f"{len(specs)} layer specs for {num_layers} "
                             f"layers")
        grouped = _group_layers(specs)
        #: each layer's spec as the model stated it
        self.specs = specs
        state = [i for i, sp in enumerate(specs)
                 if isinstance(sp, StateCacheSpec)]
        self.state_group = None
        if state:
            kinds = {specs[i] for i in state}
            if len(kinds) > 1:
                raise NotImplementedError(
                    f"state layers of {len(kinds)} specs: {sorted(kinds)}")
            if state_rows is None or kv_dtype is not None:
                raise ValueError("state layers need state_rows (the slots "
                                 "+ 1) and keep no int8 pages")
            self.state_group = StateGroup(specs[state[0]], state, state_rows)
        if not grouped:
            raise ValueError("a paged cache needs at least one attention "
                             "layer")
        if isinstance(num_blocks, dict):
            names = [g[0] for g in grouped]
            if sorted(num_blocks) != sorted(names):
                raise ValueError(f"max_blocks names groups "
                                 f"{sorted(num_blocks)}; the model's layers "
                                 f"form {names}")
            sizes = [int(num_blocks[n]) for n in names]
        else:
            sizes = [int(num_blocks)] * len(grouped)
        if kv_dtype is not None and (len(grouped) > 1
                                     or grouped[0][1].latent):
            raise ValueError("int8 KV covers K/V pools of one layer group, "
                             "not latent pages nor several groups")
        self.groups = [CacheGroup(n, sp, layers, nb, block_size, prefix_cache)
                       for (n, sp, layers), nb in zip(grouped, sizes)]
        #: each layer's paged group; None for a state layer
        self.group_of_layer = [None] * num_layers
        for gi, g in enumerate(self.groups):
            for i in g.layers:
                self.group_of_layer[i] = gi
        #: the layer whose pages a read-only layer reads; None for others
        self.source_of_layer = [
            sp.source if isinstance(sp, ReadCacheSpec) else None
            for sp in specs]
        for i, src in enumerate(self.source_of_layer):
            if src is not None and (not 0 <= src < num_layers
                                    or self.group_of_layer[src] is None
                                    or self.groups[self.group_of_layer[
                                        src]].spec.latent):
                raise ValueError(f"layer {i} reads layer {src}'s pages: "
                                 f"that layer keeps no K/V pages")
        self.num_layers = num_layers
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq or max(sizes)
        #: compute dtype of the attention math / block transfers; the
        #: storage dtype below may be narrower
        self.compute_dtype = jnp.dtype(dtype)
        self.kv_dtype = kv_dtype
        store = jnp.int8 if kv_dtype == "int8" else dtype

        # +1: physical block 0 is the null block and backs no sequence
        def pool(g, width):
            # a K/V row narrower than the lanes is stored several tokens a
            # row (``tokens_a_row``): the pool's minor dimension whole lanes
            pack = 1 if g.spec.latent or kv_dtype is not None \
                or block_size & (block_size - 1) else tokens_a_row(width)
            return jnp.zeros((g.num_blocks + 1, g.spec.kv_heads,
                              block_size // pack, width * pack), store)
        by_layer = [None if gi is None else self.groups[gi]
                    for gi in self.group_of_layer]
        sg = self.state_group

        def rows(*shape):     # a state layer's pool: one row a slot + 1
            return jnp.zeros((sg.rows,) + shape, dtype)

        def state_rows():
            # a row's recurrent state as ``ops/pallas/ssd.py`` reads it:
            # the state width on sublanes, (head, head width) along lanes
            heads, head_dim, state_dim = sg.spec.ssm
            return rows(state_dim, heads * head_dim)
        # (no pool where a layer reads another's or keeps nothing)
        bare = [src is not None or sp is None
                for sp, src in zip(specs, self.source_of_layer)]
        self.k_pools = tuple(
            None if none else state_rows() if g is None
            else pool(g, g.spec.key_dim) for g, none in zip(by_layer, bare))
        self.v_pools = tuple(
            None if none else rows(int(np.prod(sg.spec.conv))) if g is None
            else None if g.spec.latent else pool(g, g.spec.value_dim)
            for g, none in zip(by_layer, bare))
        if kv_dtype == "int8":
            # per-token-slot, per-head dequant multipliers, paged like
            # the pools themselves so block tables address both
            self.k_scales = tuple(
                jnp.zeros((g.num_blocks + 1, g.spec.kv_heads, block_size),
                          jnp.float32) for g in by_layer)
            self.v_scales = tuple(jnp.zeros_like(s) for s in self.k_scales)
        else:
            self.k_scales = ()
            self.v_scales = ()
        self._copy_fn = None  # lazily-jitted COW block copy

    def sole_group(self, what: str) -> CacheGroup:
        """The one group of a one-group cache; ``what`` refuses others, and
        a cache with state layers (a block id says nothing of their rows)."""
        if self.state_group is not None:
            raise NotImplementedError(
                f"{what} moves pages; layers "
                f"{list(self.state_group.layers)} keep a state row a slot "
                f"that no page holds (docs/SERVING.md \"State layers\")")
        if len(self.groups) > 1:
            raise NotImplementedError(
                f"{what} addresses one block id across every layer; this "
                f"cache has {len(self.groups)} layer groups "
                f"({[g.name for g in self.groups]}) with an allocator each "
                f"(docs/SERVING.md \"Layer groups\")")
        return self.groups[0]

    def assert_no_leaks(self):
        for g in self.groups:
            g.allocator.assert_no_leaks()

    def tightest(self) -> CacheGroup:
        """The group with the least allocatable share of its pool (free
        plus reclaimable): a sequence grows in every group or in none, so
        this one's headroom is the cache's."""
        return min(self.groups, key=lambda g: sum(g.fractions()))

    def block_seconds_total(self) -> float:
        """Pages held x seconds held, over every group's allocator."""
        return sum(g.allocator.block_seconds_total() for g in self.groups)

    def pool_bytes(self) -> dict:
        """Bytes the pools hold, by kind: ``latent`` (one-pool latent
        pages), ``kv`` (K and V pools, with their int8 scales) and, where
        the model has state layers, ``state`` (their state rows)."""
        out = {"latent": 0, "kv": 0}
        if self.state_group is not None:
            out["state"] = 0
        for i, gi in enumerate(self.group_of_layer):
            if self.k_pools[i] is None:
                continue            # reads another layer's pool, or none
            kind = "state" if gi is None else \
                "latent" if self.groups[gi].spec.latent else "kv"
            out[kind] += sum(
                int(p[i].nbytes) for p in (self.k_pools, self.v_pools,
                                           self.k_scales, self.v_scales)
                if p and p[i] is not None)
        return out

    @property
    def max_seq_len(self) -> int:
        """Longest sequence one block table can address."""
        return self.max_blocks_per_seq * self.block_size

    def blocks_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)  # ceil div

    def update_pools(self, k_pools, v_pools, k_scales=None, v_scales=None):
        """Swap in the pools returned by a compiled step (functional
        threading: the old arrays are dropped, nothing recompiles)."""
        self.k_pools = tuple(k_pools)
        self.v_pools = tuple(v_pools)
        if k_scales is not None:
            self.k_scales = tuple(k_scales)
        if v_scales is not None:
            self.v_scales = tuple(v_scales)

    def shard_pools(self, mesh, axis: str):
        """Tensor-parallel serving: place every pool with the KV-head
        dimension sharded over the mesh's ``axis``. One device_put per
        pool at engine construction; the compiled step keeps the
        sharding through its functional threading."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, axis, None, None))
        self.k_pools = tuple(jax.device_put(p, sh) for p in self.k_pools)
        self.v_pools = tuple(None if p is None else jax.device_put(p, sh)
                             for p in self.v_pools)
        if self.k_scales:
            ssh = NamedSharding(mesh, P(None, axis, None))
            self.k_scales = tuple(jax.device_put(p, ssh)
                                  for p in self.k_scales)
            self.v_scales = tuple(jax.device_put(p, ssh)
                                  for p in self.v_scales)

    def copy_block(self, src: int, dst: int):
        """Copy-on-write: duplicate physical block ``src`` into ``dst``
        across every layer's K and V pool. One jitted program for the
        engine's lifetime — src/dst are traced scalars, so the first
        divergence compiles it and every later COW reuses it."""
        import jax

        self.sole_group("copy_block (copy-on-write)")
        if self._copy_fn is None:
            def _copy(kps, vps, kss, vss, s, d):
                # a latent layer's v pool is None: tree_map passes it by
                return jax.tree_util.tree_map(
                    lambda p: p.at[d].set(p[s]), (kps, vps, kss, vss))
            donate = (0, 1, 2, 3) if jax.default_backend() == "tpu" else ()
            self._copy_fn = jax.jit(_copy, donate_argnums=donate)
        (self.k_pools, self.v_pools, self.k_scales,
         self.v_scales) = self._copy_fn(
            self.k_pools, self.v_pools, self.k_scales, self.v_scales,
            jnp.int32(src), jnp.int32(dst))

    # -- cross-replica block transfer (fleet disaggregation) ---------------
    def export_block(self, block_id: int) -> Tuple[np.ndarray, np.ndarray]:
        """Host-stage one physical block's KV rows across every layer:
        returns ``(k, v)`` numpy arrays of shape ``[num_layers, n_kv,
        block_size, hd]``; latent pages give ``(rows, None)`` with rows
        ``[num_layers, 1, block_size, key_dim]``. Device->host copy only
        — the caller must hold a reference on ``block_id`` for the
        duration (the fleet handoff claims one via ``reuse_cached``
        before calling)."""
        g = self.sole_group("export_block (fleet KV handoff)")
        k = np.stack([np.asarray(p[block_id]) for p in self.k_pools])
        if g.spec.latent:
            return k, None
        v = np.stack([np.asarray(p[block_id]) for p in self.v_pools])
        if self.kv_dtype == "int8":
            # wire format stays the compute dtype so handoffs work
            # between quantized and unquantized replicas
            ks = np.stack([np.asarray(p[block_id]) for p in self.k_scales])
            vs = np.stack([np.asarray(p[block_id]) for p in self.v_scales])
            cd = self.compute_dtype
            k = (k.astype(np.float32) * ks[..., None]).astype(cd)
            v = (v.astype(np.float32) * vs[..., None]).astype(cd)
        return k, v

    def import_block(self, block_id: int, k: np.ndarray, v=None):
        """Write host-staged KV rows into physical ``block_id`` on this
        replica (the inverse of :meth:`export_block`; ``v`` None for
        latent pages). One jitted
        row-set program for the cache's lifetime — destination id and
        rows are traced, so repeated handoffs reuse the executable.
        The caller owns ``block_id`` (freshly allocated) and registers
        it with the prefix index afterwards."""
        import jax

        self.sole_group("import_block (fleet KV handoff)")
        if getattr(self, "_import_fn", None) is None:
            if self.kv_dtype == "int8":
                from paddle_tpu.ops.paged_attention import \
                    quantize_kv_slots as _quantize_kv_rows

                def _imp(kps, vps, kss, vss, kr, vr, d):
                    kq, ks = _quantize_kv_rows(kr)
                    vq, vs = _quantize_kv_rows(vr)
                    return (tuple(p.at[d].set(kq[i])
                                  for i, p in enumerate(kps)),
                            tuple(p.at[d].set(vq[i])
                                  for i, p in enumerate(vps)),
                            tuple(p.at[d].set(ks[i])
                                  for i, p in enumerate(kss)),
                            tuple(p.at[d].set(vs[i])
                                  for i, p in enumerate(vss)))
            else:
                def _imp(kps, vps, kss, vss, kr, vr, d):
                    return (tuple(p.at[d].set(kr[i])
                                  for i, p in enumerate(kps)),
                            tuple(None if p is None else p.at[d].set(vr[i])
                                  for i, p in enumerate(vps)),
                            kss, vss)
            self._import_fn = jax.jit(_imp)
        dt = self.compute_dtype
        (self.k_pools, self.v_pools, self.k_scales,
         self.v_scales) = self._import_fn(
            self.k_pools, self.v_pools, self.k_scales, self.v_scales,
            jnp.asarray(k, dt), None if v is None else jnp.asarray(v, dt),
            jnp.int32(block_id))

    def match(self, tokens: Sequence[int], seed: Optional[bytes] = None):
        """Prefix admission: the longest prefix of ``n`` full blocks of
        ``tokens`` for which every group still holds what the next token
        can see there: blocks ``0..n-1`` without a window, under a window
        only those from :meth:`CacheGroup.first_visible_page`. Returns
        ``(tables, digests)``: a CLAIMED block-table prefix a group (one
        reference a block, the caller owns; null before a window group's
        first visible page) and the ``n`` chain digests. ``seed`` roots
        the chain in a namespace (the engine passes the LoRA adapter
        slot's digest so KV computed under one adapter never matches
        another tenant's identical prompt); ``None`` is the base model's.
        A one-group cache may match the whole prompt (the scheduler caps
        it by copy-on-write); several groups have no block copy and leave
        at least one token to prefill. Lookups and hits count on the first
        group's index. A cache with state layers matches nothing: reusing a
        prefix needs the state at its boundary, which no page holds; a
        lookup that found blocks counts as ``declined``."""
        lead = self.groups[0].prefix_cache
        lead.lookups += 1
        bs = self.block_size
        digests: List[bytes] = []
        parent = seed
        whole = [g.prefix_cache for g in self.groups if g.window is None]
        for i in range((len(tokens) - (len(self.groups) > 1)) // bs):
            d = chain_hash(parent, tokens[i * bs:(i + 1) * bs])
            if any(pc.lookup(d) is None for pc in whole):
                break
            digests.append(d)
            parent = d
        if self.state_group is not None:
            if digests:
                lead.declined += 1
                lead.declined_tokens += len(digests) * bs
            return [[] for _ in self.groups], []
        n = len(digests)
        while n > 0 and not all(
                g.prefix_cache.lookup(digests[i]) is not None
                for g in self.groups if g.window is not None
                for i in range(g.first_visible_page(n * bs), n)):
            n -= 1
        tables = []
        for g in self.groups:
            first = g.first_visible_page(n * bs)
            claimed = [g.prefix_cache.claim(d) for d in digests[first:n]]
            tables.append([NULL_BLOCK] * first + claimed)
        if any(b is None for t in tables for b in t):
            # an index entry outlived its block: give back, count a miss
            for g, t in zip(self.groups, tables):
                g.allocator.free([b for b in t if b])
            return [[] for _ in self.groups], []
        if n:
            lead.hits += 1
        return tables, digests[:n]

    def pad_block_table(self, block_ids: Sequence[int]) -> np.ndarray:
        """[max_blocks_per_seq] int32 row, null-padded."""
        if len(block_ids) > self.max_blocks_per_seq:
            raise ValueError(
                f"sequence holds {len(block_ids)} blocks > table width "
                f"{self.max_blocks_per_seq}")
        row = np.full((self.max_blocks_per_seq,), NULL_BLOCK, np.int32)
        row[:len(block_ids)] = block_ids
        return row

    def gauge_in_use(self):
        """Publish pool occupancy through the observability registry."""
        from paddle_tpu.observability import get_registry
        g = get_registry().gauge(
            "serving_kv_blocks_in_use",
            "KV-cache blocks currently held by live sequences")
        g.set(self.groups[0].allocator.blocks_in_use())
        return g
