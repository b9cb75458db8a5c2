"""Continuous-batching serving engine over a block-paged KV cache.

``ServingEngine`` is the request-level runtime between the model zoo's
``generate`` surface and an HTTP front-end (``serving.server``). Where
``compiled_generate`` runs one fixed batch to completion (a straggler
stalls everyone, KV memory is worst-case), the engine keeps a FIXED
``max_batch``-slot decode layout and swaps finished slots for queued
requests between steps — so the decode step is compiled EXACTLY ONCE and
requests enter/leave the batch continuously.

ONE executable, traced a single time (ISSUE 8): every engine iteration
runs a **unified step** over a token-packed ragged batch — a flat
``[1, step_tokens]`` axis holding all live decode slots (one token
each) plus as many prefill chunks as the budget covers, back to back.
Attention reads go through the Ragged-Paged-Attention Pallas kernel on
TPU (``ops/pallas/ragged_paged_attention.py``; the XLA-gather fallback
elsewhere, or where ``attn_impl=`` pins it), which
streams each sequence's real pages instead of materializing padded
contexts — and because one kernel covers every prefill/decode mix,
chunked prefill no longer needs its own compiled executable.

The step threads the per-layer block pools functionally (pools in →
pools out), with block tables, token→sequence maps, and the kernel's
work lists as traced inputs — no shape ever changes, so recompilation
is structurally impossible; the ``step_traces`` counter (incremented at
trace time) makes that checkable from tests.

The step samples its own tokens and hands back ``[max_batch]`` int32;
a decode row of the next step reads its input from that array on the
device. The run loop therefore keeps one step in flight (ISSUE 32): it
plans, packs and dispatches step n+1 while the device runs step n, then
harvests step n (``_dispatch`` / ``_harvest``; ``step()`` by hand runs
the two in turn).

Telemetry goes through ``observability.metrics`` (queue depth,
running/waiting gauges, TTFT and inter-token-latency histograms,
token/preemption counters — names in docs/SERVING.md).
"""
from __future__ import annotations

import collections
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.metrics import label_key
from ..profiler import RecordEvent, trace_gc
from .kv_cache import NULL_BLOCK, PagedKVCache, chain_hash
from .scheduler import Request, RequestState, Scheduler, Unharvested

__all__ = ["ServingEngine", "RequestHandle", "serving_metrics"]


_serving_metrics_cache = None


def serving_metrics(registry=None) -> dict:
    """The ``serving_*`` metric families (created on first use) — one
    accessor shared by the engine, the HTTP server's shed path, and the
    KV cache gauge (mirrors ``checkpoint.writer.ckpt_metrics``;
    docs/SERVING.md documents names and semantics). The default-registry
    dict is cached: the server's 503 shed path calls this per rejection,
    exactly when every request thread is contending for the lock."""
    global _serving_metrics_cache
    if registry is None and _serving_metrics_cache is not None:
        return _serving_metrics_cache
    from paddle_tpu.observability import get_registry
    reg = registry if registry is not None else get_registry()
    d = _build_serving_metrics(reg)
    if registry is None:
        _serving_metrics_cache = d
    return d


def _build_serving_metrics(reg) -> dict:
    return {
        "requests": reg.counter(
            "serving_requests_total", "requests by final outcome"),
        "queue": reg.gauge(
            "serving_queue_depth", "requests waiting for a batch slot"),
        "running": reg.gauge(
            "serving_requests_running", "requests holding a batch slot"),
        "waiting": reg.gauge(
            "serving_requests_waiting", "requests queued (incl. preempted)"),
        "ttft": reg.histogram(
            "serving_ttft_seconds", "submit -> first generated token"),
        "queue_wait": reg.histogram(
            "serving_queue_wait_seconds",
            "submit -> first batch-slot admission (the TTFT share spent "
            "on queueing rather than prefill/compile)"),
        "itl": reg.histogram(
            "serving_inter_token_seconds", "gap between streamed tokens"),
        "latency": reg.histogram(
            "serving_request_latency_seconds", "submit -> request finished"),
        "tokens": reg.counter(
            "serving_tokens_total",
            "tokens processed, by kind (prompt incl. recompute/generated)"),
        "preemptions": reg.counter(
            "serving_preemptions_total", "sequences preempted (recompute)"),
        "steps": reg.counter(
            "serving_engine_steps_total", "compiled steps run, by kind"),
        "dispatched": reg.counter(
            "serving_steps_dispatched_total",
            "compiled steps by order: ahead (dispatched while the step "
            "before was still unharvested) / serial, and a serial step "
            "by reason (idle: nothing was in flight; preempt / cow / abort "
            "/ numerics: the step before was harvested first, see "
            "docs/SERVING.md)"),
        "drafts": reg.counter(
            "serving_draft_tokens_total",
            "draft tokens a step verified, by kind: drafted (rows that "
            "carried a draft) / accepted (drafts the model's own choice "
            "confirmed: each yields a second token from its step)"),
        "rpa_steps": reg.counter(
            "serving_rpa_steps_total",
            "RPA kernel grid steps a kv head and layer, by kind: live "
            "(work items that name a real run of pages) / walked (the "
            "kernel's grid bound: live + one step for each q tile without "
            "work) / pages (the pages the live items name); where the "
            "cache has several layer groups also by group"),
        "kv_released": reg.counter(
            "serving_kv_pages_released_total",
            "pages a window layer group gave back behind its sequences' "
            "windows, by group"),
        "moe_rows": reg.counter(
            "serving_moe_expert_rows_total",
            "token rows the step's routed experts took, by layer and by "
            "held expert (models with dropless held experts only)"),
        "rejections": reg.counter(
            "serving_rejections_total",
            "requests shed by graceful degradation, by reason "
            "(queue_full / deadline / fleet_saturated)"),
        # request-ledger headline numbers (ISSUE 16): scrapeable
        # without /statusz
        "in_flight": reg.gauge(
            "serving_requests_in_flight",
            "requests accepted but not yet finished (queued + running)"),
        "kv_block_seconds": reg.counter(
            "serving_kv_block_seconds_total",
            "pool occupancy integral: KV blocks held by live sequences "
            "x seconds held (the per-request cost ledger's denominator)"),
        "kv_blocks": reg.gauge(
            "serving_kv_blocks_in_use",
            "KV-cache blocks currently held by live sequences"),
        "kv_pool_bytes": reg.gauge(
            "serving_kv_pool_bytes",
            "bytes the paged cache's pools hold, by kind (latent: "
            "one-pool latent pages; kv: K and V pools with their scales)"),
        # the two stats()-only fields promoted to real gauge families
        # (ISSUE 11): Prometheus scrapers and the bench --report gate
        # see pool pressure and compile churn without polling /healthz
        "kv_headroom": reg.gauge(
            "serving_kv_headroom",
            "fraction of KV-cache blocks allocatable (free + reclaimable "
            "prefix-cached — the pressure signal before "
            "preemption-by-recompute starts churning)"),
        "kv_reclaimable": reg.gauge(
            "serving_kv_reclaimable",
            "fraction of KV-cache blocks parked refcount-0 in the prefix "
            "cache's reclaimable LRU tier (cache capacity, not pressure)"),
        "step_compiles": reg.gauge(
            "serving_step_compiles",
            "compiles of the ONE unified step executable (>1 means the "
            "compile-once contract broke)"),
        # prefix-cache KV reuse (ISSUE 15)
        "prefix_lookups": reg.counter(
            "serving_prefix_cache_lookups",
            "admissions that consulted the prefix-cache index"),
        "prefix_hits": reg.counter(
            "serving_prefix_cache_hits",
            "admissions that reused at least one cached KV block"),
        "prefix_evictions": reg.counter(
            "serving_prefix_cache_evictions",
            "reclaimable cached blocks repurposed by the allocator"),
        "prefix_declined": reg.counter(
            "serving_prefix_declined_total",
            "admissions that found a registered prefix and were refused "
            "it, by reason (state: the model has state layers, whose "
            "state at the prefix's end no page holds)"),
        "prefix_token_fraction": reg.gauge(
            "serving_prefix_cached_token_fraction",
            "cumulative fraction of prompt tokens served from the prefix "
            "cache instead of being prefilled"),
        # multi-tenant LoRA slots (ISSUE 20)
        "adapter_slots": reg.gauge(
            "serving_adapter_slots",
            "LoRA tenant slots the engine was built with (0 = plain "
            "single-model engine)"),
        "adapter_slots_loaded": reg.gauge(
            "serving_adapter_slots_loaded",
            "tenant slots currently holding a loaded adapter"),
        "adapter_requests": reg.counter(
            "serving_adapter_requests_total",
            "requests dispatched to a non-base adapter slot, by adapter"),
        "adapter_loads": reg.counter(
            "serving_adapter_loads_total",
            "adapter installs via load_adapter (no-retrace slot writes)"),
    }


def _row_args(rows, limit: int = 250) -> dict:
    """The rows as the step runs them, ``"<new>@<context>"`` in row order
    joined by ``;`` (a TraceMe cuts a value at a comma), as span arguments
    ``rows``, ``rows_1``, ``rows_2``, ...: whole rows each, a value under
    the 256 characters a trace keeps of one. Up to some thirty rows
    ``rows`` holds them all."""
    out, key, n = {}, "rows", 0
    for row in rows:
        if key in out and len(out[key]) + 1 + len(row) > limit:
            n += 1
            key = f"rows_{n}"
        out[key] = f"{out[key]};{row}" if key in out else row
    return out or {"rows": ""}


def _a_group(arrays):
    """One device array a layer group: how the compiled step takes the
    block tables and the work lists."""
    return tuple(jnp.asarray(a) for a in arrays)


@dataclass
class _Flight:
    """A dispatched step the host has not harvested: what it ran and the
    device arrays that hold what it has to say."""
    step: int
    #: (sequence, new tokens, is a prefill chunk, samples a token, rows
    #: of the new tokens that are drafts)
    entries: list
    #: [max_batch] int32 sampled tokens; a drafting engine's [4, max_batch]:
    #: the token, the token after an accepted draft, accepted, next draft
    tokens: jax.Array
    moe_rows: Optional[jax.Array] = None
    taps: Optional[dict] = None          # the numerics twin's extra output


class RequestHandle:
    """Caller-side view of a submitted request (thread-safe wait)."""

    def __init__(self, req: Request):
        self._req = req
        self._done = threading.Event()

    @property
    def req_id(self) -> int:
        return self._req.req_id

    @property
    def trace_id(self) -> Optional[str]:
        return self._req.trace_id

    @property
    def token_ids(self) -> List[int]:
        return list(self._req.generated)

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def result(self, timeout: Optional[float] = None) -> dict:
        """Block until finished; raises on request failure/timeout."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self._req.req_id} not finished in {timeout}s")
        r = self._req
        if r.state is RequestState.FAILED:
            raise RuntimeError(f"request {r.req_id} failed: {r.error}")
        return {
            "request_id": r.req_id,
            "trace_id": r.trace_id,
            "token_ids": list(r.generated),
            "num_generated": len(r.generated),
            "prompt_len": len(r.prompt_tokens),
            "finish_reason": r.finish_reason,
            "preemptions": r.preemptions,
            "ttft_s": r.ttft(),
            "latency_s": r.latency(),
        }


class ServingEngine:
    """Continuous-batching inference over any zoo causal LM that speaks
    the ``caches=`` protocol (Llama, MoE, the latent-attention MoE of
    ``models/pangu_moe.py`` — the ``compiled_generate`` family seam).

    What a model must state: ``cfg`` (``num_hidden_layers``,
    ``num_attention_heads``, a position cap), a backbone that takes
    ``caches=[RaggedLayerCache, ...]`` and returns ``(hidden, caches)``
    (``models.generation.decode_surfaces``), and ``kv_cache_spec()``:
    the ``ops.paged_attention.LayerCacheSpec`` of its layers (one, or a
    list of one a layer: layers of equal spec form a group with its own
    pools, block tables and kernel work list, and ``max_blocks`` may then
    be a mapping by group name), from which the pools are built.
    With ``draft_tokens=1`` also a drafter: ``draft_cache_spec()`` (the
    specs of its own layers, appended to the model's), a backbone that
    takes ``keep_residual=True`` and returns the stream before its final
    norm third, and ``draft(hidden, next_ids, caches=)`` (docs/SERVING.md
    "Drafts and verify rows"); a model without them is refused.
    Optional: ``moe_expert_rows()`` (the rows each held expert took in the
    traced step, ``[layers, held]`` int32: returned by the compiled step
    beside the tokens and published by the commit span and
    ``serving_moe_expert_rows_total``) and
    ``clear_decode_side_effects()``. A layer may state an
    ``ops.paged_attention.StateCacheSpec`` instead of pages (a state-space
    layer): it is handed a ``StateLayerCache`` and keeps a state row a slot
    (docs/SERVING.md "State layers"); such a model is served without
    drafts, a mesh, LoRA slots, int8 pools or block export."""

    def __init__(self, model, max_batch: int = 8, max_blocks=64,
                 block_size: int = 16, prefill_chunk: int = 16,
                 max_blocks_per_seq: Optional[int] = None,
                 warm_start_from: Optional[str] = None,
                 attn_impl: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 mesh=None, quantize: Optional[str] = None,
                 kv_dtype: Optional[str] = None, calibration=None,
                 draft_tokens: int = 0):
        import os

        from paddle_tpu.jit.functional import functional_state
        from paddle_tpu.models.generation import decode_surfaces
        from paddle_tpu.ops import paged_attention as pa
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            build_step_maps, default_tile_q, rpa_max_items, rpa_run_pages,
            rpa_tile_q)
        from paddle_tpu.quantization.weight_only import (
            WEIGHT_MODES, calibration_from_checkpoint, quantization_metrics,
            quantize_state)
        self._build_step_maps = build_step_maps  # hot path: import once

        model.eval()
        if warm_start_from is not None:
            self._load_into_model(model, warm_start_from)
        self.model = model
        cfg = model.cfg
        train, frozen, buffers = functional_state(model)
        self._st = {**train, **frozen, **buffers}
        self._backbone, self._project, dtype = decode_surfaces(
            model, self._st)
        # weight-only quantization (ISSUE 20): replace the projection
        # leaves with (values, scales) pairs dequantized inside the
        # compiled step. After decode_surfaces (which sniffs the embed
        # leaf's dtype), before _shard_state (which places the pairs).
        self.quantize = quantize or \
            os.environ.get("PADDLE_TPU_QUANT_WEIGHTS") or None
        if self.quantize is not None and self.quantize not in WEIGHT_MODES:
            raise ValueError(
                f"quantize={self.quantize!r} (want one of "
                f"{sorted(WEIGHT_MODES)})")
        if isinstance(calibration, str):
            calibration = calibration_from_checkpoint(calibration)
        self._calibration = calibration
        if self.quantize is not None:
            self._st = quantize_state(self._st, self.quantize,
                                      calibration=self._calibration)
            self._weight_dtype = WEIGHT_MODES[self.quantize][0]
        else:
            self._weight_dtype = str(jnp.dtype(dtype))
        # paged-KV quantization (ISSUE 20): int8 pool blocks +
        # per-(slot, head) scale pools, dequantized in the gather read
        self.kv_dtype = kv_dtype or \
            os.environ.get("PADDLE_TPU_QUANT_KV") or None
        if self.kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r} (want None or 'int8')")
        # multi-tenant LoRA slots (ISSUE 20): a model prepared with
        # tuning.apply_lora(n_slots=N) carries stacked [N+1, ...]
        # adapter params; batch rows dispatch by slot id, row 0 = base
        self.n_adapter_slots = int(getattr(model, "_lora_slots", 0) or 0)
        self._adapters = {}  # slot -> adapter name
        # per-slot load generation: seeds the prefix-cache chain so KV
        # computed under one adapter (or one load of a slot) never
        # answers a request decoding under another
        self._adapter_gen = {}  # slot -> int

        nl = cfg.num_hidden_layers
        # what each layer keeps of a token is the model's to state
        # (ops.paged_attention.LayerCacheSpec), never derived from cfg
        spec_fn = getattr(model, "kv_cache_spec", None)
        if spec_fn is None:
            raise TypeError(
                f"{type(model).__name__} states no kv_cache_spec(): a "
                f"served model returns its layers' LayerCacheSpec")
        specs = spec_fn()
        #: the layers that keep a state row a slot instead of pages
        self.state_layers = [] if hasattr(specs, "kv_heads") else [
            i for i, sp in enumerate(specs)
            if isinstance(sp, pa.StateCacheSpec)]
        if self.state_layers:
            refused = [what for what, on in (
                ("draft_tokens", draft_tokens), ("mesh=", mesh),
                ("LoRA slots", self.n_adapter_slots),
                ("kv_dtype='int8'", self.kv_dtype)) if on]
            if refused:
                raise NotImplementedError(
                    f"{', '.join(refused)} over state layers "
                    f"{self.state_layers}: a drafted row's state cannot be "
                    f"rolled back, and a state row is neither sharded, "
                    f"adapted nor quantized (docs/SERVING.md \"State "
                    f"layers\")")
        #: drafts a greedy decoding sequence brings to a step (0 or 1):
        #: the model's drafter guesses the token after next, the next
        #: step verifies the guess beside the token and yields one or two
        self.draft_tokens = int(draft_tokens)
        if self.draft_tokens not in (0, 1):
            raise ValueError(f"draft_tokens={draft_tokens!r} (want 0 or 1)")
        if self.draft_tokens:
            drafter = getattr(model, "draft_cache_spec", lambda: [])()
            if not drafter:
                raise TypeError(
                    f"{type(model).__name__} states no drafter "
                    f"(draft_cache_spec(), draft()): draft_tokens=1 needs "
                    f"a model that publishes one")
            if mesh is not None:
                raise NotImplementedError(
                    "draft_tokens with a tensor-parallel mesh")
            # the drafter's layers follow the model's in the cache
            specs = ([specs] * nl if hasattr(specs, "kv_heads")
                     else list(specs)) + list(drafter)
            nl += len(drafter)
        # one spec for every layer, or one a layer; the read kernel's
        # geometry (tile height, model-parallel split) is the first paged
        # layer's
        spec = specs if hasattr(specs, "kv_heads") else next(
            sp for sp in specs if hasattr(sp, "kv_heads"))
        n_kv = spec.kv_heads
        hd = spec.key_dim
        #: block-granular prefix-cache KV reuse (ISSUE 15) — on by
        #: default; PADDLE_TPU_PREFIX_CACHE=0 (or prefix_cache=False)
        #: restores the cache-off engine, the bit-parity oracle
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "PADDLE_TPU_PREFIX_CACHE", "1").lower() not in (
                "0", "off", "false")
        self.prefix_cache_enabled = bool(prefix_cache)
        #: tensor-parallel serving (ISSUE 15): mesh= shards the weights
        #: and the per-layer KV pools over the model-parallel axis; with
        #: no explicit mesh, PADDLE_TPU_SERVING_MP=N builds an mp mesh
        #: over the first N local devices
        if mesh is None:
            mp_env = int(os.environ.get("PADDLE_TPU_SERVING_MP", "0"))
            if mp_env > 1:
                from jax.sharding import Mesh
                devs = jax.devices()
                if len(devs) < mp_env:
                    raise ValueError(
                        f"PADDLE_TPU_SERVING_MP={mp_env} but only "
                        f"{len(devs)} devices are visible")
                mesh = Mesh(np.array(devs[:mp_env]), ("mp",))
        self.mesh = mesh
        self._mp_axis = None
        if mesh is not None:
            from paddle_tpu.distributed.fleet.mpu import _mp_axis
            self._mp_axis = _mp_axis(mesh)
            mp = mesh.shape[self._mp_axis]
            if mp > 1 and n_kv % mp:
                raise ValueError(
                    f"tensor-parallel serving shards the KV pools over "
                    f"the '{self._mp_axis}' axis: num_key_value_heads "
                    f"{n_kv} must divide by its size {mp}")
            if mp > 1 and not getattr(cfg, "tensor_parallel", False):
                warnings.warn(
                    "ServingEngine(mesh=) over a model built without "
                    "tensor_parallel=True: weights stay replicated; only "
                    "the KV pools shard", RuntimeWarning)
            self._shard_state()
        # position cap = the attention layers' RoPE table length.
        # MoeConfig carries no cap of its own — its attention blocks are
        # built from _attn_cfg(), so read the cap from there (falling
        # back to pool capacity only if a family defines neither)
        max_pos = getattr(cfg, "max_position_embeddings", None)
        if max_pos is None and hasattr(cfg, "_attn_cfg"):
            max_pos = cfg._attn_cfg().max_position_embeddings
        most_blocks = max(max_blocks.values()) \
            if isinstance(max_blocks, dict) else max_blocks
        if max_pos is None:
            max_pos = most_blocks * block_size
        if max_blocks_per_seq is None:
            max_blocks_per_seq = min(most_blocks, -(-max_pos // block_size))
        self.cache = PagedKVCache(nl, max_blocks, block_size, specs,
                                  max_blocks_per_seq=max_blocks_per_seq,
                                  dtype=dtype,
                                  prefix_cache=self.prefix_cache_enabled,
                                  kv_dtype=self.kv_dtype,
                                  state_rows=(int(max_batch) + 1
                                              if self.state_layers else None))
        if self.mesh is not None:
            self.cache.shard_pools(self.mesh, self._mp_axis)
        if self.kv_dtype is not None:
            quantization_metrics()["kv_scale_bytes"].set(
                sum(int(s.nbytes) for s in
                    self.cache.k_scales + self.cache.v_scales))
        self.max_model_len = min(self.cache.max_seq_len, max_pos)
        self.max_batch = int(max_batch)
        self.prefill_chunk = int(prefill_chunk)
        #: attention read path, resolved at construction (None = rpa on
        #: TPU, gather elsewhere; gather over int8 pools) and stated on
        #: the caches the step builds: here it sizes the q tile, decides
        #: whether a step builds the kernel's work list, and is reported
        if self.kv_dtype is not None and attn_impl == "rpa":
            warnings.warn(
                "kv_dtype='int8' forces attn_impl='gather' (the RPA "
                "kernel reads unquantized pools)", RuntimeWarning)
        self.attn_impl = pa.paged_attention_impl(
            attn_impl, quantized=self.kv_dtype is not None)
        # unified-step geometry: the flat token budget covers every
        # decode slot plus one full prefill chunk, rounded up to the RPA
        # kernel's q-tile height (autotunable on chip); max_items sizes
        # the arrays of the kernel's flat work list (nothing walks it:
        # the kernel's trip count is the list's live length). A
        # gather-pinned engine keeps the default tile — sweeping RPA
        # kernel candidates it will never execute would be pure startup
        # cost
        n_heads = cfg.num_attention_heads
        groups = self.cache.groups
        # (a decode slot's rows: its own and, where it drafts, its draft's)
        budget = self.max_batch * (1 + self.draft_tokens) \
            + self.prefill_chunk
        self._tile_q = default_tile_q(n_heads // n_kv, dtype) \
            if self.attn_impl == "gather" else rpa_tile_q(
                budget, n_heads, n_kv, hd,
                block_size, self.cache.max_blocks_per_seq,
                groups[0].num_blocks, dtype=str(jnp.dtype(dtype)))
        # one tile height for every group's list (the tiles cut the one
        # packed token axis): the tallest any group's head grouping asks
        self._tile_q = max([self._tile_q] + [
            default_tile_q(n_heads // g.spec.kv_heads, dtype)
            for g in groups[1:]])
        self.step_tokens = -(-budget // self._tile_q) * self._tile_q
        num_tiles = self.step_tokens // self._tile_q

        # a group's work list: the pages an item names (the kernel reads
        # it off the pool it is handed and the layer's window, the list's
        # builder is told the same), the window where its layers have
        # one, and the static length of its arrays
        def maps_kw(g):
            sp = g.spec
            run = rpa_run_pages(
                block_size, sp.key_dim,
                sp.value_cols if sp.latent else sp.value_dim,
                self.cache.k_pools[g.layers[0]].dtype.itemsize,
                latent=sp.latent, window=sp.window)
            return dict(
                total_tokens=self.step_tokens, tile_q=self._tile_q,
                block_size=block_size, max_seqs=self.max_batch,
                run_pages=run, window=sp.window,
                max_items=rpa_max_items(
                    num_tiles, self.max_batch, self.cache.max_blocks_per_seq,
                    run, window=sp.window, tile_q=self._tile_q,
                    block_size=block_size))
        self._maps_kw = [maps_kw(g) for g in groups]
        # read by the accepted benchmark's test of its pages-per-item reader
        self._run_pages = self._maps_kw[0]["run_pages"]
        # the work lists of a step without work (one sentinel item a
        # tile): what the gather path feeds (same traced shapes, ignored
        # by the gather read — built once, not per step)
        self._null_step_maps = [build_step_maps([0], [], **kw)
                                for kw in self._maps_kw]
        self.scheduler = Scheduler(self.cache, self.max_batch,
                                   self.prefill_chunk,
                                   step_tokens=self.step_tokens,
                                   draft_tokens=self.draft_tokens)
        #: what the drafts came to: rows that carried one, those the
        #: model's choice confirmed, tokens decoding sequences emitted
        #: and the rows they were emitted from (``stats()["drafts"]``)
        self._draft_counts = {"drafted": 0, "accepted": 0, "emitted": 0,
                              "decode_seqs": 0}

        #: executable-compilation counter — incremented at TRACE time,
        #: so it equals the number of compiles of the ONE unified step
        self.step_traces = 0
        self._step = self._build_step()
        # what a step is handed where no row reads the step before
        # (token array of zeros; the source rows are it less one) and
        # where no row samples (settings, key base, key counts): built once
        self._no_tokens = jnp.zeros((self.max_batch,), jnp.int32)
        if self.mesh is not None:     # placed as a step's own tokens are
            self._no_tokens = jax.device_put(self._no_tokens,
                                             self._replicated())
        self._greedy = (jnp.zeros((3, self.max_batch), jnp.float32),
                        jax.random.key_data(jax.random.key(0)),
                        jnp.zeros((self.max_batch,), jnp.uint32))
        self._base_key = (None, None)   # the host stream's, and its words
        # a drafting engine's three more inputs at rest: each row's next
        # token, the draft's row a slot, (samples, has a draft) a slot
        self._no_drafts = (
            jnp.zeros((self.step_tokens,), jnp.int32), self._no_tokens,
            jnp.zeros((2, self.max_batch), jnp.int32)) \
            if self.draft_tokens else ()
        # the step before's output where there is none to read
        self._no_prev = jnp.zeros((4, self.max_batch), jnp.int32) \
            if self.draft_tokens else self._no_tokens
        #: steps dispatched and not harvested, oldest first: one between
        #: two turns of the run loop, two between a turn's dispatch and
        #: its harvest, none outside ``step()`` called by hand
        self._flights = collections.deque()
        #: sequences whose last token is sampled (a finish by length):
        #: the next harvest takes their slot and pages back
        self._retiring: List[Request] = []
        #: set where the step in flight must be harvested before the
        #: next is planned (``abort`` of a sequence in it)
        self._harvest_first: Optional[str] = None
        # numerics twin (docs/OBSERVABILITY.md#numerics): an instrumented
        # build of the SAME unified step, compiled lazily on the first
        # sampled step when PADDLE_TPU_NUMERICS is armed — it substitutes
        # for the plain step on sampled steps (taps are identity, so the
        # logits are the same program), feeding the decode-path
        # activation-range drift gauges. Disarmed: both stay None and the
        # engine is byte-for-byte the pre-numerics engine.
        self._numerics_step = None
        self._numerics_order = None
        self._decode_steps = 0

        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._thread: Optional[threading.Thread] = None
        self._shutdown = False
        self._handles = {}  # req_id -> RequestHandle
        self._published_preemptions = 0
        # per-request cost ledger (ISSUE 16): armed per-engine at
        # construction — PADDLE_TPU_REQUEST_LEDGER=0 builds a disarmed
        # engine whose hot path pays only attribute reads on None
        from paddle_tpu.observability import requests as obs_requests
        self._ledger = obs_requests.maybe_arm()
        self._new_trace_id = obs_requests.new_trace_id
        self._published_block_seconds = 0.0
        # prefix-cache counter cursors (registry counters are process-
        # global; publish per-engine deltas like preemptions do)
        self._published_prefix = {"lookups": 0, "hits": 0, "evictions": 0,
                                   "declined": 0}
        self._prompt_tokens_prefilled = 0
        self._init_metrics()

    # -- weights -----------------------------------------------------------
    @staticmethod
    def _read_checkpoint_state(path: str, step: Optional[int] = None):
        import os
        from paddle_tpu.framework.io import load
        if os.path.isdir(path):
            from paddle_tpu.checkpoint import load_state_dir
            state = load_state_dir(path, step=step)
        else:
            state = load(path)
        # training checkpoints hold {"model": ..., "optimizer": ...};
        # serving only wants the model half (flat state_dicts key by
        # qualified param name, never a bare "model" dict)
        if isinstance(state, dict) and isinstance(state.get("model"), dict):
            state = state["model"]
        return state

    @classmethod
    def _load_into_model(cls, model, path: str, step: Optional[int] = None):
        model.set_state_dict(cls._read_checkpoint_state(path, step))

    def load_weights(self, path: str, step: Optional[int] = None):
        """Warm-start: swap in weights from a checkpoint — a training
        ``CheckpointManager`` directory (latest or explicit ``step``), a
        single ``step_N`` dir, or a flat ``.pdparams`` file. The compiled
        unified step is untouched (the state dict is a traced input,
        same shapes/dtypes), so no recompilation happens —
        this is the serving warm-start seam (docs/CHECKPOINT.md).

        Refuses while requests are in flight: their KV cache was computed
        under the old weights, and decoding on would silently garble the
        rest of their output — ``drain()`` first.

        Dtype guard (ISSUE 20): every incoming leaf must land with the
        dtype the compiled step was traced against (a quantized leaf's
        LOGICAL dtype — the fresh weights are re-quantized afterwards).
        A floating→floating mismatch is cast loudly; anything else
        refuses with the leaf's name, so a bf16 checkpoint can never be
        device_put as garbage bits into an f32/int8 engine."""
        from paddle_tpu.jit.functional import functional_state
        from paddle_tpu.quantization.weight_only import quantize_state
        with self._lock:
            active = len(self._handles)
            if active:
                raise RuntimeError(
                    f"cannot swap weights with {active} request(s) in "
                    f"flight (their KV cache predates the new weights); "
                    f"drain() the engine first")
            # the guard must read the RAW checkpoint leaves: Layer
            # set_value casts silently, so a post-load functional_state
            # always looks clean even when the checkpoint was not
            raw = self._read_checkpoint_state(path, step)
            checked = {}
            for k, v in raw.items():
                arr = v.data if hasattr(v, "data") else v
                exp = self._st.get(k)
                if exp is not None:
                    want = jnp.dtype(exp.dtype)  # QuantizedLeaf -> logical
                    got = jnp.dtype(getattr(arr, "dtype",
                                            np.asarray(arr).dtype))
                    if got != want:
                        if jnp.issubdtype(got, jnp.floating) and \
                                jnp.issubdtype(want, jnp.floating):
                            warnings.warn(
                                f"load_weights: casting leaf '{k}' "
                                f"{got} -> {want} to match the compiled "
                                f"step", RuntimeWarning)
                            arr = jnp.asarray(
                                np.asarray(arr)).astype(want)
                        else:
                            raise ValueError(
                                f"load_weights: leaf '{k}' is {got} but "
                                f"the engine serves it as {want} — "
                                f"refusing the checkpoint")
                checked[k] = arr
            self.model.set_state_dict(checked)
            train, frozen, buffers = functional_state(self.model)
            new = {**train, **frozen, **buffers}
            if self.quantize is not None:
                # same deterministic target set as at construction, so
                # the step's input structure (and the one executable)
                # is unchanged
                new = quantize_state(new, self.quantize,
                                     calibration=self._calibration)
            self._st = new
            if self.mesh is not None:
                self._shard_state()

    def _shard_state(self):
        """Tensor-parallel mode: place every functional-state leaf on
        the engine mesh — parameters by their mpu-layer PartitionSpec
        annotation (``shard_tensor`` stamped it at construction),
        everything else replicated. One device_put per leaf; the
        compiled step's in-shardings follow the committed arrays, so
        ``warm_start_from=`` / ``load_weights`` spin-up is unchanged."""
        from jax.sharding import NamedSharding, PartitionSpec

        from paddle_tpu.distributed import spec_of
        from paddle_tpu.quantization.weight_only import (
            QuantizedLeaf, shard_quantized)

        named = dict(self.model.named_parameters())
        for n, b in self.model.named_buffers():
            if b is not None:
                named[n] = b
        rep = PartitionSpec()
        out = {}
        for k, v in self._st.items():
            spec = spec_of(named[k]) if k in named else rep
            if isinstance(v, QuantizedLeaf):
                # values carry the weight's spec, the 1-D scales its
                # channel-axis entry (dequant stays collective-free)
                out[k] = shard_quantized(v, self.mesh, spec)
            else:
                out[k] = jax.device_put(v, NamedSharding(self.mesh, spec))
        self._st = out

    def _key_words(self, key):
        """The raw words of the host stream's base key (kept: the base
        changes only with the seed)."""
        if self._base_key[0] is not key:
            self._base_key = (key, jax.random.key_data(key))
        return self._base_key[1]

    def _replicated(self):
        """Whole on every device of the engine's mesh; None without one."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    # -- the one compiled step ---------------------------------------------
    def _build_step(self, instrument: bool = False):
        import contextlib

        from paddle_tpu.core.autograd import no_grad
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.jit.functional import swap_state
        from paddle_tpu.models.generation import sample_rows
        from paddle_tpu.observability import numerics
        from paddle_tpu.ops import paged_attention as pa
        from paddle_tpu.quantization.weight_only import QuantizedLeaf
        from paddle_tpu.tuning import lora

        model, backbone, project = self.model, self._backbone, self._project
        nl = self.model.cfg.num_hidden_layers
        drafting = bool(self.draft_tokens)
        nl_all = self.cache.num_layers      # with the drafter's layers
        S = self.max_batch
        impl = self.attn_impl
        kv_quant = self.kv_dtype is not None
        n_slots = self.n_adapter_slots
        tap_order = [] if instrument else None
        moe_rows = getattr(model, "moe_expert_rows", None)

        def pool(pools, i):  # a latent layer has no v pool, and an
            # unquantized engine no scale pools
            return Tensor(pools[i]) if pools and pools[i] is not None \
                else None

        def data(t):
            return None if t is None else t.data

        group_of = self.cache.group_of_layer
        windows = [g.window for g in self.cache.groups]
        n_paged = len(self.cache.groups)
        whole = self._replicated()

        def feed_drafting(tokens, ctx, pos, sid, last_idx, prev, src,
                          last2, flags):
            """A drafting engine's rows fed from the step before (``prev``
            [4, max_batch]: token, token after an accepted draft, accepted,
            next draft; ``src``: a slot's row there, -1 where the host's
            values stand): the slot's newest token, its draft, and its
            positions, which the host packed at their least and an accepted
            draft moves on by one."""
            T = tokens.shape[1]
            r, fed = jnp.maximum(src, 0), src >= 0
            newest = jnp.where(prev[2][r] > 0, prev[1][r], prev[0][r])
            toks = tokens[0].at[jnp.where(fed, last_idx, T)].set(
                newest, mode="drop")
            toks = toks.at[jnp.where(fed & (flags[1] > 0), last2, T)].set(
                prev[3][r], mode="drop")
            shift = jnp.concatenate([jnp.where(fed, prev[2][r], 0),
                                     jnp.zeros((1,), jnp.int32)])
            return toks[None], ctx + shift, pos + shift[sid]

        def verify_and_draft(tokens, caches, last_idx, sample, nxt, last2,
                             flags):
            """A drafting engine's forward (inside the model's swapped
            state). A decoding sequence's rows are its newest token and,
            where ``flags[1]``, the draft of the one after (row
            ``last2``); ``last_idx`` is the row whose logits give its
            next token (the first of the pair; a chunk's last row). The
            draft is accepted where the model's own choice equals it, and
            the second row's choice is then the token after. The drafter
            runs over every row, fed the stream before the final norm and
            each row's next token: ``nxt`` from the host (a prompt's next
            token) and, where ``flags[0]`` (the row samples), the token
            just chosen. Returns ``([4, max_batch] int32: token, token
            after an accepted draft, accepted, next draft), caches)``."""
            T = tokens.shape[1]
            h, new_caches, resid = backbone(
                Tensor(tokens), caches=caches[:nl], keep_residual=True)

            def choose(hidden, rows):           # the head at those rows
                return project(Tensor(hidden.data[0][rows][:, None, :])) \
                    .data[:, 0].astype(jnp.float32)
            logits = choose(h, jnp.concatenate([last_idx, last2]))
            first = sample(logits[:S, None])
            second = jnp.argmax(logits[S:], -1).astype(jnp.int32)
            samples, has_draft = flags[0] > 0, flags[1] > 0
            accepted = has_draft & (first == tokens[0][last2])
            # each row's next token: the step's own choices where the
            # host could not know them (out of range: dropped)
            nxt = nxt.at[jnp.where(samples, last_idx, T)].set(
                first, mode="drop")
            nxt = nxt.at[jnp.where(has_draft, last2, T)].set(
                second, mode="drop")
            hd, draft_caches = model.draft(
                Tensor(resid.data), Tensor(nxt[None]), caches=caches[nl:])
            # the guess that is kept: at the draft's row where it was
            # accepted (that row's next token is the model's own choice)
            guess = jnp.argmax(
                choose(hd, jnp.where(accepted, last2, last_idx)), -1)
            out = jnp.stack([first, second, accepted.astype(jnp.int32),
                             guess.astype(jnp.int32)])
            return out, list(new_caches) + list(draft_caches)

        def step(stt, tokens, k_pools, v_pools, k_scales, v_scales,
                 bts, cu, ctx, sid, pos, ssqs, sbks, stls, last_idx, aid,
                 prev_tokens, src, samp, key_base, key_counts, *draft_in):
            # executes at trace time only — counting compiles is the
            # point (the compile-once guard tests read it)
            self.step_traces += 1  # analysis: allow(trace-attr-mutation)
            # a decode row's input, where the step before sampled it and
            # the host has not read it: row ``src`` of that step's token
            # array (-1: the host's token stands). The token never leaves
            # the device between the two steps.
            if drafting:
                tokens, ctx, pos = feed_drafting(
                    tokens, ctx, pos, sid, last_idx, prev_tokens, src,
                    *draft_in[1:])
            else:
                fed = jnp.concatenate(
                    [src, jnp.full((1,), -1, src.dtype)])[sid]
                tokens = jnp.where(
                    fed >= 0, prev_tokens[jnp.maximum(fed, 0)],
                    tokens[0])[None]
            # weight-only quantization: dequantize the (values, scales)
            # leaves HERE, inside the trace, so XLA fuses the multiply
            # into the consuming matmuls and swap_state sees plain
            # arrays of the model's dtype
            stt = {k: (v.dequantize() if isinstance(v, QuantizedLeaf)
                       else v) for k, v in stt.items()}
            # a set of step metadata a layer group (``bts``, ``ssqs``,
            # ``sbks``, ``stls``: one array a group); the token axis'
            # own (cu, ctx, sid, pos) is the step's
            shared = [Tensor(a) for a in (cu, ctx, sid, pos)]
            metas = [[Tensor(bt)] + shared + [Tensor(a) for a in work]
                     for bt, *work in zip(bts, ssqs, sbks, stls)]
            caches = [pa.StateLayerCache(
                pool(k_pools, i), pool(v_pools, i), Tensor(bts[n_paged]),
                *shared) if group_of[i] is None else pa.RaggedLayerCache(
                pool(k_pools, i), pool(v_pools, i), *metas[group_of[i]],
                pool(k_scales, i), pool(v_scales, i),
                impl=impl, mesh=self.mesh, window=windows[group_of[i]])
                for i in range(nl_all)]
            # per-row LoRA dispatch: pin this step's token->slot ids for
            # the adapter hooks traced inside the backbone call
            adapters = (lora.adapter_ids(aid) if n_slots
                        else contextlib.nullcontext())

            def sample(logits):
                # ``logits`` [max_batch, 1, V]: the tokens are sampled in
                # the program and the logits stay there. samp: temperature,
                # top_k, top_p a row (0 at a greedy row); a sampled row's
                # key is the host stream's, fold_in(key_base, its count)
                # (the base as its raw words: a typed key among the
                # arguments takes the jitted call off its fast path)
                base = jax.random.wrap_key_data(key_base)
                keys = jax.vmap(lambda c: jax.random.fold_in(base, c))(
                    key_counts)
                rows_v = logits[:, 0]
                if whole is not None:
                    # a vocabulary split over the model-parallel axis is
                    # gathered once, as the logits' way to the host was;
                    # the argmax and the sorts then run on whole rows
                    rows_v = jax.lax.with_sharding_constraint(rows_v, whole)
                return sample_rows(
                    rows_v.astype(jnp.float32), samp[0],
                    samp[1].astype(jnp.int32), samp[2], keys)

            with numerics.collect(instrument) as col, no_grad(), \
                    swap_state(model, stt, collect_buffers=False), \
                    adapters:
                if drafting:
                    sampled, new_caches = verify_and_draft(
                        tokens, caches, last_idx, sample, *draft_in)
                else:
                    h, new_caches = backbone(Tensor(tokens), caches=caches)
                    # logits at each sequence's LAST packed token (rows of
                    # empty metadata slots gather token 0 — discarded by
                    # the host-side harvest)
                    hsel = Tensor(h.data[0][last_idx][:, None, :])
                    logits = project(hsel)         # [max_batch, 1, V]
                # the rows each held expert took, where the model has
                # such layers (trace time: the plain step gains nothing)
                rows = () if moe_rows is None else (moe_rows().data,)
            kps = tuple(data(c.pools()[0]) for c in new_caches)
            vps = tuple(data(c.conv if isinstance(c, pa.StateLayerCache)
                             else c.v_pool) for c in new_caches)
            if kv_quant:
                kss = tuple(c.k_scale.data for c in new_caches)
                vss = tuple(c.v_scale.data for c in new_caches)
            else:
                kss, vss = (), ()
            if not drafting:
                sampled = sample(logits.data)
                if whole is not None:
                    # the next step takes the array back as placed here
                    sampled = jax.lax.with_sharding_constraint(sampled,
                                                               whole)
            out = (sampled, kps, vps, kss, vss) + rows
            if not instrument:
                return out
            # trace-time fill of the execution-order cell (jax pytrees
            # iterate dicts key-sorted; the drift gauges want model order)
            tap_order[:] = list(col.taps)
            return out + (col.taps,)

        # donating the pools (and scale pools) lets XLA update them in
        # place on TPU; the CPU backend can't honor donation (harmless
        # warning), so gate it
        donate = (2, 3, 4, 5) if jax.default_backend() == "tpu" else ()
        fn = jax.jit(step, donate_argnums=donate)
        return (fn, tap_order) if instrument else fn

    def memory_report(self):
        """XLA's memory accounting of the ONE unified step
        (``observability.memory.MemoryReport``; None when the backend
        doesn't report) — the serving-side twin of
        ``TrainStep.memory_report``. Rides :meth:`_lowered_step`, so it
        inherits the same neutrality contract as :meth:`compiled_hlo`:
        pools/scheduler/rng untouched, MoE side effects cleared, and no
        retrace (``lower`` shares the jit trace cache with real calls —
        ``step_compiles`` stays truthful)."""
        from paddle_tpu.observability.memory import MemoryReport
        return MemoryReport.from_compiled(
            self._lowered_step().compile(), source="serving_step")

    def compiled_hlo(self) -> str:
        """Compiled-HLO text of the ONE unified step (the inspection seam
        ``paddle_tpu.analysis`` audits — mirrors ``TrainStep.compiled_hlo``).

        State-neutral where it matters (the PR 7 rng-stream lesson):
        the step never executes, so pools, scheduler and rng are
        untouched, and MoE gate side effects from the trace (``l_aux``
        tracers) are cleared. The ``step_traces`` counter is NOT
        masked: ``lower()`` shares the jit trace/executable cache with
        real calls, so an inspection-first engine reads 1 after its
        first real step exactly like an uninspected one (verified by
        the state-neutrality test) — the compile-once accounting stays
        truthful rather than under-reporting a compile that happened."""
        return self._lowered_step().compile().as_text()

    def _lowered_step(self):
        """The unified step's ``jax.stages.Lowered`` on a zero-work
        layout (the ``compiled_hlo`` internals; the program auditor
        also reads ``.args_info`` from it for per-leaf donation
        accounting). Same neutrality contract as ``compiled_hlo``."""
        T, S = self.step_tokens, self.max_batch
        tokens = np.zeros((1, T), np.int32)
        bt = np.zeros((S + 1, self.cache.max_blocks_per_seq), np.int32)
        cu = np.zeros((S + 2,), np.int32)
        ctx = np.zeros((S + 1,), np.int32)
        sid = np.full((T,), S, np.int32)
        pos = np.zeros((T,), np.int32)
        last_idx = np.zeros((S,), np.int32)
        aid = np.zeros((T,), np.int32)
        maps = self._null_step_maps
        tables = [bt for _ in maps] + ([np.zeros((S + 1,), np.int32)]
                                       if self.state_layers else [])
        with self._lock:
            try:
                return self._step.lower(
                    self._st, jnp.asarray(tokens), self.cache.k_pools,
                    self.cache.v_pools, self.cache.k_scales,
                    self.cache.v_scales, _a_group(tables),
                    jnp.asarray(cu), jnp.asarray(ctx), jnp.asarray(sid),
                    jnp.asarray(pos), _a_group(m.step_seq for m in maps),
                    _a_group(m.step_blk for m in maps),
                    _a_group(m.step_tile for m in maps), jnp.asarray(last_idx),
                    jnp.asarray(aid), self._no_prev, self._no_tokens - 1,
                    *self._greedy, *self._no_drafts)
            finally:
                self._clear_model_side_effects()

    # -- metrics -----------------------------------------------------------
    def _init_metrics(self):
        m = serving_metrics()
        self._m_requests = m["requests"]
        self._m_queue = m["queue"]
        self._m_running = m["running"]
        self._m_waiting = m["waiting"]
        self._m_ttft = m["ttft"]
        self._m_queue_wait = m["queue_wait"]
        self._m_itl = m["itl"]
        self._m_latency = m["latency"]
        self._m_tokens = m["tokens"]
        self._m_preempt = m["preemptions"]
        self._m_steps = m["steps"]
        self._m_dispatched = m["dispatched"]
        self._m_rpa_steps = m["rpa_steps"]
        self._m_drafts = m["drafts"]
        self._m_kv_released = m["kv_released"]
        self._m_moe_rows = m["moe_rows"]
        self._moe_keys = []             # label keys, (layer, expert) flat
        self._m_in_flight = m["in_flight"]
        self._m_kv_block_seconds = m["kv_block_seconds"]
        self._m_kv_headroom = m["kv_headroom"]
        self._m_kv_reclaimable = m["kv_reclaimable"]
        self._m_step_compiles = m["step_compiles"]
        self._m_prefix_lookups = m["prefix_lookups"]
        self._m_prefix_hits = m["prefix_hits"]
        self._m_prefix_evictions = m["prefix_evictions"]
        self._m_prefix_declined = m["prefix_declined"]
        self._m_prefix_token_fraction = m["prefix_token_fraction"]
        self._m_adapter_requests = m["adapter_requests"]
        m["adapter_slots"].set(self.n_adapter_slots)
        m["adapter_slots_loaded"].set(len(self._adapters))
        self.cache.gauge_in_use()
        for kind, n in self.cache.pool_bytes().items():
            m["kv_pool_bytes"].set(n, kind=kind)     # fixed at construction
        self._register_memory_owners()

    def _register_memory_owners(self):
        """Register this engine's long-lived HBM owners with the memory
        ledger (docs/OBSERVABILITY.md#memory): the block-paged KV pools
        and the functional model state the step threads. Weakref
        closures so a discarded engine unregisters itself; a second
        engine in the same process simply takes over the names (the
        ledger keys by owner, latest registration wins)."""
        import weakref

        from paddle_tpu.observability import memory as _obs_memory

        wself = weakref.ref(self)

        def _kv_pools():
            eng = wself()
            if eng is None:
                return None
            # int8-KV engines: the scale pools are part of the cache's
            # HBM bill (the ledger pins the doubled-max_batch headroom)
            return (eng.cache.k_pools, eng.cache.v_pools,
                    eng.cache.k_scales, eng.cache.v_scales)

        def _model_state():
            eng = wself()
            if eng is None:
                return None
            return eng._st

        _obs_memory.register("kv_cache", _kv_pools)
        _obs_memory.register("serving_params", _model_state)

    def _update_gauges(self):
        # queue depth = never-started arrivals; waiting also counts
        # preempted sequences awaiting readmission
        fresh = sum(1 for r in self.scheduler.waiting
                    if r.preemptions == 0)
        self._m_queue.set(fresh)
        self._m_waiting.set(self.scheduler.num_waiting)
        self._m_running.set(self.scheduler.num_running)
        self.cache.gauge_in_use()
        # preemptions happen inside the scheduler; publish the delta
        # against a PER-ENGINE cursor (the registry counter is process-
        # global and may aggregate several engines)
        new = self.scheduler.num_preemptions - self._published_preemptions
        if new > 0:
            self._m_preempt.inc(new)
            self._published_preemptions += new
        # headroom splits free vs reclaimable (ISSUE 15): cached
        # refcount-0 blocks are evictable capacity, not pressure — the
        # headroom gauge counts both so load shedding doesn't misread a
        # warm cache as a full pool
        # (the tightest group's: a sequence grows in every group or none)
        free, reclaim = self.cache.tightest().fractions()
        self._m_kv_headroom.set(free + reclaim)
        self._m_kv_reclaimable.set(reclaim)
        pc = self.cache.groups[0].prefix_cache
        if pc is not None:
            for key, counter in (("lookups", self._m_prefix_lookups),
                                 ("hits", self._m_prefix_hits),
                                 ("evictions", self._m_prefix_evictions)):
                new = getattr(pc, key) - self._published_prefix[key]
                if new > 0:
                    counter.inc(new)
                    self._published_prefix[key] += new
            new = pc.declined - self._published_prefix["declined"]
            if new > 0:
                self._m_prefix_declined.inc(new, reason="state")
                self._published_prefix["declined"] += new
            seen = pc.hit_tokens + self._prompt_tokens_prefilled
            if seen:
                self._m_prefix_token_fraction.set(pc.hit_tokens / seen)
        self._m_in_flight.set(len(self._handles))
        # pool-occupancy cost: the allocator's exact integral, published
        # as a counter delta against a per-engine cursor (same pattern
        # as preemptions — the registry counter is process-global)
        bs_total = self.cache.block_seconds_total()
        d = bs_total - self._published_block_seconds
        if d > 0:
            self._m_kv_block_seconds.inc(d)
            self._published_block_seconds = bs_total
        self._m_step_compiles.set(self.step_traces)
        # per-iteration HBM poll (the serving half of the StepTimer
        # poll): refresh the ledger-backed hbm_* gauges
        from paddle_tpu.observability import memory as _obs_memory
        try:
            _obs_memory.publish()
        except Exception:
            pass  # the memory instrument must never fail a step

    # -- multi-tenant LoRA slots (ISSUE 20) --------------------------------
    def load_adapter(self, slot: int, state: dict,
                     name: Optional[str] = None):
        """Install a trained adapter (``tuning.load_adapter_state``'s
        ``{param name: array}``) into tenant ``slot`` (1..n_slots).
        Pure ``.at[slot].set`` on the stacked state leaves — shapes and
        dtypes unchanged, so the ONE compiled step is untouched (the
        ``load_weights``-without-retrace seam, per slot). Refuses while
        any in-flight request decodes against that slot."""
        if not self.n_adapter_slots:
            raise RuntimeError(
                "engine has no adapter slots — build the model with "
                "tuning.apply_lora(model, cfg, n_slots=N)")
        if not 1 <= int(slot) <= self.n_adapter_slots:
            raise ValueError(
                f"adapter slot {slot} out of range 1.."
                f"{self.n_adapter_slots}")
        slot = int(slot)
        with self._lock:
            busy = [r.req_id for r in list(self.scheduler.slotted())
                    + list(self.scheduler.waiting)
                    if r.adapter_id == slot]
            if busy:
                raise RuntimeError(
                    f"adapter slot {slot} has {len(busy)} request(s) in "
                    f"flight; drain or abort them first")
            unknown = [k for k in state if k not in self._st]
            if unknown:
                raise KeyError(
                    f"adapter state names unknown to this model: "
                    f"{sorted(unknown)[:3]}")
            for k, v in state.items():
                tgt = self._st[k]
                arr = jnp.asarray(v)
                if arr.shape != tgt.shape[1:]:
                    raise ValueError(
                        f"adapter leaf '{k}' has shape {arr.shape}, "
                        f"slot expects {tuple(tgt.shape[1:])}")
                self._st[k] = tgt.at[slot].set(arr.astype(tgt.dtype))
            self._adapters[slot] = name or f"adapter-{slot}"
            # new slot contents -> new prefix-cache namespace: blocks
            # registered under the previous occupant can never match
            self._adapter_gen[slot] = self._adapter_gen.get(slot, 0) + 1
        m = serving_metrics()
        m["adapter_loads"].inc()
        m["adapter_slots_loaded"].set(len(self._adapters))

    def unload_adapter(self, slot: int):
        """Zero tenant ``slot``'s rows (delta back to exactly 0) and
        free the slot. Same no-retrace contract as :meth:`load_adapter`."""
        slot = int(slot)
        with self._lock:
            busy = [r.req_id for r in list(self.scheduler.slotted())
                    + list(self.scheduler.waiting)
                    if r.adapter_id == slot]
            if busy:
                raise RuntimeError(
                    f"adapter slot {slot} has {len(busy)} request(s) in "
                    f"flight; drain or abort them first")
            for k, v in self._st.items():
                if k.rsplit(".", 1)[-1].startswith("lora_"):
                    self._st[k] = v.at[slot].set(0)
            self._adapters.pop(slot, None)
            self._adapter_gen[slot] = self._adapter_gen.get(slot, 0) + 1
        serving_metrics()["adapter_slots_loaded"].set(len(self._adapters))

    # -- submission --------------------------------------------------------
    def submit(self, prompt_tokens: Sequence[int], max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_token_id: Optional[int] = None,
               on_token: Optional[Callable] = None,
               trace_id: Optional[str] = None,
               adapter_id: int = 0) -> RequestHandle:
        """Enqueue a request; returns immediately with a handle. Tokens
        stream through ``on_token(request, token_id)`` as they decode.
        ``trace_id`` carries a client-supplied W3C trace id (the server's
        ``traceparent`` parse); absent, the engine mints one — either
        way every span/response for the request carries it.
        ``adapter_id`` picks the tenant's LoRA slot (0 = base model)."""
        prompt_tokens = list(prompt_tokens)
        if not prompt_tokens:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        adapter_id = int(adapter_id)
        if adapter_id:
            if not 1 <= adapter_id <= self.n_adapter_slots:
                raise ValueError(
                    f"adapter_id {adapter_id} out of range (engine has "
                    f"{self.n_adapter_slots} slots)")
            if adapter_id not in self._adapters:
                raise ValueError(
                    f"adapter slot {adapter_id} is empty — load_adapter "
                    f"first")
        total = len(prompt_tokens) + max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"prompt+max_new_tokens = {total} exceeds the engine's "
                f"max sequence length {self.max_model_len}")
        need = self.cache.blocks_for(total)
        width = self.cache.max_blocks_per_seq
        for g in self.cache.groups:
            # under a window a sequence holds a bounded number of pages
            held = min(need, g.max_pages_held(self.prefill_chunk, width))
            if need > width or held > g.allocator.capacity:
                raise ValueError(
                    f"request needs {held} KV blocks but the engine has "
                    f"{g.allocator.capacity} (table width {width}) — "
                    f"raise max_blocks or shorten the request")
        # non-base tenants hash their KV blocks under an adapter-specific
        # chain seed (slot + load generation): identical prompts under
        # different adapters produce different KV, so they must never
        # share prefix-cache entries. Slot 0 keeps the None (base) root —
        # cross-replica sketches and the pre-adapter index stay valid.
        seed = (chain_hash(None,
                           [adapter_id, self._adapter_gen[adapter_id]])
                if adapter_id else None)
        req = Request(prompt_tokens=prompt_tokens,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), eos_token_id=eos_token_id,
                      on_token=on_token,
                      trace_id=trace_id or self._new_trace_id(),
                      adapter_id=adapter_id,
                      cache_seed=seed, committed_hash=seed)
        if adapter_id:
            self._m_adapter_requests.inc(
                adapter=self._adapters.get(adapter_id,
                                           str(adapter_id)))
        handle = RequestHandle(req)
        with self._cv:
            if self._shutdown:
                raise RuntimeError("engine is shut down")
            self._handles[req.req_id] = handle
            self.scheduler.add(req)
            if self._ledger is not None:
                self._ledger.admit(req)
            self._m_requests.inc(outcome="accepted")
            self._update_gauges()
            self._cv.notify_all()
        return handle

    # -- one engine iteration ----------------------------------------------
    def _leaf(self, name: str, step: int, **args) -> RecordEvent:
        """One phase of a step as a ``RecordEvent`` (cat ``serving``). The
        phases tile the step back to back on the engine's thread, and
        nothing spans the step as a whole: an idle gap of the device then
        falls to the phase the host was in (docs/SERVING.md). ``step`` is
        the count of the step the call runs, shared by its leaves."""
        return RecordEvent(name, args={"step": step, **args}, cat="serving")

    def step(self) -> bool:
        """Plan + run one unified token-packed step (all live decode
        slots + the packed prefill chunks) and hand out its tokens: the
        serial order of the two halves the run loop overlaps, dispatch
        then harvest at once. Returns whether any work happened."""
        did = self._dispatch()
        while self._flights:
            self._harvest()
        return did

    def _turn(self):
        """One turn of the run loop: plan, pack and dispatch the next step
        while the device runs the one in flight, then harvest that one."""
        did = self._dispatch()
        if self._flights and (len(self._flights) > 1 or not did):
            self._harvest()

    def _plan(self):
        """The step's rows: ``(decode, prefills)`` as the scheduler plans
        them. Raises ``Unharvested`` where the plan cannot be made without
        a token still on the device."""
        plan = self.scheduler.schedule()
        if self._ledger is not None:
            # step-boundary occupancy sample: bill each slotted
            # request's previous holding level for the elapsed
            # interval (scheduler.preempt/finish tick pre-free,
            # so no interval is lost when blocks go back)
            self._ledger.note_occupancy_many(self.scheduler.slotted())
        # belt-and-braces against plan staleness: never act on a
        # sequence that lost its slot/blocks during planning (a
        # later allocation in the same plan may have preempted
        # it)
        decode = [s for s in plan.decode
                  if s.slot is not None
                  and s.state is RequestState.RUNNING]
        prefills = [(s, n_tok) for (s, n_tok) in plan.prefills
                    if s.slot is not None
                    and s.state is RequestState.PREFILL]
        return decode, prefills

    def _dispatch(self) -> bool:
        """The first half of a step, under the engine lock: plan, pack,
        call the compiled program, and advance every sequence by what
        needs no token value. With a step in flight this runs while the
        device does; where the plan needs that step's values (the cases
        below) it is harvested first and this one goes out serially.
        Returns whether a step was dispatched."""
        from paddle_tpu.observability import numerics

        n = self._decode_steps + 1
        with self._leaf("serving.lock", n):
            self._lock.acquire()
        try:
            # why the step in flight has to be harvested before this one
            # is planned: an abort took a sequence out of it; the
            # scheduler wants to preempt a sequence whose newest token is
            # on the device; a copy-on-write block copy beside a step that
            # writes; the numerics twin's step, read at once. (A step that
            # verified drafts is no such case: the next step's rows take
            # their tokens and their positions from it on the device.)
            first = self._harvest_first if self._flights else None
            self._harvest_first = None
            with self._leaf("serving.plan", n):
                if first is None:
                    try:
                        decode, prefills = self._plan()
                    except Unharvested:
                        first = "preempt"
                    else:
                        cow = any(s.cow_src is not None for s, _ in prefills)
                        if self._flights and (
                                cow or numerics.sample_this_step(n)):
                            first = "cow" if cow else "numerics"
            if first is not None:
                while self._flights:
                    self._harvest()
                with self._leaf("serving.plan", n):
                    decode, prefills = self._plan()
            if decode or prefills:
                self._run_unified(decode, prefills, first or "idle")
            elif not self._flights:
                # nothing ran and nothing is left to harvest: the gauges
                # of a call that found no work
                with self._leaf("serving.gauges", n):
                    self._update_gauges()
            return bool(decode or prefills)
        finally:
            self._lock.release()

    def _run_unified(self, decode: List[Request], prefills: List[tuple],
                     serial_reason: str):
        """Pack the planned work into the flat token budget, build the
        step's ragged metadata (token→sequence map, per-token positions,
        the RPA kernel's work lists) host-side, run the ONE compiled
        step, and advance the sequences; the step's tokens stay on the
        device until ``_harvest``."""
        from paddle_tpu.core import generator as G
        from paddle_tpu.observability import numerics, trace

        n_step = self._decode_steps + 1
        leaf = self._leaf("serving.pack", n_step)
        leaf.begin()
        for seq, _ in prefills:
            if seq.prefill_pos == 0 and seq.slot_time is not None \
                    and not getattr(seq, "_queue_wait_observed", False):
                # queue-wait ends at FIRST admission, observed exactly
                # once per request — slot_time never resets, so a
                # recompute prefill after preemption still reports the
                # original wait (a request preempted before its first
                # chunk must not be dropped from the histogram: overload
                # is exactly when queue-wait matters)
                seq._queue_wait_observed = True
                self._m_queue_wait.observe(
                    seq.slot_time - seq.arrival_time)

        # copy-on-write divergence (ISSUE 15): a fully-cached aligned
        # prompt shares all but its last matched block; that one is
        # device-copied into the sequence's private block BEFORE the
        # step, so the final-token write lands in owned storage and the
        # shared block stays immutable. The held source reference drops
        # once the copy ran (back to the cache's refcount).
        for seq, _ in prefills:
            if seq.cow_src is not None and seq.cow_index is not None \
                    and seq.cow_index < len(seq.tables[0]):
                self.cache.copy_block(seq.cow_src,
                                      seq.tables[0][seq.cow_index])
                self.scheduler._release_cow(seq)

        # a chunk that ends its prompt samples a token, a decode row always
        # (a decode row brings its draft's row along)
        entries = [(seq, 1 + seq.draft_rows, False, True, seq.draft_rows)
                   for seq in decode] + \
                  [(seq, n, True,
                    seq.prefill_pos + n == len(seq.pending_tokens), 0)
                   for seq, n in prefills]
        T, S = self.step_tokens, self.max_batch
        assert len(entries) <= S and \
            sum(e[1] for e in entries) <= T, "scheduler over-packed"
        tokens = np.zeros((1, T), np.int32)
        groups = self.cache.groups
        bts = [np.zeros((S + 1, self.cache.max_blocks_per_seq), np.int32)
               for _ in groups]
        if self.state_layers:
            # the state group's "table": each entry's state row, its slot's
            # (row 0, the null row, for padding)
            rows = np.zeros((S + 1,), np.int32)
            bts.append(rows)
        cu = np.zeros((S + 2,), np.int32)
        ctx = np.zeros((S + 1,), np.int32)
        sid = np.full((T,), S, np.int32)   # sentinel = padding
        pos = np.zeros((T,), np.int32)
        last_idx = np.zeros((S,), np.int32)
        aid = np.zeros((T,), np.int32)     # padding -> slot 0 (base)
        src = np.full((S,), -1, np.int32)  # the host's token stands
        samp, key_base, key_counts = None, None, None
        drafting = bool(self.draft_tokens)
        if drafting:
            # each row's next token where the host knows it, the row of a
            # slot's draft, and (samples, has a draft) a slot
            nxt = np.zeros((T,), np.int32)
            last2 = np.zeros((S,), np.int32)
            flags = np.zeros((2, S), np.int32)
        kv_lens, slack = [], []
        off = 0
        for i, (seq, n, is_prefill, samples, drafts) in enumerate(entries):
            if is_prefill:
                tokens[0, off:off + n] = seq.pending_tokens[
                    seq.prefill_pos:seq.prefill_pos + n]
                c = seq.prefill_pos
                if drafting:
                    follow = seq.pending_tokens[c + 1:c + n + 1]
                    nxt[off:off + len(follow)] = follow
            else:
                if seq.unharvested > 0:
                    # sampled by the step in flight: read on the device
                    src[i] = seq.token_row
                else:
                    tokens[0, off] = seq.last_token()
                if drafts and seq.unharvested == 0:
                    tokens[0, off + 1] = seq.draft
                c = seq.num_cached
            if samples and seq.temperature > 0:
                # the key of a sampled row: the host stream's next, one a
                # sampled row in row order (folded in the program)
                if samp is None:
                    samp = np.zeros((3, S), np.float32)
                    key_counts = np.zeros((S,), np.uint32)
                samp[:, i] = (seq.temperature, seq.top_k, seq.top_p)
                key_base, key_counts[i] = G.next_key_parts()
            for bt, table in zip(bts, seq.tables):
                bt[i] = self.cache.pad_block_table(table)
            if self.state_layers:
                rows[i] = seq.slot + 1
            ctx[i] = c
            sid[off:off + n] = i
            pos[off:off + n] = c + np.arange(n)
            aid[off:off + n] = seq.adapter_id
            cu[i + 1] = off + n
            # the row whose logits are the next token's: the last, or
            # the one before a draft's
            last_idx[i] = off + n - 1 - drafts
            if drafting:
                last2[i] = off + n - 1
                flags[:, i] = (samples, drafts)
            # (at its longest: a draft in flight may add a key)
            kv_lens.append(c + n + seq.pending_drafts)
            slack.append(seq.pending_drafts)
            off += n
        cu[len(entries) + 1:] = off
        if self.attn_impl == "rpa":
            unsure = {"slack": slack} if any(slack) else {}
            maps = [self._build_step_maps(cu[:len(entries) + 1], kv_lens,
                                          **kw, **unsure)
                    for kw in self._maps_kw]
        else:
            # the gather path ignores the kernel work list; feed the
            # cached all-sentinel one instead of rebuilding per step
            maps = self._null_step_maps

        # numerics sampling (docs/OBSERVABILITY.md#numerics): on a
        # sampled step the instrumented twin SUBSTITUTES for the plain
        # step — same program values (taps are identity), one extra
        # output carrying the per-tap activation stats that feed the
        # decode drift gauges. Lazy compile: the twin is traced on the
        # first sampled step only; disarmed engines never build it.
        self._decode_steps += 1
        step_fn, taps_out = self._step, None
        if numerics.sample_this_step(self._decode_steps):
            if self._numerics_step is None:
                self._numerics_step, self._numerics_order = \
                    self._build_step(instrument=True)
            step_fn = self._numerics_step

        # one step ahead: the step before is still unharvested (it runs,
        # or waits its turn, on the device)
        ahead = int(bool(self._flights))
        prev = self._flights[-1].tokens if ahead else self._no_prev
        leaf.end()
        leaf = self._leaf(
            "serving.dispatch", n_step, decode_rows=len(decode),
            prefill_rows=len(prefills),
            prefill_tokens=sum(n for _, n in prefills), ahead=ahead,
            **({"draft_rows": sum(e[4] for e in entries),
                "draft_seqs": sum(1 for e in entries if e[4])}
               if drafting else {}),
            # state rows the step reads and writes, those it starts from
            # zero, and the step's rows by kind
            **({"ssm_slots": len(entries),
                "ssm_fresh": int((ctx[:len(entries)] == 0).sum()),
                "ssm_decode_rows": len(decode),
                "ssm_prefill_rows": sum(n for _, n in prefills)}
               if self.state_layers else {}),
            **_row_args(f"{e[1]}@{ctx[i]}" for i, e in enumerate(entries)))
        leaf.begin()
        t0 = time.perf_counter_ns()
        compiles0 = self.step_traces
        try:
            out = step_fn(
                self._st, jnp.asarray(tokens), self.cache.k_pools,
                self.cache.v_pools, self.cache.k_scales,
                self.cache.v_scales, _a_group(bts), jnp.asarray(cu),
                jnp.asarray(ctx), jnp.asarray(sid), jnp.asarray(pos),
                _a_group(m.step_seq for m in maps),
                _a_group(m.step_blk for m in maps),
                _a_group(m.step_tile for m in maps), jnp.asarray(last_idx),
                jnp.asarray(aid), prev, jnp.asarray(src),
                *(self._greedy if samp is None else
                  (jnp.asarray(samp), self._key_words(key_base),
                   jnp.asarray(key_counts))),
                *((jnp.asarray(nxt), jnp.asarray(last2), jnp.asarray(flags))
                  if drafting else ()))
            if step_fn is not self._step:
                out, taps_out = out[:-1], out[-1]
            sampled, kps, vps, kss, vss, *moe_rows = out
        except Exception as e:
            # RESOURCE_EXHAUSTED gets one postmortem (ledger owners +
            # the unified step's memory report) before re-raising into
            # the run loop's fail-all-handles path
            from paddle_tpu.observability import memory as _obs_memory
            _obs_memory.handle_oom(e, source="serving_step",
                                   report_fn=self.memory_report)
            raise
        self.cache.update_pools(kps, vps, kss, vss)
        self._clear_model_side_effects()
        t1 = time.perf_counter_ns()
        compiled = self.step_traces - compiles0
        leaf.args["compiled"] = compiled
        if self.attn_impl == "rpa":
            # the RPA kernel's grid steps a kv head and layer: the work
            # items that name a real run of pages, the bound it walked,
            # and the pages those runs name (over live: the runs' fill).
            # The bare names are the first group's; a cache of several
            # groups also writes each group's under ``<name>_<group>``
            # (and what a window group's walks would name without the
            # window), and labels the counter by group
            lead = maps[0]
            leaf.args.update(rpa_live=lead.live, rpa_walked=lead.walked,
                             rpa_pages=lead.pages)
            for g, m in zip(groups, maps):
                label = {"group": g.name} if len(groups) > 1 else {}
                for kind in ("live", "walked", "pages"):
                    self._m_rpa_steps.inc(getattr(m, kind), kind=kind,
                                          **label)
                    if label:
                        leaf.args[f"rpa_{kind}_{g.name}"] = getattr(m, kind)
                if g.window is not None:
                    leaf.args[f"rpa_pages_causal_{g.name}"] = m.pages_causal
        self._m_steps.inc(kind="unified")
        if ahead:
            self._m_dispatched.inc(order="ahead")
        else:
            self._m_dispatched.inc(order="serial", reason=serial_reason)
        self._flights.append(_Flight(
            n_step, entries, sampled, moe_rows[0] if moe_rows else None,
            taps_out))

        # advance: what of a step's outcome needs no token value. The
        # device is at work; the next plan reads the sequences as the
        # step leaves them
        windowed = any(g.window is not None for g in groups)
        for i, (seq, n, is_prefill, samples, drafts) in enumerate(entries):
            if is_prefill:
                if trace.active() is not None:
                    # compile attribution: a chunk that rode the step
                    # that traced the executable carries compiles=1 —
                    # the "slow TTFT because XLA compiled" signal,
                    # distinct from admission or preemption
                    trace.span("serving", "prefill_chunk", t0, t1,
                               args={"req": seq.req_id,
                                     "trace": seq.trace_id, "tokens": n,
                                     "pos": seq.prefill_pos,
                                     "compiles": compiled,
                                     "preemptions": seq.preemptions})
                if self._ledger is not None:
                    self._ledger.note_prefill(seq, n, compiled)
                seq.prefill_pos += n
                seq.prefilled_tokens += n
                self._prompt_tokens_prefilled += n
                self._m_tokens.inc(n, kind="prompt")
            # (a draft's row is written, and counts once it is accepted)
            seq.num_cached += n - drafts
            seq.pending_drafts += drafts
            if samples:
                # prompt fully cached: the continuation is sampled (the
                # request's first token — or, after preemption, the next)
                seq.state = RequestState.RUNNING
                seq.num_sampled += 1
                seq.token_row = i
                if seq.all_sampled:
                    self._retiring.append(seq)
            self._commit_cached_blocks(seq)
            if windowed:
                # the pages no token still to come can see; this step's
                # own rows read them off the tables packed above
                for name, k in self.scheduler.release_behind_window(
                        seq).items():
                    self._m_kv_released.inc(k, group=name)
        leaf.end()

    def _harvest(self):
        """The second half of the oldest step in flight: wait for its
        tokens (``serving.fetch``, outside the engine lock: ``submit``
        and ``abort`` do not wait a device step), then hand them out
        (``serving.commit``): emit, finish on EOS or length, register the
        blocks the new tokens filled, take back what sequences at their
        last token hold. A row whose sequence finished meanwhile (EOS a
        step ago, an abort) is dropped."""
        from paddle_tpu.observability import fleet, numerics

        with self._lock:
            if not self._flights:       # another driver's harvest took it
                return
            flight = self._flights[0]
        n_step = flight.step
        leaf = self._leaf("serving.fetch", n_step)
        leaf.begin()
        toks = np.asarray(flight.tokens)
        if flight.taps is not None:
            try:
                h = jax.device_get(flight.taps)
                order = self._numerics_order or list(h)
                numerics.get_observatory().record_decode(
                    {n: tuple(float(v) for v in h[n])
                     for n in order if n in h})
            except Exception:
                warnings.warn("[numerics] decode sample publication "
                              "failed", RuntimeWarning)
        leaf.end()

        leaf = self._leaf("serving.commit", n_step)
        leaf.begin()
        with self._lock:
            if not self._flights or self._flights[0] is not flight:
                leaf.end()              # (a second driver: ``step()`` by
                return                  # hand beside the run loop)
            self._flights.popleft()
            if flight.moe_rows is not None:
                self._publish_moe_rows(np.asarray(flight.moe_rows), leaf)
            tokens_out = 0
            drafting = bool(self.draft_tokens)
            counts = dict.fromkeys(self._draft_counts, 0)
            for i, (seq, _, is_prefill, samples, drafts) in enumerate(
                    flight.entries):
                seq.pending_drafts -= drafts
                if not samples or seq.done:
                    continue
                # blocks the tokens harvested so far filled, BEFORE the
                # new token can finish the request
                self._commit_cached_blocks(seq)
                if not drafting:
                    self._emit_token(seq, self._sample(toks[i], seq))
                    tokens_out += 1
                    continue
                token, after, accepted, seq.draft = (int(v)
                                                     for v in toks[:, i])
                self._emit_token(seq, self._sample(token, seq))
                emitted = 1
                accepted = bool(drafts and accepted and not seq.done)
                if accepted:
                    # the draft was the model's own choice: its row is
                    # confirmed, and its logits chose one token more
                    seq.num_cached += 1
                    seq.num_sampled += 1
                    self._commit_cached_blocks(seq)
                    self._emit_token(seq, self._sample(after, seq))
                    emitted = 2
                tokens_out += emitted
                counts["drafted"] += drafts
                counts["accepted"] += accepted
                if not is_prefill:
                    counts["emitted"] += emitted
                    counts["decode_seqs"] += 1
            for seq in self._retiring:
                if not seq.done:
                    # sampled to its length, its last step dispatched:
                    # slot and pages go back before the next plan; the
                    # last token's harvest finishes the request
                    self._commit_cached_blocks(seq)
                    self.scheduler.release(seq)
            self._retiring.clear()
            leaf.args["tokens_out"] = tokens_out
            if drafting:
                leaf.args.update(counts)
                for k, v in counts.items():
                    self._draft_counts[k] += v
                self._m_drafts.inc(counts["drafted"], kind="drafted")
                self._m_drafts.inc(counts["accepted"], kind="accepted")
            leaf.end()
            with self._leaf("serving.gauges", n_step):
                # healthz liveness stamp: a wedged-but-listening
                # server shows a growing last_step_age_seconds
                fleet.note_step()
                self._update_gauges()

    def _publish_moe_rows(self, rows: np.ndarray, leaf):
        """``rows`` [layers, held experts]: the token rows the step's
        routed experts took. On the commit span: their sum, the fullest
        expert's and how many (layer, expert) pairs took any; in the
        registry: every (layer, expert) count, under one acquisition of the
        family's lock, with label keys built once an engine."""
        leaf.args.update(moe_rows=int(rows.sum()), moe_max=int(rows.max()),
                         moe_live=int(np.count_nonzero(rows)))
        if len(self._moe_keys) != rows.size:     # the first step's shape
            self._moe_keys = [label_key(layer=layer, expert=expert)
                              for layer in range(rows.shape[0])
                              for expert in range(rows.shape[1])]
        live = np.flatnonzero(rows)
        keys = self._moe_keys
        self._m_moe_rows.inc_many([keys[i] for i in live.tolist()],
                                  rows.ravel()[live].tolist())

    def _commit_cached_blocks(self, seq: Request):
        """Register every newly-completed full block in the prefix
        index whose tokens the host knows: all of a prompt's as soon as a
        step was dispatched over them, a block that holds generated
        tokens once the last of them is harvested. Runs where a step
        advanced ``num_cached`` and, at harvest, BEFORE the new token can
        finish the request — a request that ends there still leaves its
        blocks cached (they park as reclaimable when ``finish`` drops the
        refcounts). Committed blocks are never written again (sequence
        writes land at ``num_cached`` and beyond), so the index entry is
        immutable; a block registered while the step that writes its last
        row is in flight is read by later steps only."""
        if not self.prefix_cache_enabled:
            return
        bs = self.cache.block_size
        known = len(seq.prompt_tokens) + len(seq.generated)
        full = min(seq.num_cached, known) // bs
        if full <= seq.committed_blocks:
            return
        # the cached token stream: pending covers prompt (+ recompute
        # text); decode appends generated tokens in write order
        stream = seq.prompt_tokens + seq.generated
        for i in range(seq.committed_blocks, full):
            d = chain_hash(seq.committed_hash,
                           stream[i * bs:(i + 1) * bs])
            # in every group: the step that wrote the block's last token
            # wrote it in each (a window group releases only afterwards;
            # a window shorter than a block may have let the page go)
            for g, table in zip(self.cache.groups, seq.tables):
                if table[i] != NULL_BLOCK:
                    g.prefix_cache.register(d, table[i])
            seq.committed_hash = d
        seq.committed_blocks = full

    def _sample(self, token, seq: Request) -> int:
        """A row's token as the host takes it from the step's token array
        (the compiled step sampled it). The seam a test wraps to plant a
        fault in what is served (tests/benchmark)."""
        return int(token)

    def _emit_token(self, seq: Request, tok: int):
        now = time.perf_counter()
        itl = None
        if seq.first_token_time is None:
            seq.first_token_time = now
            self._m_ttft.observe(now - seq.arrival_time)
        elif seq.last_token_time is not None:
            itl = now - seq.last_token_time
            self._m_itl.observe(itl)
        if self._ledger is not None:
            self._ledger.note_token(seq, itl)
        seq.last_token_time = now
        seq.generated.append(int(tok))
        self._m_tokens.inc(kind="generated")
        if seq.on_token is not None:
            try:
                seq.on_token(seq, int(tok))
            except Exception:
                pass  # a broken stream consumer must not kill the batch
        if seq.eos_token_id is not None and tok == seq.eos_token_id:
            self._finish(seq, "eos")
        elif len(seq.generated) >= seq.max_new_tokens:
            self._finish(seq, "length")

    def _finish(self, seq: Request, reason: str,
                state: RequestState = RequestState.FINISHED):
        self.scheduler.finish(seq, state, reason)
        self._m_requests.inc(
            outcome="completed" if state is RequestState.FINISHED
            else "failed")
        if seq.latency() is not None:
            self._m_latency.observe(seq.latency())
        rec = (self._ledger.complete(seq)
               if self._ledger is not None else None)
        self._emit_request_chain(seq, reason, rec)
        handle = self._handles.pop(seq.req_id, None)
        if handle is not None:
            handle._done.set()
        with self._cv:
            self._cv.notify_all()

    def _emit_request_chain(self, seq: Request, reason: str, rec=None):
        """The per-request span chain (docs/SERVING.md): queue_wait →
        [prefill_chunk spans emitted live] → decode → request_done. The
        retrospective spans use the request's recorded timestamps, so a
        slow TTFT decomposes into admission wait vs prefill/compile time
        vs preemption recompute right in the merged trace. Every span
        carries the W3C trace id, so ``trace merge --requests`` can
        stitch the chain across processes; ``rec`` (the completed ledger
        record, when armed) enriches ``request_done`` with the cost
        summary the merge rollup reports."""
        from paddle_tpu.observability import trace
        if trace.active() is None:
            return

        def ns(t):
            return int(t * 1e9)  # perf_counter -> perf_counter_ns clock

        rid, tid = seq.req_id, seq.trace_id
        admitted = seq.slot_time
        if admitted is not None:
            trace.span("serving", "queue_wait", ns(seq.arrival_time),
                       ns(admitted), args={"req": rid, "trace": tid})
        if seq.first_token_time is not None:
            end = seq.finish_time or seq.last_token_time \
                or seq.first_token_time
            trace.span("serving", "decode", ns(seq.first_token_time),
                       ns(end),
                       args={"req": rid, "trace": tid,
                             "tokens": len(seq.generated)})
        args = {"req": rid, "trace": tid, "finish_reason": reason,
                "prompt_len": len(seq.prompt_tokens),
                "generated": len(seq.generated),
                "preemptions": seq.preemptions}
        if seq.ttft() is not None:
            args["ttft_s"] = round(seq.ttft(), 6)
        if seq.latency() is not None:
            args["latency_s"] = round(seq.latency(), 6)
        if rec is not None:
            args["prefilled_tokens"] = rec.prefilled_tokens
            args["cached_tokens"] = rec.cached_tokens
            args["decode_tokens"] = rec.decode_tokens
            args["kv_block_seconds"] = round(rec.kv_block_seconds, 6)
            p50, p99 = (rec.itl_percentile(0.5), rec.itl_percentile(0.99))
            if p50 is not None:
                args["itl_p50_ms"] = round(p50 * 1e3, 3)
                args["itl_p99_ms"] = round(p99 * 1e3, 3)
        trace.mark("serving", "request_done",
                   ts_ns=ns(seq.finish_time or time.perf_counter()),
                   args=args)

    def abort(self, req_id: int, reason: str = "aborted") -> bool:
        """Cancel a queued or in-flight request, releasing its batch slot
        and KV blocks (a waiting request simply leaves the queue). The
        graceful-degradation seam (docs/RESILIENCE.md): the HTTP server
        aborts requests that blew their deadline so abandoned work stops
        consuming engine capacity. Returns False when the request is
        unknown or already finished. Safe against a concurrent step():
        both halves of one take the engine lock, and a row the aborted
        sequence has in a step in flight is dropped at its harvest."""
        with self._cv:
            handle = self._handles.get(req_id)
            if handle is None:
                return False
            seq = handle._req
            if seq.done:
                return False
            if seq in self.scheduler.waiting:
                self.scheduler.waiting.remove(seq)
            if any(seq is e[0] for f in self._flights for e in f.entries):
                # a row of a step in flight: its harvest drops the row,
                # and comes before the next plan
                self._harvest_first = "abort"
            seq.error = reason
            # _finish records the request outcome; no extra inc here or
            # the serving_requests_total family double-counts the abort
            self._finish(seq, "aborted", RequestState.FAILED)
            self._update_gauges()
            return True

    def _clear_model_side_effects(self):
        """MoE gates stash ``l_aux`` during traced forwards; drop it so a
        later ``aux_loss()`` can't touch an escaped tracer."""
        clear = getattr(self.model, "clear_decode_side_effects", None)
        if clear is not None:
            clear()

    # -- run loop ----------------------------------------------------------
    def has_pending(self) -> bool:
        with self._lock:
            return self.scheduler.has_work() or bool(self._flights)

    def run_until_idle(self):
        """Synchronous driver (tests / batch jobs): step until every
        submitted request has finished."""
        while True:
            did = self.step()
            if not did and not self.has_pending():
                return
            if not did:
                raise RuntimeError(
                    "engine stalled with pending work — KV pool "
                    "undersized for the admitted requests")

    def start(self):
        """Background step loop (the server front-end's mode). Arms the
        collector's spans (``profiler.trace_gc``) first."""
        trace_gc()
        with self._lock:
            if self._thread is not None:
                return
            self._shutdown = False
            self._thread = threading.Thread(
                target=self._run_loop, name="pt-serving-engine",
                daemon=True)
            self._thread.start()

    def _run_loop(self):
        """One step ahead: each turn dispatches the next step while the
        device runs the one in flight, then harvests that one; a step's
        tokens reach their callbacks while the next step runs."""
        while True:
            with self._cv:
                if not self.has_pending():
                    if self._shutdown:
                        return
                    with RecordEvent("serving.idle_wait", cat="serving"):
                        self._cv.wait(timeout=0.1)
                    continue
            try:
                self._turn()
            except Exception as e:  # noqa: BLE001 — loop must not die silently
                # a step failure (OOM, scheduling bug) would otherwise
                # strand every pending handle forever: fail them all
                # loudly and stop the loop
                with self._cv:
                    self._flights.clear()
                    self._retiring.clear()
                    for seq in [h._req for h in self._handles.values()]:
                        seq.error = f"engine step failed: {e!r}"
                        self._finish(seq, "error", RequestState.FAILED)
                    self.scheduler.waiting.clear()
                    self._shutdown = True
                    self._cv.notify_all()
                raise

    def drain(self, timeout: Optional[float] = None):
        """Block until every accepted request has finished."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        while self.has_pending():
            if self._thread is None:
                self.run_until_idle()
                break
            if deadline is not None and time.perf_counter() > deadline:
                raise TimeoutError("engine drain timed out")
            with self._cv:
                if self.has_pending():
                    self._cv.wait(timeout=0.1)

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None):
        """Graceful stop: optionally finish in-flight work, then stop the
        loop thread. New submissions are rejected once shut down."""
        if drain:
            self.drain(timeout)
        with self._cv:
            self._shutdown = True
            if not drain:
                # every accepted request not finished: slotted, waiting,
                # and those whose last token is still on the device
                for seq in [h._req for h in self._handles.values()]:
                    seq.error = "engine shut down"
                    self._finish(seq, "aborted", RequestState.FAILED)
                self.scheduler.waiting.clear()
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    # -- introspection -----------------------------------------------------
    def stats(self) -> dict:
        """Lock-free snapshot (every field below is individually
        synchronized): /healthz must answer even while a step holds the
        engine lock through a first-time XLA compile."""
        lead = self.cache.groups[0]
        alloc, pc = lead.allocator, lead.prefix_cache
        # the tightest group's shares: a sequence grows in every group or
        # in none, so the router and load shedding read that one
        free, reclaim = self.cache.tightest().fractions()
        out = {
            "running": self.scheduler.num_running,
            "waiting": self.scheduler.num_waiting,
            "kv_blocks_in_use": alloc.blocks_in_use(),
            "kv_blocks_free": alloc.num_free(),
            "kv_blocks_reclaimable": alloc.num_reclaimable(),
            "preemptions": self.scheduler.num_preemptions,
            # ledger headline numbers (ISSUE 16): scrapeable without
            # /statusz — in-flight counts accepted-but-unfinished, and
            # the block-seconds integral is the allocator's exact one
            "requests_in_flight": len(self._handles),
            "kv_block_seconds_total": round(
                self.cache.block_seconds_total(), 4),
            "step_compiles": self.step_traces,
            "attn_impl": self.attn_impl,
            "step_tokens": self.step_tokens,
            # pool pressure BEFORE preemption-by-recompute starts
            # churning: ALLOCATABLE fraction — free plus reclaimable
            # prefix-cached blocks (the /healthz field operators watch),
            # split below so the HBM ledger and load shedding don't
            # misread a warm cache as pressure
            "kv_headroom": round(free + reclaim, 4),
            "kv_free_fraction": round(free, 4),
            "kv_reclaimable_fraction": round(reclaim, 4),
            "max_batch": self.max_batch,
            "max_model_len": self.max_model_len,
            "block_size": self.cache.block_size,
            "prefix_cache": None,
            "tensor_parallel": (int(self.mesh.shape[self._mp_axis])
                                if self.mesh is not None else 1),
            # quantization + multi-tenancy surface (ISSUE 20): what
            # dtype the weights/KV actually serve in, and which tenant
            # slots are occupied — /healthz and /statusz republish these
            "weight_dtype": self._weight_dtype,
            "quantize": self.quantize,
            "kv_dtype": self.kv_dtype or str(self.cache.compute_dtype),
            "adapters": {
                "slots": self.n_adapter_slots,
                "loaded": len(self._adapters),
                "occupancy": {str(s): n for s, n in
                              sorted(self._adapters.items())},
            },
        }
        if self.draft_tokens:
            out["drafts"] = dict(self._draft_counts,
                                 draft_tokens=self.draft_tokens)
        if len(self.cache.groups) > 1:
            # a layer group each: pool size, blocks held by live sequences,
            # free, and parked in the prefix cache (the kv_blocks_* keys
            # above read the first group, the fractions the tightest)
            out["kv_groups"] = {g.name: g.usage() for g in self.cache.groups}
        if self.cache.state_group is not None:
            out["state_group"] = self.cache.state_group.usage()
        if pc is not None:
            s = pc.stats()
            s["hit_rate"] = round(s["hits"] / max(s["lookups"], 1), 4)
            # the fleet router's affinity signal: truncated digests of
            # every registered block (docs/SERVING.md#serving-fleet)
            s["sketch"] = pc.sketch()
            out["prefix_cache"] = s
        return out

    # -- cross-replica KV handoff (fleet disaggregation) -------------------
    def export_kv_blocks(self, digests: Sequence[bytes]) -> List[tuple]:
        """Host-stage the KV contents of the registered blocks behind
        ``digests`` (the chain hashes of a prefilled prompt's full
        blocks, in chain order). Each exported block's reference is
        claimed through ``reuse_cached`` for the duration of the copy —
        an eviction can't tear a row mid-export — and dropped before
        returning. Stops at the first miss (a chained digest after a
        miss could never be admitted anyway). Returns ``[(digest, k, v),
        ...]`` records for :meth:`import_kv_blocks` on a peer replica."""
        if not self.prefix_cache_enabled:
            return []
        g = self.cache.sole_group("export_kv_blocks (fleet KV handoff)")
        out: List[tuple] = []
        for d in digests:
            b = g.prefix_cache.claim(d)
            if b is None:
                break
            try:
                k, v = self.cache.export_block(b)
            finally:
                g.allocator.free([b])
            out.append((d, k, v))
        return out

    def import_kv_blocks(self, records: Sequence[tuple]) -> int:
        """Adopt host-staged KV blocks from a peer replica: allocate a
        physical block per record, write the rows, register the chain
        digest in the prefix index, and park the block reclaimable — the
        next admission sharing the prefix claims it like any local
        cache hit (tail-only prefill). Already-known digests are
        skipped (first writer wins, same as ``register``); a full pool
        stops the import early. Returns the number of blocks adopted."""
        if not self.prefix_cache_enabled:
            return 0
        g = self.cache.sole_group("import_kv_blocks (fleet KV handoff)")
        pc = g.prefix_cache
        n = 0
        with self._lock:
            for d, k, v in records:
                if pc.lookup(d) is not None:
                    n += 1  # prefix already resident here
                    continue
                try:
                    (b,) = g.allocator.allocate(1)
                except MemoryError:
                    break
                self.cache.import_block(b, k, v)
                pc.register(d, b)
                g.allocator.free([b])  # parks reclaimable
                n += 1
        return n
