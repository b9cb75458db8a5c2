"""paddle_tpu.serving — continuous-batching LLM inference runtime.

The request-level serving subsystem (docs/SERVING.md) above the model
zoo's ``generate`` surface and below an HTTP front-end:

- **kv_cache** — block-paged KV-cache manager: fixed-size token blocks,
  per-sequence block tables, refcounted alloc/free, per-layer device
  pools threaded functionally through the compiled step.
- **scheduler** — FCFS continuous batching: token-budget packing of all
  decode slots plus multiple prefill chunks per step, slot swapping
  between steps, preemption-by-recompute when the block pool runs dry.
- **engine** — :class:`ServingEngine`: ONE compiled unified step
  executable over a token-packed mixed prefill+decode layout, streaming
  token callbacks, drain/graceful shutdown, serving_* metrics through
  ``observability.metrics``.
- **server** — stdlib HTTP front-end: ``POST /generate`` (optionally
  chunked streaming), ``GET /healthz``, ``GET /metrics[.json]``.
- **fleet** — multi-replica serving: :class:`FleetRouter` places
  requests across N engine replicas by chain-hash prefix affinity,
  fails over mid-stream onto survivors through the prefix cache, and
  disaggregates prefill/decode with host-staged KV block handoffs;
  :class:`RouterServer` is the router's HTTP front-end.

The attention read path is the Ragged-Paged-Attention Pallas kernel
(``ops/pallas/ragged_paged_attention.py``, the RPA paper — PAPERS.md,
arxiv 2604.15464) on TPU, with the gather-based fallback in
``ops/paged_attention.py`` as the backend-portable parity oracle
(``ServingEngine(attn_impl=...)`` pins either, for tests).
"""
from . import engine, fleet, kv_cache, scheduler, server  # noqa: F401
from .engine import RequestHandle, ServingEngine  # noqa: F401
from .fleet import FleetRouter, Replica, RouterServer, build_fleet  # noqa: F401,E501
from .kv_cache import BlockAllocator, PagedKVCache  # noqa: F401
from .scheduler import Request, RequestState, Scheduler  # noqa: F401
from .server import Server  # noqa: F401

__all__ = ["ServingEngine", "RequestHandle", "Server", "Scheduler",
           "Request", "RequestState", "PagedKVCache", "BlockAllocator",
           "FleetRouter", "Replica", "RouterServer", "build_fleet",
           "engine", "fleet", "kv_cache", "scheduler", "server"]
