"""paddle.device parity (reference: ``python/paddle/device/__init__.py`` —
set_device/get_device/device queries + the cuda submodule).

TPU mapping: devices are whatever the active PJRT backend exposes
(``tpu:N`` on hardware, ``cpu:N`` on the host mesh); ``set_device``
selects the default placement index. CUDA-specific entry points exist for
API compatibility and report absence honestly (this build has no CUDA by
constraint, BASELINE.md)."""
from __future__ import annotations

import os
from typing import Dict, List, Optional

from . import cuda  # noqa: F401

__all__ = ["set_device", "get_device", "get_all_custom_device_type",
           "get_available_device", "get_available_custom_device",
           "is_compiled_with_cuda", "is_compiled_with_rocm",
           "is_compiled_with_xpu", "is_compiled_with_npu",
           "is_compiled_with_custom_device", "device_count", "synchronize",
           "cuda", "memory_stats", "memory_allocated",
           "max_memory_allocated", "reset_max_memory_allocated",
           "apply_xla_tuning", "applied_xla_tuning", "use_compile_cache",
           "tpu_selected"]

_state = {"device": None}

# --- TPU XLA performance flags (docs/PERFORMANCE.md#xla-flags) --------------
# Appended to LIBTPU_INIT_ARGS at import, BEFORE the first jax backend
# initialization loads libtpu and reads them. They are libtpu's flags:
# jaxlib's own XLA_FLAGS parser knows none of them and aborts the process on
# the first ("Unknown flags in XLA_FLAGS", measured on jaxlib 0.9.0 / libtpu
# 0.0.34 for all eight); libtpu accepts all eight and rejects an unknown
# name. Each entry: flag name -> (value, why). The set is the standard
# compute/communication-overlap tuning the bucketed-collective train step
# (jit/bucketing.py) is designed for: async collectives are only a win if
# the scheduler is allowed to move compute between their start/done pair.
XLA_TUNING_FLAGS: Dict[str, tuple] = {
    "--xla_tpu_enable_latency_hiding_scheduler": (
        "true",
        "reorder the program so async collective start/done pairs straddle "
        "independent compute — the scheduler that actually hides the "
        "bucketed dp all-reduces behind remaining backward work"),
    "--xla_tpu_enable_async_collective_fusion": (
        "true",
        "split eligible collectives into async start/done ops the "
        "latency-hiding scheduler can move apart"),
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather": (
        "true",
        "extend async collective fusion to all-gathers (ZeRO param "
        "gathers, TP activation gathers)"),
    "--xla_tpu_enable_async_collective_fusion_multiple_steps": (
        "true",
        "let one async collective span several scheduling steps instead "
        "of forcing completion at the next step boundary"),
    "--xla_tpu_overlap_compute_collective_tc": (
        "true",
        "allow collectives to run on the transfer cores concurrently with "
        "TensorCore compute"),
    "--xla_enable_async_all_gather": (
        "true", "emit all-gathers as async start/done pairs"),
    "--xla_enable_async_collective_permute": (
        "true",
        "emit collective-permutes (pipeline-parallel edges) as async "
        "start/done pairs"),
    "--xla_tpu_data_parallel_opt_different_sized_ops": (
        "true",
        "enable pipelining of data-parallel ops across iterations even "
        "when their sizes differ (the size-targeted grad buckets are "
        "rarely equal)"),
}


def apply_xla_tuning(env: Optional[dict] = None) -> List[str]:
    """Append the TPU tuning flags to ``env['LIBTPU_INIT_ARGS']``.

    Additive and user-respecting: a flag whose name already appears in
    ``LIBTPU_INIT_ARGS`` (the user's, or the machine image's) is left
    alone. ``PADDLE_TPU_NO_XLA_TUNING=1`` disables the whole mechanism.
    Needs no platform gate: only libtpu reads the variable, so a CPU run
    never sees it. Must run before jax initializes its backend, which is
    why ``paddle_tpu.device`` calls it at import; importing jax and
    running a computation *before* paddle_tpu makes it a no-op for that
    process.

    Returns the list of flags applied. ``env`` defaults to
    ``os.environ``; pass a dict to test without process-global effects.
    """
    env = os.environ if env is None else env
    if env.get("PADDLE_TPU_NO_XLA_TUNING") == "1":
        return []
    existing = env.get("LIBTPU_INIT_ARGS", "")
    # exact flag-name match (token before '='): a plain substring test
    # would let a longer user flag shadow a shorter tuning flag whose
    # name is its prefix (e.g. ..._fusion vs ..._fusion_fuse_all_gather)
    existing_names = {tok.split("=", 1)[0] for tok in existing.split()}
    applied = [f"{name}={value}"
               for name, (value, _why) in XLA_TUNING_FLAGS.items()
               if name not in existing_names]
    if applied:
        env["LIBTPU_INIT_ARGS"] = " ".join([existing] + applied).strip()
    return applied


_applied_xla_tuning = apply_xla_tuning()


def applied_xla_tuning() -> List[str]:
    """The tuning flags this process's import actually added (empty when
    they were all pre-set, or under ``PADDLE_TPU_NO_XLA_TUNING=1``)."""
    return list(_applied_xla_tuning)


def tpu_selected(env: Optional[dict] = None) -> bool:
    """Whether a jax process started under ``env`` will pick a TPU, told
    from the environment alone — for callers that must not initialize a
    backend to find out (a launcher about to spawn the processes that
    will own the chips; the analysis CLI choosing its CPU mesh):
    ``JAX_PLATFORMS`` naming it, or, left unset, a TPU runtime variable of
    the machine image."""
    env = os.environ if env is None else env
    platforms = env.get("JAX_PLATFORMS", "").lower()
    if platforms:
        return "tpu" in platforms
    return any(k in env for k in ("TPU_NAME", "TPU_ACCELERATOR_TYPE",
                                  "TPU_WORKER_ID", "TPU_SKU",
                                  "TPU_CHIPS_PER_HOST_BOUNDS"))


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.
    Entry points (``chip_smoke.py``, ``bench.py``, ``examples/*``) call
    this before their first compile; nothing else in the package sets a
    cache directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it and nothing is touched — the machine places the cache.
    Otherwise ``<checkout>/.jax_cache``: a fixed path derived from this
    file's location (the path is part of the cache key, so a directory
    that moves never hits)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _devices():
    import jax
    return jax.devices()


def set_device(device: str) -> str:
    """Reference: device/__init__.py set_device. Accepts 'tpu', 'tpu:0',
    'cpu', 'gpu:0' (mapped to the accelerator if present)."""
    _state["device"] = device
    return device


def get_device() -> str:
    if _state["device"] is not None:
        return _state["device"]
    d = _devices()[0]
    return f"{d.platform}:{d.id}"


def get_available_device() -> List[str]:
    return [f"{d.platform}:{d.id}" for d in _devices()]


def get_all_custom_device_type() -> List[str]:
    plats = {d.platform for d in _devices()}
    return sorted(p for p in plats if p not in ("cpu", "gpu"))


def get_available_custom_device() -> List[str]:
    return [f"{d.platform}:{d.id}" for d in _devices()
            if d.platform not in ("cpu", "gpu")]


def device_count() -> int:
    return len(_devices())


def synchronize(device: Optional[str] = None):
    """Block until pending device work completes (reference:
    device.synchronize) — jax equivalent: barrier on a trivial
    computation."""
    import jax
    jax.block_until_ready(jax.numpy.zeros(()))


def _resolve_device(device):
    """Map the accepted device spellings to a jax Device: None (default
    placement), an integer index, a ``"tpu:1"``/``"cpu:0"``-style string
    (or bare platform string meaning index 0), or an actual jax Device
    object (used as-is — callers holding ``jax.devices()`` entries must
    not be forced to re-spell them)."""
    if device is not None and hasattr(device, "memory_stats"):
        return device  # already a jax Device
    idx = 0
    if isinstance(device, int):
        idx = device
    elif device and ":" in str(device):
        idx = int(str(device).rsplit(":", 1)[1])
    devs = _devices()
    if idx >= len(devs):  # a typo'd device must error, not read as 0
        raise IndexError(
            f"device index {idx} out of range ({len(devs)} devices)")
    return devs[idx]


def memory_stats(device=None) -> dict:
    """Per-device memory statistics from the PJRT runtime (the TPU analog
    of the reference's allocator stats, ``fluid/memory/``; keys follow
    jax's ``device.memory_stats()``: bytes_in_use, peak_bytes_in_use,
    bytes_limit...). Accepts a ``"tpu:1"`` string, an index, or a jax
    Device. Empty dict when the backend doesn't report."""
    dev = _resolve_device(device)
    try:
        return dict(dev.memory_stats() or {})
    except (AttributeError, NotImplementedError, RuntimeError):
        return {}  # backend doesn't report memory stats


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def reset_max_memory_allocated(device=None) -> bool:
    """Reset the runtime's peak-HBM watermark so ``max_memory_allocated``
    reflects only allocations after this call (reference:
    ``cuda.reset_max_memory_allocated``). PJRT backends are uneven here —
    whichever reset entry point this runtime exposes is used; when none
    exists (CPU, older libtpu) this warns once and returns False, and the
    memory ledger falls back to its host-side peak tracking
    (``MemoryLedger.reset_peak``)."""
    import warnings
    dev = _resolve_device(device)
    for name in ("reset_memory_stats", "reset_peak_memory_stats",
                 "clear_memory_stats"):
        fn = getattr(dev, name, None)
        if fn is None:
            continue
        try:
            fn()
            return True
        except (NotImplementedError, RuntimeError):
            continue
    warnings.warn(
        "reset_max_memory_allocated: backend exposes no peak-reset entry "
        "point; peak_bytes_in_use is cumulative for this process",
        RuntimeWarning, stacklevel=2)
    return False


def is_compiled_with_cuda() -> bool:
    return False  # hard constraint: no CUDA in this build (BASELINE.md)


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = "tpu") -> bool:
    return any(d.platform == device_type for d in _devices())
