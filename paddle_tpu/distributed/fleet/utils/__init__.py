"""fleet.utils — activation recomputation (gradient checkpointing).

Capability parity with the reference's
``python/paddle/distributed/fleet/utils/__init__.py`` ``recompute`` (backed by
``fleet/recompute/recompute.py``: a PyLayer that stashes RNG state + inputs,
drops activations, and re-runs the forward inside backward).

TPU-native redesign: rematerialization is a *compiler* feature on XLA —
``jax.checkpoint`` marks the region and XLA re-emits the forward ops inside
the backward computation, so there is no RNG stash/restore dance (the replayed
HLO reuses the traced-in RNG values, which is exactly "preserve_rng_state").
The tape integration is one ``apply_op`` call whose vjp closure is the
checkpointed function's — saving only the region's *inputs*, not its
activations, in the GradNode.
"""
from __future__ import annotations

import functools
from typing import Any

import jax

from paddle_tpu.core import autograd as _ag
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn.layer_base import Layer
from . import fs  # noqa: F401
from .fs import HDFSClient, LocalFS  # noqa: F401

__all__ = ["recompute", "recompute_sequential", "LocalFS", "HDFSClient",
           "fs", "mark_varying", "match_vma"]


def match_vma(value, like):
    """Cast ``value`` to carry (at least) the varying-manual-axes of
    ``like`` — the fix for fresh constants (scan carries, zero states)
    created INSIDE a shard_map manual region next to varying inputs: the
    scan's carry-in must type-match its carry-out. No-op outside manual
    regions."""
    missing = tuple(sorted(jax.typeof(like).vma - jax.typeof(value).vma))
    if missing:
        return jax.lax.pcast(value, missing, to="varying")
    return value


def mark_varying(x, axis):
    """Mark a freshly-created invariant array device-varying over ``axis``
    (the shard_map vma rule for scan carries whose other inputs are
    rank-dependent). No-op when the value is already varying — ``pcast``
    refuses a varying→varying cast. Shared by the ring attention and
    SPMD pipeline kernels."""
    if axis in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis, to="varying")


def _owning_layer(function) -> Layer | None:
    if isinstance(function, Layer):
        return function
    bound = getattr(function, "__self__", None)
    return bound if isinstance(bound, Layer) else None


def _collect_state(function, layer):
    """Every Tensor whose storage must be threaded through the checkpoint
    region so its gradient flows: the owning Layer's params/buffers, or —
    for a plain function — Layers/Tensors captured by its closure (the
    ``recompute(lambda x: self.mlp(x), h)`` idiom; without this the closed-
    over weights would trace as constants and silently stop training)."""
    tensors, seen = [], set()

    def add(t):
        if t is not None and id(t) not in seen:
            seen.add(id(t))
            tensors.append(t)

    def add_layer(lay):
        for _, p in lay.named_parameters():
            add(p)
        for _, b in lay.named_buffers():
            add(b)

    visited = set()

    def scan(obj, depth):
        if depth > 3 or id(obj) in visited:
            return
        visited.add(id(obj))
        if isinstance(obj, Layer):
            add_layer(obj)
        elif isinstance(obj, Tensor):
            add(obj)
        elif isinstance(obj, functools.partial):
            scan(obj.func, depth + 1)
            for a in obj.args:
                scan(a, depth + 1)
            for v in obj.keywords.values():
                scan(v, depth + 1)
        elif isinstance(obj, (list, tuple, set)):
            for o in obj:
                scan(o, depth + 1)
        elif isinstance(obj, dict):
            for o in obj.values():
                scan(o, depth + 1)
        elif callable(obj):
            bound = getattr(obj, "__self__", None)
            if isinstance(bound, Layer):
                add_layer(bound)
            for cell in getattr(obj, "__closure__", None) or ():
                try:
                    scan(cell.cell_contents, depth + 1)
                except ValueError:  # empty cell
                    pass

    if layer is not None:
        add_layer(layer)
        return tensors
    scan(function, 0)
    return tensors


def _wrap_tree(obj):
    """Rebuild Tensor wrappers around jax arrays for the inner call."""
    if isinstance(obj, jax.Array) or hasattr(obj, "aval"):
        return Tensor(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_wrap_tree(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _wrap_tree(v) for k, v in obj.items()}
    return obj


def _unwrap_tree(obj):
    if isinstance(obj, Tensor):
        return obj.data
    if isinstance(obj, (list, tuple)):
        return type(obj)(_unwrap_tree(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _unwrap_tree(v) for k, v in obj.items()}
    return obj


def recompute(function, *args, preserve_rng_state: bool = True,
              use_reentrant: bool = True, **kwargs):
    """Run ``function(*args, **kwargs)`` without saving its activations;
    the forward is re-run (by XLA rematerialization) during backward.

    ``function`` may be a ``Layer``, a bound method of a ``Layer`` (its
    parameters/buffers are threaded through so their gradients flow), or a
    pure function of its tensor arguments. ``preserve_rng_state`` and
    ``use_reentrant`` are accepted for API parity; RNG preservation is
    inherent (see module docstring).
    """
    del preserve_rng_state, use_reentrant
    layer = _owning_layer(function)
    call = layer.forward if layer is not None and isinstance(function, Layer) \
        else function
    state_tensors = _collect_state(function, layer)

    def region(state_list, arg_tree, kw_tree):
        # everything below runs on (possibly traced) jax arrays; the tape
        # must not record the inner ops — the whole region is ONE tape node
        saved = [t._data for t in state_tensors]
        for t, a in zip(state_tensors, state_list):
            t._data = a
        try:
            with _ag.no_grad():
                out = call(*_wrap_tree(arg_tree), **_wrap_tree(kw_tree))
        finally:
            for t, s in zip(state_tensors, saved):
                t._data = s
        return _unwrap_tree(out)

    ckpt = jax.checkpoint(region)
    return _ag.apply_op(ckpt, list(state_tensors), list(args), dict(kwargs),
                        op_name="recompute")


def recompute_sequential(ctx: Any, functions, *args):
    """Segment a ``Sequential``-like list of layers and recompute each segment
    (reference: ``incubate/distributed/fleet/recompute_sequential``).

    ``ctx`` accepts ``{"segments": N}`` (default 1 segment per layer).
    """
    layers = list(functions)
    segments = int((ctx or {}).get("segments", len(layers))) or 1
    per = max(1, (len(layers) + segments - 1) // segments)
    out = args
    for i in range(0, len(layers), per):
        chunk = layers[i:i + per]

        class _Seg(Layer):
            def __init__(self, mods):
                super().__init__()
                for j, m in enumerate(mods):
                    setattr(self, f"seg{j}", m)
                self._mods = mods

            def forward(self, *xs):
                for m in self._mods:
                    xs = m(*xs) if isinstance(xs, tuple) else m(xs)
                    if not isinstance(xs, tuple):
                        xs = (xs,)
                return xs if len(xs) > 1 else xs[0]

        seg = _Seg(chunk)
        res = recompute(seg, *(out if isinstance(out, tuple) else (out,)))
        out = res
    return out
