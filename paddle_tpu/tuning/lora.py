"""LoRA: rank-r adapters on the Llama projections, two lifetimes.

**Training** (``apply_lora(model, cfg)``): each targeted linear grows
``lora_A [in, r]`` (Normal init) and ``lora_B [r, out]`` (zeros — the
delta starts at exactly 0), the base weights are frozen
(``stop_gradient``), and a forward post-hook adds
``x @ A @ B * (alpha/rank)`` to the layer's output. TrainStep already
skips ``stop_gradient`` params, so ``Model.fit`` trains ONLY the
adapters; :func:`save_adapter` checkpoints just the ``lora_*`` leaves
(a few KB against a multi-GB base).

**Serving** (``apply_lora(model, cfg, n_slots=N)``): the same params
are created STACKED — ``[N + 1, in, r]`` / ``[N + 1, r, out]``, all
zeros. Row 0 is the permanently-empty base row (zero delta), rows
1..N are tenant slots the engine fills via
``ServingEngine.load_adapter`` (a pure ``.at[slot].set`` on the state
leaf — same shape, NO retrace, generalizing the load_weights seam).
Inside the compiled step the engine pins this step's per-token slot
ids with :func:`adapter_ids`; the hook gathers each token's
``A[ids[t]] / B[ids[t]]`` rows and applies per-row deltas — one
executable serves every tenant mix in the batch.

Param names are identical in both modes (``...q_proj.lora_A``), so a
training checkpoint's 2-D leaves map by name into one slot of the
serving engine's 3-D stack.
"""
from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from paddle_tpu import ops
from paddle_tpu.core.autograd import apply_op
from paddle_tpu.nn import initializer as I

__all__ = ["LoRAConfig", "apply_lora", "adapter_ids", "lora_state_dict",
           "save_adapter", "load_adapter_state", "lora_param_bytes"]

#: Llama-family projections adapted by default — attention + MLP, the
#: same surface the weight-only quantizer targets
_DEFAULT_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj",
                    "gate_proj", "up_proj", "down_proj")


@dataclass
class LoRAConfig:
    rank: int = 8
    alpha: float = 16.0
    target_modules: Tuple[str, ...] = field(
        default_factory=lambda: _DEFAULT_TARGETS)
    #: init std of ``lora_A`` (B starts at zero either way)
    init_std: float = 0.02

    @property
    def scaling(self) -> float:
        return float(self.alpha) / float(self.rank)


# thread-local: the serving engine pins the step's traced per-token
# slot ids here while tracing/running its unified step (the adapter
# hooks of a layer have no argument to receive them through)
_ids_local = threading.local()


@contextlib.contextmanager
def adapter_ids(ids):
    """Pin the per-token adapter-slot ids (``[T] int32``, traced or
    concrete) for forwards run inside the block on this thread."""
    prev = getattr(_ids_local, "value", None)
    _ids_local.value = ids
    try:
        yield
    finally:
        _ids_local.value = prev


def _lora_targets(model, cfg: LoRAConfig):
    """(qualified name, layer) for every targeted linear: last name
    component in ``target_modules`` and a 2-D ``weight``."""
    out = []
    for name, sub in model.named_sublayers():
        leaf = name.rsplit(".", 1)[-1]
        w = getattr(sub, "weight", None)
        if leaf in cfg.target_modules and getattr(w, "ndim", 0) == 2:
            out.append((name, sub))
    return out


def _make_hook(scaling: float):
    def _lora_hook(layer, inputs, out):
        A, B = layer.lora_A, layer.lora_B
        if A.ndim == 2:
            # training mode: one adapter, plain Tensor ops so autograd
            # reaches A and B through the standard vjp machinery
            delta = ops.scale(
                ops.matmul(ops.matmul(inputs[0], A), B), scaling)
            return ops.add(out, delta)

        # serving mode: per-token slot dispatch over the [N+1, ...]
        # stacks; outside an adapter_ids() block every token reads row
        # 0 — the zero base row, delta exactly 0
        ids = getattr(_ids_local, "value", None)

        def g(xa, Aa, Ba, oa):
            x2 = xa.reshape(-1, xa.shape[-1]).astype(jnp.float32)
            sl = (jnp.zeros((x2.shape[0],), jnp.int32)
                  if ids is None else ids.astype(jnp.int32))
            t = jnp.einsum("td,tdr->tr", x2,
                           Aa[sl].astype(jnp.float32))
            d = jnp.einsum("tr,tro->to", t,
                           Ba[sl].astype(jnp.float32)) * scaling
            return oa + d.reshape(oa.shape).astype(oa.dtype)

        return apply_op(g, inputs[0], A, B, out,
                        op_name="lora_dispatch")
    return _lora_hook


def apply_lora(model, cfg: Optional[LoRAConfig] = None, *,
               n_slots: Optional[int] = None, freeze_base: bool = True):
    """Attach LoRA adapters to ``model`` in place (returns it).

    ``n_slots=None``/0 builds single-adapter TRAINING params; ``n_slots
    = N`` builds the N-tenant SERVING stacks (all zeros, filled later
    by ``ServingEngine.load_adapter``). ``n_slots=None`` also consults
    ``PADDLE_TPU_LORA_SLOTS`` so a launcher can pick serving shape by
    env. ``freeze_base`` stops gradients on every pre-existing param so
    ``Model.fit`` touches only the adapters."""
    cfg = cfg or LoRAConfig()
    if n_slots is None:
        n_slots = int(os.environ.get("PADDLE_TPU_LORA_SLOTS", "0"))
    n_slots = int(n_slots)
    targets = _lora_targets(model, cfg)
    if not targets:
        raise ValueError(
            f"no LoRA targets matched {cfg.target_modules!r} on "
            f"{type(model).__name__}")
    if freeze_base:
        for p in model.parameters():
            p.stop_gradient = True
    hook = _make_hook(cfg.scaling)
    r = cfg.rank
    for _, layer in targets:
        d_in, d_out = layer.weight.shape
        if n_slots > 0:
            a_shape, b_shape = (n_slots + 1, d_in, r), (n_slots + 1, r,
                                                        d_out)
            a_init = I.Constant(0.0)
        else:
            a_shape, b_shape = (d_in, r), (r, d_out)
            a_init = I.Normal(std=cfg.init_std)
        layer.lora_A = layer.create_parameter(
            a_shape, dtype=str(layer.weight.dtype),
            default_initializer=a_init)
        layer.lora_B = layer.create_parameter(
            b_shape, dtype=str(layer.weight.dtype),
            default_initializer=I.Constant(0.0))
        if n_slots > 0:
            # serving stacks hold tenant data, not trainables
            layer.lora_A.stop_gradient = True
            layer.lora_B.stop_gradient = True
        layer.register_forward_post_hook(hook)
    model._lora_cfg = cfg
    model._lora_slots = n_slots
    return model


# -- adapter checkpointing ----------------------------------------------------

def lora_state_dict(model) -> Dict[str, np.ndarray]:
    """Just the adapter leaves of the model's functional state — the
    small thing :func:`save_adapter` checkpoints."""
    from paddle_tpu.jit.functional import functional_state
    train, frozen, _ = functional_state(model)
    merged = {**frozen, **train}
    return {k: np.asarray(v) for k, v in merged.items()
            if k.rsplit(".", 1)[-1].startswith("lora_")}


def lora_param_bytes(model) -> int:
    return sum(v.nbytes for v in lora_state_dict(model).values())


def save_adapter(model, path: str, step: int = 0):
    """Checkpoint ONLY the adapter state (a few KB) via the standard
    CheckpointManager layout, so ``load_state_dir`` reads it back."""
    from paddle_tpu.checkpoint import CheckpointManager
    mgr = CheckpointManager(path)
    mgr.save(step, lora_state_dict(model), async_=False)
    return path


def load_adapter_state(path: str,
                       step: Optional[int] = None) -> Dict[str, object]:
    """Read an adapter checkpoint back as ``{param name: array}`` —
    what ``ServingEngine.load_adapter(slot, state)`` consumes."""
    from paddle_tpu.checkpoint import load_state_dir
    state = load_state_dir(path, step=step)
    return {k: v for k, v in state.items()
            if k.rsplit(".", 1)[-1].startswith("lora_")}
