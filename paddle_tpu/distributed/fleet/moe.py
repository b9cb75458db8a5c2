"""Mixture-of-Experts with expert parallelism.

Parity with the reference's MoE stack (``python/paddle/incubate/distributed/
models/moe/moe_layer.py:261`` MoELayer, ``moe/gate/`` naive/switch/gshard
gates, ``MoEScatter``/``MoEGather`` PyLayers over the ``global_scatter/
global_gather`` all-to-all ops, and the cutlass grouped GEMM
``phi/kernels/fusion/cutlass/moe/moe_kernel.cu``).

TPU-native redesign: experts are *stacked* weight tensors
``[E, d_model, d_hidden]`` sharded on the ``ep`` mesh axis, so one einsum is
the grouped GEMM and GSPMD lowers the token redistribution to the
all-to-all the reference launches explicitly. Over-capacity tokens drop
(contribute zero), matching ``global_scatter`` semantics.

Two dispatch formulations behind the same API (``dispatch_mode``):

* ``"ragged"`` (default) — index routing, the ``global_scatter/
  global_gather`` shape: each of the T*K (token, expert) assignments gets a
  capacity slot ``e*C + position`` (position = running count within the
  expert, the same order-dependent rule as the dense path, so drops are
  bit-identical). The data movement is GATHER-ONLY in both directions:
  tiny int32 scatters invert assignment→slot into a slot→token map once,
  then dispatch-forward, dispatch-backward, combine-forward and
  combine-backward are all row gathers (``custom_vjp`` supplies the
  inverse-map backward) — TPU scatters of [*, M] rows serialize badly and
  were the measured bottleneck of the scatter-add formulation. Peak
  intermediate is O(E*C*M + T*E) — no ``[T, E, C]`` tensor ever exists,
  which at DeepSeekMoE scale (E=64, T=16K) is the difference between ~2 MB
  of routing state and a multi-GB one-hot wall.
* ``"dense"`` — the original GShard one-hot einsum formulation
  ([T, E, C] dispatch/combine contractions); kept as the differential
  -testing oracle and for tiny shapes.

:class:`HeldExpertsLayer` is the other expert layer: gated (SwiGLU)
experts of which this chip is *told which it holds*, a router over all
of them, and **no capacity**: every assignment to a held expert is
computed (grouped products over the rows laid out by expert,
``ops/pallas/moe_gmm.py``), so a token's output never depends on what
else is in the batch. It is what
expert parallelism asks of one chip without its exchange, and what a
benchmark configuration that holds a chip's share of the experts runs.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np
from jax.sharding import PartitionSpec as P

from paddle_tpu.core.autograd import apply_op
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer_base import Layer
from ..mesh import get_mesh
from ..sharding_api import shard_tensor

__all__ = ["MoELayer", "HeldExpertsLayer", "NaiveGate", "SwitchGate",
           "GShardGate"]


def _ragged_moves(n_slots):
    """Gather-only dispatch/combine over a slot↔assignment inverse map.

    ``slot_src`` [n_slots+1] holds the token filling each capacity slot
    (sentinel = T → the zero pad row); ``slots_stack`` [K, T] holds each
    assignment's slot (sentinel = n_slots → the zero pad row). The two maps
    are inverses, so every VJP is itself a gather — no [*, M] row scatter
    ever runs (TPU scatters serialize; this was the ragged path's measured
    bottleneck). Integer operands take ``float0`` cotangents.
    """
    import jax
    import jax.numpy as jnp

    def _f0(x):
        return np.zeros(x.shape, jax.dtypes.float0)

    def _take0(arr, idx):
        pad = jnp.concatenate([arr, jnp.zeros((1, arr.shape[1]),
                                              arr.dtype)])
        return pad[jnp.minimum(idx, arr.shape[0])]

    @jax.custom_vjp
    def dispatch(xt, slot_src, slots_stack):
        return _take0(xt, slot_src[:n_slots])

    def dispatch_fwd(xt, slot_src, slots_stack):
        return dispatch(xt, slot_src, slots_stack), \
            (slots_stack, slot_src, xt.shape[0])

    def dispatch_bwd(res, g):
        slots_stack, slot_src, T = res
        dxt = _take0(g, slots_stack[0])
        for k in range(1, slots_stack.shape[0]):
            dxt = dxt + _take0(g, slots_stack[k])
        return dxt, _f0(slot_src), _f0(slots_stack)

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(flat, w_stack, slot_src, slots_stack, w_slot):
        # out[t] = Σ_k flat[slots[k, t]] * w[k, t]
        out = _take0(flat, slots_stack[0]) * w_stack[0][:, None]
        for k in range(1, slots_stack.shape[0]):
            out = out + _take0(flat, slots_stack[k]) * w_stack[k][:, None]
        return out

    def combine_fwd(flat, w_stack, slot_src, slots_stack, w_slot):
        return combine(flat, w_stack, slot_src, slots_stack, w_slot), \
            (flat, w_stack, slot_src, slots_stack, w_slot)

    def combine_bwd(res, g):
        flat, w_stack, slot_src, slots_stack, w_slot = res
        # d_flat[s] = g[token(s)] * w(s): the INVERSE map makes this a
        # gather of g rows, not a scatter of weighted rows
        d_flat = _take0(g, slot_src[:n_slots]) * w_slot[:n_slots, None]
        # d_w[k, t] = <flat[slots[k, t]], g[t]>
        d_w = jnp.stack([
            (_take0(flat, slots_stack[k]) * g).sum(-1)
            for k in range(slots_stack.shape[0])])
        return d_flat, d_w.astype(w_stack.dtype), _f0(slot_src), \
            _f0(slots_stack), jnp.zeros_like(w_slot)

    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


class _GateBase(Layer):
    top_k = 2

    def __init__(self, d_model, num_experts, top_k=None):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        if top_k is not None:
            self.top_k = top_k
        self.weight = self.create_parameter(
            shape=[d_model, num_experts],
            default_initializer=I.XavierUniform())


class NaiveGate(_GateBase):
    """top-k softmax gate, no auxiliary loss (reference: gate/naive_gate.py)."""
    aux = "none"


class SwitchGate(_GateBase):
    """top-1 gate with the Switch-Transformer load-balance loss
    (reference: gate/switch_gate.py)."""
    top_k = 1
    aux = "switch"


class GShardGate(_GateBase):
    """top-2 gate with GShard's mean(me * ce) * E^2 aux loss
    (reference: gate/gshard_gate.py)."""
    top_k = 2
    aux = "gshard"


class MoELayer(Layer):
    """Reference: moe_layer.py:261. Experts are a stacked SwiGLU-free MLP
    (w1 -> act -> w2) with weights [E, ...] sharded on the expert axis;
    ``forward`` sets ``self.l_aux`` to the gate's balance loss.
    """

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard",
                 top_k=None, capacity_factor=1.25, activation="gelu",
                 dispatch_mode="ragged", mesh=None,
                 axis: Optional[str] = "ep", name=None):
        super().__init__()
        if dispatch_mode not in ("ragged", "dense"):
            raise ValueError(f"dispatch_mode {dispatch_mode!r} must be "
                             "'ragged' or 'dense'")
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.dispatch_mode = dispatch_mode
        self._activation = activation
        if isinstance(gate, str):
            cls = {"naive": NaiveGate, "switch": SwitchGate,
                   "gshard": GShardGate}[gate]
            gate = cls(d_model, num_experts, top_k=top_k)
        self.gate = gate
        std = 1.0 / math.sqrt(d_model)
        self.w1 = self.create_parameter(
            shape=[num_experts, d_model, d_hidden],
            default_initializer=I.Uniform(-std, std))
        self.b1 = self.create_parameter(shape=[num_experts, d_hidden],
                                        is_bias=True)
        self.w2 = self.create_parameter(
            shape=[num_experts, d_hidden, d_model],
            default_initializer=I.Uniform(-1.0 / math.sqrt(d_hidden),
                                          1.0 / math.sqrt(d_hidden)))
        self.b2 = self.create_parameter(shape=[num_experts, d_model],
                                        is_bias=True)
        self._mesh = mesh or get_mesh()
        if self._mesh is not None and axis in getattr(
                self._mesh, "axis_names", ()):
            ep = self._mesh.shape[axis]
            if num_experts % ep == 0:
                for w in (self.w1, self.b1, self.w2, self.b2):
                    shard_tensor(w, self._mesh, spec=P(
                        axis, *([None] * (len(w.shape) - 1))))
        self.l_aux = None

    def forward(self, x, token_mask=None):
        """x: [..., d_model] -> same shape; stores self.l_aux.

        ``token_mask`` (optional, broadcastable to x's leading dims,
        True = real token) excludes padding from routing: masked tokens
        are assigned a sentinel expert id, so they claim no capacity
        positions, no bincount share, and no aux-loss weight — the
        serving engine's inactive decode slots and padded prefill-chunk
        tails must not steal expert capacity from (or perturb the drop
        pattern of) real tokens."""
        import jax
        import jax.numpy as jnp

        E = self.num_experts
        K = self.gate.top_k
        cap_f = self.capacity_factor
        aux_kind = getattr(self.gate, "aux", "none")
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
               "silu": jax.nn.silu}[self._activation]

        ragged = self.dispatch_mode == "ragged"

        def f(xa, gw, w1, b1, w2, b2, *rest):
            lead = xa.shape[:-1]
            xt = xa.reshape(-1, xa.shape[-1])  # [T, M]
            T, M = xt.shape
            C = max(int(cap_f * T * K / E), 1)
            vm = None
            if rest:
                vm = jnp.broadcast_to(rest[0].astype(bool),
                                      lead).reshape(T)

            logits = xt @ gw  # [T, E]
            probs = jax.nn.softmax(logits, axis=-1)

            # top-k selection, vectorized but ORDER-IDENTICAL to the
            # sequential GShard argmax-and-mask walk: lax.top_k returns
            # descending picks with first-index tie-breaks (same winner
            # sequence). Per-expert running counts come from ONE stable
            # argsort of the pick-major expert ids: within a sorted
            # segment, position = index - segment start — measured ~2x
            # faster on chip than the [K*T, E] one-hot cumsum these
            # replaced (same positions, so capacity drops stay
            # bit-identical).
            if vm is None:
                me = probs.mean(axis=0)  # mean gate prob per expert
            else:
                n_real = jnp.maximum(vm.sum(), 1).astype(probs.dtype)
                me = (probs * vm[:, None].astype(probs.dtype)).sum(0) \
                    / n_real
            gate_k, idx_k = jax.lax.top_k(probs, K)  # [T, K] descending
            e_flat = jnp.swapaxes(idx_k, 0, 1).reshape(K * T)
            if vm is not None:
                # padding routes to sentinel expert E: sorts into its own
                # trailing segment, takes no positions/counts below
                e_flat = jnp.where(jnp.tile(vm, K), e_flat, E)
            order = jnp.argsort(e_flat, stable=True)
            e_sorted = e_flat[order]
            ar = jnp.arange(K * T, dtype=jnp.int32)
            boundary = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), e_sorted[1:] != e_sorted[:-1]])
            seg_start = jax.lax.associative_scan(
                jnp.maximum, jnp.where(boundary, ar, 0))
            pos_flat = jnp.zeros((K * T,), jnp.int32).at[order].set(
                ar - seg_start)
            pos_km = pos_flat.reshape(K, T)
            counts = jnp.bincount(e_flat, length=E)  # sentinel E excluded
            if vm is None:
                ce_acc = counts.astype(probs.dtype) / T
                picks = [(idx_k[:, k], gate_k[:, k], pos_km[k],
                          pos_km[k] < C) for k in range(K)]
            else:
                ce_acc = counts.astype(probs.dtype) / n_real
                picks = [(idx_k[:, k], gate_k[:, k], pos_km[k],
                          (pos_km[k] < C) & vm) for k in range(K)]

            # renormalize gates over the KEPT assignments (dense path
            # normalized the combine tensor — same entries)
            denom = sum(gv * kp.astype(gv.dtype)
                        for _, gv, _, kp in picks)
            denom = jnp.maximum(denom, 1e-9)  # [T]

            if ragged:
                # ---- index routing (global_scatter/global_gather shape):
                # slot = e*C + position; dropped assignments point at the
                # sentinel pad row. Build the slot→token inverse map with
                # tiny int32 scatters (conflict-free: positions are unique
                # per expert), then every [*, M] move is a gather.
                tok = jnp.arange(T, dtype=jnp.int32)
                slot_src = jnp.full((E * C + 1,), T, jnp.int32)
                slots_list = []
                for idx, gv, pos_t, keep in picks:
                    slots = jnp.where(keep, idx * C + pos_t, E * C)
                    slot_src = slot_src.at[slots].set(tok)
                    slots_list.append(slots)
                slots_stack = jnp.stack(slots_list)  # [K, T]
                dispatch, combine = _ragged_moves(E * C)
                expert_in = dispatch(xt, slot_src,
                                     slots_stack).reshape(E, C, M)
            else:
                # ---- dense GShard one-hot contraction ([T, E, C] lives).
                # dispatch and combine share one per-pick [T,E]x[T,C]
                # outer product so the drop encoding exists exactly once
                dispatch = jnp.zeros((T, E, C), xt.dtype)
                combine = jnp.zeros((T, E, C), xt.dtype)
                for idx, gv, pos_t, keep in picks:
                    onehot = jax.nn.one_hot(idx, E, dtype=xt.dtype)
                    pos_oh = jax.nn.one_hot(
                        jnp.where(keep, pos_t, C), C + 1,
                        dtype=xt.dtype)[:, :C]
                    cell = onehot[:, :, None] * pos_oh[:, None, :]
                    dispatch = dispatch + cell
                    combine = combine + \
                        (gv / denom).astype(xt.dtype)[:, None, None] * cell
                expert_in = jnp.einsum("tec,tm->ecm", dispatch, xt)

            # grouped GEMM over stacked experts (ep-sharded on the mesh)
            h = act(jnp.einsum("ecm,emh->ech", expert_in, w1) +
                    b1[:, None, :])
            expert_out = jnp.einsum("ech,ehm->ecm", h, w2) + b2[:, None, :]

            if ragged:
                flat = expert_out.reshape(E * C, M)
                w_stack = jnp.stack([
                    (gv * kp.astype(gv.dtype) / denom).astype(xt.dtype)
                    for _, gv, _, kp in picks])  # [K, T]
                # per-slot combine weight (for the gather-only backward):
                # same tiny int32-scatter trick as slot_src
                w_slot = jnp.zeros((E * C + 1,), xt.dtype)
                for (idx, gv, pos_t, keep), wk in zip(picks, w_stack):
                    slots = jnp.where(keep, idx * C + pos_t, E * C)
                    w_slot = w_slot.at[slots].set(wk)
                out = combine(flat, w_stack, slot_src, slots_stack, w_slot)
            else:
                out = jnp.einsum("tec,ecm->tm", combine, expert_out)

            if aux_kind == "switch":
                aux = (me * ce_acc).sum() * E
            elif aux_kind == "gshard":
                aux = (me * (ce_acc / K)).sum() * E
            else:
                aux = jnp.zeros((), xt.dtype)
            return out.reshape(*lead, xa.shape[-1]), aux

        extra = () if token_mask is None else (token_mask,)
        out, aux = apply_op(f, x, self.gate.weight, self.w1, self.b1,
                            self.w2, self.b2, *extra, op_name="moe_layer")
        self.l_aux = aux
        return out


class HeldExpertsLayer(Layer):
    """Dropless routed experts, a chip's share (by default the
    sigmoid-scored, top-k-normalised router of the DeepSeek-V3 /
    openPangu-Ultra family).

    ``num_experts`` experts exist, the router scores all of them and each
    token chooses ``top_k``; ``held`` names the experts whose weights live
    here (global ids; default: all). With ``h`` a token's input:

        s = sigmoid(W_g h)                    over all experts, float32
        w = s_top / (sum s_top + 1e-20) * routed_scaling_factor
        y = sum over the chosen experts that are held of w_k E_k(h)
        E(h) = W_down (silu(W_gate h) * W_up h)

    With ``score="softmax"`` the ``top_k`` largest router logits are
    chosen and ``w = softmax`` over those logits (float32; the softmax over
    all experts renormalised over the chosen, no scaling factor); with
    ``activation="relu"`` the gate is ``relu`` (ReGLU). ``router_input``
    (``forward``) is what the router reads where that is not the experts'
    input ``h`` (a router placed before the block's attention). With
    ``selection_bias=True`` the layer holds a bias ``b`` an expert
    (``router_bias``, zeros at birth) and the ``top_k`` largest of ``s + b``
    are chosen; the weights are still those experts' ``s`` (DeepSeek-V3's
    selection bias: it moves who is chosen, never what a choice weighs).

    ``w`` is normalised over all ``top_k`` chosen, held or not: the parts
    the shares of a deployment compute add up to the whole layer's
    routed output. What the absent experts would add is left out and
    nothing stands in for them or their exchange. No capacity and no
    dropped token: the ``T * top_k`` assignments are laid out by held
    expert in row tiles (those to absent experts last) and each held
    expert multiplies exactly its tiles, by two Pallas kernels
    (``ops/pallas/moe_gmm.py``: gate and up in one pass, then down) that
    stream each held expert's weights once a step; rows outside the
    tiles are undefined and never read. Forward only: training of
    dropless experts is ROADMAP M1. Measured alone on a TPU v5e, a
    layer's three products at 16 experts of 6144 x 2048 with 220 of
    3,072 rows live took 3.94 ms as ``jax.lax.ragged_dot`` calls and 2.0
    in the kernels, against a floor of 1.47 for reading the weights.

    ``forward`` leaves the rows each held expert took, ``[len(held)]``
    int32, in ``self.last_rows`` (a traced value inside a compiled step:
    the caller reads it in the same trace and clears it).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k, held=None,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 init_std=0.02, score="sigmoid", activation="silu",
                 selection_bias=False):
        super().__init__()
        if score not in ("sigmoid", "softmax"):
            raise ValueError(f"score {score!r} (want sigmoid|softmax)")
        if selection_bias and score != "sigmoid":
            raise ValueError("a selection bias goes with sigmoid scores")
        if activation not in ("silu", "relu"):
            raise ValueError(f"activation {activation!r} (want silu|relu)")
        self.score, self.activation = score, activation
        held = tuple(range(num_experts)) if held is None \
            else tuple(int(e) for e in held)
        if len(set(held)) != len(held) or not held \
                or not all(0 <= e < num_experts for e in held):
            raise ValueError(f"held experts {held} of {num_experts}")
        self.d_model, self.d_hidden = d_model, d_hidden
        self.num_experts, self.top_k, self.held = num_experts, top_k, held
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        n, init = len(held), I.Normal(std=init_std)
        self.router = self.create_parameter(
            shape=[d_model, num_experts], default_initializer=init)
        self.w_gate = self.create_parameter(
            shape=[n, d_model, d_hidden], default_initializer=init)
        self.w_up = self.create_parameter(
            shape=[n, d_model, d_hidden], default_initializer=init)
        self.w_down = self.create_parameter(
            shape=[n, d_hidden, d_model], default_initializer=init)
        self.router_bias = self.create_parameter(
            shape=[num_experts], default_initializer=I.Constant(0.0)) \
            if selection_bias else None
        self.last_rows = None

    def forward(self, x, token_mask=None, router_input=None):
        """x: [..., d_model] -> the held experts' part of the routed
        output, same shape. ``token_mask`` (broadcastable to x's leading
        dims, True = real token): padding chooses no expert.
        ``router_input`` (x's shape): what the router scores, default x."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import moe_gmm

        E, K, n = self.num_experts, self.top_k, len(self.held)
        scale, norm = self.routed_scaling_factor, self.norm_topk_prob
        local_of = np.full((E,), n, np.int32)      # global id -> held slot
        local_of[list(self.held)] = np.arange(n, dtype=np.int32)
        softmax = self.score == "softmax"
        routed, masked = router_input is not None, token_mask is not None
        biased = self.router_bias is not None

        def f(xa, gw, wg, wu, wd, *rest):
            lead = xa.shape[:-1]
            xt = xa.reshape(-1, xa.shape[-1])
            T = xt.shape[0]
            rt = rest[0].reshape(T, -1) if routed else xt
            logits = jnp.dot(
                rt.astype(jnp.float32), gw.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)         # [T, E]
            if softmax:
                top_s, top_i = jax.lax.top_k(logits, K)
                w = jax.nn.softmax(top_s, axis=-1)
            else:
                s = jax.nn.sigmoid(logits)
                if biased:
                    _, top_i = jax.lax.top_k(
                        s + rest[-1 - masked].astype(jnp.float32), K)
                    top_s = jnp.take_along_axis(s, top_i, -1)
                else:
                    top_s, top_i = jax.lax.top_k(s, K)
                w = top_s * scale
                if norm:
                    w = w / (top_s.sum(-1, keepdims=True) + 1e-20)
            slot = jnp.asarray(local_of)[top_i]              # [T, K]
            if masked:
                vm = jnp.broadcast_to(rest[-1].astype(bool), lead).reshape(T)
                slot = jnp.where(vm[:, None], slot, n)
            # the T*K assignments laid out by held expert in row tiles
            # (their height from the rows an expert takes at a full
            # budget), absent ones after the last tile; token t's k-th
            # sits at row where[t, k]
            R = T * K
            tm = moe_gmm.row_tile(R / E)
            sizes, starts, tiles, dest = moe_gmm.layout(slot.reshape(R), n,
                                                        tm)
            src = jnp.zeros((moe_gmm.laid_rows(R, n, tm),), jnp.int32) \
                .at[dest].set(jnp.arange(R, dtype=jnp.int32) // K)
            act = moe_gmm.gate_up(xt[src], wg, wu, starts, tiles, tm=tm,
                                  activation=self.activation)
            out = moe_gmm.down(act, wd, starts, tiles, tm=tm)
            where = dest.reshape(T, K)
            # combine, gather-only. A select, not a weight of nought: the
            # rows outside the kernels' tiles (an absent expert's among
            # them) are undefined, and on a TPU that memory may hold a NaN
            y = sum(jnp.where(slot[:, k:k + 1] < n,
                              out[where[:, k]].astype(jnp.float32)
                              * w[:, k:k + 1], 0.0)
                    for k in range(K))
            return y.astype(xa.dtype).reshape(xa.shape), sizes

        extra = (() if router_input is None else (router_input,)) \
            + ((self.router_bias,) if biased else ()) \
            + (() if token_mask is None else (token_mask,))
        out, rows = apply_op(f, x, self.router, self.w_gate, self.w_up,
                             self.w_down, *extra, op_name="held_experts")
        self.last_rows = rows
        return out
