"""Granite 4.0-H family (``model_type`` ``granitemoehybrid`` without
experts): Mamba-2 state-space layers and a few full attention layers
without position encoding, every layer followed by a SwiGLU MLP.

``layer_types[l]`` is ``"mamba"`` or ``"attention"``. For a layer's input
``x`` (``N_*`` an RMSNorm with its own gain, ``r`` the
``residual_multiplier``):

    a = x + r Mixer(N_1(x))
    y = a + r W_o(silu(g) * u)        [g | u] = N_2(a) W_in, the gate first

An attention mixer is grouped-query attention with no bias and no rotary,
scores scaled by ``attention_multiplier``. A Mamba-2 mixer (heads ``H``,
head width ``P``, state width ``N``, one group, ``d_inner = expand * d``):

    [z | xBC | dt] = x W_in                    d_inner | d_inner + 2N | H
    xBC' = silu(b + sum_k w_k * xBC_{t-3+k})   causal depthwise conv, width 4
    [x | B | C] = xBC'                         x as [H, P]
    dt = softplus(dt + dt_bias);  A = -exp(A_log)      one a head
    h_t = exp(dt A) h_{t-1} + dt x_t (x) B_t   h [H, P, N]
    y_t = h_t C_t + D x_t
    out = W_out(RMSNorm(y * silu(z)) * g)      the gated norm over d_inner

The embedding is scaled by ``embedding_multiplier``; after the final
RMSNorm the logits are ``h E^T / logits_scaling`` (the head is tied).

Through the serving engine an attention layer keeps K/V pages and a Mamba
layer one state row a slot (``kv_cache_spec()``: a ``LayerCacheSpec`` or a
``StateCacheSpec`` a layer): the convolution's last three inputs and
``h``. The state update over the step's ragged rows is
``ops/pallas/ssd.py``; the convolution and the gated norm run in XLA
around it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn, ops
from paddle_tpu.core.autograd import apply_op
from paddle_tpu.observability import numerics
from paddle_tpu.ops.paged_attention import LayerCacheSpec, StateCacheSpec

__all__ = ["GraniteHybridConfig", "GraniteHybridModel",
           "GraniteHybridForCausalLM", "causal_conv_rows"]


def _micro_layout() -> Tuple[str, ...]:
    return tuple("attention" if i in (5, 15, 25, 35) else "mamba"
                 for i in range(40))


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = field(default_factory=_micro_layout)
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than the depth")
        if self.mamba_n_groups != 1 or self.mamba_proj_bias \
                or not self.mamba_conv_bias:
            raise NotImplementedError("one B/C group, a convolution bias and "
                                      "no projection bias")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError(f"{self.mamba_n_heads} heads x "
                             f"{self.mamba_d_head} != d_inner {self.d_inner}")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """Test size: two Mamba layers about one attention layer."""
        base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
                    layer_types=("mamba", "attention", "mamba"),
                    num_attention_heads=4, num_key_value_heads=2,
                    attention_multiplier=0.125, shared_intermediate_size=48,
                    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                    max_position_embeddings=512)
        base.update(kw)
        return GraniteHybridConfig(**base)


def _linear(d_in, d_out):
    return nn.Linear(d_in, d_out, bias_attr=False)


def _rms(x, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def causal_conv_rows(raw, cw, cb, conv, rows, cu, ctx, sid, pos):
    """A state layer's causal depthwise convolution over the step's packed
    rows ``raw`` [T, C] (weights ``cw`` [K, C], bias ``cb``): a row's
    earlier inputs are this step's rows of its entry or, before its first,
    the entry's convolution state row (``conv`` [slots + 1, (K - 1) * C],
    entry ``e``'s row ``rows[e]``; zero where the entry starts at position
    0). Returns ``(pre-activation [T, C] float32, conv', fresh [E], new
    rows an entry [E])``, each entry's last ``K - 1`` inputs written as
    its new state row."""
    T, C = raw.shape
    K, S1 = cw.shape[0], rows.shape[0]
    xbc, cw = raw.astype(jnp.float32), cw.astype(jnp.float32)
    fresh = ctx == 0                                     # [S1]
    past = conv[rows].reshape(S1, K - 1, C)
    past = jnp.where(fresh[:, None, None], 0, past)
    flat = past.reshape(S1 * (K - 1), C)
    j = pos - ctx[sid]                 # a row's place in its entry
    acc = cb.astype(jnp.float32) + xbc * cw[K - 1]
    for k in range(1, K):
        held = flat[sid * (K - 1) + jnp.clip(K - 1 + j - k, 0, K - 2)
                    ].astype(jnp.float32)
        prev = jnp.where((j >= k)[:, None], jnp.roll(xbc, k, 0), held)
        acc = acc + prev * cw[K - 1 - k]
    # each entry's last K - 1 inputs are its new convolution state
    n = cu[1:S1 + 1] - cu[:S1]
    keep = []
    for m in range(K - 1):
        i = n - (K - 1) + m
        keep.append(jnp.where(
            (i >= 0)[:, None],
            raw[jnp.clip(cu[:S1] + i, 0, T - 1)],
            past[jnp.arange(S1), jnp.clip(n + m, 0, K - 2)]))
    dest = jnp.where(n > 0, rows, conv.shape[0])
    conv = conv.at[dest].set(
        jnp.stack(keep, 1).reshape(S1, -1).astype(conv.dtype), mode="drop")
    return acc, conv, fresh, n


class GraniteMamba2(nn.Layer):
    """The Mamba-2 mixer; its state is a ``StateLayerCache``'s rows."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        from paddle_tpu.nn import initializer as I
        self.cfg = cfg
        H, K = cfg.mamba_n_heads, cfg.mamba_d_conv
        self.in_proj = _linear(cfg.hidden_size,
                               cfg.d_inner + cfg.conv_dim + H)
        self.conv_weight = self.create_parameter(
            [K, cfg.conv_dim], default_initializer=I.Normal(std=0.2))
        self.conv_bias = self.create_parameter([cfg.conv_dim], is_bias=True)
        # Mamba-2's initialisation: A = -(1..H), dt from [1e-3, 1e-1]
        self.A_log = self.create_parameter(
            [H], default_initializer=I.Assign(
                np.log(np.arange(1, H + 1, dtype=np.float32))))
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), H))
        self.dt_bias = self.create_parameter(
            [H], default_initializer=I.Assign(
                (dt + np.log(-np.expm1(-dt))).astype(np.float32)))
        self.D = self.create_parameter([H],
                                       default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            [cfg.d_inner], default_initializer=I.Constant(1.0))
        self.out_proj = _linear(cfg.d_inner, cfg.hidden_size)

    def forward(self, x, cache):
        """``x`` [1, T, d]: the step's packed rows; ``cache`` a
        ``StateLayerCache``. Returns ``(out, cache')``."""
        cfg = self.cfg

        def mix(xa, w_in, cw, cb, a_log, dt_b, dd, gw, w_out, ssm, conv,
                rows, cu, ctx, sid, pos):
            from paddle_tpu.ops.pallas.ssd import ssd_update
            H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
            di, C = cfg.d_inner, cfg.conv_dim
            T, S1 = xa.shape[1], rows.shape[0]
            zxd = xa[0] @ w_in
            z, raw, dt = zxd[:, :di], zxd[:, di:di + C], zxd[:, di + C:]
            acc, conv, fresh, n = causal_conv_rows(raw, cw, cb, conv, rows,
                                                   cu, ctx, sid, pos)
            xbc = jax.nn.silu(acc)
            xs = xbc[:, :di].reshape(T, H, P).astype(jnp.float32)
            B = xbc[:, di:di + N]
            Cm = xbc[:, di + N:]
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + dt_b.astype(jnp.float32))
            A = -jnp.exp(a_log.astype(jnp.float32))
            S = S1 - 1
            y, ssm = ssd_update(ssm, xs * dt[:, :, None], dt * A, B, Cm,
                                rows[:S], cu[:S], n[:S], fresh[:S],
                                jnp.sum(n[:S] > 0))
            y = (y + dd.astype(jnp.float32)[:, None] * xs).reshape(T, di)
            g = _rms(y * jax.nn.silu(z.astype(jnp.float32)), cfg.rms_norm_eps)
            g = (g * gw.astype(jnp.float32)).astype(xa.dtype)
            return (g @ w_out)[None], ssm, conv

        out, ssm, conv = apply_op(
            mix, x, self.in_proj.weight, self.conv_weight, self.conv_bias,
            self.A_log, self.dt_bias, self.D, self.norm_weight,
            self.out_proj.weight, cache.ssm, cache.conv, cache.rows,
            cache.cu_seqlens, cache.context_lens, cache.seq_ids,
            cache.positions, op_name="mamba2_mixer")
        return out, cache.with_pools((ssm, conv))


class GraniteAttention(nn.Layer):
    """Grouped-query attention without position encoding."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        d, hd = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(d, cfg.num_attention_heads * hd)
        self.k_proj = _linear(d, cfg.num_key_value_heads * hd)
        self.v_proj = _linear(d, cfg.num_key_value_heads * hd)
        self.o_proj = _linear(cfg.num_attention_heads * hd, d)

    def forward(self, x, cache):
        """``cache`` a ``RaggedLayerCache``: writes the step's K/V and
        reads the pages (``ops/paged_attention.attend``)."""
        from paddle_tpu.ops import paged_attention as pa
        cfg = self.cfg
        T = x.shape[1]
        H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        q = ops.reshape(self.q_proj(x), [T, H, hd])
        k = ops.reshape(self.k_proj(x), [T, G, hd])
        v = ops.reshape(self.v_proj(x), [T, G, hd])

        def paged(qa, ka, va, c):
            out, c = pa.attend(c, qa, ka, va,
                               scale=cfg.attention_multiplier)
            return (out.reshape(1, T, H * hd),) + c.pools()
        out, *pools = apply_op(paged, q, k, v, cache,
                               op_name="ragged_paged_kv_attention")
        return self.o_proj(out), cache.with_pools(pools)


class GraniteMLP(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        F = cfg.shared_intermediate_size
        self.input_linear = _linear(cfg.hidden_size, 2 * F)
        self.output_linear = _linear(F, cfg.hidden_size)

    def forward(self, x):
        def swiglu(xa, w_in, w_out):
            gu = xa @ w_in
            F = w_out.shape[0]
            return (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ w_out
        return apply_op(swiglu, x, self.input_linear.weight,
                        self.output_linear.weight, op_name="swiglu_mlp")


class GraniteHybridDecoderLayer(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig, layer_idx: int):
        super().__init__()
        d, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.cfg = cfg
        self.kind = cfg.layer_types[layer_idx]
        self.input_layernorm = nn.RMSNorm(d, epsilon=eps)
        if self.kind == "mamba":
            self.mamba = GraniteMamba2(cfg)
        else:
            self.self_attn = GraniteAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.shared_mlp = GraniteMLP(cfg)

    def forward(self, x, cache):
        r = self.cfg.residual_multiplier
        mixer = self.mamba if self.kind == "mamba" else self.self_attn
        m, new_cache = mixer(self.input_layernorm(x), cache)
        a = ops.add(x, ops.scale(numerics.tap("mixer", m), r))
        f = self.shared_mlp(self.post_attention_layernorm(a))
        out = numerics.tap("resid", ops.add(a, ops.scale(
            numerics.tap("mlp", f), r)))
        return out, new_cache


class GraniteHybridModel(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([GraniteHybridDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches):
        """``input_ids`` [1, T] the step's packed rows, ``caches`` one a
        layer; returns the normed hidden states and the new caches."""
        if len(caches) != len(self.layers):
            raise ValueError(f"caches has {len(caches)} entries for "
                             f"{len(self.layers)} layers")
        x = ops.scale(self.embed_tokens(input_ids),
                      self.cfg.embedding_multiplier)
        x = numerics.tap("embed", x)
        new_caches = []
        for i, layer in enumerate(self.layers):
            with numerics.scope(f"layers.{i}"):
                x, nc = layer(x, caches[i])
                new_caches.append(nc)
        return numerics.tap("final_norm", self.norm(x)), new_caches


class GraniteHybridForCausalLM(nn.Layer):
    """Decoder-only LM served through ``serving.ServingEngine``
    (``decode_surfaces``: the trunk at ``model``, ``_logits`` the tied
    head). It has no forward of its own outside the engine: the plain
    reference is ``benchmark/reference/granite_hybrid.py``."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GraniteHybridModel(cfg)
        from paddle_tpu.nn import initializer as I
        init = I.Normal(std=cfg.initializer_range)
        for name, p in self.named_parameters():
            if len(p.shape) == 2 and not name.endswith("conv_weight"):
                p.set_value(init(p.shape))

    def _logits(self, h):
        w = self.model.embed_tokens.weight

        def head(ha, wa):
            return jnp.einsum("...d,vd->...v", ha, wa) \
                / self.cfg.logits_scaling
        return apply_op(head, h, w, op_name="tied_logits")

    def kv_cache_spec(self):
        """A layer: K/V pages of ``head_dim`` under each KV head for an
        attention layer; one state row (the convolution's last inputs,
        the recurrent state) for a Mamba layer."""
        cfg = self.cfg
        return [LayerCacheSpec.kv(cfg.num_key_value_heads, cfg.head_dim)
                if cfg.layer_types[i] == "attention" else StateCacheSpec(
                    conv=(cfg.mamba_d_conv - 1, cfg.conv_dim),
                    ssm=(cfg.mamba_n_heads, cfg.mamba_d_head,
                         cfg.mamba_d_state))
                for i in range(cfg.num_hidden_layers)]
