"""Phi-4-mini-flash (``model_type`` ``phi4flash``, the SambaY
decoder-hybrid-decoder): a Samba self-decoder of Mamba-1 layers between
window-attention layers, one full-attention layer whose K/V is the only
full cache, and a cross-decoder whose attention layers read that K/V and
whose gated memory units (GMU) gate the self-decoder's last Mamba output.

With ``L`` layers and ``h = L // 2`` (``mb_per_layer`` 2): layer ``l < h``
is Mamba where ``l`` is even, else window attention; ``h`` is Mamba and
its gated output is the **memory**; ``h + 1`` is full attention; from
``h + 2`` on, even layers are GMUs and odd ones cross attention. For a
layer's input ``x`` (``LN`` a LayerNorm with gain and bias):

    a = x + Mixer(LN_1(x))
    y = a + (u * silu(g)) W_fc2       [g | u] = LN_2(a) W_fc1, the gate first

Mamba-1 (channels ``D = expand d``, state ``N``, dt rank ``R``):

    [x | z] = u W_in;  x' = silu(conv_causal(x) + b)
    [dr | B | C] = x' W_x;  dt = softplus(dr W_dt + b_dt);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + (dt_t x'_t) (x) B_t;  y_t = h_t C_t + D x'_t
    out = (y * silu(z)) W_out          (y * silu(z) is the memory at layer h)

GMU: ``out = (silu(u W_1) * m) W_2``, ``m`` the memory at the same
position. Differential attention (no position encoding; ``q`` of ``2H``
half-heads of width ``hd``, ``k`` and ``v`` of ``2G``): for differential
head ``j`` over KV pair ``g = j // (H / G)``,

    o_j = (A_1 - lam A_2) [v1_g | v2_g],  A_i = softmax(q_i k_i^T / sqrt(hd) + mask)
    o_j = RMSNorm(o_j) * g_sub * (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,  lam_init = 0.8 - 0.6 exp(-0.3 l)
    out = [o_0 ... o_{H-1}] W_o + b_o,  [q | k | v] = u W_qkv + b_qkv

The halves are interleaved: half-head ``2j + i`` is half ``i`` of
differential head ``j`` (``k`` and ``v`` alike). The logits are
``LN_f(h) E^T`` (tied); the embedding is not scaled.

Through the serving engine a window or full layer keeps its page as ``G``
"pair heads" of width ``2 hd``, ``k' = [k1 | k2]`` and ``v' = [v1 | v2]``
(the same bytes a token), and reads it with ``2H`` query heads of width
``2 hd``, ``[q1 | 0]`` and ``[0 | q2]``: ``ops/paged_attention.attend``
then returns ``A_1 [v1 | v2]`` and ``A_2 [v1 | v2]`` exactly. A cross
layer states a ``ReadCacheSpec`` of layer ``h + 1`` (no pages of its own,
queries only), a Mamba layer a ``StateCacheSpec`` (``ops/pallas/
selective_scan.py`` updates its state), a GMU nothing. The model states
where its cross-decoder starts (``cross_decoder_start``): the engine
hands the backbone the rows that sample (``rows=``), and layers from
there on run on those alone.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import nn
from paddle_tpu.core.autograd import apply_op
from paddle_tpu.models.granite_hybrid import causal_conv_rows
from paddle_tpu.observability import numerics
from paddle_tpu.ops.paged_attention import (LayerCacheSpec, ReadCacheSpec,
                                            StateCacheSpec)

__all__ = ["Phi4FlashConfig", "Phi4FlashModel", "Phi4FlashForCausalLM",
           "lambda_init"]


def lambda_init(depth: int) -> float:
    """Differential attention's ``lam_init`` at layer ``depth``."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None     # None: ceil(hidden / 16)
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.num_hidden_layers % 4:
            raise NotImplementedError(
                "Mamba and attention alternate (mb_per_layer 2) and the "
                "memory layer (depth // 2) is a Mamba layer")
        if self.num_key_value_heads % 2 or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads come in differential pairs, the query "
                             "heads grouped over the KV heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def memory_layer(self) -> int:
        return self.num_hidden_layers // 2

    def layer_kind(self, l: int) -> str:
        """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``."""
        h = self.memory_layer
        if l <= h:
            return "mamba" if l % self.mb_per_layer == 0 else "window"
        if l == h + 1:
            return "full"
        return "gmu" if l % self.mb_per_layer == 0 else "cross"


def subln(o, gain, eps):
    """A differential head's RMSNorm over its ``2 hd`` values."""
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * gain


def _linear(d_in, d_out, bias=False):
    return nn.Linear(d_in, d_out, bias_attr=None if bias else False)


class Phi4FlashLayerNorm(nn.Layer):
    """LayerNorm with gain and bias, its statistics in float32."""

    def __init__(self, d: int, eps: float):
        super().__init__()
        from paddle_tpu.nn import initializer as I
        self.eps = eps
        self.weight = self.create_parameter(
            [d], default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter([d], is_bias=True)

    def forward(self, x):
        def norm(xa, w, b):
            v = xa.astype(jnp.float32)
            v = v - jnp.mean(v, -1, keepdims=True)
            v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                  + self.eps)
            return (v * w.astype(jnp.float32)
                    + b.astype(jnp.float32)).astype(xa.dtype)
        return apply_op(norm, x, self.weight, self.bias, op_name="layer_norm")


class Phi4FlashMamba(nn.Layer):
    """The Mamba-1 mixer; its state is a ``StateLayerCache``'s rows (the
    recurrent state ``[N, D]`` and the convolution's last inputs)."""

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        from paddle_tpu.nn import initializer as I
        self.cfg = cfg
        D, N, K, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, \
            cfg.dt_rank
        self.in_proj = _linear(cfg.hidden_size, 2 * D)
        self.conv_weight = self.create_parameter(
            [K, D], default_initializer=I.Uniform(-0.5, 0.5))
        self.conv_bias = self.create_parameter([D], is_bias=True)
        self.x_proj = _linear(D, R + 2 * N)
        self.dt_proj = _linear(R, D, bias=True)
        # Mamba-1's initialisation: A = -(1..N) a channel, dt from
        # [1e-3, 1e-1] through the projection's bias
        self.A_log = self.create_parameter(
            [D, N], default_initializer=I.Assign(np.log(np.tile(
                np.arange(1, N + 1, dtype=np.float32), (D, 1)))))
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), D))
        self.dt_proj.bias.set_value(
            jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32))
        self.D = self.create_parameter([D],
                                       default_initializer=I.Constant(1.0))
        self.out_proj = _linear(D, cfg.hidden_size)

    def forward(self, x, cache):
        """``x`` [1, T, d]: the step's packed rows; ``cache`` a
        ``StateLayerCache``. Returns ``(out, cache', memory)``, the memory
        ``y * silu(z)`` [1, T, D]."""
        cfg = self.cfg

        def mix(xa, w_in, cw, cb, w_x, w_dt, b_dt, a_log, dd, w_out, ssm,
                conv, rows, cu, ctx, sid, pos):
            from paddle_tpu.ops.pallas.selective_scan import \
                selective_scan_update
            D, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
            S1 = rows.shape[0]
            xz = xa[0] @ w_in
            raw, z = xz[:, :D], xz[:, D:]
            acc, conv, fresh, n = causal_conv_rows(raw, cw, cb, conv, rows,
                                                   cu, ctx, sid, pos)
            xs = jax.nn.silu(acc)                              # f32 [T, D]
            dbc = xs.astype(xa.dtype) @ w_x
            dt = jax.nn.softplus(
                (dbc[:, :R] @ w_dt).astype(jnp.float32)
                + b_dt.astype(jnp.float32))
            B = dbc[:, R:R + N].astype(jnp.float32)
            C = dbc[:, R + N:].astype(jnp.float32)
            A = -jnp.exp(a_log.astype(jnp.float32)).T          # [N, D]
            S = S1 - 1
            y, ssm = selective_scan_update(
                ssm, dt * xs, dt, A, B, C, rows[:S], cu[:S], n[:S],
                fresh[:S], jnp.sum(n[:S] > 0))
            y = y + dd.astype(jnp.float32) * xs
            m = (y * jax.nn.silu(z.astype(jnp.float32))).astype(xa.dtype)
            return (m @ w_out)[None], ssm, conv, m[None]

        out, ssm, conv, m = apply_op(
            mix, x, self.in_proj.weight, self.conv_weight, self.conv_bias,
            self.x_proj.weight, self.dt_proj.weight, self.dt_proj.bias,
            self.A_log, self.D, self.out_proj.weight, cache.ssm, cache.conv,
            cache.rows, cache.cu_seqlens, cache.context_lens, cache.seq_ids,
            cache.positions, op_name="mamba1_mixer")
        return out, cache.with_pools((ssm, conv)), m


class Phi4FlashDiffAttention(nn.Layer):
    """Differential attention over pair-head pages (the module's
    docstring); ``cross`` projects queries only and reads another layer's
    pages."""

    def __init__(self, cfg: Phi4FlashConfig, layer_idx: int, cross: bool):
        super().__init__()
        from paddle_tpu.nn import initializer as I
        self.cfg, self.cross = cfg, cross
        self.lambda_init = lambda_init(layer_idx)
        d, hd = cfg.hidden_size, cfg.head_dim
        kv = 0 if cross else 2 * cfg.num_key_value_heads * hd
        self.Wqkv = _linear(d, cfg.num_attention_heads * hd + kv, bias=True)
        self.out_proj = _linear(cfg.num_attention_heads * hd, d, bias=True)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                [hd], default_initializer=I.Normal(std=0.1)))
        self.subln = self.create_parameter(
            [2 * hd], default_initializer=I.Constant(1.0))

    def forward(self, x, cache):
        """``cache`` a ``RaggedLayerCache``: this layer's pages (the step's
        K/V written, then read), or for a cross layer the pages it reads."""
        from paddle_tpu.ops import paged_attention as pa
        cfg = self.cfg
        T = x.shape[1]
        H2, G2, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        lam0, eps = self.lambda_init, cfg.layer_norm_eps

        def diff(xa, w, b, lq1, lk1, lq2, lk2, gsub, c):
            qkv = xa[0] @ w + b
            q = qkv[:, :H2 * hd].reshape(T, H2 // 2, 2, hd)
            # [q1 | 0] and [0 | q2]: a half-head's query on its half of
            # the pair head's key row
            zero = jnp.zeros_like(q)
            wide = jnp.stack([jnp.concatenate([q[:, :, 0], zero[:, :, 0]],
                                              -1),
                              jnp.concatenate([zero[:, :, 1], q[:, :, 1]],
                                              -1)], 2)
            wide = wide.reshape(T, H2, 2 * hd)
            if self.cross:
                out, c = pa.attend(c, wide, scale=1.0 / math.sqrt(hd))
            else:
                k = qkv[:, H2 * hd:(H2 + G2) * hd].reshape(T, G2 // 2,
                                                            2 * hd)
                v = qkv[:, (H2 + G2) * hd:].reshape(T, G2 // 2, 2 * hd)
                out, c = pa.attend(c, wide, k, v, scale=1.0 / math.sqrt(hd))
            out = out.astype(jnp.float32).reshape(T, H2 // 2, 2, 2 * hd)
            f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
            lam = jnp.exp(jnp.sum(f32(lq1) * f32(lk1))) \
                - jnp.exp(jnp.sum(f32(lq2) * f32(lk2))) + lam0
            o = subln(out[:, :, 0] - lam * out[:, :, 1], f32(gsub), eps)
            o = (o * (1.0 - lam0)).astype(xa.dtype)
            # (a cross layer's pools are the source layer's: unchanged)
            return (o.reshape(1, T, H2 * hd),) + (
                () if self.cross else c.pools())

        out, *pools = apply_op(
            diff, x, self.Wqkv.weight, self.Wqkv.bias, self.lambda_q1,
            self.lambda_k1, self.lambda_q2, self.lambda_k2, self.subln,
            cache, op_name="diff_paged_attention")
        return self.out_proj(out), \
            cache if self.cross else cache.with_pools(pools)


class Phi4FlashGMU(nn.Layer):
    """Gated memory unit: ``(silu(x W_1) * m) W_2``; it keeps nothing."""

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.in_proj = _linear(cfg.hidden_size, cfg.d_inner)
        self.out_proj = _linear(cfg.d_inner, cfg.hidden_size)

    def forward(self, x, memory):
        def gate(xa, w1, w2, m):
            g = jax.nn.silu((xa @ w1).astype(jnp.float32)) \
                * m.astype(jnp.float32)
            return g.astype(xa.dtype) @ w2
        return apply_op(gate, x, self.in_proj.weight, self.out_proj.weight,
                        memory, op_name="gated_memory_unit")


class Phi4FlashMLP(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        F = cfg.intermediate_size
        self.fc1 = _linear(cfg.hidden_size, 2 * F)
        self.fc2 = _linear(F, cfg.hidden_size)

    def forward(self, x):
        def glu(xa, w1, w2):
            gu = xa @ w1
            F = w2.shape[0]
            return (gu[..., F:] * jax.nn.silu(gu[..., :F])) @ w2
        return apply_op(glu, x, self.fc1.weight, self.fc2.weight,
                        op_name="gated_mlp")


class Phi4FlashDecoderLayer(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig, layer_idx: int):
        super().__init__()
        d, eps = cfg.hidden_size, cfg.layer_norm_eps
        self.kind = cfg.layer_kind(layer_idx)
        self.input_layernorm = Phi4FlashLayerNorm(d, eps)
        if self.kind == "mamba":
            self.mixer = Phi4FlashMamba(cfg)
        elif self.kind == "gmu":
            self.mixer = Phi4FlashGMU(cfg)
        else:
            self.mixer = Phi4FlashDiffAttention(cfg, layer_idx,
                                                cross=self.kind == "cross")
        self.post_attention_layernorm = Phi4FlashLayerNorm(d, eps)
        self.mlp = Phi4FlashMLP(cfg)

    def forward(self, x, cache, memory=None):
        """Returns ``(out, cache', memory')``: a Mamba layer's memory is its
        gated output, any other layer passes ``memory`` on."""
        u = self.input_layernorm(x)
        if self.kind == "mamba":
            m, cache, memory = self.mixer(u, cache)
        elif self.kind == "gmu":
            m = self.mixer(u, memory)
        else:
            m, cache = self.mixer(u, cache)
        from paddle_tpu import ops
        a = ops.add(x, numerics.tap("mixer", m))
        f = self.mlp(self.post_attention_layernorm(a))
        return numerics.tap("resid", ops.add(a, f)), cache, memory


def _take_rows(x, rows):
    """Rows ``rows`` [R] of the packed axis of ``x`` [1, T, ...]."""
    return apply_op(lambda xa, r: xa[:, r], x, rows, op_name="cross_rows")


class Phi4FlashModel(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([Phi4FlashDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.final_layernorm = Phi4FlashLayerNorm(cfg.hidden_size,
                                                  cfg.layer_norm_eps)

    def forward(self, input_ids, caches, rows=None):
        """``input_ids`` [1, T] the step's packed rows, ``caches`` one a
        layer (None where a layer keeps nothing; a cross layer's without
        pools: it reads those the full layer writes). With ``rows``
        ([R] int32, the sampling rows) the cross-decoder runs on those
        rows alone and the hidden states returned are theirs ``[1, R,
        d]``. Returns the normed hidden states and the new caches."""
        if len(caches) != len(self.layers):
            raise ValueError(f"caches has {len(caches)} entries for "
                             f"{len(self.layers)} layers")
        cfg = self.cfg
        full = cfg.memory_layer + 1
        x = numerics.tap("embed", self.embed_tokens(input_ids))
        memory, new_caches = None, []
        for i, layer in enumerate(self.layers):
            cache = caches[i]
            if i == full + 1 and rows is not None:
                x, memory = _take_rows(x, rows), _take_rows(memory, rows)
            if layer.kind == "cross":
                src = new_caches[full]
                cache = cache._replace(k_pool=src.k_pool, v_pool=src.v_pool)
            with numerics.scope(f"layers.{i}"):
                x, nc, mem = layer(x, cache, memory)
            if i == cfg.memory_layer:
                memory = mem
            new_caches.append(caches[i] if layer.kind == "cross" else nc)
        return numerics.tap("final_norm", self.final_layernorm(x)), \
            new_caches


class Phi4FlashForCausalLM(nn.Layer):
    """Decoder-only LM served through ``serving.ServingEngine``
    (``decode_surfaces``: the trunk at ``model``, ``_logits`` the tied
    head). It has no forward of its own outside the engine: the plain
    reference is ``benchmark/reference/phi4flash.py``."""

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Phi4FlashModel(cfg)
        from paddle_tpu.nn import initializer as I
        init = I.Normal(std=cfg.initializer_range)
        for name, p in self.named_parameters():
            if len(p.shape) == 2 and not name.endswith(("conv_weight",
                                                        "A_log")):
                p.set_value(init(p.shape))

    def _logits(self, h):
        w = self.model.embed_tokens.weight
        return apply_op(lambda ha, wa: jnp.einsum("...d,vd->...v", ha, wa),
                        h, w, op_name="tied_logits")

    def cross_decoder_start(self) -> int:
        """The first layer that changes nothing a cache keeps: from here
        on the engine runs the layers on the sampling rows alone."""
        return self.cfg.memory_layer + 2

    def kv_cache_spec(self):
        """A layer: a state row for a Mamba layer; ``G / 2`` pair heads of
        width ``2 hd`` for a window or the full layer; the full layer's
        pages for a cross layer; nothing for a GMU."""
        cfg = self.cfg
        pairs, width = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
        full = cfg.memory_layer + 1

        def spec(l):
            kind = cfg.layer_kind(l)
            if kind == "mamba":
                return StateCacheSpec(
                    conv=(cfg.mamba_d_conv - 1, cfg.d_inner),
                    ssm=(cfg.d_inner, 1, cfg.mamba_d_state))
            if kind == "window":
                return LayerCacheSpec.kv(pairs, width,
                                         window=cfg.sliding_window)
            if kind == "full":
                return LayerCacheSpec.kv(pairs, width)
            return ReadCacheSpec(full) if kind == "cross" else None
        return [spec(l) for l in range(cfg.num_hidden_layers)]
