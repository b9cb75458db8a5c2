"""SmallThinker family (``model_name`` ``smallthinker_*``): window and full
attention layers mixed, the full ones without any position encoding, and
softmax-routed ReGLU experts whose router reads the layer's input.

Layer ``l`` is a **full** layer where ``sliding_window_layout[l] == 0``
(there ``rope_layout[l] == 0`` too: NoPE) and a **window** layer otherwise
(RoPE, the last ``sliding_window_size`` positions). For its input ``x``,
with ``N_*`` an RMSNorm with its own gain:

    r = x W_r                         float32; the router reads the layer's
                                      input, before the norm and attention
    a = N_1(x);  q, k, v = a W_q, a W_k, a W_v     no bias, no q/k norm
    window layer: q, k rotated at absolute positions; full layer: not
    o = softmax(q k^T / sqrt(head_dim) + mask) v   key j visible to query i
                                      iff j <= i, under a window also
                                      i - j < window
    x = x + o W_o
    m = N_2(x)
    S = the top_k largest of r;  w = softmax(r_S)
    y = sum_{e in S} w_e (relu(m W_gate^e) * (m W_up^e)) W_down^e
    x = x + y

then a final RMSNorm and an untied head. Through the serving engine the
two kinds of layer are two **cache groups** (``kv_cache_spec()`` is a list,
one ``LayerCacheSpec`` a layer): the window group's pages behind the window
go back to its allocator. Both layouts are held whole as published and the
model reads the first ``num_hidden_layers`` entries.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu import nn, ops
from paddle_tpu.core.autograd import apply_op
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import numerics
from paddle_tpu.ops.paged_attention import LayerCacheSpec
from .llama import _gather_rope, _rot_interleaved

__all__ = ["SmallThinkerConfig", "SmallThinkerModel",
           "SmallThinkerForCausalLM"]


def _period4(n: int) -> Tuple[int, ...]:
    return tuple(0 if i % 4 == 0 else 1 for i in range(n))


@dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64       # the router's outputs
    moe_num_active_primary_experts: int = 6
    sliding_window_size: int = 4096
    #: a layer: 1 = window attention, 0 = full (as published, 52 entries)
    sliding_window_layout: Tuple[int, ...] = field(
        default_factory=lambda: _period4(52))
    #: a layer: 1 = RoPE, 0 = no position encoding
    rope_layout: Tuple[int, ...] = field(default_factory=lambda: _period4(52))
    rope_theta: float = 1500000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 16384
    initializer_range: float = 0.02

    def __post_init__(self):
        n = self.num_hidden_layers
        if n % 4:
            raise ValueError(f"num_hidden_layers {n}: the layer pattern's "
                             f"period is 4")
        if min(len(self.sliding_window_layout), len(self.rope_layout)) < n:
            raise ValueError("the layouts are shorter than the depth")

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """Test size: one period, 7 query heads a KV head, a window of 32."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                    num_attention_heads=7, num_key_value_heads=1,
                    head_dim=16, moe_ffn_hidden_size=32,
                    moe_num_primary_experts=8,
                    moe_num_active_primary_experts=2, sliding_window_size=32,
                    sliding_window_layout=_period4(8),
                    rope_layout=_period4(8), max_position_embeddings=256)
        base.update(kw)
        return SmallThinkerConfig(**base)

    def window_of(self, layer: int) -> Optional[int]:
        return self.sliding_window_size \
            if self.sliding_window_layout[layer] else None


def _linear(d_in, d_out):
    return nn.Linear(d_in, d_out, bias_attr=False)


class SmallThinkerAttention(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig, layer_idx: int):
        super().__init__()
        self.cfg = cfg
        self.n_heads, self.n_kv = (cfg.num_attention_heads,
                                   cfg.num_key_value_heads)
        self.head_dim = cfg.head_dim
        self.window = cfg.window_of(layer_idx)
        self.rotary = bool(cfg.rope_layout[layer_idx])
        d = cfg.hidden_size
        self.q_proj = _linear(d, self.n_heads * self.head_dim)
        self.k_proj = _linear(d, self.n_kv * self.head_dim)
        self.v_proj = _linear(d, self.n_kv * self.head_dim)
        self.o_proj = _linear(self.n_heads * self.head_dim, d)

    def _rotate(self, qa, ka, pos):
        """q, k ``[..., S, heads, hd]`` rotated at ``pos`` [S]; a NoPE
        layer leaves them as they are."""
        if not self.rotary:
            return qa, ka
        n = self.cfg.max_position_embeddings
        pidx = jnp.clip(pos.astype(jnp.int32), 0, n - 1)
        cos, sin = _gather_rope(pidx[None, :], self.head_dim,
                                self.cfg.rope_theta, str(qa.dtype), n)
        return (_rot_interleaved(qa, cos[0], sin[0]),
                _rot_interleaved(ka, cos[0], sin[0]))

    def forward(self, x, cache=None):
        """``x`` [B, S, hidden]. Without a cache: plain masked attention,
        returns the output. With a ``RaggedLayerCache`` (``x`` [1, T,
        hidden], the serving step's packed tokens): the cache writes the
        step's K/V and reads its pages under the layer's window
        (``ops/paged_attention.attend``); returns ``(out, cache')``."""
        B, S = x.shape[0], x.shape[1]
        H, G, hd = self.n_heads, self.n_kv, self.head_dim
        q = ops.reshape(self.q_proj(x), [B, S, H, hd])
        k = ops.reshape(self.k_proj(x), [B, S, G, hd])
        v = ops.reshape(self.v_proj(x), [B, S, G, hd])
        scale = 1.0 / math.sqrt(hd)
        if cache is None:
            def plain(qa, ka, va):
                qa, ka = self._rotate(qa, ka, jnp.arange(S))
                qg = qa.reshape(B, S, G, H // G, hd)
                s = jnp.einsum("bqkgh,blkh->bkgql", qg, ka,
                               preferred_element_type=jnp.float32) * scale
                i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
                visible = j <= i
                if self.window is not None:
                    visible &= i - j < self.window
                s = jnp.where(visible, s, jnp.finfo(jnp.float32).min)
                p = jax.nn.softmax(s, axis=-1).astype(va.dtype)
                return jnp.einsum("bkgql,blkh->bqkgh", p, va).reshape(
                    B, S, H * hd)
            return self.o_proj(apply_op(plain, q, k, v,
                                        op_name="window_attention"))

        from paddle_tpu.ops import paged_attention as pa

        def paged(qa, ka, va, c):
            qa, ka = self._rotate(qa[0], ka[0], c.positions)
            out, c = pa.attend(c, qa, ka, va[0], scale=scale)
            return (out.reshape(1, S, H * hd),) + c.pools()
        out, *pools = apply_op(paged, q, k, v, cache,
                               op_name="ragged_paged_kv_attention")
        return self.o_proj(out), cache.with_pools(pools)


class SmallThinkerDecoderLayer(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig, layer_idx: int):
        super().__init__()
        from paddle_tpu.distributed.fleet import HeldExpertsLayer
        d, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.self_attn = SmallThinkerAttention(cfg, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.mlp = HeldExpertsLayer(
            d, cfg.moe_ffn_hidden_size, cfg.moe_num_primary_experts,
            cfg.moe_num_active_primary_experts,
            init_std=cfg.initializer_range, score="softmax",
            activation="relu")

    def forward(self, x, cache=None):
        h = self.input_layernorm(x)
        if cache is None:
            attn, new_cache, kw = self.self_attn(h), None, {}
        else:
            attn, new_cache = self.self_attn(h, cache=cache)
            # the step's budget padding chooses no expert
            kw = {"token_mask": cache.live_mask()}
        resid = ops.add(x, numerics.tap("attn", attn))
        # the router scores the layer's input ``x``, the experts take the
        # normed stream after attention
        m = self.mlp(self.post_attention_layernorm(resid), router_input=x,
                     **kw)
        out = numerics.tap("resid", ops.add(resid, numerics.tap("mlp", m)))
        return out if cache is None else (out, new_cache)


class SmallThinkerModel(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([SmallThinkerDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        """Normalised hidden states; with ``caches`` (one
        ``RaggedLayerCache`` a layer) also the new caches."""
        x = numerics.tap("embed", self.embed_tokens(input_ids))
        if caches is not None and len(caches) != len(self.layers):
            raise ValueError(f"caches has {len(caches)} entries for "
                             f"{len(self.layers)} layers")
        new_caches = []
        for i, layer in enumerate(self.layers):
            with numerics.scope(f"layers.{i}"):
                if caches is None:
                    x = layer(x)
                else:
                    x, nc = layer(x, cache=caches[i])
                    new_caches.append(nc)
        h = numerics.tap("final_norm", self.norm(x))
        return h if caches is None else (h, new_caches)


class SmallThinkerForCausalLM(nn.Layer):
    """Decoder-only LM; ``forward(ids)`` returns the logits. Served
    through ``serving.ServingEngine`` (``decode_surfaces``: the trunk at
    ``model``, ``_logits`` the projector)."""

    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__()
        self.cfg = cfg
        self.model = SmallThinkerModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)
        from paddle_tpu.nn import initializer as I
        init = I.Normal(std=cfg.initializer_range)
        for _, p in self.named_parameters():
            if len(p.shape) == 2:    # the experts' stacks are born so
                p.set_value(init(p.shape))

    def forward(self, input_ids):
        return numerics.tap("logits", self._logits(self.model(input_ids)))

    def _logits(self, h):
        return self.lm_head(h)

    def kv_cache_spec(self):
        """One spec a layer: a K and a V row of ``head_dim`` under each
        KV head, and the layer's window (None in a full layer). Layers of
        equal spec share a cache group."""
        cfg = self.cfg
        return [LayerCacheSpec.kv(cfg.num_key_value_heads, cfg.head_dim,
                                  window=cfg.window_of(i))
                for i in range(cfg.num_hidden_layers)]

    def moe_expert_rows(self):
        """``[layers, experts]`` int32: the token rows each expert
        took in the forward just traced. Read inside the same
        trace (the serving step returns it)."""
        return Tensor(jnp.stack([l.mlp.last_rows.data
                                 for l in self.model.layers]))

    def clear_decode_side_effects(self):
        """Drop the rows a traced forward left behind."""
        for layer in self.model.layers:
            layer.mlp.last_rows = None
