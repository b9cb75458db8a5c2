"""EXAONE-MoE family (``model_type`` ``exaone_moe``): window and full
grouped-query attention layers mixed (``sliding_windows[l]``: the window, 0
a full layer), q and k normed a head, a leading dense layer and then
sigmoid-routed SwiGLU experts beside a shared expert, and a
multi-token-prediction module that the engine runs as the model's
**drafter** (docs/SERVING.md "Drafts and verify rows").

For a layer's input ``x`` (``N_*`` an RMSNorm with its own gain):

    u = N_1(x);  q, k, v = u W_q, u W_k, u W_v        no bias
    q = N_q(q), k = N_k(k)                  over each head's ``head_dim``
    window layer: q, k rotated at absolute positions; full layer: not
    o = softmax(q k^T / sqrt(head_dim) + mask) v      key j visible to
                                            query i iff j <= i, under a
                                            window also i - j < window
    a = x + o W_o
    y = a + F(N_2(a))

``F`` is a SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers and the expert layer after them:
``s = sigmoid(W_r h)``, the ``num_experts_per_tok`` largest of ``s + b``
chosen, ``w = s_top / sum s_top * routed_scaling_factor``,
``F(h) = sum_k w_k E_k(h) + E_shared(h)`` (``fleet.HeldExpertsLayer``,
told which experts this chip holds). Then a final RMSNorm and an untied
head.

The drafter (DeepSeek-V3's multi-token-prediction module): with ``h_i``
the residual stream after the last layer at position ``i`` (before the
final norm) and ``t_{i+1}`` the next token,

    z_i = W_p [N_e(Emb(t_{i+1})) ; N_h(h_i)]
    one block as above, full attention over z_0..z_i (its own K/V)
    N_out, the model's head: the logits of t_{i+2}

Through the serving engine the two kinds of layer are two cache groups
(``kv_cache_spec()``: one ``LayerCacheSpec`` a layer) and the drafter's
block is one more layer of the full group (``draft_cache_spec()``).
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
from .llama import LlamaConfig, LlamaMLP, _rot_interleaved

__all__ = ["ExaoneMoeConfig", "ExaoneMoeModel", "ExaoneMoeForCausalLM"]


def _lllg(n: int, window: int) -> Tuple[int, ...]:
    return tuple(0 if i % 4 == 3 else window for i in range(n))


@dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    moe_intermediate_size: int = 2048
    num_experts: int = 128                  # the router's outputs
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: a layer: its window, 0 = full attention (as published, 48 entries)
    sliding_windows: Tuple[int, ...] = field(
        default_factory=lambda: _lllg(48, 128))
    #: the drafter's blocks (``mtp_sliding_windows``; 0 = full attention)
    num_nextn_predict_layers: int = 1
    mtp_sliding_windows: Tuple[int, ...] = (0,)
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    #: global ids of the experts whose weights live here (None: all)
    held_experts: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if len(self.sliding_windows) < self.num_hidden_layers:
            raise ValueError("sliding_windows is shorter than the depth")
        if self.num_nextn_predict_layers not in (0, 1):
            raise NotImplementedError("one multi-token-prediction module")
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)

    @staticmethod
    def tiny(**kw) -> "ExaoneMoeConfig":
        """Test size: a dense layer and one ``L L G L`` run after it, 4
        query heads a KV head, a window of 16."""
        base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=5, num_attention_heads=4,
                    num_key_value_heads=1, head_dim=16,
                    moe_intermediate_size=32, num_experts=8,
                    num_experts_per_tok=2,
                    sliding_windows=_lllg(8, 16),
                    max_position_embeddings=256)
        base.update(kw)
        return ExaoneMoeConfig(**base)

    def window_of(self, layer: int) -> Optional[int]:
        return self.sliding_windows[layer] or None

    def _mlp_cfg(self, width: int) -> LlamaConfig:
        return LlamaConfig(hidden_size=self.hidden_size,
                           intermediate_size=width)


def _linear(d_in, d_out):
    return nn.Linear(d_in, d_out, bias_attr=False)


class ExaoneMoeAttention(nn.Layer):
    """``window`` None: a full layer, no position encoding."""

    def __init__(self, cfg: ExaoneMoeConfig, window: Optional[int]):
        super().__init__()
        self.cfg = cfg
        self.n_heads, self.n_kv = (cfg.num_attention_heads,
                                   cfg.num_key_value_heads)
        self.head_dim = cfg.head_dim
        self.window = window
        d, hd, eps = cfg.hidden_size, cfg.head_dim, cfg.rms_norm_eps
        self.q_proj = _linear(d, self.n_heads * hd)
        self.k_proj = _linear(d, self.n_kv * hd)
        self.v_proj = _linear(d, self.n_kv * hd)
        self.o_proj = _linear(self.n_heads * hd, d)
        self.q_norm = nn.RMSNorm(hd, epsilon=eps)
        self.k_norm = nn.RMSNorm(hd, epsilon=eps)

    def _rotate(self, qa, ka, pos):
        """q, k ``[..., S, heads, hd]`` rotated at ``pos`` [S]; a full
        layer leaves them as they are. The angles are made from the
        positions (no table: the position cap is 262,144)."""
        if self.window is None:
            return qa, ka
        hd = self.head_dim
        inv = 1.0 / (self.cfg.rope_theta ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = pos.astype(jnp.float32)[:, None, None] * inv
        cos, sin = jnp.cos(ang).astype(qa.dtype), jnp.sin(ang).astype(qa.dtype)
        return (_rot_interleaved(qa, cos, sin),
                _rot_interleaved(ka, cos, sin))

    def forward(self, x, cache=None):
        """``x`` [B, S, hidden]. Without a cache: plain masked attention.
        With a ``RaggedLayerCache`` (``x`` [1, T, hidden], the serving
        step's packed tokens): the cache writes the step's K/V and reads
        its pages under the layer's window; returns ``(out, cache')``."""
        B, S = x.shape[0], x.shape[1]
        H, G, hd = self.n_heads, self.n_kv, self.head_dim
        q = self.q_norm(ops.reshape(self.q_proj(x), [B, S, H, hd]))
        k = self.k_norm(ops.reshape(self.k_proj(x), [B, S, G, hd]))
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


class ExaoneMoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig, window: Optional[int],
                 dense: bool):
        super().__init__()
        d, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.self_attn = ExaoneMoeAttention(cfg, window)
        self.post_attention_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.is_dense = dense
        if dense:
            self.mlp = LlamaMLP(cfg._mlp_cfg(cfg.intermediate_size))
            self.shared_experts = None
        else:
            from paddle_tpu.distributed.fleet import HeldExpertsLayer
            self.mlp = HeldExpertsLayer(
                d, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.held_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                init_std=cfg.initializer_range, selection_bias=True)
            self.shared_experts = LlamaMLP(cfg._mlp_cfg(
                cfg.moe_intermediate_size * cfg.num_shared_experts)) \
                if cfg.num_shared_experts else None

    def forward(self, x, cache=None):
        h = self.input_layernorm(x)
        if cache is None:
            attn, new_cache = self.self_attn(h), None
        else:
            attn, new_cache = self.self_attn(h, cache=cache)
        x = ops.add(x, numerics.tap("attn", attn))
        h = self.post_attention_layernorm(x)
        if self.is_dense:
            m = self.mlp(h)
        else:
            # the step's budget padding chooses no expert
            kw = {} if cache is None else {"token_mask": cache.live_mask()}
            m = self.mlp(h, **kw)
            if self.shared_experts is not None:
                m = ops.add(m, self.shared_experts(h))
        x = numerics.tap("resid", ops.add(x, numerics.tap("mlp", m)))
        return x if cache is None else (x, new_cache)


class ExaoneMoeModel(nn.Layer):
    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            ExaoneMoeDecoderLayer(cfg, cfg.window_of(i),
                                  i < cfg.first_k_dense_replace)
            for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None, keep_residual=False):
        """Normalised hidden states; with ``caches`` (one
        ``RaggedLayerCache`` a layer) also the new caches; with
        ``keep_residual`` also the stream before the final norm (what the
        drafter reads)."""
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
        out = (h,) if caches is None else (h, new_caches)
        if keep_residual:
            out += (x,)
        return out[0] if len(out) == 1 else out


class ExaoneMoeMTP(nn.Layer):
    """The multi-token-prediction module: its two input norms, the
    projection of their concatenation, one decoder block (an expert layer)
    and its output norm. Embedding and head are the model's."""

    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        d, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.enorm = nn.RMSNorm(d, epsilon=eps)
        self.hnorm = nn.RMSNorm(d, epsilon=eps)
        self.eh_proj = _linear(2 * d, d)
        self.block = ExaoneMoeDecoderLayer(
            cfg, cfg.mtp_sliding_windows[0] or None, dense=False)
        self.norm = nn.RMSNorm(d, epsilon=eps)

    def forward(self, emb_next, hidden, cache=None):
        z = self.eh_proj(ops.concat(
            [self.enorm(emb_next), self.hnorm(hidden)], axis=-1))
        if cache is None:
            return self.norm(self.block(z))
        x, cache = self.block(z, cache=cache)
        return self.norm(x), cache


class ExaoneMoeForCausalLM(nn.Layer):
    """Decoder-only LM; ``forward(ids)`` returns the logits. Served
    through ``serving.ServingEngine`` (``decode_surfaces``: the trunk at
    ``model``, ``_logits`` the projector); with ``draft_tokens=1`` the
    engine also runs ``draft`` over the step's rows."""

    def __init__(self, cfg: ExaoneMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = ExaoneMoeModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)
        self.mtp = ExaoneMoeMTP(cfg) if cfg.num_nextn_predict_layers \
            else None
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
        KV head, and the layer's window (None in a full layer)."""
        cfg = self.cfg
        return [LayerCacheSpec.kv(cfg.num_key_value_heads, cfg.head_dim,
                                  window=cfg.window_of(i))
                for i in range(cfg.num_hidden_layers)]

    # -- the drafter (what ``ServingEngine(draft_tokens=1)`` asks for) ------
    def draft_cache_spec(self):
        """The specs of the drafter's own layers (one: its block joins
        the model's full layers' group); empty without the module."""
        if self.mtp is None:
            return []
        cfg = self.cfg
        return [LayerCacheSpec.kv(cfg.num_key_value_heads, cfg.head_dim,
                                  window=cfg.mtp_sliding_windows[0] or None)]

    def draft(self, hidden, next_ids, caches=None):
        """The drafter's normalised hidden states (the model's head makes
        them the logits of the token after next). ``hidden`` [B, S, d]:
        the model's stream before its final norm (``model(ids,
        keep_residual=True)``); ``next_ids`` [B, S]: the token that follows
        each position. With ``caches`` (one a drafter layer) also the new
        caches."""
        emb = self.model.embed_tokens(next_ids)
        if caches is None:
            return self.mtp(emb, hidden)
        h, c = self.mtp(emb, hidden, cache=caches[0])
        return h, [c]

    def moe_expert_rows(self):
        """``[blocks, held experts]`` int32: the token rows each held
        expert took in the forward just traced, a row a layer of the model
        (zeros for a dense one) and, where the drafter ran, one more for
        its block. Read inside the same trace."""
        blocks = list(self.model.layers)
        if self.mtp is not None and self.mtp.block.mlp.last_rows is not None:
            blocks.append(self.mtp.block)
        routed = next(b.mlp for b in blocks if not b.is_dense)
        zero = jnp.zeros((len(routed.held),), jnp.int32)
        return Tensor(jnp.stack([
            zero if b.is_dense else b.mlp.last_rows.data for b in blocks]))

    def clear_decode_side_effects(self):
        """Drop the rows a traced forward left behind."""
        for layer in self.model.layers:
            if not layer.is_dense:
                layer.mlp.last_rows = None
        if self.mtp is not None:
            self.mtp.block.mlp.last_rows = None
