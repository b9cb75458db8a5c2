"""openPangu-Ultra-MoE family (``model_type`` ``pangu_ultra_moe``): latent
attention (MLA), sandwich-norm blocks, and sigmoid-routed gated experts of
which a chip may hold its share.

A block, with ``N_*`` an RMSNorm with its own gain (``sandwich_norm``):

    a = N_post_attn(Attn(N_in(x)));      x = x + a
    m = N_post_mlp(F(N_pre_mlp(x)));     x = x + m

``F`` is a SwiGLU (``LlamaMLP``) in the ``first_k_dense_replace`` leading
layers and ``HeldExpertsLayer`` plus the shared expert after them.

MLA, per token with hidden ``h``:

    c_q = N_q(W_qa h);   [q_nope | q_rope] = W_qb c_q      per head
    [c_kv | k_r] = W_kva h;   c = N_kv(c_kv);   k_rope = RoPE(k_r)
    [k_nope | v] = W_kvb c                                  per head
    scores = (q_nope.k_nope + RoPE(q_rope).k_rope) / sqrt(nope + rope)

The cache row of a token is ``[c | k_rope]`` (``kv_lora_rank +
qk_rope_head_dim`` numbers, one head). Without a cache the layer attends
in this expanded form. Through the serving engine's token-packed paged
cache (``RaggedLayerCache``) it reads the row **absorbed**: with ``W_UK``,
``W_UV`` the two halves of ``W_kvb``, ``q' = [W_UK^T q_nope | q_rope]``,
scores ``q'.[c | k_rope]``, ``u = P c`` and the head's output ``W_UV u``:
the same arithmetic regrouped, one read path (the ``rpa_mla`` kernel) for
prefill chunks and decode rows alike.

The multi-token-prediction module of the published model predicts the
token after next; the next-token logits do not depend on it and it is not
built here.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu import nn, ops
from paddle_tpu.core.autograd import apply_op
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import numerics
from paddle_tpu.ops.paged_attention import LayerCacheSpec
from .llama import LlamaConfig, LlamaMLP, _gather_rope, _rot_interleaved

__all__ = ["PanguMoeConfig", "PanguMoeModel", "PanguMoeForCausalLM"]


@dataclass
class PanguMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432      # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 2048   # each routed and shared expert
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256         # the router's outputs
    #: global ids of the routed experts whose weights live here (a chip's
    #: share of an expert-parallel deployment); None: all of them
    held_experts: Optional[Tuple[int, ...]] = None
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02

    @staticmethod
    def tiny(**kw) -> "PanguMoeConfig":
        """Test size: one dense and two expert layers."""
        base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=256)
        base.update(kw)
        return PanguMoeConfig(**base)

    @property
    def latent_row(self) -> int:
        """Numbers a token keeps in the cache, a layer: ``[c | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_cols(self) -> int:
        """Columns of a latent page: the row, zero-padded to whole
        128-lane vregs. A 576-wide bf16 page occupies 640 columns in the
        tiled layout the read kernel takes anyway, and the compiler keeps
        a pool of unaligned width transposed in HBM, with a whole-pool
        copy to the kernel's layout and back, a layer and step."""
        return -(-self.latent_row // 128) * 128

    def _mlp_cfg(self, width: int) -> LlamaConfig:
        return LlamaConfig(hidden_size=self.hidden_size,
                           intermediate_size=width)


def _linear(d_in, d_out):
    return nn.Linear(d_in, d_out, bias_attr=False)


class PanguMLA(nn.Layer):
    def __init__(self, cfg: PanguMoeConfig):
        super().__init__()
        self.cfg = cfg
        H, d = cfg.num_attention_heads, cfg.hidden_size
        self.qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _linear(d, cfg.q_lora_rank)
        self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank,
                                        epsilon=cfg.rms_norm_eps)
        self.q_b_proj = _linear(cfg.q_lora_rank, H * self.qk_dim)
        self.kv_a_proj_with_mqa = _linear(d, cfg.latent_row)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank,
                                         epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = _linear(
            cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = _linear(H * cfg.v_head_dim, d)

    def forward(self, x, cache=None):
        """``x`` [B, S, hidden]. Without a cache: causal attention in the
        expanded form, returns the output. With a ``RaggedLayerCache``
        (``x`` [1, T, hidden], the serving step's packed tokens): writes
        the tokens' latent rows into the layer's one pool and reads it
        absorbed; returns ``(out, cache')``."""
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        H, rank, rope = (cfg.num_attention_heads, cfg.kv_lora_rank,
                         cfg.qk_rope_head_dim)
        q = ops.reshape(
            self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x))),
            [B, S, H, self.qk_dim])
        ckv = self.kv_a_proj_with_mqa(x)                 # [B, S, rank+rope]
        c = self.kv_a_layernorm(ckv[:, :, :rank])
        k_r = ckv[:, :, rank:]
        if cache is None:
            out = apply_op(self._expanded, q, c, k_r, self.kv_b_proj.weight,
                           op_name="mla_expanded_attention")
            return self.o_proj(out)
        out, *pools = apply_op(
            self._absorbed, q, c, k_r, self.kv_b_proj.weight, cache,
            op_name="ragged_latent_attention")
        return self.o_proj(out), cache.with_pools(pools)

    def _rope(self, pos, dtype):
        cfg = self.cfg
        n = cfg.max_position_embeddings
        pidx = jnp.clip(pos.astype(jnp.int32), 0, n - 1)
        cos, sin = _gather_rope(pidx[None, :], cfg.qk_rope_head_dim,
                                cfg.rope_theta, str(dtype), n)
        return cos[0], sin[0]                            # [S, 1, rope/2]

    def _split_kvb(self, w):
        """``W_kvb`` [rank, H * (nope + v)] -> ``W_UK`` [rank, H, nope],
        ``W_UV`` [rank, H, v]."""
        cfg = self.cfg
        nope = cfg.qk_nope_head_dim
        w = w.reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                      nope + cfg.v_head_dim)
        return w[..., :nope], w[..., nope:]

    def _expanded(self, qa, ca, kra, wkvb):
        cfg = self.cfg
        B, S, H, _ = qa.shape
        nope = cfg.qk_nope_head_dim
        cos, sin = self._rope(jnp.arange(S), qa.dtype)
        q_rope = _rot_interleaved(qa[..., nope:], cos, sin)
        k_rope = _rot_interleaved(kra[:, :, None, :], cos, sin)
        w_uk, w_uv = self._split_kvb(wkvb)
        k_nope = jnp.einsum("bsc,chn->bshn", ca, w_uk)
        v = jnp.einsum("bsc,chv->bshv", ca, w_uv)
        s = (jnp.einsum("bqhn,bkhn->bhqk", qa[..., :nope], k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope[:, :, 0],
                          preferred_element_type=jnp.float32)) \
            / math.sqrt(self.qk_dim)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal[None, None], s, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", p, v).reshape(B, S, -1)

    def _absorbed(self, qa, ca, kra, wkvb, cache):
        from paddle_tpu.ops import paged_attention as pa
        cfg = self.cfg
        nope = cfg.qk_nope_head_dim
        qa, ca, kra = qa[0], ca[0], kra[0]               # the packed axis
        T = qa.shape[0]
        cos, sin = self._rope(cache.positions, qa.dtype)
        q_rope = _rot_interleaved(qa[..., nope:], cos, sin)
        k_rope = _rot_interleaved(kra[:, None, :], cos, sin)[:, 0]
        w_uk, w_uv = self._split_kvb(wkvb)
        q_abs = jnp.einsum("thn,chn->thc", qa[..., :nope], w_uk)
        pad = cfg.latent_cols - cfg.latent_row       # zeros: score nothing
        u, cache = pa.attend(
            cache,
            jnp.concatenate(
                [q_abs, q_rope, jnp.zeros((T, q_abs.shape[1], pad),
                                          q_abs.dtype)], -1),
            jnp.concatenate([ca, k_rope, jnp.zeros((T, pad), ca.dtype)], -1),
            value_cols=cfg.kv_lora_rank, scale=1.0 / math.sqrt(self.qk_dim))
        out = jnp.einsum("thc,chv->thv", u, w_uv)
        return (out.reshape(1, T, -1),) + cache.pools()


class PanguDecoderLayer(nn.Layer):
    def __init__(self, cfg: PanguMoeConfig, layer_idx: int):
        super().__init__()
        d, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.self_attn = PanguMLA(cfg)
        self.post_attention_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.pre_mlp_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.post_mlp_layernorm = nn.RMSNorm(d, epsilon=eps)
        self.is_dense = layer_idx < cfg.first_k_dense_replace
        if self.is_dense:
            self.mlp = LlamaMLP(cfg._mlp_cfg(cfg.intermediate_size))
            self.shared_experts = None
        else:
            from paddle_tpu.distributed.fleet import HeldExpertsLayer
            self.mlp = HeldExpertsLayer(
                d, cfg.moe_intermediate_size, cfg.n_routed_experts,
                cfg.num_experts_per_tok, held=cfg.held_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                init_std=cfg.initializer_range)
            self.shared_experts = LlamaMLP(cfg._mlp_cfg(
                cfg.moe_intermediate_size * cfg.n_shared_experts)) \
                if cfg.n_shared_experts else None

    def forward(self, x, cache=None):
        h = self.input_layernorm(x)
        if cache is None:
            attn, new_cache = self.self_attn(h), None
        else:
            attn, new_cache = self.self_attn(h, cache=cache)
        x = ops.add(x, numerics.tap(
            "attn", self.post_attention_layernorm(attn)))
        h = self.pre_mlp_layernorm(x)
        if self.is_dense:
            m = self.mlp(h)
        else:
            # the step's budget padding chooses no expert and counts in
            # no expert's rows
            kw = {} if cache is None else {"token_mask": cache.live_mask()}
            m = self.mlp(h, **kw)
            if self.shared_experts is not None:
                m = ops.add(m, self.shared_experts(h))
        x = ops.add(x, numerics.tap("mlp", self.post_mlp_layernorm(m)))
        x = numerics.tap("resid", x)
        return x if cache is None else (x, new_cache)


class PanguMoeModel(nn.Layer):
    def __init__(self, cfg: PanguMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([PanguDecoderLayer(cfg, i)
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


class PanguMoeForCausalLM(nn.Layer):
    """Decoder-only LM; ``forward(ids)`` returns the logits. Served
    through ``serving.ServingEngine`` (``decode_surfaces``: the trunk at
    ``model``, ``_logits`` the projector)."""

    def __init__(self, cfg: PanguMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = PanguMoeModel(cfg)
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
        """Each layer keeps one latent row ``[c | k_rope]`` a token under
        one head (in a page ``latent_cols`` wide); its values are the
        row's first ``kv_lora_rank`` columns, so there is no value pool."""
        cfg = self.cfg
        return LayerCacheSpec(1, cfg.latent_cols, None, cfg.kv_lora_rank)

    def moe_expert_rows(self):
        """``[layers, held experts]`` int32: the token rows each held
        expert took in the forward just traced (zeros for a dense layer).
        Read inside the same trace (the serving step returns it)."""
        held = [l.mlp for l in self.model.layers if not l.is_dense]
        zero = jnp.zeros((len(held[0].held),), jnp.int32)
        return Tensor(jnp.stack([
            zero if l.is_dense else l.mlp.last_rows.data
            for l in self.model.layers]))

    def clear_decode_side_effects(self):
        """Drop the rows a traced forward left behind."""
        for layer in self.model.layers:
            if not layer.is_dense:
                layer.mlp.last_rows = None
