"""Mixture-of-Experts decoder LMs (BASELINE.md DeepSeekMoE / Qwen2-MoE
configs).

Reference capability: ``python/paddle/incubate/distributed/models/moe/
moe_layer.py:261`` (MoELayer + global_scatter/gather) — here the expert
dispatch is the expert-parallel ``fleet.moe.MoELayer`` (GShard-style
combine/dispatch einsums, expert axis sharded on the mesh).

The decoder reuses the Llama attention stack; only the FFN differs:
  * ``num_shared_experts > 0`` adds DeepSeekMoE's always-on shared experts
    alongside the routed ones;
  * Qwen2-MoE shape = shared expert + fine-grained routed experts with
    top-k gating — both are config points of the same block.
"""
from __future__ import annotations

from dataclasses import dataclass

from paddle_tpu import ops
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.paged_attention import RaggedLayerCache
from .llama import LlamaAttention, LlamaConfig, LlamaMLP

__all__ = ["MoeConfig", "MoeDecoderLayer", "MoeForCausalLM"]


@dataclass
class MoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5632       # shared-expert / dense FFN width
    moe_intermediate_size: int = 1408   # per routed expert
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 60
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1      # DeepSeekMoE: first layers stay dense
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    aux_loss_weight: float = 0.01
    tensor_parallel: bool = False

    @staticmethod
    def qwen2_moe_a14b(**kw) -> "MoeConfig":
        base = dict(hidden_size=3584, intermediate_size=18944,
                         moe_intermediate_size=2560, num_hidden_layers=28,
                         num_attention_heads=28, num_key_value_heads=4,
                         num_experts=64, num_experts_per_tok=8,
                         first_k_dense_replace=0)
        base.update(kw)
        return MoeConfig(**base)

    @staticmethod
    def deepseek_moe_16b(**kw) -> "MoeConfig":
        base = dict(vocab_size=102400, hidden_size=2048,
                         intermediate_size=10944, moe_intermediate_size=1408,
                         num_hidden_layers=28, num_attention_heads=16,
                         num_key_value_heads=16, num_experts=64,
                         num_experts_per_tok=6, num_shared_experts=2,
                         first_k_dense_replace=1)
        base.update(kw)
        return MoeConfig(**base)

    @staticmethod
    def tiny(**kw) -> "MoeConfig":
        base = dict(vocab_size=128, hidden_size=32,
                         intermediate_size=64, moe_intermediate_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         num_key_value_heads=2, num_experts=4,
                         num_experts_per_tok=2, num_shared_experts=1,
                         first_k_dense_replace=1)
        base.update(kw)
        return MoeConfig(**base)

    def _attn_cfg(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            rope_theta=self.rope_theta, rms_norm_eps=self.rms_norm_eps,
            tensor_parallel=self.tensor_parallel)


class MoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: MoeConfig, layer_idx: int):
        super().__init__()
        acfg = cfg._attn_cfg()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(acfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_norm_eps)
        self.is_dense = layer_idx < cfg.first_k_dense_replace
        if self.is_dense:
            self.mlp = LlamaMLP(acfg)
        else:
            from paddle_tpu.distributed.fleet import MoELayer
            self.mlp = MoELayer(cfg.hidden_size, cfg.moe_intermediate_size,
                                cfg.num_experts, gate="gshard",
                                top_k=cfg.num_experts_per_tok,
                                activation="silu")
            if cfg.num_shared_experts > 0:
                shared_cfg = cfg._attn_cfg()
                shared_cfg.intermediate_size = (
                    cfg.moe_intermediate_size * cfg.num_shared_experts)
                self.shared_expert = LlamaMLP(shared_cfg)
            else:
                self.shared_expert = None

    def forward(self, x, cache=None):
        if cache is None:
            x = ops.add(x, self.self_attn(self.input_layernorm(x)))
        else:
            attn_out, new_cache = self.self_attn(self.input_layernorm(x),
                                                 cache=cache)
            x = ops.add(x, attn_out)
        h = self.post_attention_layernorm(x)
        if self.is_dense:
            out = ops.add(x, self.mlp(h))
        else:
            # paged serving: the step's budget padding must not steal
            # expert capacity from real tokens
            kw = {}
            if isinstance(cache, RaggedLayerCache):
                kw["token_mask"] = cache.live_mask()
            routed = self.mlp(h, **kw)
            if self.shared_expert is not None:
                routed = ops.add(routed, self.shared_expert(h))
            out = ops.add(x, routed)
        return out if cache is None else (out, new_cache)


class MoeForCausalLM(nn.Layer):
    """Decoder-only MoE LM; ``forward(ids, labels)`` returns
    (logits, loss) with the gate-balance aux loss folded in."""

    def __init__(self, cfg: MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([MoeDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    # vocab size from which the fused chunked CE pays for itself.
    # Profiled on chip at V=32000: the fused path's backward logits
    # RECOMPUTE costs more than the plain path's materialization, so the
    # gate stays at the Llama-validated 32768 — what pays at 32000 is
    # slicing h BEFORE the head matmul (see forward)
    _FUSED_CE_MIN_VOCAB = 32768

    def kv_cache_spec(self):
        """One K and V row of ``head_dim`` under each KV head, a layer
        (the serving engine builds its pools from this)."""
        from paddle_tpu.ops.paged_attention import LayerCacheSpec
        attn = self.layers[0].self_attn
        return LayerCacheSpec.kv(attn.n_kv, attn.head_dim)

    def aux_loss(self):
        total = None
        for layer in self.layers:
            la = getattr(layer.mlp, "l_aux", None)
            if la is not None:
                total = la if total is None else ops.add(total, la)
        return total

    def clear_decode_side_effects(self):
        """Drop per-layer gate side state (``l_aux``) left behind by a
        TRACED forward. Any compiled decode path — ``generate_compiled``
        and the ``serving.ServingEngine`` step — must call this after
        tracing so a later ``aux_loss()`` can't touch an escaped tracer
        (the balance loss only means something in training forwards)."""
        for layer in self.layers:
            if hasattr(layer.mlp, "l_aux"):
                layer.mlp.l_aux = None

    def forward(self, input_ids, labels=None, caches=None):
        x = self.embed_tokens(input_ids)
        if caches is not None:
            # cached path returns NORMALIZED HIDDEN states (not logits):
            # generate() projects only the positions it needs — a long
            # prefill must not pay a [B, S, vocab] lm_head matmul
            if len(caches) != len(self.layers):
                raise ValueError(
                    f"caches has {len(caches)} entries for "
                    f"{len(self.layers)} layers")
            new_caches = []
            for layer, c in zip(self.layers, caches):
                x, nc = layer(x, cache=c)
                new_caches.append(nc)
            return self.norm(x), new_caches
        for layer in self.layers:
            x = layer(x)
        h = self.norm(x)
        if labels is not None and labels.shape[1] < 2:
            raise ValueError(
                "causal-LM loss needs sequences of length >= 2")
        if labels is not None and \
                self.cfg.vocab_size >= self._FUSED_CE_MIN_VOCAB:
            # fused chunked matmul-CE: the [T, V] logits never
            # materialize. Profiling the train step showed the PLAIN path
            # spending ~25% of the whole step on head-side data movement
            # (a 250 MB logits reshape, a [T, V] one-hot, softmax-grad
            # passes) — the same reason the Llama recipe fuses
            # (ops/fused_ce.py). Returns (None, loss).
            from paddle_tpu.core.autograd import apply_op
            from paddle_tpu.ops.fused_ce import causal_lm_loss
            import jax.numpy as jnp
            w = self.lm_head.weight  # [d, V] -> fused CE wants [V, d]

            def f(ha, wa, lab):
                return causal_lm_loss(ha, jnp.swapaxes(wa, 0, 1), lab)

            loss = apply_op(f, h, w, labels, op_name="fused_causal_ce")
            aux = self.aux_loss()
            if aux is not None:
                loss = ops.add(loss,
                               ops.scale(aux, self.cfg.aux_loss_weight))
            return None, loss
        if labels is None:
            return self.lm_head(h)
        # HF-style contract: labels == input_ids; the shift happens HERE.
        # Slice h BEFORE the head matmul: logits[:, :-1] AFTER it forces
        # a non-contiguous 250 MB copy at reshape (profiled ~1.2 ms/step)
        # and computes a column of logits the loss never reads. Loss-only
        # path returns (None, loss) like the fused branch.
        logits = self.lm_head(h[:, :-1])
        loss = F.cross_entropy(
            ops.reshape(logits, [-1, logits.shape[-1]]),
            ops.reshape(labels[:, 1:], [-1]))
        aux = self.aux_loss()
        if aux is not None:
            loss = ops.add(loss, ops.scale(aux, self.cfg.aux_loss_weight))
        return None, loss

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id=None):
        """KV-cached decoding (see models/generation.py)."""
        from .generation import generate_loop

        def prefill(ids):
            caches = [(None, None)] * self.cfg.num_hidden_layers
            h, caches = self(ids, caches=caches)
            return self.lm_head(h[:, -1:]), caches

        def decode(tok, caches):
            h, caches = self(tok, caches=caches)
            return self.lm_head(h), caches

        return generate_loop(prefill, decode, input_ids, max_new_tokens,
                             temperature, top_k, top_p, eos_token_id)

    def generate_compiled(self, input_ids, max_new_tokens: int = 32,
                          temperature: float = 0.0, top_k: int = 0,
                          top_p: float = 1.0, eos_token_id=None,
                          prefill_chunk: int = 0):
        """Whole-loop compiled generation over static KV buffers (see
        ``generation.compiled_generate``); greedy output is
        token-for-token equal to ``generate``."""
        from .generation import compiled_generate
        out = compiled_generate(self, input_ids, max_new_tokens,
                                temperature, top_k, top_p, eos_token_id,
                                prefill_chunk=prefill_chunk)
        # tracing the loop stored TRACERS in every MoE layer's l_aux;
        # clear them so a later aux_loss() can't touch an escaped tracer
        self.clear_decode_side_effects()
        return out
