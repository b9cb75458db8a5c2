"""Llama-3 family (BASELINE.md north-star model).

Capability parity target: the PaddleNLP Llama recipe the reference runs for
its headline numbers (the reference repo itself carries no LLM zoo; its
fused-attention seam is ``paddle/phi/kernels/gpu/flash_attn_kernel.cu``).

TPU-first design decisions:
  * attention goes through ``nn.functional.flash_attention`` → the Pallas
    flash kernel on TPU;
  * GQA (num_key_value_heads < num_attention_heads) is a reshape +
    broadcast, no repeat_interleave materialization;
  * with ``tensor_parallel=True`` the projections are mpu Column/Row
    parallel layers and the embedding is vocab-parallel — GSPMD places the
    collectives (SURVEY.md §7 principle 3);
  * rotary embedding is a single fused tape node (one jnp body), cached
    per (seq, dim, dtype).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.autograd import apply_op
from paddle_tpu import ops
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.observability import numerics
from paddle_tpu.ops.paged_attention import RaggedLayerCache

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM"]


@dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    tensor_parallel: bool = False
    recompute: bool = False

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_70b(**kw) -> "LlamaConfig":
        base = dict(hidden_size=8192, intermediate_size=28672,
                    num_hidden_layers=80, num_attention_heads=64,
                    num_key_value_heads=8)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-size config: runs forward+backward in <1s on CPU."""
        base = dict(vocab_size=256, hidden_size=64,
                    intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    max_position_embeddings=128)
        base.update(kw)
        return LlamaConfig(**base)


@functools.lru_cache(maxsize=32)
def _rope_cache(seq_len: int, dim: int, theta: float, dtype_name: str):
    # numpy on purpose: this cache is shared across traces, so it must
    # never hold jax tracers (a traced entry would leak into later traces
    # as an UnexpectedTracerError); the arrays become XLA constants at use
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    t = np.arange(seq_len, dtype=np.float64)
    freqs = np.outer(t, inv)  # [S, dim/2]
    to = jnp.dtype(dtype_name)
    return (np.cos(freqs).astype(to), np.sin(freqs).astype(to))


def _rot_interleaved(t, cos, sin):
    """THE rotation convention (even/odd lane pairs, re-interleaved) —
    the single definition every path (eager, static-cache, paged
    serving) must share so their numerics can never desynchronize.
    ``cos``/``sin`` broadcast against ``t`` [..., S, H, D/2]."""
    t1, t2 = t[..., 0::2], t[..., 1::2]
    return jnp.stack([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                     axis=-1).reshape(t.shape)


def _gather_rope(pidx, dim, theta, dtype_name, table_len):
    """cos/sin [B, S, 1, dim/2] at PER-ROW absolute positions ``pidx``
    [B, S] (already clipped to the table) from the cached table."""
    cos_np, sin_np = _rope_cache(table_len, dim, theta, dtype_name)
    return (jnp.asarray(cos_np)[pidx][:, :, None, :],
            jnp.asarray(sin_np)[pidx][:, :, None, :])


def apply_rotary(q, k, theta: float = 500000.0, pos_offset: int = 0,
                 table_len: int = 0):
    """Rotate q,k ([B,S,H,D]) by absolute position (``pos_offset`` shifts
    the position index — the KV-cached decode path's token lands at
    position P, not 0). ``table_len`` fixes the cached table size (pass
    max_position_embeddings so every decode step hits ONE lru entry
    instead of minting a new table per length). One tape node."""
    def f(qa, ka):
        s, d = qa.shape[1], qa.shape[-1]
        n = max(table_len, pos_offset + s)
        cos, sin = _rope_cache(n, d, theta, str(qa.dtype))
        cos = jnp.asarray(cos)[None, pos_offset:pos_offset + s, None, :]
        sin = jnp.asarray(sin)[None, pos_offset:pos_offset + s, None, :]
        return (_rot_interleaved(qa, cos, sin),
                _rot_interleaved(ka, cos, sin))
    return apply_op(f, q, k, op_name="rotary_embedding")


def apply_rotary_positions(q, k, position_ids, theta: float = 500000.0,
                           table_len: int = 0):
    """Rotate q,k ([B,S,H,D]) at PER-TOKEN positions ``position_ids``
    [B,S] — the packed-sequence form (docs/DATA.md): each document inside
    a packed row restarts at position 0, so RoPE must be gathered per
    token instead of sliced by row offset. Same table and rotation
    convention as :func:`apply_rotary` (one ``_rope_cache`` /
    ``_rot_interleaved`` pair for every path)."""
    def f(qa, ka, pidx):
        s, d = qa.shape[1], qa.shape[-1]
        n = max(table_len, s)
        pidx = jnp.clip(pidx.astype(jnp.int32), 0, n - 1)
        cos, sin = _gather_rope(pidx, d, theta, str(qa.dtype), n)
        return (_rot_interleaved(qa, cos, sin),
                _rot_interleaved(ka, cos, sin))
    return apply_op(f, q, k, position_ids, op_name="rotary_embedding")


def _linear_cls(cfg: LlamaConfig, kind: str):
    if not cfg.tensor_parallel:
        return None
    from paddle_tpu.distributed.fleet import (
        ColumnParallelLinear, RowParallelLinear)
    return ColumnParallelLinear if kind == "col" else RowParallelLinear


def _make_linear(cfg, d_in, d_out, kind):
    cls = _linear_cls(cfg, kind)
    if cls is None:
        return nn.Linear(d_in, d_out, bias_attr=False)
    if kind == "col":
        return cls(d_in, d_out, has_bias=False, gather_output=False)
    return cls(d_in, d_out, has_bias=False, input_is_parallel=True)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.n_heads = cfg.num_attention_heads
        self.n_kv = cfg.num_key_value_heads
        self.q_proj = _make_linear(cfg, cfg.hidden_size,
                                   self.n_heads * self.head_dim, "col")
        self.k_proj = _make_linear(cfg, cfg.hidden_size,
                                   self.n_kv * self.head_dim, "col")
        self.v_proj = _make_linear(cfg, cfg.hidden_size,
                                   self.n_kv * self.head_dim, "col")
        self.o_proj = _make_linear(cfg, self.n_heads * self.head_dim,
                                   cfg.hidden_size, "row")

    def forward(self, x, cache=None, attention_mask=None, pos_offsets=None,
                position_ids=None):
        """``cache=(k, v)`` ([B, P, n_kv, hd] each, P may be 0) switches to
        the incremental-decode path: returns (out, (k', v')). A
        ``cache=(k_buf, v_buf, pos)`` triple ([B, L, n_kv, hd] preallocated
        buffers + scalar write position) takes the STATIC-shape path —
        every decode step has identical shapes, which is what lets the
        whole generate loop compile into one program
        (``generation.compiled_generate``). Without a cache, plain causal
        flash attention returns just ``out``.

        ``attention_mask`` (reference mask threading:
        ``python/paddle/nn/layer/transformer.py:84 _convert_attention_mask``
        + ``fused_attention_op.cc`` arbitrary masks):
          * cacheless path — [B, S] 1/0 padding mask routed into the flash
            kernel's segment-id path (pad tokens attend nothing real);
          * static-cache path — [B, L] KEY-liveness mask over the whole
            buffer (False = never attend: pads and unwritten slots ahead
            are excluded by it and by the causal bound).
        ``pos_offsets`` ([B] int32, static path) shifts RoPE positions per
        row — a LEFT-padded row with ``pad`` pads has its first real token
        at position 0, not ``pad`` (the ragged-serving shape).
        ``position_ids`` ([B, S] int32, cacheless path) sets PER-TOKEN
        RoPE positions — the packed-training shape (docs/DATA.md): with a
        packed batch, ``attention_mask`` carries the packer's SEGMENT IDS
        (1, 2, … per document, 0 = pad; the kernel attends only within
        equal ids, which is exactly the 1/0 padding form generalized) and
        ``position_ids`` restarts at 0 inside each document.

        A :class:`~paddle_tpu.ops.paged_attention.RaggedLayerCache` is the
        block-paged cache in its TOKEN-PACKED form (the serving engine's
        one unified prefill+decode step): ``x`` is ``[1, total_tokens,
        hidden]``, per-token RoPE positions come from the cache, and the
        cache writes the step's K/V and reads its pages itself
        (``ops/paged_attention.attend``)."""
        if isinstance(cache, RaggedLayerCache):
            if attention_mask is not None or pos_offsets is not None \
                    or position_ids is not None:
                raise NotImplementedError(
                    "the ragged paged path derives per-token positions "
                    "and key liveness from the cache itself")
            return self._ragged_paged_forward(x, cache)
        if cache is not None and position_ids is not None:
            raise NotImplementedError(
                "position_ids is a cacheless (packed training) argument")
        if cache is not None and len(cache) == 3:
            return self._static_forward(x, cache, attention_mask,
                                        pos_offsets)
        if cache is not None and (attention_mask is not None
                                  or pos_offsets is not None):
            raise NotImplementedError(
                "attention_mask/pos_offsets are supported on the "
                "cacheless (training) and static-cache (compiled "
                "generation) paths; the eager growing-cache path has no "
                "ragged support — use generate_compiled(attention_mask=…)")
        B, S = x.shape[0], x.shape[1]
        q = ops.reshape(self.q_proj(x), [B, S, self.n_heads, self.head_dim])
        k = ops.reshape(self.k_proj(x), [B, S, self.n_kv, self.head_dim])
        v = ops.reshape(self.v_proj(x), [B, S, self.n_kv, self.head_dim])
        if cache is None:
            if position_ids is not None:
                q, k = apply_rotary_positions(
                    q, k, position_ids, self.cfg.rope_theta,
                    table_len=self.cfg.max_position_embeddings)
            else:
                q, k = apply_rotary(q, k, self.cfg.rope_theta)
            if attention_mask is not None:
                # padding -> segment ids (real tokens segment 1, pads 0):
                # the flash kernel's varlen form — pads never mix with
                # real tokens in either direction
                seg = ops.cast(attention_mask, "int32")
                out = F.flash_attention(q, k, v, causal=True,
                                        q_segment_ids=seg,
                                        kv_segment_ids=seg)
            else:
                # GQA served natively by the attention kernel: KV stay at
                # n_kv heads end-to-end (no replication in HBM)
                out = F.flash_attention(q, k, v, causal=True)
            return self.o_proj(ops.reshape(out, [B, S, -1]))
        past_k, past_v = cache
        P = 0 if past_k is None else past_k.shape[1]
        q, k = apply_rotary(q, k, self.cfg.rope_theta, pos_offset=P,
                            table_len=self.cfg.max_position_embeddings)
        if P:
            k_all = ops.concat([past_k, k], axis=1)
            v_all = ops.concat([past_v, v], axis=1)
        else:
            k_all, v_all = k, v
        # offset-causal over [S queries x P+S keys]: query j (absolute
        # position P+j) sees keys <= P+j — covers full prefill (P=0),
        # CHUNKED prefill (P>0, S>1), and decode (S=1: all keys) in one
        # mask (sdpa's tril offset is s_k - s_q = P); GQA heads stay at n_kv
        out = F.scaled_dot_product_attention(q, k_all, v_all, is_causal=True)
        return self.o_proj(ops.reshape(out, [B, S, -1])), (k_all, v_all)

    def _static_forward(self, x, cache, key_mask=None, pos_offsets=None):
        """Fixed-shape KV-cached attention: rotary at a TRACED position,
        dynamic_update_slice into the preallocated buffers, masked
        attention over the whole buffer (keys past ``pos+S`` masked out).
        One tape node; S_q is 1 in decode, the prompt length in prefill.

        Ragged batches: ``key_mask`` [B, L] marks attendable buffer slots
        (pads False), ``pos_offsets`` [B] shifts each row's RoPE positions
        so a left-padded row's first REAL token sits at position 0 —
        buffer INDEX space stays row-independent (every row writes at
        ``pos``..``pos+S``), only position space is per-row."""
        import jax
        import jax.numpy as jnp

        B, S = x.shape[0], x.shape[1]
        q = ops.reshape(self.q_proj(x), [B, S, self.n_heads, self.head_dim])
        k = ops.reshape(self.k_proj(x), [B, S, self.n_kv, self.head_dim])
        v = ops.reshape(self.v_proj(x), [B, S, self.n_kv, self.head_dim])
        k_buf, v_buf, pos = cache
        L = int(k_buf.shape[1])
        hd = self.head_dim
        grp = self.n_heads // self.n_kv
        theta = self.cfg.rope_theta
        scale = 1.0 / math.sqrt(hd)
        ragged = key_mask is not None or pos_offsets is not None
        if ragged:
            if pos_offsets is None:
                pos_offsets = ops.zeros([B], dtype="int32")
            if key_mask is None:
                key_mask = ops.ones([B, L], dtype="bool")

        def f(qa, ka, va, kb, vb, p, *extra):
            p = jnp.reshape(p, ()).astype(jnp.int32)
            if ragged:
                po, km = extra
                # per-row positions: row b, query j -> p + j - pad_b
                pidx = jnp.clip(p + jnp.arange(S)[None, :]
                                - po[:, None].astype(jnp.int32), 0, L - 1)
                cos, sin = _gather_rope(pidx, hd, theta, str(qa.dtype), L)
            else:
                cos_np, sin_np = _rope_cache(L, hd, theta, str(qa.dtype))
                cos = jax.lax.dynamic_slice_in_dim(
                    jnp.asarray(cos_np), p, S)[None, :, None, :]
                sin = jax.lax.dynamic_slice_in_dim(
                    jnp.asarray(sin_np), p, S)[None, :, None, :]

            qr = _rot_interleaved(qa, cos, sin)
            kr = _rot_interleaved(ka, cos, sin)
            kb = jax.lax.dynamic_update_slice(kb, kr, (0, p, 0, 0))
            vb = jax.lax.dynamic_update_slice(vb, va, (0, p, 0, 0))
            qg = qr.reshape(B, S, self.n_kv, grp, hd)
            s = jnp.einsum("bskgh,blkh->bskgl", qg.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            q_pos = p + jnp.arange(S)
            causal = jnp.arange(L)[None, :] <= q_pos[:, None]  # [S, L]
            if ragged:
                live = causal[None, :, :] & km[:, None, :]     # [B, S, L]
                s = jnp.where(live[:, :, None, None, :], s,
                              jnp.finfo(jnp.float32).min)
            else:
                s = jnp.where(causal[None, :, None, None, :], s,
                              jnp.finfo(jnp.float32).min)
            w = jax.nn.softmax(s, axis=-1).astype(va.dtype)
            out = jnp.einsum("bskgl,blkh->bskgh", w, vb)
            return out.reshape(B, S, self.n_heads * hd), kb, vb

        extra = (pos_offsets, key_mask) if ragged else ()
        out, kb2, vb2 = apply_op(f, q, k, v, k_buf, v_buf, pos, *extra,
                                 op_name="static_kv_attention")
        return self.o_proj(out), (kb2, vb2, pos + S)

    def _ragged_paged_forward(self, x, cache):
        """Token-packed block-paged attention (the unified serving
        step): ``x`` [1, T, hidden] carries every scheduled sequence's
        new tokens back to back; RoPE at the cache's per-token absolute
        positions; the cache then writes the new K/V into its pools and
        reads each sequence's pages (``ops/paged_attention.attend``)."""
        import jax.numpy as jnp

        from paddle_tpu.ops import paged_attention as pa

        T = x.shape[1]
        q = ops.reshape(self.q_proj(x), [T, self.n_heads, self.head_dim])
        k = ops.reshape(self.k_proj(x), [T, self.n_kv, self.head_dim])
        v = ops.reshape(self.v_proj(x), [T, self.n_kv, self.head_dim])
        hd = self.head_dim
        theta = self.cfg.rope_theta
        table_len = self.cfg.max_position_embeddings
        scale = 1.0 / math.sqrt(hd)

        def f(qa, ka, va, c):
            pidx = jnp.clip(c.positions.astype(jnp.int32), 0, table_len - 1)
            cos, sin = _gather_rope(pidx[None, :], hd, theta,
                                    str(qa.dtype), table_len)
            cos, sin = cos[0], sin[0]          # [T, 1, hd/2]
            out, c = pa.attend(c, _rot_interleaved(qa, cos, sin),
                               _rot_interleaved(ka, cos, sin), va,
                               scale=scale)
            return (out.reshape(T, -1),) + c.pools()

        out, *pools = apply_op(f, q, k, v, cache,
                               op_name="ragged_paged_kv_attention")
        # back to [1, T, hidden] for the backbone's residual stream
        return self.o_proj(ops.reshape(out, [1, T, -1])), \
            cache.with_pools(pools)


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = _make_linear(cfg, cfg.hidden_size,
                                      cfg.intermediate_size, "col")
        self.up_proj = _make_linear(cfg, cfg.hidden_size,
                                    cfg.intermediate_size, "col")
        self.down_proj = _make_linear(cfg, cfg.intermediate_size,
                                      cfg.hidden_size, "row")

    def forward(self, x):
        # numerics tap seam (docs/OBSERVABILITY.md#numerics): identity
        # unless an instrumented executable is being traced. The gated
        # activation is where Llama-family bf16 ranges blow up first.
        act = numerics.tap(
            "mlp_act",
            ops.multiply(F.silu(self.gate_proj(x)), self.up_proj(x)))
        return self.down_proj(act)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cache=None, attention_mask=None, pos_offsets=None,
                position_ids=None):
        if cache is None:
            x = ops.add(x, numerics.tap(
                "attn", self.self_attn(self.input_layernorm(x),
                                       attention_mask=attention_mask,
                                       position_ids=position_ids)))
            x = ops.add(x, numerics.tap(
                "mlp", self.mlp(self.post_attention_layernorm(x))))
            return numerics.tap("resid", x)
        attn_out, new_cache = self.self_attn(self.input_layernorm(x),
                                             cache=cache,
                                             attention_mask=attention_mask,
                                             pos_offsets=pos_offsets)
        x = ops.add(x, numerics.tap("attn", attn_out))
        x = ops.add(x, numerics.tap(
            "mlp", self.mlp(self.post_attention_layernorm(x))))
        return numerics.tap("resid", x), new_cache


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        if cfg.tensor_parallel:
            from paddle_tpu.distributed.fleet import VocabParallelEmbedding
            self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                       cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None, attention_mask=None,
                pos_offsets=None, position_ids=None):
        """``attention_mask``: [B, S] 1/0 padding mask — or packed
        SEGMENT IDS (docs/DATA.md) — on the cacheless path (flash
        segment ids), [B, L] buffer key-liveness mask on the static-cache
        path; ``pos_offsets``: [B] per-row RoPE shift for left-padded
        ragged batches (static path only); ``position_ids``: [B, S]
        per-token RoPE positions (cacheless packed path only). Reference
        mask threading: ``nn/layer/transformer.py:84``."""
        x = numerics.tap("embed", self.embed_tokens(input_ids))
        if caches is None:
            kw = {}
            if attention_mask is not None:
                kw["attention_mask"] = attention_mask
            if position_ids is not None:
                kw["position_ids"] = position_ids
            for i, layer in enumerate(self.layers):
                with numerics.scope(f"layers.{i}"):
                    if self.cfg.recompute and self.training:
                        from paddle_tpu.distributed.fleet import recompute
                        # taps inside a remat region would leak its
                        # tracers through the collector — suppress them
                        # and tap the region's output instead
                        with numerics.suppress():
                            x = recompute(layer, x, **kw) if kw \
                                else recompute(layer, x)
                        x = numerics.tap("resid", x)
                    else:
                        x = layer(x, **kw)
            return numerics.tap("final_norm", self.norm(x))
        if len(caches) != len(self.layers):
            raise ValueError(
                f"caches has {len(caches)} entries for "
                f"{len(self.layers)} layers")
        new_caches = []
        for i, (layer, c) in enumerate(zip(self.layers, caches)):
            with numerics.scope(f"layers.{i}"):
                x, nc = layer(x, cache=c, attention_mask=attention_mask,
                              pos_offsets=pos_offsets)
            new_caches.append(nc)
        return numerics.tap("final_norm", self.norm(x)), new_caches


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = _make_linear(cfg, cfg.hidden_size,
                                        cfg.vocab_size, "col")
        self._init_weights()

    def _init_weights(self):
        """Llama recipe init: every 2-D weight (embedding, projections)
        ~ N(0, initializer_range); norms stay at ones. Without this the
        tied logits head scales like sqrt(d) and the initial loss explodes
        (HF LlamaPreTrainedModel._init_weights semantics)."""
        from paddle_tpu.nn import initializer as I
        init = I.Normal(std=self.cfg.initializer_range)
        for _, p in self.named_parameters():
            if len(p.shape) == 2:
                p.set_value(init(p.shape))  # set_value casts to p's dtype

    # vocab size from which the fused chunked CE pays for itself (below
    # it, the [T, V] logits are small and the plain path keeps `logits`
    # available to callers)
    _FUSED_CE_MIN_VOCAB = 32768

    def forward(self, input_ids, labels=None, attention_mask=None,
                position_ids=None):
        """``attention_mask`` [B, S] (1 real / 0 pad) masks padded tokens
        out of attention (flash segment ids); set padded label positions
        to -100 so the loss ignores them too. A PACKED batch
        (``paddle_tpu.data`` pipeline, docs/DATA.md) passes segment ids
        as ``attention_mask`` and per-document ``position_ids`` — this
        signature matches the packer's batch keys, so
        ``Model.prepare(opt, loss=None)`` + ``fit(pipeline)`` feeds
        batches straight through as kwargs."""
        h = self.model(input_ids, attention_mask=attention_mask,
                       position_ids=position_ids)
        if labels is not None and labels.shape[1] < 2:
            raise ValueError(
                "causal-LM loss needs sequences of length >= 2 (the "
                "internal shift leaves nothing to predict for length 1)")
        if (labels is not None and self.lm_head is None
                and self.cfg.vocab_size >= self._FUSED_CE_MIN_VOCAB):
            # large tied vocab: fused chunked matmul-CE — the [T, V]
            # logits never materialize (ops/fused_ce.py). Returns
            # (None, loss): producing logits would rebuild the tensor the
            # fusion exists to avoid.
            from paddle_tpu.ops.fused_ce import causal_lm_loss
            w = self.model.embed_tokens.weight
            loss = apply_op(causal_lm_loss, h, w, labels,
                            op_name="fused_causal_ce")
            return None, loss
        logits = numerics.tap("logits", self._logits(h))
        if labels is None:
            return logits
        # HF-style contract: labels == input_ids; the shift happens HERE
        # (position t predicts token t+1) — do not pre-shift labels
        loss = F.cross_entropy(
            ops.reshape(logits[:, :-1], [-1, logits.shape[-1]]),
            ops.reshape(labels[:, 1:], [-1]))
        return logits, loss

    def kv_cache_spec(self):
        """What each layer keeps of a token in a paged cache (the serving
        engine builds its pools from this): a K and a V row of
        ``head_dim`` under each KV head."""
        from paddle_tpu.ops.paged_attention import LayerCacheSpec
        attn = self.model.layers[0].self_attn
        return LayerCacheSpec.kv(attn.n_kv, attn.head_dim)

    def _logits(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        return ops.matmul(h, ops.transpose(
            self.model.embed_tokens.weight, [1, 0]))

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id=None):
        """KV-cached autoregressive decoding (greedy when
        ``temperature == 0``); see models/generation.py for the loop."""
        from .generation import generate_loop

        def prefill(ids):
            caches = [(None, None)] * self.cfg.num_hidden_layers
            h, caches = self.model(ids, caches=caches)
            return self._logits(h[:, -1:]), caches

        def decode(tok, caches):
            h, caches = self.model(tok, caches=caches)
            return self._logits(h), caches

        return generate_loop(prefill, decode, input_ids, max_new_tokens,
                             temperature, top_k, top_p, eos_token_id)

    def generate_compiled(self, input_ids, max_new_tokens: int = 32,
                          temperature: float = 0.0, top_k: int = 0,
                          top_p: float = 1.0, eos_token_id=None,
                          prefill_chunk: int = 0, attention_mask=None):
        """Whole-loop compiled generation: prefill + every decode step in
        ONE jitted program over static KV buffers (see
        ``generation.compiled_generate``). Greedy output is token-for-token
        equal to ``generate``; ``attention_mask`` serves a LEFT-padded
        batch of unequal prompts, each row equal to its solo run."""
        from .generation import compiled_generate
        return compiled_generate(self, input_ids, max_new_tokens,
                                 temperature, top_k, top_p, eos_token_id,
                                 prefill_chunk=prefill_chunk,
                                 attention_mask=attention_mask)

    @staticmethod
    def flops_per_token(cfg: LlamaConfig) -> float:
        """Analytic fwd FLOPs/token (2 MAC) — feeds MFU accounting."""
        d, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
        hd = d // cfg.num_attention_heads
        kv = cfg.num_key_value_heads * hd
        per_layer = 2 * d * (d + 2 * kv + d) + 2 * 3 * d * f
        return L * per_layer + 2 * d * cfg.vocab_size
