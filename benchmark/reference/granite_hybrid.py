"""Plain reference for the Granite 4.0-H family (``model_type``
``granitemoehybrid`` without experts): float32, straightforward
``jax.numpy``, no kernels, no cache, no batching of sequences, the state
space recurrence one position at a time (``lax.scan``). It imports nothing
of ``paddle_tpu`` and takes nothing the program has made: it regenerates
the seeded weights itself (``benchmark.weights_granite_hybrid``), one
layer at a time, under ``jax.default_matmul_precision("highest")``.

For a layer's input ``x`` (``N_*`` an RMSNorm with its own gain, ``r`` the
``residual_multiplier``; ``layer_types`` says which mixer):

    a = x + r Mixer(N_1(x));  y = a + r W_o(silu(g) * u), [g | u] = N_2(a) W_in

attention:  q, k, v = x W_q, x W_k, x W_v, no bias, no position encoding;
            o = softmax(q k^T * attention_multiplier + causal mask) v,
            query head h reading KV head h // (heads / kv_heads); o W_o
Mamba-2:    [z | xBC | dt] = x W_in;  xBC' = silu(b + sum_{k=0..3} w_k
            xBC_{t-3+k}) (zeros before the first position);
            [x | B | C] = xBC';  dt = softplus(dt + dt_bias);
            A = -exp(A_log);  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;
            y_t = h_t C_t + D x_t;  W_out(RMSNorm(y * silu(z)) * g)

The embedding is scaled by ``embedding_multiplier``; the logits are the
final RMSNorm's output times the embedding's transpose over
``logits_scaling``.

``mode`` picks how the linear layers multiply (``reference/mistral.py``):
``exact`` float32 at ``highest``, or ``int8`` (weights per output channel,
activations per row), the serving control: every product of the model
(the projections, the MLPs, the head), the recurrence itself in float32.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark import weights_granite_hybrid as W
from benchmark.reference.mistral import HIGHEST, linear, rms_norm


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def attention(a, w, z, mm, block=256):
    """Causal NoPE grouped-query attention of one row ``a`` [L, d]: the
    scores of ``block`` query rows at a time against every key, so that a
    long row fits."""
    L, H, G, hd = a.shape[0], z["heads"], z["kv"], z["hd"]
    q = mm(a, w["wq"]).reshape(L, G, H // G, hd)
    k = mm(a, w["wk"]).reshape(L, G, hd)
    v = mm(a, w["wv"]).reshape(L, G, hd)
    block = min(block, L)
    if L % block:
        raise ValueError(f"a row of {L} is no multiple of the block {block}")
    cols = jnp.arange(L)

    def one(args):
        qs, start = args
        s = jnp.einsum("qkgh,lkh->kgql", qs, k, precision=HIGHEST) \
            * z["attn_scale"]
        causal = cols[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgql,lkh->qkgh", p, v, precision=HIGHEST)

    n = L // block
    o = jax.lax.map(one, (q.reshape(n, block, G, H // G, hd),
                          jnp.arange(n) * block))
    return mm(o.reshape(L, H * hd), w["wo"])


def mamba(a, w, z, mm):
    """The Mamba-2 mixer over one row ``a`` [L, d], position by position."""
    L, di, N, K, H, P = a.shape[0], z["di"], z["N"], z["K"], z["mh"], z["mp"]
    zxd = mm(a, w["in_proj"])
    gate, xbc, dt = zxd[:, :di], zxd[:, di:di + z["conv"]], \
        zxd[:, di + z["conv"]:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = w["conv_b"] + sum(w["conv_w"][k] * padded[k:k + L]
                             for k in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(L, H, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])

    def one(h, inp):
        xt, bt, ct, dtt = inp
        h = jnp.exp(dtt * A)[:, None, None] * h \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, ct, precision=HIGHEST)
    _, y = jax.lax.scan(one, jnp.zeros((H, P, N)), (x, B, C, dt))
    y = (y + w["D"][:, None] * x).reshape(L, di)
    g = rms_norm(y * jax.nn.silu(gate), w["gnorm"], z["eps"])
    return mm(g, w["out_proj"])


def block(x, w, z, mm, kind):
    """One decoder layer on one row ``x`` [L, d]; ``w`` float32 leaves."""
    a = rms_norm(x, w["ln1"], z["eps"])
    x = x + z["resid"] * (mamba(a, w, z, mm) if kind == "mamba"
                          else attention(a, w, z, mm))
    m = mm(rms_norm(x, w["ln2"], z["eps"]), w["mlp_in"])
    F = z["ffn"]
    return x + z["resid"] * mm(jax.nn.silu(m[:, :F]) * m[:, F:],
                               w["mlp_out"])


def period(kinds) -> int:
    """The shortest run of layer kinds the whole pattern repeats."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


@functools.lru_cache(maxsize=8)
def _forward_fn(cfg_key, weight_dtype, mode):
    cfg = json.loads(cfg_key)
    z = W.sizes(cfg)
    mm = linear(mode)
    kinds = z["kinds"]
    p = period(kinds)

    def run(key, tokens, rows, cols):
        """``tokens`` [R, L] (right-padded). Logits [N, vocab] at
        ``(rows[n], cols[n])``."""
        g = _f32(W.global_leaves(key, cfg, weight_dtype))
        x = g["embed"][tokens] * z["emb_mult"]

        def one_period(x, first):
            # the layers' weights are made here: one period's float32
            # copies live at a time
            for j, kind in enumerate(kinds[:p]):
                w = _f32(W.kind_leaves(key, first + j, kind, cfg,
                                       weight_dtype))
                x = jax.lax.map(lambda xr: block(xr, w, z, mm, kind), x)
            return x, None
        x, _ = jax.lax.scan(one_period, x, jnp.arange(0, z["layers"], p))
        h = rms_norm(x[rows, cols], g["norm"], z["eps"])
        return mm(h, g["embed"].T) / z["logits_div"]
    return jax.jit(run)


def serve_logits(seed, cfg, tokens, rows, cols, mode="exact",
                 weight_dtype=None):
    """The seeded model's logits at chosen positions of right-padded rows
    (causal: padding after a position never reaches it). The weights are
    the seeded leaves as the configuration stores them (its ``dtype``),
    widened to float32."""
    weight_dtype = weight_dtype or cfg.get("dtype", "bfloat16")
    fn = _forward_fn(W.hashable(cfg), weight_dtype, mode)
    with jax.default_matmul_precision("highest"):
        return fn(W.seed_key(seed), jnp.asarray(tokens, jnp.int32),
                  jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32))
