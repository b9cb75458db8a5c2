"""Plain reference for Phi-4-mini-flash (``model_type`` ``phi4flash``, the
SambaY decoder-hybrid-decoder): float32, straightforward ``jax.numpy``, no
kernels, no cache, no batching of sequences, the Mamba-1 recurrence one
position at a time (``lax.scan``), the cross-decoder over every position.
It imports nothing of ``paddle_tpu`` and takes nothing the program has
made: it regenerates the seeded weights itself
(``benchmark.weights_phi4flash``), one layer at a time, under
``jax.default_matmul_precision("highest")``.

Layers by the published map (``weights_phi4flash.layer_kind``): Mamba and
window attention alternate up to layer ``h = L // 2`` (Mamba, whose gated
output is the memory), ``h + 1`` is full attention, then GMUs and cross
attention in turn. For a layer's input ``x`` (``LN`` a LayerNorm with gain
and bias):

    a = x + Mixer(LN_1(x));  y = a + (u * silu(g)) W_fc2, [g | u] = LN_2(a) W_fc1

Mamba-1:  [x | z] = u W_in;  x' = silu(b + sum_k w_k x_{t-3+k}) (zeros
          before the first position);  [dr | B | C] = x' W_x;
          dt = softplus(dr W_dt + b_dt);  A = -exp(A_log)  [D, N];
          h_t = exp(dt_t A) h_{t-1} + (dt_t x'_t) (x) B_t;
          y_t = h_t C_t + D x'_t;  m = y * silu(z);  m W_out
GMU:      (silu(u W_1) * m_16) W_2, m_16 the memory at the same position
diff. attention (no position encoding): [q | k | v] = u W_qkv + b (a cross
          layer: q = u W_q + b, k and v layer h + 1's); half-heads
          interleaved (half-head 2j + i is half i of head j), differential
          head j reading KV pair j // (H / G):
          A_i = softmax(q_i k_i^T / sqrt(hd) + mask), the mask causal, or
          0 <= i - j < window in a window layer;
          o = A_1 [v_1 | v_2] - lam A_2 [v_1 | v_2], each product written
          out; o = RMSNorm(o) * g_sub * (1 - lam_init);  [o ...] W_o + b_o
          lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,
          lam_init = 0.8 - 0.6 exp(-0.3 l)

The logits are the final LayerNorm's output times the embedding's
transpose; the embedding is not scaled.

``mode`` picks how the linear layers multiply (``reference/mistral.py``):
``exact`` float32 at ``highest``, or ``int8`` (weights per output channel,
activations per row), the serving control: every product of the model
(the projections, the MLPs, the head), the recurrence and attention
themselves in float32.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark import weights_phi4flash as W
from benchmark.reference.mistral import HIGHEST, linear


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def lambda_init(depth):
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth)


def diff_attention(q, k, v, w, z, depth, window=None, block=256):
    """Differential attention of one row: ``q`` [L, H, 2, hd] (half-heads
    by differential head), ``k``, ``v`` [L, G, 2, hd] (by KV pair); the
    scores of ``block`` query rows at a time against every key, so that a
    long row fits. Returns ``[L, H * 2 hd]`` before ``W_o``."""
    L, H, _, hd = q.shape
    G = k.shape[1]
    block = min(block, L)
    if L % block:
        raise ValueError(f"a row of {L} is no multiple of the block {block}")
    cols = jnp.arange(L)
    lam0 = lambda_init(depth)
    lam = jnp.exp(jnp.dot(w["lq1"], w["lk1"], precision=HIGHEST)) \
        - jnp.exp(jnp.dot(w["lq2"], w["lk2"], precision=HIGHEST)) + lam0

    def one(args):
        qs, start = args                         # [block, G, H/G, 2, hd]
        rows = (start + jnp.arange(block))[:, None]
        mask = cols[None, :] <= rows
        if window is not None:
            mask &= cols[None, :] > rows - window
        A = []
        for i in (0, 1):
            s = jnp.einsum("qgjh,lgh->gjql", qs[:, :, :, i], k[:, :, i],
                           precision=HIGHEST) / jnp.sqrt(float(hd))
            A.append(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))

        def read(a, half):
            return jnp.einsum("gjql,lgh->qgjh", a, v[:, :, half],
                              precision=HIGHEST)
        o1 = jnp.concatenate([read(A[0], 0), read(A[0], 1)], -1)
        o2 = jnp.concatenate([read(A[1], 0), read(A[1], 1)], -1)
        o = o1 - lam * o2                        # [block, G, H/G, 2 hd]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + z["eps"])
        return o * w["subln"] * (1.0 - lam0)

    n = L // block
    o = jax.lax.map(one, (q.reshape(n, block, G, H // G, 2, hd),
                          jnp.arange(n) * block))
    return o.reshape(L, H * 2 * hd)


def attention(a, w, z, mm, depth, window=None, kv=None):
    """A window or full layer (``kv`` None: its own K/V, returned beside
    the output) or a cross layer (``kv`` the full layer's ``(k, v)``)."""
    L, H, G, hd = a.shape[0], z["heads"] // 2, z["kv"] // 2, z["hd"]
    if kv is None:
        qkv = mm(a, w["wqkv"]) + w["bqkv"]
        q = qkv[:, :2 * H * hd]
        k = qkv[:, 2 * H * hd:2 * (H + G) * hd].reshape(L, G, 2, hd)
        v = qkv[:, 2 * (H + G) * hd:].reshape(L, G, 2, hd)
    else:
        q = mm(a, w["wq"]) + w["bq"]
        k, v = kv
    o = diff_attention(q.reshape(L, H, 2, hd), k, v, w, z, depth, window)
    return mm(o, w["wo"]) + w["bo"], (k, v)


def cross_kv(u, kv, w_full, z, mm):
    """The K/V a cross layer reads: the full layer's, whatever its own
    input ``u`` (``w_full`` the full layer's leaves)."""
    return kv


def mamba(a, w, z, mm):
    """The Mamba-1 mixer over one row ``a`` [L, d], position by position.
    Returns ``(out, memory)``."""
    L, D, N, K, R = a.shape[0], z["di"], z["N"], z["K"], z["R"]
    xz = mm(a, w["in_proj"])
    x, gate = xz[:, :D], xz[:, D:]
    padded = jnp.concatenate([jnp.zeros((K - 1, D)), x])
    x = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][k] * padded[k:k + L]
                                      for k in range(K)))
    dbc = mm(x, w["x_proj"])
    dt = jax.nn.softplus(mm(dbc[:, :R], w["dt_w"]) + w["dt_b"])
    B, C = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(w["A_log"])                              # [D, N]

    def one(h, inp):
        xt, bt, ct, dtt = inp
        h = jnp.exp(dtt[:, None] * A) * h + (dtt * xt)[:, None] * bt[None, :]
        return h, jnp.einsum("dn,n->d", h, ct, precision=HIGHEST)
    _, y = jax.lax.scan(one, jnp.zeros((D, N)), (x, B, C, dt))
    m = (y + w["D"] * x) * jax.nn.silu(gate)
    return mm(m, w["out_proj"]), m


def mlp(a, w, z, mm):
    F = z["ffn"]
    gu = mm(layer_norm(a, w["ln2_w"], w["ln2_b"], z["eps"]), w["fc1"])
    return a + mm(gu[:, F:] * jax.nn.silu(gu[:, :F]), w["fc2"])


def block(x, w, z, mm, kind, depth, memory=None, kv=None, w_full=None):
    """One decoder layer on one row ``x`` [L, d]; ``w`` float32 leaves.
    Returns ``(y, memory', kv')``: a Mamba layer's memory is its gated
    output, an attention layer's ``kv'`` its K/V."""
    u = layer_norm(x, w["ln1_w"], w["ln1_b"], z["eps"])
    if kind == "mamba":
        m, memory = mamba(u, w, z, mm)
    elif kind == "gmu":
        m = mm(jax.nn.silu(mm(u, w["gmu_in"])) * memory, w["gmu_out"])
    else:
        window = z["window"] if kind == "window" else None
        m, kv = attention(u, w, z, mm, depth, window,
                          cross_kv(u, kv, w_full, z, mm)
                          if kind == "cross" else None)
    return mlp(x + m, w, z, mm), memory, kv


@functools.lru_cache(maxsize=8)
def _forward_fn(cfg_key, weight_dtype, mode):
    cfg = json.loads(cfg_key)
    z = W.sizes(cfg)
    mm = linear(mode)
    h = z["layers"] // 2

    def run(key, tokens, rows, cols):
        """``tokens`` [R, L] (right-padded). Logits [N, vocab] at
        ``(rows[n], cols[n])``."""
        g = _f32(W.global_leaves(key, cfg, weight_dtype))

        def layer(l, kind):
            return _f32(W.kind_leaves(key, l, kind, cfg, weight_dtype))

        def one_row(tokens_r):
            x = g["embed"][tokens_r]

            # the self-decoder, a (Mamba, window) pair at a time: one
            # pair's float32 weights live at a time
            def pair(x, l):
                x, _, _ = block(x, layer(l, "mamba"), z, mm, "mamba", l)
                x, _, _ = block(x, layer(l + 1, "window"), z, mm, "window",
                                l + 1)
                return x, None
            x, _ = jax.lax.scan(pair, x, jnp.arange(0, h, 2))
            x, memory, _ = block(x, layer(h, "mamba"), z, mm, "mamba", h)
            w_full = layer(h + 1, "full")
            x, _, kv = block(x, w_full, z, mm, "full", h + 1)

            def cross_pair(x, l):
                x, _, _ = block(x, layer(l, "gmu"), z, mm, "gmu", l, memory)
                x, _, _ = block(x, layer(l + 1, "cross"), z, mm, "cross",
                                l + 1, kv=kv, w_full=w_full)
                return x, None
            x, _ = jax.lax.scan(cross_pair, x,
                                jnp.arange(h + 2, z["layers"], 2))
            return x

        x = jax.lax.map(one_row, tokens)
        hid = layer_norm(x[rows, cols], g["norm_w"], g["norm_b"], z["eps"])
        return mm(hid, g["embed"].T)
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
