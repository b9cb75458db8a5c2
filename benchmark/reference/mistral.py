"""Plain reference for Mistral-7B-shaped decoders: float32, straightforward
``jax.numpy``, no kernels, no cache, no batching tricks. It imports nothing
of ``paddle_tpu`` and takes nothing the program has made: it regenerates the
seeded weights itself (``benchmark.weights``), one layer at a time where the
whole model would not fit beside anything else.

Follows the published description (pre-norm blocks, RMSNorm, grouped-query
attention with rotary embedding, SwiGLU, untied head, no bias, no window).
One departure, the program's: the rotation pairs interleaved lanes
(2i, 2i+1) where the published code pairs lane i with lane i + head/2. With
seeded weights that is a fixed permutation of each head's lanes: the same
arithmetic at the same cost.

``mode`` picks how the linear layers multiply:

- ``exact``  float32 at ``highest`` precision: the reference;
- ``int8``   weights rounded to int8 per output channel and activations to
             int8 per row, the nearest precision below a bf16 serving
             configuration: the serving control;
- ``fp8``    both operands rounded to float8_e4m3 per tensor (straight-
             through for the gradient), the nearest precision below a bf16
             training configuration: the training control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- multiplies --
def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST,
                   preferred_element_type=jnp.float32)


def _int8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fp8(x):
    """4 exponent and 3 mantissa bits, scaled per tensor to the format's
    largest normal (240 with an IEEE top exponent). ``reduce_precision``,
    not a pair of converts: XLA drops those (see ``weights.rounded``)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    q = jax.lax.reduce_precision(x / s, 4, 3) * s
    return x + jax.lax.stop_gradient(q - x)      # straight-through


def linear(mode):
    """``mm(a[rows, in], w[in, out]) -> [rows, out]`` in the given mode."""
    if mode == "exact":
        return _dot
    if mode == "int8":
        return lambda a, w: _dot(_int8(a, -1), _int8(w, 0))
    if mode == "fp8":
        return lambda a, w: _dot(_fp8(a), _fp8(w))
    raise ValueError(f"unknown mode {mode!r}")


# ------------------------------------------------------------ the blocks --
def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """``x`` [L, heads, hd] rotated at positions ``pos`` [L]; lanes pair up
    as (2i, 2i+1) (the program's convention, see the module docstring)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def causal_attention(q, k, v, block=512):
    """Grouped-query causal attention, [L, heads, hd] x [L, kv, hd], the
    scores of ``block`` query rows at a time so that a long row fits."""
    L, heads, hd = q.shape
    kv = k.shape[1]
    g = heads // kv
    block = min(block, L)
    if L % block:
        raise ValueError(f"a row of {L} is no multiple of the block {block}")
    qb = q.reshape(L // block, block, kv, g, hd)
    starts = jnp.arange(L // block) * block
    cols = jnp.arange(L)

    @jax.checkpoint          # keep a block's scores out of the backward's
    def one(args):           # residuals: they are recomputed, not stored
        qs, start = args
        s = jnp.einsum("qkgh,lkh->kgql", qs, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        rows = start + jnp.arange(block)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgql,lkh->qkgh", p, v, precision=HIGHEST)

    out = jax.lax.map(one, (qb, starts))
    return out.reshape(L, heads * hd)


def stored_as(dtype):
    """Weights as the configuration stores them: a float32 master leaf is
    seen by the forward pass rounded to ``dtype`` (bf16 parameters beside
    f32 master weights), the gradient passing straight through to the
    master. For float32 it is the identity."""
    if jnp.dtype(dtype) == jnp.float32:
        return lambda w: w
    return lambda w: w + jax.lax.stop_gradient(
        W.rounded(w, dtype).astype(jnp.float32) - w)


def block_forward(x, w, z, mm, pos, stored=lambda w: w):
    """One decoder block on one row ``x`` [L, d]; ``w`` float32 leaves."""
    w = {n: stored(a) for n, a in w.items()}
    L = x.shape[0]
    h = rms_norm(x, w["ln1"], z["eps"])
    q = mm(h, w["wq"]).reshape(L, z["heads"], z["hd"])
    k = mm(h, w["wk"]).reshape(L, z["kv"], z["hd"])
    v = mm(h, w["wv"]).reshape(L, z["kv"], z["hd"])
    a = causal_attention(rope(q, pos, z["theta"]), rope(k, pos, z["theta"]), v)
    x = x + mm(a, w["wo"])
    h = rms_norm(x, w["ln2"], z["eps"])
    return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                  w["w_down"])


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------- serving --
@functools.lru_cache(maxsize=8)
def _serve_logits_fn(cfg_items, weight_dtype, mode):
    cfg = dict(cfg_items)
    z = W.sizes(cfg)
    mm = linear(mode)

    def run(key, tokens, rows, cols):
        """``tokens`` [R, L] (right-padded); logits [N, vocab] at
        ``(rows[n], cols[n])``. Weights are regenerated layer by layer
        inside the scan, so one layer's float32 copy lives at a time."""
        g = _f32(W.global_leaves(key, cfg, weight_dtype))
        x = g["embed"][tokens]                      # [R, L, d]
        pos = jnp.arange(tokens.shape[1])

        def layer(x, i):
            w = _f32(W.layer_leaves(key, i, cfg, weight_dtype))
            return jax.lax.map(
                lambda xr: block_forward(xr, w, z, mm, pos), x), None

        x, _ = jax.lax.scan(layer, x, jnp.arange(z["layers"]))
        h = rms_norm(x[rows, cols], g["norm"], z["eps"])
        return mm(h, g["head"])
    return jax.jit(run)


def serve_logits(seed, cfg, tokens, rows, cols, mode="exact",
                 weight_dtype="bfloat16"):
    """Full-forward logits of the seeded model at chosen positions of
    right-padded rows (causal: padding after a position never reaches it)."""
    fn = _serve_logits_fn(W.hashable(cfg), weight_dtype, mode)
    return fn(W.seed_key(seed), jnp.asarray(tokens, jnp.int32),
              jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32))


# --------------------------------------------------------------- training --
def _row_loss(params, row, z, mm, stored):
    """Mean next-token cross entropy of one row of token ids [S]."""
    x = stored(params["embed"][row])
    pos = jnp.arange(row.shape[0])
    for w in params["layers"]:
        x = jax.checkpoint(
            lambda x_, w_: block_forward(x_, w_, z, mm, pos, stored))(x, w)
    logits = mm(rms_norm(x, stored(params["norm"]), z["eps"]),
                stored(params["head"]))
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))


def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


@functools.lru_cache(maxsize=8)
def _train_step_fn(cfg_items, mode, opt_items, param_dtype="float32"):
    cfg, opt = dict(cfg_items), dict(opt_items)
    z = W.sizes(cfg)
    mm = linear(mode)
    stored = stored_as(param_dtype)
    lr, b1, b2, eps, wd, clip = (opt["learning_rate"], opt["beta1"],
                                 opt["beta2"], opt["epsilon"],
                                 opt["weight_decay"], opt["clip_norm"])

    def step(params, m, v, t, batch):
        """One AdamW step (decoupled decay, global-norm clip) in float32.
        Returns the new state, the loss, and the per-leaf norms of the
        gradient as the optimizer got it (after the clip)."""
        def loss_of(p):
            return jnp.mean(jax.vmap(
                lambda r: _row_loss(p, r, z, mm, stored))(batch))
        loss, g = jax.value_and_grad(loss_of)(params)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                          for a in jax.tree_util.tree_leaves(g)))
        scale = clip / jnp.maximum(gn, clip)
        g = jax.tree_util.tree_map(lambda a: a * scale, g)
        t = t + 1
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree_util.tree_map(
            lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        params = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ * (1 - lr * wd)
            - lr_t * m_ / (jnp.sqrt(v_) + eps), params, m, v)
        return params, m, v, t, loss, _leaf_norms(g)
    return jax.jit(step, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=8)
def _change_fn(cfg_items, weight_dtype):
    cfg = dict(cfg_items)

    def change(key, params):
        """Per-leaf norm of ``params`` minus the seeded start, the start
        regenerated (``weights.rounded`` keeps its rounding real)."""
        p0 = W.global_leaves(key, cfg, weight_dtype)
        p0["layers"] = [W.layer_leaves(key, i, cfg, weight_dtype)
                        for i in range(W.sizes(cfg)["layers"])]
        return jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a - b.astype(jnp.float32)))), params, p0)
    return jax.jit(change)


def train_steps(seed, cfg, opt, batches, mode="exact",
                weight_dtype="bfloat16"):
    """Follow the first ``len(batches)`` steps from the seeded start: f32
    master weights, moments and arithmetic; the forward pass sees the
    parameters in ``weight_dtype``, as the configuration stores them.
    Returns ``{"loss": [..], "grad_norms": {leaf: norm} of step 1,
    "change_norms": {leaf: norm} after the last step}`` with leaves named
    ``embed``, ``norm``, ``head``, ``layers.<i>.<leaf>``."""
    key = W.seed_key(seed)
    params = _f32(W.all_weights(seed, cfg, weight_dtype))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = _train_step_fn(W.hashable(cfg), mode,
                          tuple(sorted(opt.items())), weight_dtype)
    t = jnp.zeros((), jnp.float32)
    losses, grad_norms, sketches = [], None, None
    for b in batches:
        params, m, v, t, loss, gn = step(params, m, v, t,
                                         jnp.asarray(b, jnp.int32))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = flatten(jax.device_get(gn))
            sketches = grad_sketches(seed, m, 1.0 / (1.0 - opt["beta1"]))
    del m, v
    change = flatten(jax.device_get(
        _change_fn(W.hashable(cfg), weight_dtype)(key, params)))
    return {"loss": losses, "grad_norms": grad_norms, "change_norms": change,
            "grad_sketches": sketches}


def grad_sketches(seed, moment, scale) -> dict:
    """Count sketches (``benchmark.sketch``) of the first gradient, read
    like the program's from the first moment after one step."""
    import numpy as np
    from benchmark import sketch
    leaves = [(n, moment[n]) for n in W.GLOBAL_LEAVES] + [
        (f"layers.{i}.{n}", lw[n]) for i, lw in enumerate(moment["layers"])
        for n in W.LAYER_LEAVES]
    return {name: np.asarray(sketch.sketch(a, sketch.leaf_key(seed, i), scale))
            for i, (name, a) in enumerate(leaves)}


def flatten(tree) -> dict:
    """``{"embed": x, "layers": [{"wq": y}]}`` -> ``{"embed": x,
    "layers.0.wq": y}`` with plain floats."""
    out = {}
    for n in W.GLOBAL_LEAVES:
        out[n] = float(tree[n])
    for i, lw in enumerate(tree["layers"]):
        for n, a in lw.items():
            out[f"layers.{i}.{n}"] = float(a)
    return out
