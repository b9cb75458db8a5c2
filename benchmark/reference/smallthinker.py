"""Plain reference for the SmallThinker family (``model_name``
``smallthinker_*``): float32, straightforward ``jax.numpy``, no kernels, no
cache, no batching tricks, full attention matrices under the two masks. It
imports nothing of ``paddle_tpu`` and takes nothing the program has made: it
regenerates the seeded weights itself (``benchmark.weights_smallthinker``),
one layer at a time and an expert at a time, under
``jax.default_matmul_precision("highest")``.

The equations, for a layer's input ``x`` (``N_*`` an RMSNorm with its own
gain, eps from the configuration):

    r = x W_r                       the router reads the layer's INPUT: the
                                    un-normed residual stream, before attention
    a = N_1(x);  q, k, v = a W_q, a W_k, a W_v   (28 query heads on 4 KV
                                    heads: query head h reads KV head h // 7)
    window layer (sliding_window_layout[l] == 1): q, k rotated (RoPE at
    absolute positions; rope_layout[l] == 1); full layer: not rotated (NoPE)
    o = softmax(q k^T / sqrt(head_dim) + mask) v    key j visible to query i
                                    iff j <= i, in a window layer also
                                    i - j < sliding_window_size
    x = x + o W_o
    m = N_2(x)
    S = the top_k largest of r;  w = softmax(r_S)  (the softmax over all the
                                    experts renormalised over the chosen)
    y = sum_{e in S} w_e (relu(m W_gate^e) * (m W_up^e)) W_down^e
    x = x + y

then the final RMSNorm and the untied head.

Assumed, as the configuration's file lists: the router's input is the
un-normed residual stream; no biases and no q/k norm; the window counts the
query's own position. Departures: the rotation pairs interleaved lanes
(2i, 2i+1), the program's convention (see ``reference/mistral.py``).

``mode`` picks how the linear layers multiply (``reference/mistral.py``):
``exact`` float32 at ``highest``, or ``int8`` (weights per output channel
and activations per row), the serving control: every product of the model,
the router's among them.

**Undecided positions.** A token's output changes by tenths of a logit
when another expert enters its chosen ``top_k`` (each carries about a sixth
of the layer's output), and the choice is a comparison of two router
logits: where they lie closer than the configuration's precision resolves
them, the published equations do not say, at that precision, which experts
the token takes. ``margin`` is that distance (the last chosen logit above
the best one left out: the least change of a router logit that changes
which experts the token takes) **over the spread of the
token's router logits** (their standard deviation over the experts: the
logits' scale grows with the residual stream's from layer to layer, and the
bf16 error with it), the least over the layers. The configuration states
under ``reference.undecided_margin`` the ratio below which a position is
undecided, and ``serve_logits`` answers an undecided position with a row of
equal logits: no token is wrong there, so the comparison reads nought at it
and is made over the decided positions. Without the key nothing is
undecided.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_smallthinker as W
from benchmark.reference.mistral import HIGHEST, linear, rms_norm, rope


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def attention(a, w, z, mm, pos, windowed, rotary, block=256):
    """Masked grouped-query attention of one row ``a`` [L, d] (already
    normed): the scores of ``block`` query rows at a time against every
    key, so that a long row fits. ``windowed`` and ``rotary`` (may be
    traced) say which kind of layer this is."""
    L, H, G, hd = a.shape[0], z["heads"], z["kv"], z["hd"]
    q = mm(a, w["wq"]).reshape(L, H, hd)
    k = mm(a, w["wk"]).reshape(L, G, hd)
    v = mm(a, w["wv"]).reshape(L, G, hd)
    q = jnp.where(rotary, rope(q, pos, z["theta"]), q)
    k = jnp.where(rotary, rope(k, pos, z["theta"]), k)
    # a full layer's window reaches past every position
    window = jnp.where(windowed, z["window"], L + 1)
    block = min(block, L)
    if L % block:
        raise ValueError(f"a row of {L} is no multiple of the block {block}")
    cols = jnp.arange(L)

    def one(args):
        qs, start = args                                # [block, G, H/G, hd]
        s = jnp.einsum("qkgh,lkh->kgql", qs, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        rows = start + jnp.arange(block)
        visible = (cols[None, :] <= rows[:, None]) \
            & (rows[:, None] - cols[None, :] < window)
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgql,lkh->qkgh", p, v, precision=HIGHEST)

    n = L // block
    out = jax.lax.map(one, (q.reshape(n, block, G, H // G, hd),
                            jnp.arange(n) * block))
    return mm(out.reshape(L, H * hd), w["wo"])


def router_weights(t, z):
    """``[L, experts]`` combine weights from the router's logits ``t``:
    the softmax over a token's chosen ``top_k`` logits, nought elsewhere."""
    top_t, top_i = jax.lax.top_k(t, z["top_k"])
    w = jax.nn.softmax(top_t, axis=-1)
    rows = jnp.arange(t.shape[0])[:, None]
    return jnp.zeros_like(t).at[rows, top_i].set(w)


def margin(t, z):
    """``[L]``: the least change of a router logit that changes which
    experts a token takes (the last chosen logit's distance above the best
    one left out), over the standard deviation of the token's logits."""
    top, _ = jax.lax.top_k(t, z["top_k"] + 1)
    return (top[:, -2] - top[:, -1]) / (jnp.std(t, axis=-1) + 1e-30)


def expert_layer(m, t, z, mm, expert_weights):
    """``sum_e w_e E_e(m)`` over the chosen experts, ``E`` a ReGLU; ``expert_weights(e)`` gives the leaves of the expert with
    global id ``e``, one expert at a time."""
    combine = router_weights(t, z)

    def one(y, e):
        ew = expert_weights(e)
        act = jax.nn.relu(mm(m, ew["e_gate"])) * mm(m, ew["e_up"])
        return y + combine[:, e][:, None] * mm(act, ew["e_down"]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(z["experts"]))
    return y


def block_forward(x, w, z, mm, pos, windowed, rotary, expert_weights):
    """One decoder layer on one row ``x`` [L, d]; ``w`` float32 leaves.
    Returns ``(x, margin [L])``."""
    t = mm(x, w["router"])             # the layer's input, un-normed
    a = rms_norm(x, w["ln1"], z["eps"])
    x = x + attention(a, w, z, mm, pos, windowed, rotary)
    m = rms_norm(x, w["ln2"], z["eps"])
    return x + expert_layer(m, t, z, mm, expert_weights), margin(t, z)


@functools.lru_cache(maxsize=8)
def _forward_fn(cfg_key, weight_dtype, mode):
    cfg = json.loads(cfg_key)
    z = W.sizes(cfg)
    mm = linear(mode)
    windowed = jnp.asarray(z["windowed"])
    rotary = jnp.asarray(z["rotary"])

    def run(key, tokens, rows, cols):
        """``tokens`` [R, L] (right-padded). At ``(rows[n], cols[n])``:
        logits [N, vocab] and each layer's margin [layers, N]."""
        g = _f32(W.global_leaves(key, cfg, weight_dtype))
        x = g["embed"][tokens]
        pos = jnp.arange(tokens.shape[1])

        def layer(x, i):
            # the layer's weights are made here, an expert's inside the
            # scan over experts: one float32 copy of each lives at a time
            w = _f32(W.layer_leaves(key, i, cfg, weight_dtype, experts=False))

            def expert_weights(e):
                return {n: W.expert_leaf(key, i, n, e, cfg, weight_dtype)
                        .astype(jnp.float32) for n in W.EXPERT_LEAVES}
            x, mg = jax.lax.map(lambda xr: block_forward(
                xr, w, z, mm, pos, windowed[i], rotary[i], expert_weights), x)
            return x, mg[rows, cols]
        x, margins = jax.lax.scan(layer, x, jnp.arange(z["layers"]))
        h = rms_norm(x[rows, cols], g["norm"], z["eps"])
        return mm(h, g["head"]), margins
    return jax.jit(run)


def forward_at(seed, cfg, tokens, rows, cols, mode="exact",
               weight_dtype=None):
    """``(logits, margin)`` of the seeded model's full forward at chosen
    positions of right-padded rows (causal: padding after a position never
    reaches it); ``margin`` [layers, N] as :func:`margin` gives it. The
    weights are the seeded leaves as the configuration stores them (its
    ``dtype``), widened to float32."""
    weight_dtype = weight_dtype or cfg.get("dtype", "bfloat16")
    fn = _forward_fn(W.hashable(cfg), weight_dtype, mode)
    with jax.default_matmul_precision("highest"):
        return fn(W.seed_key(seed), jnp.asarray(tokens, jnp.int32),
                  jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32))


def serve_logits(seed, cfg, tokens, rows, cols, mode="exact",
                 weight_dtype=None):
    """The reference's logits at the chosen positions; the exact mode
    answers an undecided position (module docstring) with equal logits and
    says how many of the distinct positions it found so."""
    logits, margins = forward_at(seed, cfg, tokens, rows, cols, mode,
                                 weight_dtype)
    eps = float(cfg.get("reference", {}).get("undecided_margin", 0.0))
    if mode != "exact" or eps <= 0.0:
        return logits
    undecided = np.asarray(margins.min(0) < eps)
    _, first = np.unique(np.stack([np.asarray(rows), np.asarray(cols)]),
                         axis=1, return_index=True)
    print(f"reference: {int(undecided[first].sum())} of {len(first)} "
          f"positions undecided (an expert within {eps} router-logit "
          f"deviations of the cut in some layer): answered with equal logits")
    return jnp.where(undecided[:, None], 0.0, logits)
