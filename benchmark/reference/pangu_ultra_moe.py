"""Plain reference for the openPangu-Ultra-MoE family (``model_type``
``pangu_ultra_moe``): float32, straightforward ``jax.numpy``, no kernels, no
cache, no batching tricks, and attention in the **expanded** form (every
head's keys and values up-projected from the latent). It imports nothing of
``paddle_tpu`` and takes nothing the program has made: it regenerates the
seeded weights itself (``benchmark.weights_pangu``), one layer at a time and
a routed expert at a time, under ``jax.default_matmul_precision("highest")``.

The equations (``x`` a token's residual, ``N_*`` an RMSNorm with its own
gain, eps from the configuration):

- block (sandwich norm): ``a = N_post_attn(Attn(N_in(x)))``, ``x = x + a``,
  ``m = N_post_mlp(F(N_pre_mlp(x)))``, ``x = x + m``; ``F`` a SwiGLU in the
  leading dense layers, the expert layer after them.
- latent attention: ``c_q = N_q(W_qa h)``; ``[q_nope | q_rope] = W_qb c_q``
  per head; ``[c_kv | k_r] = W_kva h``; ``c = N_kv(c_kv)``; ``k_rope =
  RoPE(k_r)`` (one head, shared); ``q_rope = RoPE(q_rope)``; ``[k_nope | v]
  = W_kvb c`` per head; scores ``(q_nope.k_nope + q_rope.k_rope) /
  sqrt(nope + rope)``, causal softmax, ``o = W_o concat_h(P v)``.
- expert layer: ``s = sigmoid(W_g h)`` over all the router's outputs; the
  ``top_k`` largest; ``w = s_top / (sum s_top + 1e-20) *
  routed_scaling_factor``; ``y = sum_k w_k E_k(h) + E_shared(h)``, each
  ``E`` a SwiGLU. Where the configuration holds a chip's share of the
  experts, the sum runs over the chosen experts that are held (``w`` is
  still normalised over all chosen) and the shared expert is whole: what
  the absent experts would add is left out, as in the program.

Assumed, as the configuration's file lists: sigmoid scores with no
selection bias and no group-limited choice; the placement of the four
norms; no YaRN factor on the softmax scale. Departures: the rotation pairs
interleaved lanes (2i, 2i+1), the program's convention (see
``reference/mistral.py``); the multi-token-prediction module is not built
(the next-token logits do not depend on it).

``mode`` picks how the linear layers multiply (``reference/mistral.py``):
``exact`` float32 at ``highest``, or ``int8`` (weights per output channel
and activations per row), the serving control: every product of the model,
the router's among them.

**Undecided positions.** A token's output on this chip changes by tenths of
a logit when a held expert enters or leaves its chosen ``top_k``, and the
choice is a comparison of two router logits: where they lie closer than the
configuration's precision resolves them, the published equations do not
say, at that precision, which experts the token takes (on the chip a bf16
run chose otherwise than this float32 one only at such positions, PERF.md
§6). ``held_margin`` is that distance, the least over a position's expert
layers; the configuration states under ``reference.undecided_margin`` the
distance below which a position is undecided, and ``serve_logits`` answers
an undecided position with a row of equal logits: no token is wrong there,
so the comparison reads nought at it and is made over the decided
positions. Without the key nothing is undecided.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_pangu as W
from benchmark.reference.mistral import HIGHEST, linear, rms_norm, rope


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def latent_attention(h, w, z, mm, pos, block=128):
    """Causal latent attention of one row ``h`` [L, d], expanded: the
    scores of ``block`` query rows at a time so that a long row fits."""
    L, H = h.shape[0], z["heads"]
    nope, rp, v = z["nope"], z["rope"], z["v"]
    q = mm(rms_norm(mm(h, w["wqa"]), w["ln_q"], z["eps"]),
           w["wqb"]).reshape(L, H, nope + rp)
    ckv = mm(h, w["wkva"])
    c = rms_norm(ckv[:, :z["kv_rank"]], w["ln_kv"], z["eps"])
    k_rope = rope(ckv[:, None, z["kv_rank"]:], pos, z["theta"])[:, 0]
    q_rope = rope(q[..., nope:], pos, z["theta"])
    kv = mm(c, w["wkvb"]).reshape(L, H, nope + v)
    k_nope, val = kv[..., :nope], kv[..., nope:]
    block = min(block, L)
    if L % block:
        raise ValueError(f"a row of {L} is no multiple of the block {block}")
    cols = jnp.arange(L)

    def one(args):
        qn, qr, start = args                           # [block, H, ...]
        s = (jnp.einsum("qhn,lhn->hql", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("qhr,lr->hql", qr, k_rope, precision=HIGHEST)) \
            / jnp.sqrt(jnp.float32(nope + rp))
        rows = start + jnp.arange(block)
        s = jnp.where(cols[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hql,lhv->qhv", p, val, precision=HIGHEST)

    n = L // block
    out = jax.lax.map(one, (q[..., :nope].reshape(n, block, H, nope),
                            q_rope.reshape(n, block, H, rp),
                            jnp.arange(n) * block))
    return mm(out.reshape(L, H * v), w["wo"])


def router_weights(t, z):
    """``[L, experts]`` combine weights from the router's logits ``t``:
    ``w_k`` at a token's chosen experts, nought elsewhere."""
    s = jax.nn.sigmoid(t)
    top_s, top_i = jax.lax.top_k(s, z["top_k"])
    w = top_s * z["scaling"]
    if z["norm_topk"]:
        w = w / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(t.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, top_i].set(w)


def held_margin(t, z):
    """``(margin [L], chosen [L, held])``: which held experts a token
    chose, and the least change of a router logit that would change that:
    a chosen held expert's distance above the best expert left out, a held
    expert left out's distance below the last one chosen."""
    top, _ = jax.lax.top_k(t, z["top_k"] + 1)
    last_in, first_out = top[:, -2:-1], top[:, -1:]
    th = t[:, jnp.asarray(z["held"], jnp.int32)]
    chosen = th >= last_in
    return jnp.where(chosen, th - first_out, last_in - th).min(-1), chosen


def expert_layer(h, w, z, mm, expert_weights, shared=True):
    """``sum_k w_k E_k(h)`` over the chosen experts that are held, plus
    (``shared``) the shared expert, and ``held_margin`` of the router's
    logits. ``expert_weights(e)`` gives the leaves of the routed expert
    with global id ``e``, one expert at a time."""
    t = mm(h, w["router"])
    combine = router_weights(t, z)
    held = jnp.asarray(z["held"], jnp.int32)

    def one(y, e):
        ew = expert_weights(e)
        return y + combine[:, e][:, None] * swiglu(
            h, ew["e_gate"], ew["e_up"], ew["e_down"], mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), held)
    if shared and z["shared"]:
        y = y + swiglu(h, w["s_gate"], w["s_up"], w["s_down"], mm)
    return y, held_margin(t, z)


def block_forward(x, w, z, mm, pos, dense, expert_weights=None):
    """One block on one row ``x`` [L, d]; ``w`` float32 leaves. An expert
    layer also gives its ``held_margin``."""
    a = latent_attention(rms_norm(x, w["ln_in"], z["eps"]), w, z, mm, pos)
    x = x + rms_norm(a, w["ln_post_attn"], z["eps"])
    h = rms_norm(x, w["ln_pre_mlp"], z["eps"])
    if dense:
        m, routed = swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm), ()
    else:
        m, routed = expert_layer(h, w, z, mm, expert_weights)
    return (x + rms_norm(m, w["ln_post_mlp"], z["eps"]), *routed)


def _layer(key, i, cfg, weight_dtype, z, mm, pos, dense):
    """``x [R, L, d] -> (x, ...)`` as ``block_forward`` gives them, through
    layer ``i`` (may be traced), its weights made here and a routed
    expert's inside the scan over experts, so that one layer's (and one
    expert's) float32 copy lives at a time."""
    def run(x):
        w = _f32(W.layer_leaves(key, i, cfg, weight_dtype, dense,
                                experts=False))

        def expert_weights(e):
            return {n: W.expert_leaf(key, i, n, e, cfg, weight_dtype)
                    .astype(jnp.float32)
                    for n in ("e_gate", "e_up", "e_down")}
        return jax.lax.map(lambda xr: block_forward(
            xr, w, z, mm, pos, dense, expert_weights), x)
    return run


@functools.lru_cache(maxsize=8)
def _serve_logits_fn(cfg_key, weight_dtype, mode):
    cfg = json.loads(cfg_key)
    z = W.sizes(cfg)
    mm = linear(mode)

    def run(key, tokens, rows, cols):
        """``tokens`` [R, L] (right-padded). At ``(rows[n], cols[n])``:
        logits [N, vocab], and of each expert layer ``held_margin``'s
        margin [layers, N] and chosen held experts [layers, N, held]."""
        g = _f32(W.global_leaves(key, cfg, weight_dtype))
        x = g["embed"][tokens]
        pos = jnp.arange(tokens.shape[1])
        for i in range(z["dense"]):
            x, = _layer(key, i, cfg, weight_dtype, z, mm, pos, True)(x)

        def expert_block(x, i):
            x, margin, chosen = _layer(key, i, cfg, weight_dtype, z, mm, pos,
                                       False)(x)
            return x, (margin[rows, cols], chosen[rows, cols])
        x, routed = jax.lax.scan(expert_block, x,
                                 jnp.arange(z["dense"], z["layers"]))
        h = rms_norm(x[rows, cols], g["norm"], z["eps"])
        return (mm(h, g["head"]), *routed)
    return jax.jit(run)


def forward_at(seed, cfg, tokens, rows, cols, mode="exact",
               weight_dtype=None):
    """``(logits, margin, chosen)`` of the seeded model's full forward at
    chosen positions of right-padded rows (causal: padding after a position
    never reaches it); ``margin`` and ``chosen`` as ``held_margin`` gives
    them, a row an expert layer. The weights are the seeded leaves as the
    configuration stores them (its ``dtype``), widened to float32."""
    weight_dtype = weight_dtype or cfg.get("dtype", "bfloat16")
    fn = _serve_logits_fn(W.hashable(cfg), weight_dtype, mode)
    with jax.default_matmul_precision("highest"):
        return fn(W.seed_key(seed), jnp.asarray(tokens, jnp.int32),
                  jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32))


def serve_logits(seed, cfg, tokens, rows, cols, mode="exact",
                 weight_dtype=None):
    """The reference's logits at the chosen positions; the exact mode
    answers an undecided position (module docstring) with equal logits and
    says how many of the distinct positions it found so."""
    logits, margin, _ = forward_at(seed, cfg, tokens, rows, cols, mode,
                                   weight_dtype)
    eps = float(cfg.get("reference", {}).get("undecided_margin", 0.0))
    if mode != "exact" or eps <= 0.0 or not margin.shape[0]:
        return logits
    undecided = np.asarray(margin.min(0) < eps)
    _, first = np.unique(np.stack([np.asarray(rows), np.asarray(cols)]),
                         axis=1, return_index=True)
    print(f"reference: {int(undecided[first].sum())} of {len(first)} "
          f"positions undecided (a held expert within {eps} of the router's "
          f"cut in some expert layer): answered with equal logits")
    return jnp.where(undecided[:, None], 0.0, logits)
