"""Plain reference for the EXAONE-MoE family (``model_type``
``exaone_moe``): float32, straightforward ``jax.numpy``, no kernels, no
cache, no batching tricks, full attention matrices under the two masks. It
imports nothing of ``paddle_tpu`` and takes nothing the program has made: it
regenerates the seeded weights itself (``benchmark.weights_exaone_moe``),
one block at a time and a routed expert at a time, under
``jax.default_matmul_precision("highest")``.

The equations (``x`` a token's residual, ``N_*`` an RMSNorm with its own
gain, eps from the configuration):

- block ``l`` (pre-norm): ``a = x + Attn_l(N_1(x))``, ``y = a + F_l(N_2(a))``;
  ``F_l`` a SwiGLU of ``intermediate_size`` where ``l <
  first_k_dense_replace``, the expert layer after.
- attention: ``q = u W_q`` as ``heads`` of ``head_dim``, ``k = u W_k``,
  ``v = u W_v`` as ``kv`` heads, no bias; ``q <- N_q(q)``, ``k <- N_k(k)``
  a head (gains of ``head_dim``); RoPE in a window layer only
  (``sliding_windows[l] > 0``), none in a full one; query head ``h`` reads
  KV head ``h // (heads / kv)``; scores over ``sqrt(head_dim)``; key ``j``
  visible to query ``i`` iff ``0 <= i - j < window`` in a window layer, iff
  ``j <= i`` in a full one; output ``W_o``.
- expert layer: ``s = sigmoid(W_r h)`` over all the router's outputs in
  float32; the ``top_k`` largest of ``s + b`` chosen (``b`` the selection
  bias); ``w = s_top / (sum s_top + 1e-20) * routed_scaling_factor``;
  ``F(h) = sum_k w_k E_k(h) + E_shared(h)``, each ``E`` a SwiGLU. Where the
  configuration holds a chip's share of the experts, the sum runs over the
  chosen experts that are held (``w`` still normalised over all chosen)
  and the shared expert is whole.
- drafter (the multi-token-prediction module): with ``h_i`` the stream
  after the last block at position ``i`` (before the final norm) and
  ``t_{i+1}`` the next token, ``z_i = W_p [N_e(Emb(t_{i+1})) ; N_h(h_i)]``,
  one block as above with full attention over ``z_0..z_i``, ``N_out``, the
  model's head: the logits of ``t_{i+2}``.

Assumed and departures: as the configuration's file lists them.

``mode`` picks how the linear layers multiply (``reference/mistral.py``):
``exact`` float32 at ``highest``, or ``int8`` (weights per output channel
and activations per row), the serving control: every product of the model,
the router's among them.

**Undecided positions.** As in ``reference/pangu_ultra_moe.py``: a token's
output on this chip changes by tenths of a logit when a held expert enters
or leaves its chosen ``top_k``, and the choice compares two selection
scores ``s + b``; ``held_margin`` is the least change of one that changes
which held experts a token takes, the least over a position's expert
layers, and ``serve_logits`` answers a position whose margin is under the
configuration's ``reference.undecided_margin`` with a row of equal logits.
Without the key nothing is undecided.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_exaone_moe as W
from benchmark.reference.mistral import HIGHEST, linear, rms_norm, rope


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def attention(u, w, z, mm, pos, window, block=256):
    """Masked grouped-query attention of one row ``u`` [L, d] (already
    normed): the scores of ``block`` query rows at a time against every
    key. ``window`` (may be traced): the layer's window, 0 a full layer
    (no rotation, every earlier key)."""
    L, H, G, hd = u.shape[0], z["heads"], z["kv"], z["hd"]
    q = rms_norm(mm(u, w["wq"]).reshape(L, H, hd), w["ln_q"], z["eps"])
    k = rms_norm(mm(u, w["wk"]).reshape(L, G, hd), w["ln_k"], z["eps"])
    v = mm(u, w["wv"]).reshape(L, G, hd)
    rotary = window > 0
    q = jnp.where(rotary, rope(q, pos, z["theta"]), q)
    k = jnp.where(rotary, rope(k, pos, z["theta"]), k)
    reach = jnp.where(rotary, window, L + 1)
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
            & (rows[:, None] - cols[None, :] < reach)
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgql,lkh->qkgh", p, v, precision=HIGHEST)

    n = L // block
    out = jax.lax.map(one, (q.reshape(n, block, G, H // G, hd),
                            jnp.arange(n) * block))
    return mm(out.reshape(L, H * hd), w["wo"])


def router_weights(t, b, z):
    """``[L, experts]`` combine weights from the router's logits ``t`` and
    selection bias ``b``: ``w_k`` at a token's chosen experts (the largest
    of ``s + b``; weighed by ``s``), nought elsewhere."""
    s = jax.nn.sigmoid(t)
    _, top_i = jax.lax.top_k(s + b, z["top_k"])
    top_s = jnp.take_along_axis(s, top_i, -1)
    w = top_s * z["scaling"]
    if z["norm_topk"]:
        w = w / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(t.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, top_i].set(w)


def held_margin(t, b, z):
    """``(margin [L], chosen [L, held])``: which held experts a token
    chose, and the least change of a selection score ``s + b`` that would
    change that: a chosen held expert's distance above the best expert
    left out, a held expert left out's distance below the last chosen."""
    sel = jax.nn.sigmoid(t) + b
    top, _ = jax.lax.top_k(sel, z["top_k"] + 1)
    last_in, first_out = top[:, -2:-1], top[:, -1:]
    sh = sel[:, jnp.asarray(z["held"], jnp.int32)]
    chosen = sh >= last_in
    return jnp.where(chosen, sh - first_out, last_in - sh).min(-1), chosen


def expert_layer(h, w, z, mm, expert_weights, held=None, shared=True):
    """``sum_k w_k E_k(h)`` over the chosen experts among ``held`` (global
    ids; default the configuration's), plus (``shared``) the shared expert,
    and ``held_margin`` of the router. ``expert_weights(e)`` gives the
    leaves of the routed expert with global id ``e``."""
    t = mm(h, w["router"])
    combine = router_weights(t, w["router_bias"], z)
    held = jnp.asarray(z["held"] if held is None else held, jnp.int32)

    def one(y, e):
        ew = expert_weights(e)
        return y + combine[:, e][:, None] * swiglu(
            h, ew["e_gate"], ew["e_up"], ew["e_down"], mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), held)
    if shared and z["shared"]:
        y = y + swiglu(h, w["s_gate"], w["s_up"], w["s_down"], mm)
    return y, held_margin(t, w["router_bias"], z)


def block_forward(x, w, z, mm, pos, window, dense, expert_weights=None):
    """One block on one row ``x`` [L, d]; ``w`` float32 leaves. An expert
    layer also gives its ``held_margin``."""
    a = x + attention(rms_norm(x, w["ln1"], z["eps"]), w, z, mm, pos, window)
    h = rms_norm(a, w["ln2"], z["eps"])
    if dense:
        return (a + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm),)
    m, routed = expert_layer(h, w, z, mm, expert_weights)
    return (a + m, *routed)


def next_tokens(tokens):
    """``t_{i+1}`` at position ``i`` of each row (the last wraps: no
    position that is read lies there)."""
    return jnp.roll(tokens, -1, axis=1)


def _expert_weights(key, block, cfg, weight_dtype):
    def get(e):
        return {n: W.expert_leaf(key, block, n, e, cfg, weight_dtype)
                .astype(jnp.float32) for n in W.ROUTED_LEAVES}
    return get


def _layer(key, i, cfg, weight_dtype, z, mm, pos, window, dense):
    """``x [R, L, d] -> (x, ...)`` as ``block_forward`` gives them, through
    block ``i`` (may be traced), its weights made here and a routed
    expert's inside the scan over experts, so that one block's (and one
    expert's) float32 copy lives at a time."""
    def run(x):
        w = _f32(W.layer_leaves(key, i, cfg, weight_dtype, dense,
                                experts=False))
        return jax.lax.map(lambda xr: block_forward(
            xr, w, z, mm, pos, window, dense,
            _expert_weights(key, i, cfg, weight_dtype)), x)
    return run


def _trunk(key, cfg, weight_dtype, z, mm, g, tokens, rows, cols):
    """The model's blocks over ``tokens`` [R, L]: the stream before the
    final norm, and of each expert layer ``held_margin`` at ``(rows,
    cols)``: margin [expert layers, N], chosen [expert layers, N, held]."""
    x = g["embed"][tokens]
    pos = jnp.arange(tokens.shape[1])
    windows = jnp.asarray(z["windows"], jnp.int32)
    for i in range(z["dense"]):
        x, = _layer(key, i, cfg, weight_dtype, z, mm, pos, windows[i],
                    True)(x)

    def expert_block(x, i):
        x, margin, chosen = _layer(key, i, cfg, weight_dtype, z, mm, pos,
                                   windows[i], False)(x)
        return x, (margin[rows, cols], chosen[rows, cols])
    return jax.lax.scan(expert_block, x,
                        jnp.arange(z["dense"], z["layers"]))


@functools.lru_cache(maxsize=8)
def _forward_fn(cfg_key, weight_dtype, mode, drafter):
    cfg = json.loads(cfg_key)
    z = W.sizes(cfg)
    mm = linear(mode)

    def run(key, tokens, rows, cols):
        """``tokens`` [R, L] (right-padded). At ``(rows[n], cols[n])``:
        logits [N, vocab] and the expert layers' margins and choices; with
        ``drafter`` the drafter's logits there instead (the token after
        next, the next token read from ``tokens[:, col + 1]``) and its
        block's margin and choices."""
        g = _f32(W.global_leaves(key, cfg, weight_dtype))
        x, routed = _trunk(key, cfg, weight_dtype, z, mm, g, tokens, rows,
                           cols)
        if not drafter:
            h = rms_norm(x[rows, cols], g["norm"], z["eps"])
            return (mm(h, g["head"]), *routed)
        w = _f32(W.mtp_leaves(key, cfg, weight_dtype, experts=False))
        nxt = next_tokens(tokens)
        pos = jnp.arange(tokens.shape[1])
        zin = jnp.concatenate(
            [rms_norm(g["embed"][nxt], w["mtp_enorm"], z["eps"]),
             rms_norm(x, w["mtp_hnorm"], z["eps"])], -1)
        y, margin, chosen = jax.lax.map(
            lambda zr: block_forward(
                mm(zr, w["mtp_proj"]), w["block"], z, mm, pos,
                jnp.int32(z["mtp_windows"][0]), False,
                _expert_weights(key, W.MTP_BLOCK, cfg, weight_dtype)), zin)
        h = rms_norm(y[rows, cols], w["mtp_norm"], z["eps"])
        return (mm(h, g["head"]), margin[rows, cols][None],
                chosen[rows, cols][None])
    return jax.jit(run)


def forward_at(seed, cfg, tokens, rows, cols, mode="exact",
               weight_dtype=None, drafter=False):
    """``(logits, margin, chosen)`` of the seeded model's full forward at
    chosen positions of right-padded rows (causal: padding after a position
    never reaches it); ``margin`` and ``chosen`` as ``held_margin`` gives
    them, a row an expert layer. With ``drafter`` the drafter's logits at
    those positions (of the token two on; position ``col + 1`` of the row
    must hold the next token) and its own block's margin. The weights are
    the seeded leaves as the configuration stores them (its ``dtype``),
    widened to float32."""
    weight_dtype = weight_dtype or cfg.get("dtype", "bfloat16")
    fn = _forward_fn(W.hashable(cfg), weight_dtype, mode, bool(drafter))
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
          f"positions undecided (a held expert within {eps} of the "
          f"router's cut in some expert layer): answered with equal logits")
    return jnp.where(undecided[:, None], 0.0, logits)


def greedy(seed, cfg, tokens, n, pad_to=None, weight_dtype=None):
    """``n`` tokens of plain greedy decoding after ``tokens``: a full
    forward a token (first index on ties). Small sizes only."""
    out = list(tokens)
    L = pad_to or len(out) + n
    for _ in range(n):
        row = np.zeros((1, L), np.int32)
        row[0, :len(out)] = out
        logits, _, _ = forward_at(seed, cfg, row, [0], [len(out) - 1],
                                  weight_dtype=weight_dtype)
        out.append(int(np.asarray(logits)[0].argmax()))
    return out[len(tokens):]


def accepted_drafts(tokens, prompt_len, guesses):
    """``(drafted, accepted)`` as an engine with one draft a step counts
    them over the greedy sequence ``tokens`` (prompt, then every generated
    token): ``guesses[i]`` is the drafter's choice at position ``i`` (its
    guess of ``tokens[i + 2]``). A step whose newest confirmed token is at
    ``n`` verifies ``guesses[n - 1]`` against ``tokens[n + 1]`` if at least
    two tokens are still to come, and moves on by two where they agree."""
    drafted = accepted = 0
    n, last = prompt_len, len(tokens) - 1
    while n < last:
        if last - n >= 2:
            drafted += 1
            if int(guesses[n - 1]) == int(tokens[n + 1]):
                accepted += 1
                n += 1
        n += 1
    return drafted, accepted
