"""Seeded weights of model ``exaone_moe``, made on the device in one jitted
call; shared by the program's side (``sut_exaone_moe.py``) and the plain
reference (``reference/exaone_moe.py``), as ``weights_pangu.py`` is for
model ``pangu_ultra_moe``.

A leaf depends only on (seed, block, leaf name) and, for a routed expert,
on the expert's *global* id: the share of the experts a configuration holds
gets the values the uncut model holds for those experts, which is what lets
the shares of a layer add up to the whole. The multi-token-prediction
module's block is block ``MTP_BLOCK`` whatever the depth held, so a deeper
cut holds the same drafter. Matrices are ``N(0, initializer_range)``; the
norm gains are drawn too, ``1 + N(0, GAIN_STD)`` (as in
``weights_smallthinker.py``: with gains of one a norm left out, or a q/k
norm applied on the wrong side of the rotation, would pass), and so is the
router's selection bias, ``N(0, BIAS_STD)`` (zeros would not tell a program
that weighs by ``s + b`` from one that weighs by ``s``). How wide: the bias
is DeepSeek-V3's (report, section 2.1.2; Wang et al. 2024, "Auxiliary-loss-
free load balancing", arXiv 2408.15664), moved a step at a time against an
expert's excess load, so a trained one holds the experts' loads within a few
per cent of their mean (the paper's largest excess over the mean, 0.04-0.07,
as remembered). A seeded router is balanced before any bias (its 128 logits
are alike in law), so a bias here can only *un*balance it, and its width is
bounded by the imbalance it may bring. A router logit is ``N(0, 0.02 x
sqrt(6144) = 1.57)``; the 8 of 128 chosen lie 1.53 deviations up, where
``s`` is 0.92 and the sigmoid's slope 0.076; a bias ``b`` moves an expert's
cut by ``b / 0.076 / 1.57`` deviations and its share of the tokens by 1.97
times that (the normal's density over its tail at 1.53): 0.005 is 8 % of a
share a deviation of the bias, the order of a trained router's excess; 0.05
is a factor of 2.1 up or 0.41 down, a router no balancing has touched (on
the chip the fullest held expert took 2.7-4.0 times the mean, against 1.31
at 0.005; PERF.md section 6, PR 33). It is bounded below by the tests: at
0.005 a program that leaves the bias out still fails.

Sizes come from the configuration file. Where it holds a chip's share,
``num_experts`` is the number held here and ``published`` carries the
router's width; ``held_experts`` (optional) names the global ids held,
default the first ``num_experts``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.weights import rounded, seed_key  # noqa: F401

GAIN_STD = 0.1
BIAS_STD = 0.005
#: the block index the drafter's block is seeded under
MTP_BLOCK = 1 << 20
GLOBAL_LEAVES = ("embed", "norm", "head")
ATTN_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln_q", "ln_k", "ln2")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("router", "router_bias", "e_gate", "e_up", "e_down",
                 "s_gate", "s_up", "s_down")
ROUTED_LEAVES = ("e_gate", "e_up", "e_down")
MTP_LEAVES = ("mtp_enorm", "mtp_hnorm", "mtp_proj", "mtp_norm")
_NAMES = GLOBAL_LEAVES + ATTN_LEAVES + DENSE_LEAVES + EXPERT_LEAVES \
    + MTP_LEAVES


def sizes(cfg: dict) -> dict:
    """The widths the configuration states, under short names."""
    held = int(cfg["num_experts"])
    experts = int(cfg.get("published", {}).get("num_experts", held))
    ids = tuple(int(e) for e in cfg.get("held_experts", range(held)))
    if len(ids) != held:
        raise ValueError(f"{len(ids)} held_experts, num_experts {held}")
    layers = int(cfg["num_hidden_layers"])
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        ffn=int(cfg["intermediate_size"]),
        moe_ffn=int(cfg["moe_intermediate_size"]), experts=experts,
        held=ids, top_k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["num_shared_experts"]),
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        vocab=int(cfg["vocab_size"]), layers=layers,
        dense=int(cfg["first_k_dense_replace"]),
        windows=tuple(int(w) for w in cfg["sliding_windows"][:layers]),
        mtp=int(cfg["num_nextn_predict_layers"]),
        mtp_windows=tuple(int(w) for w in cfg["mtp_sliding_windows"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
        std=float(cfg.get("initializer_range", 0.02)),
        max_pos=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg: dict) -> dict:
    """Shape of every leaf; a routed expert's is one expert's."""
    z = sizes(cfg)
    d, q, kv = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"]
    f, sf = z["moe_ffn"], z["moe_ffn"] * z["shared"]
    return {
        "embed": (z["vocab"], d), "norm": (d,), "head": (d, z["vocab"]),
        "ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
        "wo": (q, d), "ln_q": (z["hd"],), "ln_k": (z["hd"],), "ln2": (d,),
        "w_gate": (d, z["ffn"]), "w_up": (d, z["ffn"]),
        "w_down": (z["ffn"], d),
        "router": (d, z["experts"]), "router_bias": (z["experts"],),
        "e_gate": (d, f), "e_up": (d, f), "e_down": (f, d),
        "s_gate": (d, sf), "s_up": (d, sf), "s_down": (sf, d),
        "mtp_enorm": (d,), "mtp_hnorm": (d,), "mtp_proj": (2 * d, d),
        "mtp_norm": (d,)}


def _leaf(key, slot, name, shape, std, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, slot),
                           _NAMES.index(name))
    x = jax.random.normal(k, shape, jnp.float32)
    if name == "router_bias":
        return rounded(BIAS_STD * x, dtype)
    if len(shape) == 1:                    # a norm's gain
        return rounded(1.0 + GAIN_STD * x, dtype)
    return rounded(x * std, dtype)


def expert_leaf(key, block, name, expert, cfg: dict, dtype):
    """Leaf ``name`` (``e_gate``, ``e_up``, ``e_down``) of the routed
    expert with global id ``expert`` (may be traced) of ``block`` (may be
    traced; ``MTP_BLOCK`` the drafter's)."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, block + 1), _NAMES.index(name)), expert)
    return rounded(jax.random.normal(k, leaf_shapes(cfg)[name], jnp.float32)
                   * sizes(cfg)["std"], dtype)


def layer_leaves(key, block, cfg: dict, dtype, dense: bool,
                 experts: bool = True):
    """Block ``block``'s leaves (``block`` may be traced, ``dense`` says
    which kind it is). Slot 0 is the globals'. A routed leaf is the held
    experts' stack ``[held, ...]``; ``experts=False`` leaves those out (the
    reference makes them one expert at a time)."""
    shapes, z = leaf_shapes(cfg), sizes(cfg)
    out = {}
    for n in ATTN_LEAVES + (DENSE_LEAVES if dense else EXPERT_LEAVES):
        if n in ROUTED_LEAVES:
            if experts:
                out[n] = jax.vmap(lambda e, n=n: expert_leaf(
                    key, block, n, e, cfg, dtype))(
                        jnp.asarray(z["held"], jnp.int32))
        else:
            out[n] = _leaf(key, block + 1, n, shapes[n], z["std"], dtype)
    return out


def mtp_leaves(key, cfg: dict, dtype, experts: bool = True):
    """The drafter's leaves: the module's own (its two input norms, the
    projection, its output norm) and its block's, an expert layer."""
    shapes, std = leaf_shapes(cfg), sizes(cfg)["std"]
    out = {n: _leaf(key, MTP_BLOCK + 1, n, shapes[n], std, dtype)
           for n in MTP_LEAVES}
    out["block"] = layer_leaves(key, MTP_BLOCK, cfg, dtype, False, experts)
    return out


def global_leaves(key, cfg: dict, dtype):
    shapes, std = leaf_shapes(cfg), sizes(cfg)["std"]
    return {n: _leaf(key, 0, n, shapes[n], std, dtype) for n in GLOBAL_LEAVES}


def hashable(cfg: dict) -> str:
    """A key for the jitted makers' caches (the file holds lists)."""
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=8)
def _all_weights_fn(cfg_key, dtype_name):
    cfg, dtype = json.loads(cfg_key), jnp.dtype(dtype_name)
    z = sizes(cfg)

    def make(key):
        out = global_leaves(key, cfg, dtype)
        out["layers"] = [layer_leaves(key, i, cfg, dtype, i < z["dense"])
                         for i in range(z["layers"])]
        if z["mtp"]:
            out["mtp"] = mtp_leaves(key, cfg, dtype)
        return out
    return jax.jit(make)


def all_weights(seed: int, cfg: dict, dtype="bfloat16"):
    """Every leaf held here, on the default device, in one jitted call:
    ``{"embed", "norm", "head", "layers": [{...}, ...], "mtp": {...}}``."""
    return _all_weights_fn(hashable(cfg), str(jnp.dtype(dtype)))(
        seed_key(seed))


def n_params(cfg: dict) -> dict:
    """Matrix parameters from the sizes: a block's attention, a dense
    layer's SwiGLU, one routed expert, the shared expert, the router, the
    embedding, the head and the drafter's projection; ``expert_layer`` is
    one expert layer as held here, ``drafter`` the module with its block,
    ``held_total`` what this chip holds."""
    s, z = leaf_shapes(cfg), sizes(cfg)
    cnt = {n: math.prod(s[n]) for n in s}
    attn = sum(cnt[n] for n in ("wq", "wk", "wv", "wo"))
    dense = cnt["w_gate"] + cnt["w_up"] + cnt["w_down"]
    expert = cnt["e_gate"] + cnt["e_up"] + cnt["e_down"]
    shared = cnt["s_gate"] + cnt["s_up"] + cnt["s_down"] if z["shared"] else 0
    expert_layer = attn + cnt["router"] + shared + len(z["held"]) * expert
    drafter = (cnt["mtp_proj"] + expert_layer) * z["mtp"]
    total = z["dense"] * (attn + dense) \
        + (z["layers"] - z["dense"]) * expert_layer \
        + cnt["embed"] + cnt["head"] + drafter
    return {"attention": attn, "dense_mlp": dense, "expert": expert,
            "shared": shared, "router": cnt["router"],
            "embed": cnt["embed"], "head": cnt["head"],
            "mtp_proj": cnt["mtp_proj"] * z["mtp"],
            "expert_layer": expert_layer, "drafter": drafter,
            "held_total": total}
