"""Seeded weights of model ``smallthinker``, made on the device in one
jitted call; shared by the program's side (``sut_smallthinker.py``) and the
plain reference (``reference/smallthinker.py``), as ``weights_pangu.py`` is
for model ``pangu_ultra_moe``.

A leaf depends only on (seed, layer, leaf name) and, for an expert, on the
expert's *global* id, so a share of the experts gets the values the whole
model holds for them. Matrices are ``N(0, initializer_range)``; the norm
gains are drawn too, ``1 + N(0, GAIN_STD)``: with gains of one the router's
choice would be the same on either side of a layer's input norm, and a
program that fed the router the normed input would differ from the
equations only in the softmax's temperature.

Sizes come from the configuration file. Every expert is held.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.weights import rounded, seed_key  # noqa: F401

GAIN_STD = 0.1
GLOBAL_LEAVES = ("embed", "norm", "head")
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router")
EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
_NAMES = GLOBAL_LEAVES + LAYER_LEAVES + EXPERT_LEAVES


def sizes(cfg: dict) -> dict:
    """The widths the configuration states, under short names."""
    experts = int(cfg["moe_num_primary_experts"])
    layers = int(cfg["num_hidden_layers"])
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        moe_ffn=int(cfg["moe_ffn_hidden_size"]), experts=experts,
        top_k=int(cfg["moe_num_active_primary_experts"]),
        vocab=int(cfg["vocab_size"]), layers=layers,
        window=int(cfg["sliding_window_size"]),
        windowed=tuple(bool(v) for v in
                       cfg["sliding_window_layout"][:layers]),
        rotary=tuple(bool(v) for v in cfg["rope_layout"][:layers]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        std=float(cfg.get("initializer_range", 0.02)),
        max_pos=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg: dict) -> dict:
    """Shape of every leaf; an expert's is one expert's."""
    z = sizes(cfg)
    d, q, kv, f = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"], z["moe_ffn"]
    return {"embed": (z["vocab"], d), "norm": (d,), "head": (d, z["vocab"]),
            "ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "ln2": (d,), "router": (d, z["experts"]),
            "e_gate": (d, f), "e_up": (d, f), "e_down": (f, d)}


def _leaf(key, slot, name, shape, std, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, slot),
                           _NAMES.index(name))
    x = jax.random.normal(k, shape, jnp.float32)
    if len(shape) == 1:                    # a norm's gain
        return rounded(1.0 + GAIN_STD * x, dtype)
    return rounded(x * std, dtype)


def expert_leaf(key, layer, name, expert, cfg: dict, dtype):
    """Leaf ``name`` (``e_gate``, ``e_up``, ``e_down``) of the expert with
    global id ``expert`` (may be traced) of ``layer`` (may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, layer + 1), _NAMES.index(name)), expert)
    return rounded(jax.random.normal(k, leaf_shapes(cfg)[name], jnp.float32)
                   * sizes(cfg)["std"], dtype)


def layer_leaves(key, layer, cfg: dict, dtype, experts: bool = True):
    """Layer ``layer``'s leaves (``layer`` may be traced). Slot 0 is the
    globals'. An expert leaf is the experts' stack ``[experts, ...]``;
    ``experts=False`` leaves those out (the reference makes them one expert
    at a time)."""
    shapes, z = leaf_shapes(cfg), sizes(cfg)
    out = {n: _leaf(key, layer + 1, n, shapes[n], z["std"], dtype)
           for n in LAYER_LEAVES}
    if experts:
        for n in EXPERT_LEAVES:
            out[n] = jax.vmap(lambda e, n=n: expert_leaf(
                key, layer, n, e, cfg, dtype))(jnp.arange(z["experts"]))
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

    def make(key):
        out = global_leaves(key, cfg, dtype)
        out["layers"] = [layer_leaves(key, i, cfg, dtype)
                         for i in range(sizes(cfg)["layers"])]
        return out
    return jax.jit(make)


def all_weights(seed: int, cfg: dict, dtype="bfloat16"):
    """Every leaf held here, on the default device, in one jitted call:
    ``{"embed", "norm", "head", "layers": [{...}, ...]}``."""
    return _all_weights_fn(hashable(cfg), str(jnp.dtype(dtype)))(
        seed_key(seed))


def n_params(cfg: dict) -> dict:
    """Matrix parameters from the sizes: a layer's attention, its router,
    one expert, the embedding and the head; ``total`` the whole model."""
    s, z = leaf_shapes(cfg), sizes(cfg)
    cnt = {n: math.prod(s[n]) for n in s}
    attn = sum(cnt[n] for n in ("wq", "wk", "wv", "wo"))
    expert = sum(cnt[n] for n in EXPERT_LEAVES)
    total = z["layers"] * (attn + cnt["router"] + z["experts"] * expert) \
        + cnt["embed"] + cnt["head"]
    return {"attention": attn, "router": cnt["router"], "expert": expert,
            "embed": cnt["embed"], "head": cnt["head"], "total": total}
