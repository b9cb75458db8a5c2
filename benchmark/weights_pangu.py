"""Seeded weights of model ``pangu_ultra_moe``, made on the device in one
jitted call; shared by the program's side (``sut_pangu.py``) and the plain
reference (``reference/pangu_ultra_moe.py``), as ``weights.py`` is for
model ``mistral``.

A leaf depends only on (seed, layer, leaf name) and, for a routed expert,
on the expert's *global* id: the share of the experts a configuration holds
gets the same values the uncut model holds for those experts, which is what
lets the shares of a layer add up to the whole (the guide's share test).

Sizes come from the configuration file. Where it holds a chip's share,
``n_routed_experts`` is the number held here and ``published`` carries the
router's width; ``held_experts`` (optional) names the global ids held,
default the first ``n_routed_experts``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.weights import rounded, seed_key  # noqa: F401

GLOBAL_LEAVES = ("embed", "norm", "head")
ATTN_LEAVES = ("ln_in", "wqa", "ln_q", "wqb", "wkva", "ln_kv", "wkvb", "wo",
               "ln_post_attn", "ln_pre_mlp", "ln_post_mlp")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
                 "s_down")
_NAMES = GLOBAL_LEAVES + ATTN_LEAVES + DENSE_LEAVES + EXPERT_LEAVES


def sizes(cfg: dict) -> dict:
    """The widths the configuration states, under short names."""
    held = int(cfg["n_routed_experts"])
    experts = int(cfg.get("published", {}).get("n_routed_experts", held))
    ids = tuple(int(e) for e in cfg.get("held_experts", range(held)))
    if len(ids) != held:
        raise ValueError(f"{len(ids)} held_experts, n_routed_experts {held}")
    return dict(
        d=int(cfg["hidden_size"]), heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), ffn=int(cfg["intermediate_size"]),
        moe_ffn=int(cfg["moe_intermediate_size"]), experts=experts,
        held=ids, top_k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]),
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        vocab=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        std=float(cfg.get("initializer_range", 0.02)),
        max_pos=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg: dict) -> dict:
    """Shape of every leaf; a routed expert's is one expert's."""
    z = sizes(cfg)
    d, H, f, sf = z["d"], z["heads"], z["moe_ffn"], z["moe_ffn"] * z["shared"]
    return {
        "embed": (z["vocab"], d), "norm": (d,), "head": (d, z["vocab"]),
        "ln_in": (d,), "wqa": (d, z["q_rank"]), "ln_q": (z["q_rank"],),
        "wqb": (z["q_rank"], H * (z["nope"] + z["rope"])),
        "wkva": (d, z["kv_rank"] + z["rope"]), "ln_kv": (z["kv_rank"],),
        "wkvb": (z["kv_rank"], H * (z["nope"] + z["v"])),
        "wo": (H * z["v"], d), "ln_post_attn": (d,), "ln_pre_mlp": (d,),
        "ln_post_mlp": (d,),
        "w_gate": (d, z["ffn"]), "w_up": (d, z["ffn"]),
        "w_down": (z["ffn"], d),
        "router": (d, z["experts"]), "e_gate": (d, f), "e_up": (d, f),
        "e_down": (f, d), "s_gate": (d, sf), "s_up": (d, sf),
        "s_down": (sf, d)}


def _leaf(key, slot, name, shape, std, dtype):
    if len(shape) == 1:                    # norm gains start at one
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, slot),
                           _NAMES.index(name))
    return rounded(jax.random.normal(k, shape, jnp.float32) * std, dtype)


def expert_leaf(key, layer, name, expert, cfg: dict, dtype):
    """Leaf ``name`` (``e_gate``, ``e_up``, ``e_down``) of the routed
    expert with global id ``expert`` (may be traced) of ``layer``."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, layer + 1), _NAMES.index(name)), expert)
    return rounded(jax.random.normal(k, leaf_shapes(cfg)[name], jnp.float32)
                   * sizes(cfg)["std"], dtype)


def layer_leaves(key, layer, cfg: dict, dtype, dense: bool,
                 experts: bool = True):
    """Layer ``layer``'s leaves (``layer`` may be traced, ``dense`` says
    which kind it is). Slot 0 is the globals'. A routed leaf is the held
    experts' stack ``[held, ...]``; ``experts=False`` leaves those out (the
    reference makes them one expert at a time)."""
    shapes, z = leaf_shapes(cfg), sizes(cfg)
    names = ATTN_LEAVES + (DENSE_LEAVES if dense else EXPERT_LEAVES)
    out = {}
    for n in names:
        if n in ("e_gate", "e_up", "e_down"):
            if experts:
                out[n] = jax.vmap(lambda e, n=n: expert_leaf(
                    key, layer, n, e, cfg, dtype))(
                        jnp.asarray(z["held"], jnp.int32))
        else:
            out[n] = _leaf(key, layer + 1, n, shapes[n], z["std"], dtype)
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
        return out
    return jax.jit(make)


def all_weights(seed: int, cfg: dict, dtype="bfloat16"):
    """Every leaf held here, on the default device, in one jitted call:
    ``{"embed", "norm", "head", "layers": [{...}, ...]}``."""
    return _all_weights_fn(hashable(cfg), str(jnp.dtype(dtype)))(
        seed_key(seed))


def n_params(cfg: dict) -> dict:
    """Matrix parameters from the sizes: a layer's attention, a dense
    layer's SwiGLU, one routed expert, the shared expert, the router, the
    embedding and the head; ``held_total`` is what this chip holds."""
    s, z = leaf_shapes(cfg), sizes(cfg)
    cnt = {n: math.prod(s[n]) for n in s}
    attn = sum(cnt[n] for n in ("wqa", "wqb", "wkva", "wkvb", "wo"))
    dense = cnt["w_gate"] + cnt["w_up"] + cnt["w_down"]
    expert = cnt["e_gate"] + cnt["e_up"] + cnt["e_down"]
    shared = cnt["s_gate"] + cnt["s_up"] + cnt["s_down"] if z["shared"] else 0
    moe_layers = z["layers"] - z["dense"]
    total = z["layers"] * attn + z["dense"] * dense + moe_layers * (
        len(z["held"]) * expert + shared + cnt["router"]) \
        + cnt["embed"] + cnt["head"]
    return {"attention": attn, "dense_mlp": dense, "expert": expert,
            "shared": shared, "router": cnt["router"], "embed": cnt["embed"],
            "head": cnt["head"], "held_total": total}
