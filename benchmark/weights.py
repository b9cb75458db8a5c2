"""Seeded weights, made on the device in one jitted call.

One generator serves both sides: the system under test gets the leaves in
the dtype it serves or trains in, and the plain reference regenerates the
same leaves (one layer at a time, where the whole model would not fit) and
widens them to float32. A leaf depends only on (seed, layer, leaf name), so
a reference that builds layer ``i`` alone gets the values the program holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: per-layer leaves in generation order: name -> shape from the sizes
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
                "w_down")
GLOBAL_LEAVES = ("embed", "norm", "head")


def sizes(cfg: dict) -> dict:
    """The widths a Mistral-shaped config file states, under short names."""
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or d // heads)
    return dict(d=d, heads=heads, kv=int(cfg["num_key_value_heads"]), hd=hd,
                ffn=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
                std=float(cfg.get("initializer_range", 0.02)))


def leaf_shapes(cfg: dict) -> dict:
    z = sizes(cfg)
    d, q, kv, f, v = (z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"],
                      z["ffn"], z["vocab"])
    return {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "ln2": (d,), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d), "embed": (v, d), "norm": (d,), "head": (d, v)}


def seed_key(seed: int):
    """A key from any non-negative whole number (the driver's seeds pass
    2**31, which one signed 32-bit word does not hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, slot, name, shape, std, dtype):
    if len(shape) == 1:                    # norm gains start at one
        return jnp.ones(shape, dtype)
    names = LAYER_LEAVES + GLOBAL_LEAVES
    k = jax.random.fold_in(jax.random.fold_in(key, slot), names.index(name))
    return rounded(jax.random.normal(k, shape, jnp.float32) * std, dtype)


def rounded(x, dtype):
    """``x`` (float32) rounded to ``dtype`` and returned in ``dtype``. The
    rounding is spelled ``reduce_precision``: XLA (with its default
    ``xla_allow_excess_precision``) drops a float32 -> bf16 -> float32 pair of
    converts inside one program, and a reference that regenerated the
    weights inside its own jit then held the *unrounded* values: 3.5e-5 rms
    off every weight of a 0.02-wide matrix (found in PR 23)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.float32:
        return x
    fi = jnp.finfo(dt)
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant).astype(dt)


def layer_leaves(key, layer, cfg: dict, dtype):
    """Layer ``layer``'s leaves (``layer`` may be traced: the reference
    generates inside its scan over layers). Slot 0 is the globals'."""
    shapes, std = leaf_shapes(cfg), sizes(cfg)["std"]
    return {n: _leaf(key, layer + 1, n, shapes[n], std, dtype)
            for n in LAYER_LEAVES}


def global_leaves(key, cfg: dict, dtype):
    shapes, std = leaf_shapes(cfg), sizes(cfg)["std"]
    return {n: _leaf(key, 0, n, shapes[n], std, dtype) for n in GLOBAL_LEAVES}


@functools.lru_cache(maxsize=8)
def _all_weights_fn(cfg_items, dtype_name):
    cfg, dtype = dict(cfg_items), jnp.dtype(dtype_name)

    def make(key):
        out = global_leaves(key, cfg, dtype)
        out["layers"] = [layer_leaves(key, i, cfg, dtype)
                         for i in range(sizes(cfg)["layers"])]
        return out
    return jax.jit(make)


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def all_weights(seed: int, cfg: dict, dtype="bfloat16"):
    """Every leaf of the model, on the default device, in one jitted call:
    ``{"embed", "norm", "head", "layers": [{...}, ...]}``."""
    return _all_weights_fn(hashable(cfg), str(jnp.dtype(dtype)))(
        seed_key(seed))


def n_params(cfg: dict) -> dict:
    """Parameter counts from the sizes: per layer (attention, mlp, norms),
    embedding, head, total."""
    import math
    s = leaf_shapes(cfg)
    cnt = {n: math.prod(s[n]) for n in s}
    attn = cnt["wq"] + cnt["wk"] + cnt["wv"] + cnt["wo"]
    mlp = cnt["w_gate"] + cnt["w_up"] + cnt["w_down"]
    layer = attn + mlp + cnt["ln1"] + cnt["ln2"]
    L = sizes(cfg)["layers"]
    return {"attention": attn, "mlp": mlp, "layer": layer,
            "embed": cnt["embed"], "head": cnt["head"],
            "total": L * layer + cnt["embed"] + cnt["head"] + cnt["norm"]}
