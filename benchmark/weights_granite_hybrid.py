"""Seeded weights of model ``granite_hybrid``, made on the device in one
jitted call; shared by the program's side (``sut_granite_hybrid.py``) and
the plain reference (``reference/granite_hybrid.py``), as ``weights.py`` is
for model ``mistral``.

A leaf depends only on (seed, layer, leaf name). Matrices are
``N(0, initializer_range)``; the norm gains are drawn too, ``1 + N(0,
GAIN_STD)``. The Mamba-2 leaves follow Mamba-2's published initialisation,
not the matrices' normal draw (with ``N(0, 0.02)`` every head would decay
at ``exp(-softplus(N(0, 0.9)))``, one rate for all): ``A = -exp(A_log)``
with ``A`` uniform on [1, 16], ``dt_bias`` the inverse softplus of a ``dt``
log-uniform on [0.001, 0.1] (at least 1e-4), ``D`` one; the convolution's
weights and bias are uniform on ``+-1/sqrt(width)`` (a depthwise
convolution's default, fan-in ``width``).

Sizes come from the configuration file.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.weights import rounded, seed_key  # noqa: F401

GAIN_STD = 0.1
GLOBAL_LEAVES = ("embed", "norm")
SHARED_LEAVES = ("ln1", "ln2", "mlp_in", "mlp_out")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "A_log", "dt_bias", "D",
                "gnorm", "out_proj")
_NAMES = GLOBAL_LEAVES + SHARED_LEAVES + ATTENTION_LEAVES + MAMBA_LEAVES


def sizes(cfg: dict) -> dict:
    """The widths the configuration states, under short names."""
    d, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    heads = int(cfg["num_attention_heads"])
    di = int(cfg["mamba_expand"]) * d
    N = int(cfg["mamba_d_state"]) * int(cfg["mamba_n_groups"])
    return dict(
        d=d, layers=layers, heads=heads, kv=int(cfg["num_key_value_heads"]),
        hd=d // heads, ffn=int(cfg["shared_intermediate_size"]),
        vocab=int(cfg["vocab_size"]), di=di,
        mh=int(cfg["mamba_n_heads"]), mp=int(cfg["mamba_d_head"]),
        N=N, K=int(cfg["mamba_d_conv"]), conv=di + 2 * N,
        kinds=tuple(cfg["layer_types"][:layers]),
        attn_scale=float(cfg["attention_multiplier"]),
        emb_mult=float(cfg["embedding_multiplier"]),
        resid=float(cfg["residual_multiplier"]),
        logits_div=float(cfg["logits_scaling"]),
        eps=float(cfg["rms_norm_eps"]),
        std=float(cfg.get("initializer_range", 0.02)),
        max_pos=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg: dict) -> dict:
    z = sizes(cfg)
    d, di, mh = z["d"], z["di"], z["mh"]
    return {"embed": (z["vocab"], d), "norm": (d,), "ln1": (d,), "ln2": (d,),
            "mlp_in": (d, 2 * z["ffn"]), "mlp_out": (z["ffn"], d),
            "wq": (d, z["heads"] * z["hd"]), "wk": (d, z["kv"] * z["hd"]),
            "wv": (d, z["kv"] * z["hd"]), "wo": (z["heads"] * z["hd"], d),
            "in_proj": (d, di + z["conv"] + mh),
            "conv_w": (z["K"], z["conv"]), "conv_b": (z["conv"],),
            "A_log": (mh,), "dt_bias": (mh,), "D": (mh,), "gnorm": (di,),
            "out_proj": (di, d)}


def layer_names(kind: str):
    return SHARED_LEAVES + (MAMBA_LEAVES if kind == "mamba"
                            else ATTENTION_LEAVES)


def _leaf(key, slot, name, shape, z, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, slot),
                           _NAMES.index(name))
    if name == "A_log":
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "D":
        x = jnp.ones(shape, jnp.float32)
    elif name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(z["K"])
        x = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    elif len(shape) == 1:                  # a norm's gain
        x = 1.0 + GAIN_STD * jax.random.normal(k, shape, jnp.float32)
    else:
        x = jax.random.normal(k, shape, jnp.float32) * z["std"]
    return rounded(x, dtype)


def layer_leaves(key, layer: int, cfg: dict, dtype):
    """Layer ``layer``'s leaves (a Python int: the kind decides the leaves;
    or traced, with ``kind`` fixed by the caller through ``kind_leaves``).
    Slot 0 is the globals'."""
    z = sizes(cfg)
    return kind_leaves(key, layer, z["kinds"][layer], cfg, dtype)


def kind_leaves(key, layer, kind: str, cfg: dict, dtype):
    """The leaves of a layer of ``kind`` (``layer`` may be traced)."""
    shapes, z = leaf_shapes(cfg), sizes(cfg)
    return {n: _leaf(key, layer + 1, n, shapes[n], z, dtype)
            for n in layer_names(kind)}


def global_leaves(key, cfg: dict, dtype):
    shapes, z = leaf_shapes(cfg), sizes(cfg)
    return {n: _leaf(key, 0, n, shapes[n], z, dtype) for n in GLOBAL_LEAVES}


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
    """Every leaf, on the default device, in one jitted call:
    ``{"embed", "norm", "layers": [{...}, ...]}``."""
    return _all_weights_fn(hashable(cfg), str(jnp.dtype(dtype)))(
        seed_key(seed))


def n_params(cfg: dict) -> dict:
    """Parameters from the sizes: a Mamba block's, an attention block's
    (each with its MLP and norms), the tied embedding, and ``total``."""
    s, z = leaf_shapes(cfg), sizes(cfg)
    cnt = {n: math.prod(s[n]) for n in s}
    shared = sum(cnt[n] for n in SHARED_LEAVES)
    mamba = shared + sum(cnt[n] for n in MAMBA_LEAVES)
    attention = shared + sum(cnt[n] for n in ATTENTION_LEAVES)
    n_m = sum(1 for k in z["kinds"] if k == "mamba")
    total = n_m * mamba + (z["layers"] - n_m) * attention + cnt["embed"] \
        + cnt["norm"]
    return {"mamba": mamba, "attention": attention, "embed": cnt["embed"],
            "total": total}
