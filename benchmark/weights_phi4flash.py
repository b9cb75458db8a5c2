"""Seeded weights of model ``phi4flash``, made on the device in one jitted
call; shared by the program's side (``sut_phi4flash.py``) and the plain
reference (``reference/phi4flash.py``), as ``weights.py`` is for model
``mistral``.

A leaf depends only on (seed, layer, leaf name). Matrices and biases are
``N(0, initializer_range)``; the LayerNorm gains and the differential
heads' ``subln`` gain are ``1 + N(0, GAIN_STD)``; the ``lambda`` vectors
are ``N(0, 0.1)`` as published. The Mamba-1 leaves follow Mamba-1's own
initialisation, not the matrices' normal draw (drawn ``N(0, 0.02)`` every
channel would decay at ``exp(-softplus(N(0, 0.4)) exp(N(0, 0.02)))``, one
rate for all states, and ``dt`` the same for every position): ``A_log =
log(1..N)`` for every channel, the ``dt`` bias the inverse softplus of a
``dt`` log-uniform on [0.001, 0.1] (at least 1e-4), ``D`` one, the
convolution's weights and bias uniform on ``+-1/2`` (a depthwise
convolution's default, fan-in ``width``).

Layer kinds (``kinds``): ``mamba``, ``window``, ``full``, ``gmu`` and
``cross`` by the published rule (``paddle_tpu/models/phi4flash.py``).
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
LAMBDA_STD = 0.1
GLOBAL_LEAVES = ("embed", "norm_w", "norm_b")
SHARED_LEAVES = ("ln1_w", "ln1_b", "ln2_w", "ln2_b", "fc1", "fc2")
MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_w", "dt_b",
                "A_log", "D", "out_proj")
DIFF_LEAVES = ("wo", "bo", "lq1", "lk1", "lq2", "lk2", "subln")
ATTENTION_LEAVES = ("wqkv", "bqkv") + DIFF_LEAVES
CROSS_LEAVES = ("wq", "bq") + DIFF_LEAVES
GMU_LEAVES = ("gmu_in", "gmu_out")
_NAMES = GLOBAL_LEAVES + SHARED_LEAVES + MAMBA_LEAVES + ATTENTION_LEAVES \
    + ("wq", "bq") + GMU_LEAVES
_GAINS = ("norm_w", "ln1_w", "ln2_w", "subln")


def layer_kind(layers: int, l: int) -> str:
    """The published layer map (``mb_per_layer`` 2): Mamba and window
    layers alternate up to the memory layer ``L // 2`` (Mamba), then one
    full layer, then GMU and cross layers in turn."""
    h = layers // 2
    if l <= h:
        return "mamba" if l % 2 == 0 else "window"
    if l == h + 1:
        return "full"
    return "gmu" if l % 2 == 0 else "cross"


def sizes(cfg: dict) -> dict:
    """The widths the configuration states, under short names."""
    d, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return dict(
        d=d, layers=layers, heads=heads, kv=kv, hd=d // heads,
        ffn=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        di=int(cfg["mamba_expand"]) * d, N=int(cfg["mamba_d_state"]),
        K=int(cfg["mamba_d_conv"]), R=int(cfg["mamba_dt_rank"]),
        window=int(cfg["sliding_window"]),
        kinds=tuple(layer_kind(layers, l) for l in range(layers)),
        eps=float(cfg["layer_norm_eps"]),
        std=float(cfg.get("initializer_range", 0.02)),
        max_pos=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg: dict) -> dict:
    z = sizes(cfg)
    d, di, hd, q = z["d"], z["di"], z["hd"], z["heads"] * z["hd"]
    kv = z["kv"] * hd
    return {"embed": (z["vocab"], d), "norm_w": (d,), "norm_b": (d,),
            "ln1_w": (d,), "ln1_b": (d,), "ln2_w": (d,), "ln2_b": (d,),
            "fc1": (d, 2 * z["ffn"]), "fc2": (z["ffn"], d),
            "in_proj": (d, 2 * di), "conv_w": (z["K"], di), "conv_b": (di,),
            "x_proj": (di, z["R"] + 2 * z["N"]), "dt_w": (z["R"], di),
            "dt_b": (di,), "A_log": (di, z["N"]), "D": (di,),
            "out_proj": (di, d),
            "wqkv": (d, q + 2 * kv), "bqkv": (q + 2 * kv,), "wq": (d, q),
            "bq": (q,),
            "wo": (q, d), "bo": (d,), "lq1": (hd,), "lk1": (hd,),
            "lq2": (hd,), "lk2": (hd,), "subln": (2 * hd,),
            "gmu_in": (d, di), "gmu_out": (di, d)}


def layer_names(kind: str):
    return SHARED_LEAVES + {"mamba": MAMBA_LEAVES, "window": ATTENTION_LEAVES,
                            "full": ATTENTION_LEAVES, "cross": CROSS_LEAVES,
                            "gmu": GMU_LEAVES}[kind]


def _leaf(key, slot, name, shape, z, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, slot),
                           _NAMES.index(name))
    if name == "A_log":
        x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1,
                                                dtype=jnp.float32)), shape)
    elif name == "dt_b":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "D":
        x = jnp.ones(shape, jnp.float32)
    elif name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(z["K"])
        x = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    elif name in _GAINS:
        x = 1.0 + GAIN_STD * jax.random.normal(k, shape, jnp.float32)
    elif name in ("lq1", "lk1", "lq2", "lk2"):
        x = LAMBDA_STD * jax.random.normal(k, shape, jnp.float32)
    else:
        x = jax.random.normal(k, shape, jnp.float32) * z["std"]
    return rounded(x, dtype)


def kind_leaves(key, layer, kind: str, cfg: dict, dtype):
    """The leaves of a layer of ``kind`` (``layer`` may be traced). Slot 0
    is the globals'."""
    shapes, z = leaf_shapes(cfg), sizes(cfg)
    return {n: _leaf(key, layer + 1, n, shapes[n], z, dtype)
            for n in layer_names(kind)}


def layer_leaves(key, layer: int, cfg: dict, dtype):
    return kind_leaves(key, layer, sizes(cfg)["kinds"][layer], cfg, dtype)


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
    ``{"embed", "norm_w", "norm_b", "layers": [{...}, ...]}``."""
    return _all_weights_fn(hashable(cfg), str(jnp.dtype(dtype)))(
        seed_key(seed))


def n_params(cfg: dict) -> dict:
    """Parameters from the sizes: a block of each kind (with its MLP and
    norms), the tied embedding, and ``total``."""
    s, z = leaf_shapes(cfg), sizes(cfg)
    cnt = {n: math.prod(s[n]) for n in s}
    out = {kind: sum(cnt[n] for n in layer_names(kind))
           for kind in ("mamba", "window", "full", "cross", "gmu")}
    out["embed"] = cnt["embed"]
    out["total"] = sum(out[k] for k in z["kinds"]) + cnt["embed"] \
        + cnt["norm_w"] + cnt["norm_b"]
    return out
