"""The system under test for model ``granite_hybrid``, as the benchmark
builds it: ``paddle_tpu``'s ``GraniteHybridForCausalLM`` at a configuration
file's sizes with the benchmark's seeded weights
(``weights_granite_hybrid.py``), wrapped in ``serving.ServingEngine``.
Everything goes through the program's public entry points; the model serves
only (no trainer).
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights_granite_hybrid as W

#: benchmark leaf -> the program's parameter suffix within a layer
_LAYER_NAMES = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "mlp_in": "shared_mlp.input_linear.weight",
    "mlp_out": "shared_mlp.output_linear.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "in_proj": "mamba.in_proj.weight", "conv_w": "mamba.conv_weight",
    "conv_b": "mamba.conv_bias", "A_log": "mamba.A_log",
    "dt_bias": "mamba.dt_bias", "D": "mamba.D", "gnorm": "mamba.norm_weight",
    "out_proj": "mamba.out_proj.weight"}
_GLOBAL_NAMES = {"embed": "model.embed_tokens.weight",
                 "norm": "model.norm.weight"}


def program_config(cfg: dict):
    """The program's config at the file's sizes."""
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    z = W.sizes(cfg)
    return GraniteHybridConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        num_hidden_layers=z["layers"], layer_types=z["kinds"],
        num_attention_heads=z["heads"], num_key_value_heads=z["kv"],
        attention_multiplier=z["attn_scale"],
        shared_intermediate_size=z["ffn"], mamba_n_heads=z["mh"],
        mamba_d_head=z["mp"], mamba_d_state=int(cfg["mamba_d_state"]),
        mamba_n_groups=int(cfg["mamba_n_groups"]), mamba_d_conv=z["K"],
        mamba_expand=int(cfg["mamba_expand"]),
        mamba_conv_bias=bool(cfg["mamba_conv_bias"]),
        mamba_proj_bias=bool(cfg["mamba_proj_bias"]),
        embedding_multiplier=z["emb_mult"],
        residual_multiplier=z["resid"], logits_scaling=z["logits_div"],
        rms_norm_eps=z["eps"], max_position_embeddings=z["max_pos"],
        initializer_range=z["std"])


def build_model(cfg: dict, seed: int, dtype="bfloat16"):
    """The program's model at ``cfg``'s sizes holding the seeded weights.
    As ``sut_smallthinker.build_model``: the model is built without a layer
    and the layers are built and appended one at a time, each leaf's
    float32 storage dropped as soon as its shape is known; the seeded
    leaves then arrive from one jitted call."""
    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.models.granite_hybrid import (GraniteHybridDecoderLayer,
                                                  GraniteHybridForCausalLM)
    pcfg = program_config(cfg)
    n_layers = pcfg.num_hidden_layers
    placeholder = jnp.zeros((), jnp.dtype(dtype))

    def release(layer):
        for _, p in layer.named_parameters():
            p._data = placeholder
    pcfg.num_hidden_layers = 0
    model = GraniteHybridForCausalLM(pcfg)
    release(model)
    for i in range(n_layers):
        layer = GraniteHybridDecoderLayer(pcfg, i)
        release(layer)
        model.model.layers.append(layer)
    pcfg.num_hidden_layers = n_layers
    tree = W.all_weights(seed, cfg, dtype)
    params = dict(model.named_parameters())

    def put(name, arr):
        p = params[name]
        p._data = arr
        p._version += 1
    for leaf, name in _GLOBAL_NAMES.items():
        put(name, tree[leaf])
    for i, leaves in enumerate(tree["layers"]):
        for leaf, arr in leaves.items():
            put(f"model.layers.{i}.{_LAYER_NAMES[leaf]}", arr)
    for layer in model.sublayers(include_self=True):
        layer._dtype = convert_dtype(dtype)      # what ``.bfloat16()`` sets
    return model


def build_engine(cfg: dict, seed: int, overrides=None):
    """``ServingEngine`` at the configuration's deployment settings (the
    ``engine`` group of the file), weights in place before the pools are."""
    from paddle_tpu.serving import ServingEngine
    model = build_model(cfg, seed, cfg.get("dtype", "bfloat16"))
    model.eval()
    kw = dict(cfg["engine"])
    kw.update(overrides or {})
    return ServingEngine(model, **kw)
