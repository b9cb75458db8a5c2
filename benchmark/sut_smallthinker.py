"""The system under test for model ``smallthinker``, as the benchmark
builds it: ``paddle_tpu``'s ``SmallThinkerForCausalLM`` at a configuration
file's sizes with the benchmark's seeded weights
(``weights_smallthinker.py``), wrapped in ``serving.ServingEngine``.
Everything goes through the program's public entry points; the model serves
only (no trainer).
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights_smallthinker as W

#: benchmark leaf -> the program's parameter suffix within a layer
_LAYER_NAMES = {
    "ln1": "input_layernorm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "ln2": "post_attention_layernorm.weight", "router": "mlp.router",
    "e_gate": "mlp.w_gate", "e_up": "mlp.w_up", "e_down": "mlp.w_down"}
_GLOBAL_NAMES = {"embed": "model.embed_tokens.weight",
                 "norm": "model.norm.weight", "head": "lm_head.weight"}


def program_config(cfg: dict):
    """The program's config at the file's sizes; both layouts whole."""
    from paddle_tpu.models.smallthinker import SmallThinkerConfig
    z = W.sizes(cfg)
    return SmallThinkerConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        num_hidden_layers=z["layers"], num_attention_heads=z["heads"],
        num_key_value_heads=z["kv"], head_dim=z["hd"],
        moe_ffn_hidden_size=z["moe_ffn"],
        moe_num_primary_experts=z["experts"],
        moe_num_active_primary_experts=z["top_k"],
        sliding_window_size=z["window"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"]),
        rope_layout=tuple(cfg["rope_layout"]), rope_theta=z["theta"],
        rms_norm_eps=z["eps"], max_position_embeddings=z["max_pos"],
        initializer_range=z["std"])


def build_model(cfg: dict, seed: int, dtype="bfloat16"):
    """The program's model at ``cfg``'s sizes holding the seeded weights.
    As ``sut_pangu.build_model``: the model is built one period deep and
    the other layers are built and appended one at a time, each leaf's
    float32 storage dropped as soon as its shape is known (a layer is born
    with 1.6 GB of it); the seeded leaves then arrive from one jitted
    call."""
    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.models.smallthinker import (SmallThinkerDecoderLayer,
                                                SmallThinkerForCausalLM)
    pcfg = program_config(cfg)
    n_layers = pcfg.num_hidden_layers
    placeholder = jnp.zeros((), jnp.dtype(dtype))

    def release(layer):
        for _, p in layer.named_parameters():
            p._data = placeholder
    pcfg.num_hidden_layers = 0
    model = SmallThinkerForCausalLM(pcfg)
    release(model)
    for i in range(n_layers):
        layer = SmallThinkerDecoderLayer(pcfg, i)
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
