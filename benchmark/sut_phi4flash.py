"""The system under test for model ``phi4flash``, as the benchmark builds
it: ``paddle_tpu``'s ``Phi4FlashForCausalLM`` at a configuration file's
sizes with the benchmark's seeded weights (``weights_phi4flash.py``),
wrapped in ``serving.ServingEngine``. Everything goes through the program's
public entry points; the model serves only (no trainer).
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights_phi4flash as W

#: benchmark leaf -> the program's parameter suffix within a layer
_LAYER_NAMES = {
    "ln1_w": "input_layernorm.weight", "ln1_b": "input_layernorm.bias",
    "ln2_w": "post_attention_layernorm.weight",
    "ln2_b": "post_attention_layernorm.bias",
    "fc1": "mlp.fc1.weight", "fc2": "mlp.fc2.weight",
    "in_proj": "mixer.in_proj.weight", "conv_w": "mixer.conv_weight",
    "conv_b": "mixer.conv_bias", "x_proj": "mixer.x_proj.weight",
    "dt_w": "mixer.dt_proj.weight", "dt_b": "mixer.dt_proj.bias",
    "A_log": "mixer.A_log", "D": "mixer.D",
    "out_proj": "mixer.out_proj.weight",
    "wqkv": "mixer.Wqkv.weight", "wq": "mixer.Wqkv.weight",
    "bqkv": "mixer.Wqkv.bias", "bq": "mixer.Wqkv.bias", "wo": "mixer.out_proj.weight",
    "bo": "mixer.out_proj.bias", "lq1": "mixer.lambda_q1",
    "lk1": "mixer.lambda_k1", "lq2": "mixer.lambda_q2",
    "lk2": "mixer.lambda_k2", "subln": "mixer.subln",
    "gmu_in": "mixer.in_proj.weight", "gmu_out": "mixer.out_proj.weight"}
_GLOBAL_NAMES = {"embed": "model.embed_tokens.weight",
                 "norm_w": "model.final_layernorm.weight",
                 "norm_b": "model.final_layernorm.bias"}


def program_config(cfg: dict):
    """The program's config at the file's sizes."""
    from paddle_tpu.models.phi4flash import Phi4FlashConfig
    z = W.sizes(cfg)
    return Phi4FlashConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        intermediate_size=z["ffn"], num_hidden_layers=z["layers"],
        num_attention_heads=z["heads"], num_key_value_heads=z["kv"],
        sliding_window=z["window"], mb_per_layer=int(cfg["mb_per_layer"]),
        mamba_d_state=z["N"], mamba_d_conv=z["K"],
        mamba_expand=int(cfg["mamba_expand"]), mamba_dt_rank=z["R"],
        layer_norm_eps=z["eps"], max_position_embeddings=z["max_pos"],
        initializer_range=z["std"])


def build_model(cfg: dict, seed: int, dtype="bfloat16"):
    """The program's model at ``cfg``'s sizes holding the seeded weights.
    As ``sut_granite_hybrid.build_model``: the model is built without a
    layer and the layers are built and appended one at a time, each leaf's
    float32 storage dropped as soon as its shape is known; the seeded
    leaves then arrive from one jitted call."""
    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.models.phi4flash import (Phi4FlashDecoderLayer,
                                             Phi4FlashForCausalLM)
    pcfg = program_config(cfg)
    n_layers = pcfg.num_hidden_layers
    placeholder = jnp.zeros((), jnp.dtype(dtype))

    def release(layer):
        for _, p in layer.named_parameters():
            p._data = placeholder
    pcfg.num_hidden_layers = 0
    model = Phi4FlashForCausalLM(pcfg)
    release(model)
    pcfg.num_hidden_layers = n_layers
    for i in range(n_layers):
        layer = Phi4FlashDecoderLayer(pcfg, i)
        release(layer)
        model.model.layers.append(layer)
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
