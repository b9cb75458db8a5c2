"""The system under test for model ``exaone_moe``, as the benchmark builds
it: ``paddle_tpu``'s ``ExaoneMoeForCausalLM`` at a configuration file's
sizes with the benchmark's seeded weights (``weights_exaone_moe.py``), its
multi-token-prediction module among them, wrapped in
``serving.ServingEngine``. Everything goes through the program's public
entry points; the model serves only (no trainer).
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights_exaone_moe as W

#: benchmark leaf -> the program's parameter suffix within a block
_LAYER_NAMES = {
    "ln1": "input_layernorm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight", "ln_q": "self_attn.q_norm.weight",
    "ln_k": "self_attn.k_norm.weight",
    "ln2": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "router": "mlp.router", "router_bias": "mlp.router_bias",
    "e_gate": "mlp.w_gate", "e_up": "mlp.w_up", "e_down": "mlp.w_down",
    "s_gate": "shared_experts.gate_proj.weight",
    "s_up": "shared_experts.up_proj.weight",
    "s_down": "shared_experts.down_proj.weight"}
_GLOBAL_NAMES = {"embed": "model.embed_tokens.weight",
                 "norm": "model.norm.weight", "head": "lm_head.weight"}
_MTP_NAMES = {"mtp_enorm": "mtp.enorm.weight", "mtp_hnorm": "mtp.hnorm.weight",
              "mtp_proj": "mtp.eh_proj.weight", "mtp_norm": "mtp.norm.weight"}


def program_config(cfg: dict):
    """The program's config at the file's sizes: the router keeps its
    published width and the model is told which experts it holds; the
    layer pattern is held whole and the model reads what its depth needs."""
    from paddle_tpu.models.exaone_moe import ExaoneMoeConfig
    z = W.sizes(cfg)
    return ExaoneMoeConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        intermediate_size=z["ffn"], num_hidden_layers=z["layers"],
        num_attention_heads=z["heads"], num_key_value_heads=z["kv"],
        head_dim=z["hd"], moe_intermediate_size=z["moe_ffn"],
        num_experts=z["experts"], held_experts=z["held"],
        num_experts_per_tok=z["top_k"], num_shared_experts=z["shared"],
        first_k_dense_replace=z["dense"],
        routed_scaling_factor=z["scaling"], norm_topk_prob=z["norm_topk"],
        sliding_windows=tuple(int(w) for w in cfg["sliding_windows"]),
        num_nextn_predict_layers=z["mtp"],
        mtp_sliding_windows=z["mtp_windows"], rope_theta=z["theta"],
        rms_norm_eps=z["eps"], max_position_embeddings=z["max_pos"],
        initializer_range=z["std"])


def build_model(cfg: dict, seed: int, dtype="bfloat16"):
    """The program's model at ``cfg``'s sizes holding the seeded weights.
    As ``sut_pangu.build_model``: the model is built without a layer (its
    drafter is born with it) and the layers are built and appended one at
    a time, each leaf's float32 storage dropped as soon as its shape is
    known (an expert layer is born with 3 GB of it); the seeded leaves
    then arrive from one jitted call."""
    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.models.exaone_moe import (ExaoneMoeDecoderLayer,
                                              ExaoneMoeForCausalLM)
    pcfg = program_config(cfg)
    n_layers = pcfg.num_hidden_layers
    placeholder = jnp.zeros((), jnp.dtype(dtype))

    def release(layer):
        for _, p in layer.named_parameters():
            p._data = placeholder
    pcfg.num_hidden_layers = 0
    model = ExaoneMoeForCausalLM(pcfg)
    release(model)
    for i in range(n_layers):
        layer = ExaoneMoeDecoderLayer(pcfg, pcfg.window_of(i),
                                      i < pcfg.first_k_dense_replace)
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
    if "mtp" in tree:
        for leaf, name in _MTP_NAMES.items():
            put(name, tree["mtp"][leaf])
        for leaf, arr in tree["mtp"]["block"].items():
            put(f"mtp.block.{_LAYER_NAMES[leaf]}", arr)
    for layer in model.sublayers(include_self=True):
        layer._dtype = convert_dtype(dtype)      # what ``.bfloat16()`` sets
    return model


def build_engine(cfg: dict, seed: int, overrides=None):
    """``ServingEngine`` at the configuration's deployment settings (the
    ``engine`` group of the file: ``draft_tokens`` among them), weights in
    place before the pools are."""
    from paddle_tpu.serving import ServingEngine
    model = build_model(cfg, seed, cfg.get("dtype", "bfloat16"))
    model.eval()
    kw = dict(cfg["engine"])
    kw.update(overrides or {})
    return ServingEngine(model, **kw)
