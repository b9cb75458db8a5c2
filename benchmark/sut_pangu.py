"""The system under test for model ``pangu_ultra_moe``, as the benchmark
builds it: ``paddle_tpu``'s ``PanguMoeForCausalLM`` at a configuration
file's sizes with the benchmark's seeded weights (``weights_pangu.py``),
wrapped in ``serving.ServingEngine``. Everything goes through the
program's public entry points; the model serves only (no trainer).
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights_pangu as W

#: benchmark leaf -> the program's parameter suffix within a layer
_LAYER_NAMES = {
    "ln_in": "input_layernorm.weight",
    "wqa": "self_attn.q_a_proj.weight",
    "ln_q": "self_attn.q_a_layernorm.weight",
    "wqb": "self_attn.q_b_proj.weight",
    "wkva": "self_attn.kv_a_proj_with_mqa.weight",
    "ln_kv": "self_attn.kv_a_layernorm.weight",
    "wkvb": "self_attn.kv_b_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "ln_post_attn": "post_attention_layernorm.weight",
    "ln_pre_mlp": "pre_mlp_layernorm.weight",
    "ln_post_mlp": "post_mlp_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "router": "mlp.router", "e_gate": "mlp.w_gate", "e_up": "mlp.w_up",
    "e_down": "mlp.w_down",
    "s_gate": "shared_experts.gate_proj.weight",
    "s_up": "shared_experts.up_proj.weight",
    "s_down": "shared_experts.down_proj.weight"}
_GLOBAL_NAMES = {"embed": "model.embed_tokens.weight",
                 "norm": "model.norm.weight", "head": "lm_head.weight"}


def program_config(cfg: dict):
    """The program's config at the file's sizes: the router keeps its
    published width and the model is told which experts it holds."""
    from paddle_tpu.models.pangu_moe import PanguMoeConfig
    z = W.sizes(cfg)
    return PanguMoeConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        intermediate_size=z["ffn"], moe_intermediate_size=z["moe_ffn"],
        num_hidden_layers=z["layers"], first_k_dense_replace=z["dense"],
        num_attention_heads=z["heads"], q_lora_rank=z["q_rank"],
        kv_lora_rank=z["kv_rank"], qk_nope_head_dim=z["nope"],
        qk_rope_head_dim=z["rope"], v_head_dim=z["v"],
        n_routed_experts=z["experts"], held_experts=z["held"],
        num_experts_per_tok=z["top_k"], n_shared_experts=z["shared"],
        routed_scaling_factor=z["scaling"], norm_topk_prob=z["norm_topk"],
        rope_theta=z["theta"], rms_norm_eps=z["eps"],
        max_position_embeddings=z["max_pos"], initializer_range=z["std"])


def build_model(cfg: dict, seed: int, dtype="bfloat16"):
    """The program's model at ``cfg``'s sizes holding the seeded weights.
    As ``sut.build_model``: the model is built one layer deep and the other
    layers are built and appended one at a time, each leaf's float32
    storage dropped as soon as its shape is known (an expert layer is born
    with 4 GB of it); the seeded leaves then arrive from one jitted call."""
    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.models.pangu_moe import (PanguDecoderLayer,
                                             PanguMoeForCausalLM)
    pcfg = program_config(cfg)
    n_layers = pcfg.num_hidden_layers
    pcfg.num_hidden_layers = 1
    model = PanguMoeForCausalLM(pcfg)
    placeholder = jnp.zeros((), jnp.dtype(dtype))

    def release(layer):
        for _, p in layer.named_parameters():
            p._data = placeholder
    release(model)
    for i in range(1, n_layers):
        layer = PanguDecoderLayer(pcfg, i)
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
    ``engine`` group of the file), weights in place before the pool is."""
    from paddle_tpu.serving import ServingEngine
    model = build_model(cfg, seed, cfg.get("dtype", "bfloat16"))
    model.eval()
    kw = dict(cfg["engine"])
    kw.update(overrides or {})
    return ServingEngine(model, **kw)
