"""The tiny ``granite_hybrid`` configuration the tests share: two Mamba-2
layers about one attention layer, in the benchmark's file format. The
embedding is not scaled up and the weights are drawn at 0.2, so that the
mixers' share of the residual stream decides which token comes next (at
the published 12 x and 0.02 a tiny model echoes its input token)."""
CFG = {
    "model": "granite_hybrid", "dtype": "float32",
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.125, "shared_intermediate_size": 48,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "embedding_multiplier": 1, "residual_multiplier": 0.22,
    "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "initializer_range": 0.2,
    "engine": {"max_batch": 4, "max_blocks": 48, "block_size": 8,
               "prefill_chunk": 16},
}
