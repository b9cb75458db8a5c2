"""The tiny ``phi4flash`` configuration the tests share, in the benchmark's
file format: eight layers, every kind of the published map (Mamba, window,
Mamba, window, Mamba with the memory, full, GMU, cross), a window of two
pages of 8. The weights are drawn at 0.2, so that the mixers' share of the
residual stream decides which token comes next (at the published 0.02 a
tiny model echoes its input token)."""
CFG = {
    "model": "phi4flash", "dtype": "float32",
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "sliding_window": 16, "mb_per_layer": 2,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 2, "layer_norm_eps": 1e-5,
    "max_position_embeddings": 512, "initializer_range": 0.2,
    "engine": {"max_batch": 4, "max_blocks": {"window": 24, "full": 48},
               "block_size": 8, "prefill_chunk": 16},
}
