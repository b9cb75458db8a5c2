"""The tiny ``exaone_moe`` configuration the tests share: a dense layer and
one ``L L G L`` run after it, a window of 16, 4 of 8 experts held, float32,
with its multi-token-prediction module; as a benchmark configuration file
would state it."""
CFG = dict(
    model="exaone_moe", hidden_size=64, intermediate_size=96,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=1,
    head_dim=16, moe_intermediate_size=32, num_experts=4,
    published={"num_experts": 8}, held_experts=[0, 2, 5, 7],
    num_experts_per_tok=2, num_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, vocab_size=128,
    sliding_windows=[16, 16, 16, 0] * 2, mtp_sliding_windows=[0],
    num_nextn_predict_layers=1,
    rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
    rms_norm_eps=1e-5, max_position_embeddings=256, initializer_range=0.1,
    dtype="float32",
    engine=dict(max_batch=4, max_blocks={"window": 24, "full": 64},
                block_size=8, prefill_chunk=16, max_blocks_per_seq=32,
                draft_tokens=1))
