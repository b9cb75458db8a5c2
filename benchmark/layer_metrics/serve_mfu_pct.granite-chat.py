def read(run):
    """The model's operations in the published form
    (``kernels/granite_hybrid_model.py``) for the rows of the traced span's
    steps (``serving.dispatch``'s rows), over span x the chip's bf16 peak:
    every layer's projections and MLP and every Mamba layer's recurrence
    and convolution a row, attention by the (row, context) pairs the causal
    mask lets through, the tied head a token chosen (``serving.commit``'s
    ``tokens_out``)."""
    tr = run.get("traced") or {}
    from benchmark.kernels import granite_hybrid_model as gm
    from benchmark.layer_metrics import _smallthinker as st
    steps = st.span_steps(run) if tr else None
    if not steps:
        return None
    rows = [r for s in steps.values() for r in s.get("rows", ())]
    chosen = sum(s.get("tokens_out", 0.0) for s in steps.values())
    if not rows:
        return None
    cfg = run["cfg"]
    _, attn = gm.layer_kinds(cfg)
    flops = sum(n for n, _ in rows) * gm.row_flops(cfg) \
        + attn * gm.attention_flops_per_pair(cfg) * gm.visible_pairs(rows) \
        + chosen * 2.0 * gm.matmul_params(cfg)["head"]
    return 100.0 * flops / (tr["span_s"] * run["peaks"]["bf16_flops_per_s"])
