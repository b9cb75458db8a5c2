def read(run):
    """The model's operations in the published form
    (``kernels/smallthinker_model.py``) for the tokens the engine's
    counters say it processed in the traced span, over span x the chip's
    bf16 peak: projections and the router for every token, the head for
    tokens sampled, experts by the rows that fell on them (the commit
    spans' ``moe_rows``), attention by the span's (row, context) pairs the
    mask lets through: all in a full layer, the window's in a window
    layer."""
    tr = run.get("traced") or {}
    if not tr:
        return None
    from benchmark.kernels import smallthinker_model as sm
    from benchmark.layer_metrics import _smallthinker as st
    cfg, c = run["cfg"], tr["counters"]
    steps = st.span_steps(run) or {}
    moe_rows = [s["moe_rows"] for s in steps.values() if "moe_rows" in s]
    processed = c["prompt_tokens"] + c["generated_tokens"]
    if processed <= 0 or not moe_rows:
        return None
    z, m = st.sizes(run), sm.matmul_params(cfg)
    full, windowed = sm.layer_kinds(cfg)
    rows = st.span_rows(run)
    flops = processed * 2.0 * z["layers"] * (m["attention"] + m["router"]) \
        + sum(moe_rows) * 2.0 * m["expert"] \
        + c["generated_tokens"] * 2.0 * m["head"] \
        + sm.attention_flops_per_pair(cfg) * (
            full * sm.visible_pairs(rows)
            + windowed * sm.visible_pairs(rows, z["window"]))
    return 100.0 * flops / (tr["span_s"] * run["peaks"]["bf16_flops_per_s"])
