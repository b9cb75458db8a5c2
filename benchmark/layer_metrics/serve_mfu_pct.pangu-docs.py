def read(run):
    """The model's operations, in the published (expanded) form
    (``kernels/pangu_model.py``), for the tokens the engine's counters say
    it processed in the traced span, over span x the chip's bf16 peak:
    projections and the shared expert for every token, a token's latent
    up-projected once, the head for tokens sampled, attention by the
    span's (row, context) pairs, routed experts by the rows that fell on
    held experts (the commit spans' ``moe_rows``)."""
    tr = run.get("traced") or {}
    if not tr:
        return None
    from benchmark.kernels import pangu_model as pm
    from benchmark.layer_metrics import _pangu
    import benchmark.weights_pangu as W
    cfg, c = run["cfg"], tr["counters"]
    steps = _pangu.span_steps(run) or {}
    moe_rows = [s["moe_rows"] for s in steps.values() if "moe_rows" in s]
    processed = c["prompt_tokens"] + c["generated_tokens"]
    if processed <= 0 or not moe_rows:
        return None
    z, m = W.sizes(cfg), pm.matmul_params(cfg)
    moe_layers = z["layers"] - z["dense"]
    pairs = sum(n * ctx + n * (n + 1) / 2.0
                for n, ctx in _pangu.span_rows(run))
    flops = processed * 2.0 * (
        z["layers"] * m["attention"] + z["dense"] * m["dense_mlp"]
        + moe_layers * m["expert_fixed"]) \
        + sum(moe_rows) * 2.0 * m["expert"] \
        + c["generated_tokens"] * 2.0 * m["head"] \
        + z["layers"] * pm.attention_flops_per_pair(cfg) * pairs
    return 100.0 * flops / (tr["span_s"] * run["peaks"]["bf16_flops_per_s"])
