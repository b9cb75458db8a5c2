def read(run):
    """The model's operations in the published form
    (``kernels/phi4flash_model.py``) over span x the chip's bf16 peak:
    layers 0-17 for every row of the traced span's steps, the
    cross-decoder for the rows that ran it (``cross_rows``), attention by
    the (row, key) pairs the masks let through (the window layers', the
    full layer's, the cross layers' ``cross_keys``), the tied head a token
    chosen (``serving.commit``'s ``tokens_out``)."""
    tr = run.get("traced") or {}
    from benchmark import weights_phi4flash as W
    from benchmark.kernels import phi4flash_model as pm
    from benchmark.layer_metrics import _phi4
    steps = _phi4.cross_steps(run)
    if not steps:
        return None
    cfg = run["cfg"]
    rows, n = _phi4.rows_of(steps), pm.layer_kinds(cfg)
    window = W.sizes(cfg)["window"]
    flops = sum(k for k, _ in rows) * pm.self_row_flops(cfg) \
        + sum(s["cross_rows"] for s in steps) * pm.cross_row_flops(cfg) \
        + pm.pair_flops(cfg) * (
            n["window"] * pm.visible_pairs(rows, window)
            + n["full"] * pm.visible_pairs(rows)
            + n["cross"] * sum(s["cross_keys"] for s in steps)) \
        + sum(s.get("tokens_out", 0.0) for s in steps) * 2.0 \
        * pm.matmul_params(cfg)["head"]
    return 100.0 * flops / (tr["span_s"] * run["peaks"]["bf16_flops_per_s"])
