def read(run):
    """The model's operations with the drafter's, in the published form
    (``kernels/exaone_moe_model.py``), for the rows of the traced span's
    steps (``serving.dispatch``'s rows, a draft's row among them), over
    span x the chip's bf16 peak: every block's projections, the dense
    layer, routers and shared experts and the drafter's projection for
    every row; the held experts by the rows that fell on them (the commit
    spans' ``moe_rows``, the drafter's block among them); attention by the
    (row, context) pairs the mask lets through; the head for the rows whose
    logits choose a token (a decode row, its draft's row, a prompt's last)
    and once more for each sequence's kept guess."""
    tr = run.get("traced") or {}
    from benchmark.kernels import exaone_moe_model as em
    from benchmark.layer_metrics import _kexaone as kx
    steps = kx.drafting_steps(run) if tr else None
    if not steps or not any("moe_rows" in s for s in steps):
        return None
    cfg = run["cfg"]
    m = em.matmul_params(cfg)
    full, windowed = em.layer_kinds(cfg)
    rows = [r for s in steps for r in s["rows"]]
    decode = sum(s.get("decode_rows", 0) for s in steps)
    drafted = sum(s["draft_rows"] for s in steps)
    # prompts that ended in the span: tokens out beyond the decoding ones'
    finals = sum(max(0.0, s.get("tokens_out", 0) - s["emitted"])
                 for s in steps)
    heads = (decode + drafted + finals) + (decode + finals)
    flops = sum(n for n, _ in rows) * em.row_flops(cfg) \
        + sum(s.get("moe_rows", 0) for s in steps) * 2.0 * m["expert"] \
        + heads * 2.0 * m["head"] \
        + em.attention_flops_per_pair(cfg) * (
            full * em.visible_pairs(rows)
            + windowed * em.visible_pairs(rows, em.window(cfg)))
    return 100.0 * flops / (tr["span_s"] * run["peaks"]["bf16_flops_per_s"])
