def read(run):
    """Forward + backward operations a token x tokens a second over the
    chip's peak; recomputed work is not counted."""
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] \
        / run["peaks"]["bf16_flops_per_s"]
