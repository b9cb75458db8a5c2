def read(run):
    """Rows that carry a draft over all the rows of the traced span's steps
    (``serving.dispatch``: ``draft_rows`` over the new tokens of ``rows``),
    in percent: what of a step's work is verification."""
    from benchmark.layer_metrics._kexaone import drafting_steps
    steps = drafting_steps(run)
    if not steps:
        return None
    rows = sum(n for s in steps for n, _ in s["rows"])
    return 100.0 * sum(s["draft_rows"] for s in steps) / rows if rows else None
