def read(run):
    """Rows that ran the cross-decoder over the rows packed, over the
    traced span's steps (``serving.dispatch``'s ``cross_rows`` over its
    rows' new tokens): what is left of the cross-decoder's work once a
    prefill chunk's rows stop at the full layer."""
    from benchmark.layer_metrics import _phi4
    steps = _phi4.cross_steps(run)
    if not steps:
        return None
    packed = sum(n for n, _ in _phi4.rows_of(steps))
    return 100.0 * sum(s["cross_rows"] for s in steps) / packed \
        if packed > 0 else None
