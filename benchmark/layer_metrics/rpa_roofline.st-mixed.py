def read(run):
    """Least time for the span's live (row, context) pairs in the full
    (NoPE) layers (``kernels/rpa.py``: operations and bytes, one call a
    full layer) over the device time of the ``rpa.N`` calls in the trace
    (the unwindowed K/V form; ``rpa_win`` is the window layers')."""
    from benchmark.kernels import rpa, rpa_win
    from benchmark.kernels.smallthinker_model import layer_kinds
    from benchmark.layer_metrics import _smallthinker as st
    rows = st.span_rows(run) if run.get("traced") else None
    if not rows:
        return None
    z, (full, _) = st.sizes(run), layer_kinds(run["cfg"])
    flops, nbytes = rpa.required(rows, z["heads"], z["kv"], z["hd"])
    return st.kernel_roofline(run, rpa_win.FULL_TRACE_PATTERN, flops * full,
                              nbytes * full)
