def read(run):
    """Least time for the span's live (row, visible keys) pairs in the
    window layers (``kernels/rpa_win.py``: the pairs inside the window, the
    K and V rows any token of a row can see read once; one call a window
    layer) over the ``rpa_win`` kernel's device time in the trace."""
    from benchmark.kernels import rpa_win
    from benchmark.kernels.smallthinker_model import layer_kinds
    from benchmark.layer_metrics import _smallthinker as st
    rows = st.span_rows(run) if run.get("traced") else None
    if not rows:
        return None
    z, (_, windowed) = st.sizes(run), layer_kinds(run["cfg"])
    flops, nbytes = rpa_win.required(rows, z["heads"], z["kv"], z["hd"],
                                     z["window"])
    return st.kernel_roofline(run, rpa_win.TRACE_PATTERN, flops * windowed,
                              nbytes * windowed)
