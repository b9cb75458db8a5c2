def read(run):
    """Least time for the span's live (row, visible keys) pairs in the
    eight window layers, in the published differential form
    (``kernels/phi4flash_model.py``: the pairs inside the window, the K and
    V rows any token of a row can see read once) over the ``rpa_win``
    kernel's device time in the trace."""
    from benchmark import weights_phi4flash as W
    from benchmark.kernels import phi4flash_model as pm, rpa_win
    from benchmark.layer_metrics import _phi4
    from benchmark.layer_metrics._smallthinker import span_rows
    rows = span_rows(run) if run.get("traced") else None
    if not rows:
        return None
    cfg = run["cfg"]
    flops, nbytes = pm.attention_required(cfg, rows, W.sizes(cfg)["window"])
    windowed = pm.layer_kinds(cfg)["window"]
    return _phi4.kernel_roofline(run, rpa_win.TRACE_PATTERN,
                                 flops * windowed, nbytes * windowed)
