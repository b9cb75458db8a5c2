def read(run):
    """Least time for the span's live (row, context) pairs in the attention
    layers (``kernels/rpa.py``: operations and bytes, one call an attention
    layer, head width 64) over the device time of the ``rpa.N`` calls in
    the trace."""
    from benchmark.kernels import rpa, rpa_win
    from benchmark.kernels.granite_hybrid_model import layer_kinds
    from benchmark.layer_metrics import _granite as g
    rows = g.span_rows(run) if run.get("traced") else None
    if not rows:
        return None
    z, (_, attn) = g.sizes(run), layer_kinds(run["cfg"])
    flops, nbytes = rpa.required(rows, z["heads"], z["kv"], z["hd"])
    return g.kernel_roofline(run, rpa_win.FULL_TRACE_PATTERN, flops * attn,
                             nbytes * attn)
