def read(run):
    """Least time for the span's live (row, context) pairs read absorbed
    (``kernels/rpa_mla.py``: operations and bytes, one call a layer) over
    the ``rpa_mla`` kernel's device time in the trace."""
    tr, trace = run.get("traced") or {}, run.get("trace")
    if not tr or trace is None:
        return None
    from benchmark.kernels import flash, rpa_mla
    from benchmark.layer_metrics import _pangu
    import benchmark.weights_pangu as W
    rows = _pangu.span_rows(run)
    seconds = trace.op_seconds(rpa_mla.TRACE_PATTERN)
    if seconds <= 0 or not rows:
        return None
    z = W.sizes(run["cfg"])
    flops, nbytes = rpa_mla.required(rows, z["heads"],
                                     z["kv_rank"] + z["rope"], z["kv_rank"])
    least, _ = flash.least_seconds(flops * z["layers"], nbytes * z["layers"],
                                   run["peaks"])
    return 100.0 * least / seconds
