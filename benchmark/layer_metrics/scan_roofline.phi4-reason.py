def read(run):
    """Least time for the traced span's scans (``kernels/selective_scan.py``:
    each live sequence's state and convolution rows read and written once,
    the rows' inputs and outputs, the recurrence in sequential form; one
    call a Mamba layer, every row runs them) over the device time of the
    ``selective_scan.N`` calls in the trace."""
    from benchmark import weights_phi4flash as W
    from benchmark.kernels import selective_scan
    from benchmark.kernels.phi4flash_model import layer_kinds
    from benchmark.layer_metrics import _phi4
    from benchmark.layer_metrics._smallthinker import span_rows
    rows = span_rows(run) if run.get("traced") else None
    if not rows:
        return None
    cfg = run["cfg"]
    z, mamba = W.sizes(cfg), layer_kinds(cfg)["mamba"]
    flops, nbytes = selective_scan.required(rows, z["di"], z["N"], z["K"])
    return _phi4.kernel_roofline(run, selective_scan.TRACE_PATTERN,
                                 flops * mamba, nbytes * mamba)
