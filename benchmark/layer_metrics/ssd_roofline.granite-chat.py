def read(run):
    """Least time for the traced span's state updates (``kernels/ssd.py``:
    each live sequence's state read and written once, the rows' inputs and
    outputs, the recurrence in sequential form; one call a Mamba layer) over
    the device time of the ``ssd.N`` calls in the trace."""
    from benchmark.kernels import ssd
    from benchmark.kernels.granite_hybrid_model import layer_kinds
    from benchmark.layer_metrics import _granite as g
    rows = g.span_rows(run) if run.get("traced") else None
    if not rows:
        return None
    z, (mamba, _) = g.sizes(run), layer_kinds(run["cfg"])
    flops, nbytes = ssd.required(rows, z["mh"], z["mp"], z["N"], z["K"],
                                 z["conv"], state_itemsize=g.state_itemsize(run))
    return g.kernel_roofline(run, ssd.TRACE_PATTERN, flops * mamba,
                             nbytes * mamba)
