def read(run):
    """Least time for the span's live (row, context) pairs in the full
    layers, the drafter's block among them (``kernels/rpa.py``: operations
    and bytes, one call a full block) over the device time of the ``rpa.N``
    calls in the trace (``rpa_win`` is the window layers')."""
    from benchmark.kernels import exaone_moe_model as em, rpa, rpa_win
    from benchmark.layer_metrics import _kexaone as kx
    steps = kx.drafting_steps(run) if run.get("traced") else None
    if not steps:
        return None
    rows = [r for s in steps for r in s["rows"]]
    z, (full, _) = kx.sizes(run), em.layer_kinds(run["cfg"])
    flops, nbytes = rpa.required(rows, z["heads"], z["kv"], z["hd"])
    return kx.kernel_roofline(run, rpa_win.FULL_TRACE_PATTERN, flops * full,
                              nbytes * full)
