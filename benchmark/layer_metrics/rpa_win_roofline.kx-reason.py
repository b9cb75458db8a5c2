def read(run):
    """Least time for the span's live (row, visible keys) pairs in the
    window layers (``kernels/rpa_win.py`` at a window of 128, one page: the
    pairs inside the window, the K and V rows any token of a row can see
    read once; one call a window layer) over the ``rpa_win`` kernel's
    device time in the trace."""
    from benchmark.kernels import exaone_moe_model as em, rpa_win
    from benchmark.layer_metrics import _kexaone as kx
    steps = kx.drafting_steps(run) if run.get("traced") else None
    if not steps:
        return None
    rows = [r for s in steps for r in s["rows"]]
    z, (_, windowed) = kx.sizes(run), em.layer_kinds(run["cfg"])
    flops, nbytes = rpa_win.required(rows, z["heads"], z["kv"], z["hd"],
                                     em.window(run["cfg"]))
    return kx.kernel_roofline(run, rpa_win.TRACE_PATTERN, flops * windowed,
                              nbytes * windowed)
