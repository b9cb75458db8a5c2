def read(run):
    """Least time for the eight ``rpa.N`` calls a step over the shared
    full pool, in the published differential form
    (``kernels/phi4flash_model.py``): the full layer's (row, context) pairs
    over every row, and the seven cross layers' over the rows that ran the
    cross-decoder (``serving.dispatch``'s ``cross_rows`` and
    ``cross_keys``); over the device time of the ``rpa.N`` calls in the
    trace."""
    from benchmark.kernels import phi4flash_model as pm, rpa_win
    from benchmark.layer_metrics import _phi4
    steps = _phi4.cross_steps(run)
    if not steps:
        return None
    cfg = run["cfg"]
    n = pm.layer_kinds(cfg)
    flops, nbytes = pm.attention_required(cfg, _phi4.rows_of(steps))
    cf, cb = pm.cross_required(cfg, sum(s["cross_rows"] for s in steps),
                               sum(s["cross_keys"] for s in steps))
    return _phi4.kernel_roofline(
        run, rpa_win.FULL_TRACE_PATTERN, n["full"] * flops + n["cross"] * cf,
        n["full"] * nbytes + n["cross"] * cb)
