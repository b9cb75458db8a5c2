def read(run):
    """The ``selective_scan.N`` calls' device seconds over the device's
    busy seconds in the traced span: the Mamba-1 scan's share of the
    work."""
    from benchmark.kernels import selective_scan
    trace = run.get("trace")
    if trace is None or trace.busy_s <= 0:
        return None
    seconds = trace.op_seconds(selective_scan.TRACE_PATTERN)
    return 100.0 * seconds / trace.busy_s if seconds > 0 else None
