def read(run):
    """The ``ssd.N`` calls' device seconds over the device's busy seconds
    in the traced span: the state update's share of the work."""
    from benchmark.kernels import ssd
    trace = run.get("trace")
    if trace is None or trace.busy_s <= 0:
        return None
    seconds = trace.op_seconds(ssd.TRACE_PATTERN)
    return 100.0 * seconds / trace.busy_s if seconds > 0 else None
