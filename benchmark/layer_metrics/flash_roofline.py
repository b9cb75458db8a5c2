import re


def read(run):
    """The flash kernels (forward and both backward) of every layer of
    every step in the traced span: least time for their required
    operations and bytes over their device time in the trace."""
    trace, span = run.get("trace"), run.get("trace_span_s")
    if trace is None or not span or span != span:
        return None
    from benchmark.kernels import flash
    import benchmark.weights as W
    seconds = trace.op_seconds(flash.TRACE_PATTERN)
    calls = sum(n for name, n in trace.op_counts.items()
                if re.search(flash.TRACE_PATTERN, name))
    if seconds <= 0 or not calls:
        return None
    z, mix = W.sizes(run["cfg"]), run["mix"]
    need = flash.required(mix["batch"], z["heads"], z["kv"], mix["seq_len"],
                          z["hd"])
    # three kernel calls a layer a step: forward, dq, dk/dv
    per_layer_step = flash.least_seconds(*need["fwd"], run["peaks"])[0] \
        + flash.least_seconds(*need["bwd"], run["peaks"])[0]
    return 100.0 * per_layer_step * (calls / 3.0) / seconds
