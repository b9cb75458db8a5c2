"""Arithmetic the readers share. A reader takes the run's facts (counters,
the client's request log, the reduced trace) and returns one number, or
None where it finds nothing to read."""

def percentile(values, q):
    """``open_loop.percentile`` over the values that are numbers; None where
    there is none."""
    from benchmark.kinds.open_loop import percentile as pct
    values = [v for v in values if v == v]
    return pct(values, q) if values else None


def device_idle_pct(run):
    """1 - (union of device-op intervals) / traced span."""
    trace, span = run.get("trace"), _span(run)
    if trace is None or not span or trace.busy_s <= 0:
        return None
    from benchmark.harness import traced_window_s
    return 100.0 * (1.0 - trace.busy_s / traced_window_s(trace, span))


def _span(run):
    if run["kind"] == "train_job":
        s = run.get("trace_span_s")
    else:
        s = run.get("traced", {}).get("span_s")
    return s if s and s == s else None


def engine_step_ms(run):
    steps = run["counters"]["steps"]
    return 1e3 * run["window_s"] / steps if steps > 0 else None


def serve_mfu_pct(run):
    """Model operations of every token the engine processed in the traced
    span (prefilled and generated, from its counters; the head only for
    tokens sampled; attention from the rows of the span) over span x peak."""
    tr = run.get("traced") or {}
    if not tr:
        return None
    from benchmark.kernels import model
    cfg, c = run["cfg"], tr["counters"]
    m = model.matmul_params(cfg)
    import benchmark.weights as W
    z = W.sizes(cfg)
    processed = c["prompt_tokens"] + c["generated_tokens"]
    flops = processed * 2.0 * m["layer"] * z["layers"] \
        + c["generated_tokens"] * 2.0 * m["head"]
    flops += z["layers"] * sum(4.0 * z["heads"] * z["hd"]
                               * (n * ctx + n * (n + 1) / 2.0)
                               for n, ctx in tr["rows"])
    if processed <= 0:
        return None
    return 100.0 * flops / (tr["span_s"] * run["peaks"]["bf16_flops_per_s"])


def rpa_roofline(run):
    """Least time for the span's live pages (bytes and operations from the
    rows, one call a layer) over the kernel's device time in the trace."""
    tr, trace = run.get("traced") or {}, run.get("trace")
    if not tr or trace is None or not tr["rows"]:
        return None
    from benchmark.kernels import flash, rpa
    import benchmark.weights as W
    z = W.sizes(run["cfg"])
    seconds = trace.op_seconds(rpa.TRACE_PATTERN)
    if seconds <= 0:
        return None
    flops, nbytes = rpa.required(tr["rows"], z["heads"], z["kv"], z["hd"])
    least, _ = flash.least_seconds(flops * z["layers"], nbytes * z["layers"],
                                   run["peaks"])
    return 100.0 * least / seconds
