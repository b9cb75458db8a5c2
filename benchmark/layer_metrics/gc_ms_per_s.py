"""Milliseconds of collection a second of the traced span: every
``python.gc`` span on every thread, summed, over the traced window (as
``device_idle_pct`` takes it). Collections never overlap: the sum is the
time the interpreter stood still."""
from benchmark.layer_metrics import _gc
from benchmark.layer_metrics._shared import _span


def read(run):
    ev, span, trace = _gc.events(run), _span(run), run.get("trace")
    if ev is None or not span:
        return None
    if trace is not None:
        from benchmark.harness import traced_window_s
        span = traced_window_s(trace, span)
    return sum(_gc.pauses_ms(ev)) / span
