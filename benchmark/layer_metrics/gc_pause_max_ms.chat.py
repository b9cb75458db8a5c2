"""The longest single collection in the traced span, ms: a pause longer
than a decode step is a gap every running request sees."""
from benchmark.layer_metrics import _gc


def read(run):
    ev = _gc.events(run)
    if ev is None:
        return None
    return max(_gc.pauses_ms(ev), default=0.0)
