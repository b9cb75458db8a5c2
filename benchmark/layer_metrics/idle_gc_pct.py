"""Of the device's idle seconds in the traced span, the share that falls
inside ``python.gc`` spans: idle the host's collector held the device to,
cut at each collection's ends (``spans.split``). Nothing where the span has
no idle."""
from benchmark import spans
from benchmark.layer_metrics import _gc


def read(run):
    ev = _gc.events(run)
    sp = spans.split(ev) if ev is not None else None
    if sp is None or sp.idle_s <= 0:
        return None
    row = sp.rows.get(_gc.GC)
    return 100.0 * row[2] / sp.idle_s if row else 0.0
