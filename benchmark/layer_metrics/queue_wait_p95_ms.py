from benchmark.layer_metrics._shared import percentile


def read(run):
    """The engine's own slot time minus arrival time, 95th over the
    requests that reached a first token."""
    v = percentile([r.queue_wait_s for r in run["records"]], 95)
    return None if v is None else 1e3 * v
