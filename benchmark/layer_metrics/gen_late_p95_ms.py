from benchmark.layer_metrics._shared import percentile


def read(run):
    """How late the generator sent: send time minus due time, 95th."""
    late = [r.sent_s - r.ask.due_s for r in run["records"] if r.sent_s == r.sent_s]
    v = percentile(late, 95)
    return None if v is None else 1e3 * v
