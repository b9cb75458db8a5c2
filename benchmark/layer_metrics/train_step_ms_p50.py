import statistics


def read(run):
    """Median gap between step completions in the window."""
    t = run["step_done_s"]
    gaps = [b - a for a, b in zip(t, t[1:])]
    return 1e3 * statistics.median(gaps) if gaps else None
