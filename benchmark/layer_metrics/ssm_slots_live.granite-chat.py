def read(run):
    """State rows a step of the traced span reads and writes: the mean of
    ``serving.dispatch``'s ``ssm_slots`` (a slot with rows in the step)."""
    from benchmark.layer_metrics import _granite as g
    steps = g.state_steps(run)
    if not steps:
        return None
    return sum(s["ssm_slots"] for s in steps) / len(steps)
