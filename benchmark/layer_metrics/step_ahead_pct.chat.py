"""How often the engine's loop ran one step ahead. The engine writes into a
step's ``serving.dispatch`` span ``ahead``: 1 where the step before was
still unharvested when this one was dispatched (its tokens on the device,
the device at work or about to be), 0 where nothing was in flight: the
first step after the engine waited for requests, or one that had to read
the step before first (``serving/engine.py``; the registry's
``serving_steps_dispatched_total`` names why). A program that writes no
``ahead`` (a commit before the loop ran ahead) leaves the metric out."""


def read(run):
    """Over the traced span's whole steps that carry ``ahead``, the share
    where it is 1, in percent; None where no such step carries it."""
    from benchmark import spans
    path = spans.find_path(run)
    if not path:
        return None
    leaves, ahead = {}, {}
    for name, _, _, stats in spans.load(path).host:
        step = stats.get("step")
        if step is None or name not in spans.STEP_LEAVES:
            continue
        leaves.setdefault(step, set()).add(name)
        if name == "serving.dispatch" and "ahead" in stats:
            ahead[step] = float(stats["ahead"])
    whole = [ahead[s] for s, names in leaves.items()
             if s in ahead and len(names) == len(spans.STEP_LEAVES)]
    if not whole:
        return None
    return 100.0 * sum(1 for a in whole if a > 0) / len(whole)
