"""How much of the RPA kernel's grid walk is live work. The engine writes
both numbers of a step's work list into its ``serving.dispatch`` span
(``serving/engine.py``): ``rpa_live``, the items that name a real
(q tile, sequence, page), and ``rpa_walked``, the grid bound the kernel was
handed (live, and one item for each q tile without work), each a kv head
and layer. A program that does not write them (the gather reader; a commit
before the flat work list) leaves the metric out."""


def live_step_pct(run):
    """Sum of ``rpa_live`` over sum of ``rpa_walked`` across the traced
    span's whole steps, in percent; None where no such step carries both."""
    from benchmark import spans
    path = spans.find_path(run)
    if not path:
        return None
    leaves, counts = {}, {}
    for name, _, _, stats in spans.load(path).host:
        step = stats.get("step")
        if step is None or name not in spans.STEP_LEAVES:
            continue
        leaves.setdefault(step, set()).add(name)
        if name == "serving.dispatch" and "rpa_live" in stats \
                and "rpa_walked" in stats:
            counts[step] = (float(stats["rpa_live"]),
                            float(stats["rpa_walked"]))
    whole = [counts[s] for s, names in leaves.items()
             if s in counts and len(names) == len(spans.STEP_LEAVES)]
    walked = sum(w for _, w in whole)
    if walked <= 0:
        return None
    return 100.0 * sum(v for v, _ in whole) / walked
