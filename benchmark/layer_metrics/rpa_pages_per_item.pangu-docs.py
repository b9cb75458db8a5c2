"""How full the RPA kernel's work items are. An item is a run of up to P
consecutive pages of one sequence that a q tile can see (P read off the
pool's shape: 4 for the latent pool of this cell), and the engine writes
into a step's ``serving.dispatch`` span ``rpa_live``, the items that name a
real run, and ``rpa_pages``, the pages those runs name
(``serving/engine.py``). Pages over items is the run's fill: P where every
run is full, less by what the last run of each (q tile, sequence) walk
leaves empty. A program that writes no ``rpa_pages`` (a commit before the
run; the gather reader) leaves the metric out."""


def read(run):
    """Sum of ``rpa_pages`` over sum of ``rpa_live`` across the traced
    span's whole steps; None where no such step carries both."""
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
        if name == "serving.dispatch" and "rpa_pages" in stats \
                and "rpa_live" in stats:
            counts[step] = (float(stats["rpa_pages"]),
                            float(stats["rpa_live"]))
    whole = [counts[s] for s, names in leaves.items()
             if s in counts and len(names) == len(spans.STEP_LEAVES)]
    live = sum(v for _, v in whole)
    if live <= 0:
        return None
    return sum(p for p, _ in whole) / live
