"""What the readers of model ``smallthinker``'s cells share. The traced
span's steps come from the program's own spans (:func:`span_steps`: a
step's rows ``(new, context)`` from ``serving.dispatch``, ``moe_rows`` and
``moe_live``, the (layer, expert) pairs that took a row, from
``serving.commit``); the cache's layer groups from ``engine.stats()``
(``kv_groups``) and from the per-group arguments of ``serving.dispatch``
(``rpa_pages_<group>``, ``rpa_pages_causal_<group>``). A program that
writes none of it (a commit before the layer groups) leaves the metrics
out."""
from benchmark.layer_metrics._pangu import registry_rows  # noqa: F401


def span_steps(run):
    """``{step: {"rows": [(new, context)], "tokens_out", "moe_rows",
    "moe_max", "moe_live"}}`` for the steps whose spans the trace holds;
    None without a trace. A step's rows are ``serving.dispatch``'s
    ``rows``, ``rows_1``, ``rows_2``, ... in turn (a trace keeps 256
    characters of a value, and a step of 64 slots has more)."""
    from benchmark import spans
    path = spans.find_path(run)
    if not path:
        return None
    steps = {}
    for name, _, _, stats in spans.load(path).host:
        step = stats.get("step")
        if step is None:
            continue
        one = steps.setdefault(step, {})
        if name == "serving.dispatch" and "rows" in stats:
            keys = ["rows"] + sorted(
                (k for k in stats if k.startswith("rows_")),
                key=lambda k: int(k[5:]))
            one["rows"] = [tuple(int(v) for v in r.split("@"))
                           for k in keys for r in str(stats[k]).split(";")
                           if r]
        elif name == "serving.commit":
            for k in ("tokens_out", "moe_rows", "moe_max", "moe_live"):
                if k in stats:
                    one[k] = float(stats[k])
    return steps


def span_rows(run):
    """Every ``(new, context)`` row of the span's steps, from the dispatch
    spans; else the rows the kind rebuilt from the client's log."""
    steps = span_steps(run) or {}
    rows = [r for s in steps.values() for r in s.get("rows", ())]
    return rows or list((run.get("traced") or {}).get("rows") or ())


def sizes(run):
    import benchmark.weights_smallthinker as W
    return W.sizes(run["cfg"])


def kernel_roofline(run, pattern, flops, nbytes):
    """Least time for ``(flops, nbytes)`` on the run's chip over the device
    seconds of the ops that match ``pattern`` in the trace, in percent."""
    trace = run.get("trace")
    if trace is None or flops <= 0:
        return None
    seconds = trace.op_seconds(pattern)
    if seconds <= 0:
        return None
    from benchmark.kernels import flash
    least, _ = flash.least_seconds(flops, nbytes, run["peaks"])
    return 100.0 * least / seconds


def pool_used_pct(run, group: str):
    """Pages of layer group ``group`` that live sequences hold or the
    prefix cache has parked, at the window's end, over the group's pool."""
    g = ((run.get("stats") or {}).get("kv_groups") or {}).get(group)
    if not g or not g.get("blocks"):
        return None
    return 100.0 * (g["blocks"] - g["free"]) / g["blocks"]


def dispatch_sums(run, key_a, key_b):
    """Over the traced span's whole steps: the sums of ``serving.dispatch``'s
    arguments ``key_a`` and ``key_b``; None where no step carries both."""
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
        if name == "serving.dispatch" and key_a in stats and key_b in stats:
            counts[step] = (float(stats[key_a]), float(stats[key_b]))
    whole = [counts[s] for s, names in leaves.items()
             if s in counts and len(names) == len(spans.STEP_LEAVES)]
    if not whole:
        return None
    return sum(a for a, _ in whole), sum(b for _, b in whole)
