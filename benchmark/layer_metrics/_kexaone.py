"""What the readers of model ``exaone_moe``'s cells share. The traced
span's steps come from the program's own spans (:func:`span_steps`: a
step's rows ``(new, context)`` and its counts of decode and draft rows from
``serving.dispatch``; the tokens it yielded, the drafts it verified and
accepted and the rows its held experts took from ``serving.commit``); the
run's drafts from ``engine.stats()["drafts"]``; the cache's layer groups
from ``engine.stats()["kv_groups"]``. A program that writes none of it (a
commit before the drafts) leaves the metrics out."""
from benchmark.layer_metrics._pangu import registry_rows  # noqa: F401
from benchmark.layer_metrics._smallthinker import (  # noqa: F401
    dispatch_sums, kernel_roofline, pool_used_pct)


def span_steps(run):
    """``{step: {"rows": [(new, context)], <every other argument of the
    step's dispatch and commit spans as a number>}}`` for the steps whose
    spans the trace holds; None without a trace. A step's rows are
    ``serving.dispatch``'s ``rows``, ``rows_1``, ... in turn."""
    from benchmark import spans
    path = spans.find_path(run)
    if not path:
        return None
    steps = {}
    for name, _, _, stats in spans.load(path).host:
        step = stats.get("step")
        if step is None or name not in ("serving.dispatch", "serving.commit"):
            continue
        one = steps.setdefault(step, {})
        if name == "serving.dispatch" and "rows" in stats:
            keys = ["rows"] + sorted(
                (k for k in stats if k.startswith("rows_")),
                key=lambda k: int(k[5:]))
            one["rows"] = [tuple(int(v) for v in r.split("@"))
                           for k in keys for r in str(stats[k]).split(";")
                           if r]
        for k, v in stats.items():
            if k != "step" and not k.startswith("rows"):
                try:
                    one[k] = float(v)
                except (TypeError, ValueError):
                    pass
    return steps


def drafting_steps(run):
    """The span's steps that a drafting engine wrote whole: their dispatch
    carries ``draft_rows`` and their commit ``emitted``; None where there
    is none (no trace, or a program that drafts nothing)."""
    steps = [s for s in (span_steps(run) or {}).values()
             if "draft_rows" in s and "emitted" in s and "rows" in s]
    return steps or None


def drafts(run):
    """``engine.stats()["drafts"]`` at the window's end, or None."""
    return (run.get("stats") or {}).get("drafts") or None


def sizes(run):
    import benchmark.weights_exaone_moe as W
    return W.sizes(run["cfg"])
