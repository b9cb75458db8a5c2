"""What the readers of model ``pangu_ultra_moe``'s cells share: the traced
span's steps as the program's own spans describe them, and the registry's
counts of the rows the held experts took.

A step's ``serving.dispatch`` span carries its rows (``"<new>@<context>"``
in row order) and its ``serving.commit`` span the tokens it put out and,
where the model has held routed experts, ``moe_rows`` (token rows on held
experts, all layers), ``moe_max`` (the fullest expert's) and ``moe_live``
((layer, expert) pairs that took a row). A program that writes none of it
(a commit before these spans) leaves the metrics out.
"""


def span_steps(run):
    """``{step: {"rows": [(new, context)], "tokens_out", "moe_rows",
    "moe_max", "moe_live"}}`` for the steps whose spans the trace holds;
    None without a trace."""
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
            one["rows"] = [tuple(int(v) for v in r.split("@"))
                           for r in str(stats["rows"]).split(";") if r]
        elif name == "serving.commit":
            for k in ("tokens_out", "moe_rows", "moe_max", "moe_live"):
                if k in stats:
                    one[k] = float(stats[k])
    return steps


def span_rows(run):
    """Every ``(new, context)`` row of the span's steps, from the
    dispatch spans; else the rows the kind rebuilt from the client's log."""
    steps = span_steps(run) or {}
    rows = [r for s in steps.values() for r in s.get("rows", ())]
    return rows or list((run.get("traced") or {}).get("rows") or ())


def expert_layers(cfg):
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def registry_rows():
    """``{(layer, expert): rows}`` from ``serving_moe_expert_rows_total``,
    over the whole run (warm-up included); None where the program has no
    such counter."""
    from paddle_tpu.observability import get_registry
    fam = get_registry().get("serving_moe_expert_rows_total")
    if fam is None:
        return None
    with fam._lock:                  # the family has no accessor for all
        samples = dict(fam._samples)           # of its label sets
    out = {}
    for key, value in samples.items():
        lab = dict(key)
        if "layer" in lab and "expert" in lab:
            out[(lab["layer"], lab["expert"])] = float(value)
    return out or None
