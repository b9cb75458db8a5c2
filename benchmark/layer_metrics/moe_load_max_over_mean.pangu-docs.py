def read(run):
    """The fullest held expert's rows over the mean held expert's, by
    (layer, expert) over the whole run (the registry's
    ``serving_moe_expert_rows_total``): 1 is an even load."""
    from benchmark.layer_metrics import _pangu
    rows = _pangu.registry_rows()
    if not rows:
        return None
    cfg = run["cfg"]
    n = _pangu.expert_layers(cfg) * int(cfg["n_routed_experts"])
    mean = sum(rows.values()) / n           # an expert without a row counts
    return max(rows.values()) / mean if mean > 0 else None
