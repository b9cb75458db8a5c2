def read(run):
    """The fullest held expert's rows over the mean held expert's, by
    (block, expert) over the whole run (the registry's
    ``serving_moe_expert_rows_total``): 1 is an even load."""
    from benchmark.layer_metrics import _kexaone as kx
    rows = kx.registry_rows()
    if not rows or not kx.drafts(run):
        return None
    z = kx.sizes(run)
    mean = sum(rows.values()) / (
        (z["layers"] - z["dense"] + z["mtp"]) * len(z["held"]))
    return max(rows.values()) / mean if mean > 0 else None
