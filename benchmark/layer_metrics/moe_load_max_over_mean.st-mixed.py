def read(run):
    """The fullest expert's rows over the mean expert's, by (layer,
    expert) over the whole run (the registry's
    ``serving_moe_expert_rows_total``): 1 is an even load."""
    from benchmark.layer_metrics import _smallthinker as st
    rows = st.registry_rows()
    if not rows:
        return None
    z = st.sizes(run)
    mean = sum(rows.values()) / (z["layers"] * z["experts"])
    return max(rows.values()) / mean if mean > 0 else None
