def read(run):
    """Token rows an expert takes in a step and layer: the registry's
    ``serving_moe_expert_rows_total`` over the run's steps, layers and
    experts (whole run, warm-up included). Every expert is held: this is
    the deployment's own load, and what sets the grouped products'
    arithmetic intensity (rows an expert against its 11.8 MB)."""
    from benchmark.layer_metrics import _smallthinker as st
    from paddle_tpu.serving.engine import serving_metrics
    rows = st.registry_rows()
    steps = serving_metrics()["steps"].value(kind="unified")
    if not rows or steps <= 0:
        return None
    z = st.sizes(run)
    return sum(rows.values()) / (steps * z["layers"] * z["experts"])
