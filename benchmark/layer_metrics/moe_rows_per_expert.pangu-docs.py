def read(run):
    """Token rows a held expert takes in a step and layer: the registry's
    ``serving_moe_expert_rows_total`` over the run's steps, expert layers
    and held experts (whole run, warm-up included). A 16-chip deployment
    sends a held expert 16 times this at the same step size: how near the
    cell's expert load is to the deployment's."""
    from benchmark.layer_metrics import _pangu
    from paddle_tpu.serving.engine import serving_metrics
    rows = _pangu.registry_rows()
    steps = serving_metrics()["steps"].value(kind="unified")
    if not rows or steps <= 0:
        return None
    cfg = run["cfg"]
    return sum(rows.values()) / (steps * _pangu.expert_layers(cfg)
                                 * int(cfg["n_routed_experts"]))
