def read(run):
    """Token rows a held expert takes in a step and block: the registry's
    ``serving_moe_expert_rows_total`` over the run's steps, its expert
    layers with the drafter's block, and the held experts (whole run,
    warm-up included). A chip of the deployment sees eight times these."""
    from benchmark.layer_metrics import _kexaone as kx
    from paddle_tpu.serving.engine import serving_metrics
    rows = kx.registry_rows()
    steps = serving_metrics()["steps"].value(kind="unified")
    if not rows or steps <= 0 or not kx.drafts(run):
        return None
    z = kx.sizes(run)
    blocks = z["layers"] - z["dense"] + z["mtp"]
    return sum(rows.values()) / (steps * blocks * len(z["held"]))
