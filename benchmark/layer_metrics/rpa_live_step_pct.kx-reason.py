from benchmark.layer_metrics._rpa_steps import live_step_pct as read  # noqa: F401
