from benchmark.layer_metrics._shared import engine_step_ms as read  # noqa: F401
