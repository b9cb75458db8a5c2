from benchmark.layer_metrics._shared import serve_mfu_pct as read  # noqa: F401
