from benchmark.layer_metrics._shared import rpa_roofline as read  # noqa: F401
