from benchmark.spans import step_host_ms as read  # noqa: F401
