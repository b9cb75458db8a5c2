from benchmark.spans import train_enqueue_ms as read  # noqa: F401
