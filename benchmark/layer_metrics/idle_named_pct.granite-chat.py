from benchmark.spans import idle_named_pct as read  # noqa: F401
