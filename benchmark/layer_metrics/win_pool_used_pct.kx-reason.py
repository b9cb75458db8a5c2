def read(run):
    """Pages of the window layers' pool held by live sequences or parked in
    the prefix cache at the window's end, over the pool
    (``engine.stats()["kv_groups"]``)."""
    from benchmark.layer_metrics._kexaone import pool_used_pct
    return pool_used_pct(run, "window")
