def read(run):
    """Pages of the latent pool held by live sequences or parked in the
    prefix cache at the window's end, over the pool (the engine's
    ``stats()``: blocks in use and reclaimable over all blocks)."""
    st = run.get("stats") or {}
    total = sum(st.get(k, 0) for k in ("kv_blocks_in_use", "kv_blocks_free",
                                       "kv_blocks_reclaimable"))
    if total <= 0 or "kv_blocks_free" not in st:
        return None
    return 100.0 * (total - st["kv_blocks_free"]) / total
