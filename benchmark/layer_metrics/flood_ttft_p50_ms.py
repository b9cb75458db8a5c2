def read(run):
    """Median first-token time from the due time (recorded, not judged:
    above capacity the backlog grows by design)."""
    return run["e2e"]["ttft_p50_ms"]
