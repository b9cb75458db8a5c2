def read(run):
    """95th percentile over all gaps between successive tokens of the
    window's requests, at the client (recorded, not judged: above capacity
    the cell's end-to-end metric is the tokens served). The two tokens of
    an accepted draft arrive together; every other gap is a step."""
    return run["e2e"].get("itl_p95_ms")
