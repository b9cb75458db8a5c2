def read(run):
    """Pages the window group's work lists name over the pages causal lists
    would name for the same steps (``serving.dispatch``:
    ``rpa_pages_window`` over ``rpa_pages_causal_window``, the traced span's
    whole steps): what is left of the walk under a window of one page."""
    from benchmark.layer_metrics._kexaone import dispatch_sums
    sums = dispatch_sums(run, "rpa_pages_window", "rpa_pages_causal_window")
    if sums is None or sums[1] <= 0:
        return None
    return 100.0 * sums[0] / sums[1]
