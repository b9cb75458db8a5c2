def read(run):
    """Pages the window group's work lists name over the pages causal lists
    would name for the same steps (``serving.dispatch``:
    ``rpa_pages_<group>`` over ``rpa_pages_causal_<group>``, the traced
    span's whole steps): what is left of the walk under the window."""
    from benchmark.layer_metrics import _smallthinker as st
    sums = st.dispatch_sums(run, "rpa_pages_window",
                            "rpa_pages_causal_window")
    if sums is None or sums[1] <= 0:
        return None
    return 100.0 * sums[0] / sums[1]
