"""How full the RPA kernel's work items are in the ``window`` layer group
of a cache of two groups (``serve-kexaone-reason``, window one page;
``serve-smallthinker-mixed``, window 4,096 keys). Since PR 34 a window
walk is laid in runs from its first page, so a walk of at most P pages is
one item: a decode pair's two pages under a window of one page (P 2) read
2.0. ``serving.dispatch``'s ``rpa_pages_window`` over ``rpa_live_window``,
as ``rpa_full_pages_per_item`` reads its group's; nothing where a program
writes neither."""


def read(run):
    """Sum of ``rpa_pages_window`` over sum of ``rpa_live_window`` across
    the traced span's whole steps; None where no such step carries both."""
    from benchmark.layer_metrics._smallthinker import dispatch_sums
    sums = dispatch_sums(run, "rpa_pages_window", "rpa_live_window")
    if sums is None or sums[1] <= 0:
        return None
    return sums[0] / sums[1]
