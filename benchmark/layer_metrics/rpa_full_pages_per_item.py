"""How full the RPA kernel's work items are in the ``full`` layer group of a
cache of two groups (``serve-kexaone-reason``, ``serve-smallthinker-mixed``).
An item is a run of up to P consecutive pages of one sequence that a q tile
can see (P read off the pool's shape: 4 for a K/V pool of head width 128 in
bf16 pages of 128 since PR 34, 1 before), and the engine writes into a
step's ``serving.dispatch`` span, a group at a time, ``rpa_live_full``, the
items that name a real run, and ``rpa_pages_full``, the pages those runs
name (``serving/engine.py``). Pages over items is the runs' fill: P where
every run is full. A program that writes neither (a cache of one group;
the gather reader) leaves the metric out."""


def read(run):
    """Sum of ``rpa_pages_full`` over sum of ``rpa_live_full`` across the
    traced span's whole steps; None where no such step carries both."""
    from benchmark.layer_metrics._smallthinker import dispatch_sums
    sums = dispatch_sums(run, "rpa_pages_full", "rpa_live_full")
    if sums is None or sums[1] <= 0:
        return None
    return sums[0] / sums[1]
