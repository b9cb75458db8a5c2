def read(run):
    """Least time for the grouped products of the span's steps
    (``kernels/moe_gmm.py``: three products a row that fell on a held
    expert, the commit spans' ``moe_rows``, the drafter's block among them;
    the matrices of the (block, expert) pairs that took a row read once a
    step, ``moe_live``) over the device time of those products in the
    trace (``ragged-dot`` today)."""
    from benchmark.kernels import moe_gmm
    from benchmark.layer_metrics import _kexaone as kx
    steps = kx.drafting_steps(run) if run.get("traced") else None
    if not steps:
        return None
    rows = sum(s.get("moe_rows", 0) for s in steps)
    hit = sum(s.get("moe_live", 0) for s in steps)
    if rows <= 0 or hit <= 0:
        return None
    z = kx.sizes(run)
    flops, nbytes = moe_gmm.required(rows, hit, z["d"], z["moe_ffn"])
    return kx.kernel_roofline(run, moe_gmm.TRACE_PATTERN, flops, nbytes)
