def read(run):
    """Least time for the grouped products of the span's steps
    (``kernels/moe_gmm.py``: three products a row that fell on an expert,
    the commit spans' ``moe_rows``; the matrices of the (layer, expert)
    pairs that took a row read once a step, ``moe_live``) over the device
    time of those products in the trace (``ragged-dot`` today)."""
    from benchmark.kernels import moe_gmm
    from benchmark.layer_metrics import _smallthinker as st
    if not run.get("traced"):
        return None
    steps = (st.span_steps(run) or {}).values()
    rows = sum(s["moe_rows"] for s in steps if "moe_rows" in s)
    hit = sum(s["moe_live"] for s in steps if "moe_live" in s)
    if rows <= 0 or hit <= 0:
        return None
    z = st.sizes(run)
    flops, nbytes = moe_gmm.required(rows, hit, z["d"], z["moe_ffn"])
    return st.kernel_roofline(run, moe_gmm.TRACE_PATTERN, flops, nbytes)
