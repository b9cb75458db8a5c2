"""The flash attention kernels (``ops/pallas/flash_attention.py``): forward
and the two backward kernels (dq; dk and dv), causal, grouped-query.

Required operations per (row, query head), in units of S*S*hd multiply-adds
at the causal half: forward 2 (QK^T, PV); backward 5 (one recomputation of
the scores, which the algorithm cannot avoid without storing them, dP, dV,
dQ, dK). The two backward kernels each recompute the scores, so they do 7;
the second recomputation is the implementation's, and is not counted.
Bytes: every operand read or written once (q, k, v, o, do, dq, dk, dv and
the row statistics).
"""
#: names under which the trace shows the kernels' device ops
#: (``jvp_jit__flash__.N custom-call`` forward, ``transpose_jvp_jit__flash___.N``
#: the two backward kernels; read off a trace of PR 23)
TRACE_PATTERN = r"^(transpose_)?jvp_jit__flash_\S* custom-call"


def required(batch, heads, kv_heads, seq, hd, itemsize=2):
    """``{"fwd": (flops, bytes), "bwd": (flops, bytes)}`` for one call of
    the attention of one layer on ``[batch, seq]`` tokens."""
    unit = 2.0 * batch * heads * seq * seq * hd * 0.5    # one causal matmul
    q = batch * heads * seq * hd * itemsize
    kv = batch * kv_heads * seq * hd * itemsize
    stats = batch * heads * seq * 4
    fwd = (2 * unit, q + 2 * kv + q + stats)
    # reads q, k, v, o, do, stats; writes dq, dk, dv
    bwd = (5 * unit, (3 * q + 2 * kv + stats) + (q + 2 * kv))
    return {"fwd": fwd, "bwd": bwd}


def least_seconds(flops, nbytes, peaks):
    """The roofline: the larger of operations over peak rate and bytes
    over peak bandwidth, and which of the two it is."""
    tc, tm = flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
