"""The ragged-paged-attention kernel
(``ops/pallas/ragged_paged_attention.py``): one call per layer per engine
step over a token-packed batch.

A row of the step is ``(new, context)``: ``new`` query tokens of one
sequence that already holds ``context`` keys. What the algorithm needs for
it: QK^T and PV over the keys each token may see (causal inside the new
tokens), and the sequence's K and V pages read once, q read and the output
written once. Dead grid steps, pages fetched once per q tile, and padding
rows are the implementation's and are not counted.
"""
#: the serving step's only Mosaic call; the trace names a custom call after
#: the jitted function it sits in (``step.N custom-call``, read off a trace
#: of PR 23) until the program gives the kernel a name of its own
TRACE_PATTERN = r"^(step|ragged_paged_attention|rpa)\S* custom-call"


def required(rows, heads, kv_heads, hd, itemsize=2):
    """``(flops, bytes)`` of one layer's call for the step's ``rows``."""
    flops = nbytes = 0.0
    for new, context in rows:
        seen = new * context + new * (new + 1) / 2.0
        flops += 4.0 * heads * hd * seen
        nbytes += 2.0 * kv_heads * hd * (context + new) * itemsize \
            + 2.0 * new * heads * hd * itemsize
    return flops, nbytes
