"""The ragged-paged-attention kernel under a layer's attention window
(``rpa_win``: ``ops/pallas/ragged_paged_attention.py`` with ``window``):
one call per window layer per engine step over a token-packed batch.

A row of the step is ``(new, context)``: ``new`` query tokens of one
sequence that already holds ``context`` keys, of which a token sees the
last ``window`` (its own among them). What the algorithm needs for it: QK^T
and PV over the (query, key) pairs the mask lets through; the K and V rows
any of the row's tokens can see read once (the keys from ``context - window
+ 1`` on: at most ``window + new - 1``), q read and the output written
once. Dead grid steps, pages fetched once per q tile, masked keys in a
page, and padding rows are the implementation's and are not counted.
"""
from benchmark.kernels.smallthinker_model import visible_pairs

#: the kernel's device op in a trace (``rpa_win.N custom-call``)
TRACE_PATTERN = r"^rpa_win\S* custom-call"
#: the unwindowed K/V form beside it (``rpa.N custom-call``, and neither
#: ``rpa_win`` nor ``rpa_mla``): a model of both kinds of layer reads each
FULL_TRACE_PATTERN = r"^rpa(\.\d+)? custom-call"


def required(rows, heads, kv_heads, hd, window, itemsize=2):
    """``(flops, bytes)`` of one layer's call for the step's ``rows``."""
    flops = 4.0 * heads * hd * visible_pairs(rows, window)
    nbytes = 0.0
    for new, context in rows:
        keys = min(context + new, window + new - 1)
        nbytes += 2.0 * kv_heads * hd * keys * itemsize \
            + 2.0 * new * heads * hd * itemsize
    return flops, nbytes
