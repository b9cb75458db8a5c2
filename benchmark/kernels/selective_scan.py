"""The selective scan of a Mamba-1 layer over a serving step's rows
(``ops/pallas/selective_scan.py``): one call per state-space layer per
engine step.

A row of the step is ``(new, context)``: ``new`` tokens of one sequence
whose state holds ``context`` tokens (0: it starts from zero). What the
scan needs, whatever implements it: each live sequence's state read and
written once (the recurrent state ``N x D`` and the convolution's last
``(width - 1) x D`` inputs), the rows' inputs read once (``x``, ``dt``,
``B``, ``C``, ``z``) and ``y`` written once, in the model's dtype; and the
recurrence in its sequential form, ``6 N D`` operations a row (the decay's
product and exponential, the decay times ``h``, the outer product and its
sum into ``h``, ``h C`` out). Rows of padding and a state row read for a
sequence that starts from zero are the implementation's and are not
counted.
"""
#: the kernel's device op in a trace (``selective_scan.N custom-call``)
TRACE_PATTERN = r"^selective_scan(\.\d+)? custom-call"


def required(rows, channels, state_dim, conv_width, state_itemsize=2,
             itemsize=2):
    """``(flops, bytes)`` of one layer's scan for the step's ``rows``."""
    state = state_dim * channels + (conv_width - 1) * channels
    per_row = 4 * channels + 2 * state_dim
    flops = nbytes = 0.0
    for new, _ in rows:
        flops += 6.0 * state_dim * channels * new
        nbytes += 2.0 * state * state_itemsize + new * per_row * itemsize
    return flops, nbytes
