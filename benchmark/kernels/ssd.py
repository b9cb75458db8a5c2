"""The state update of a Mamba-2 layer over a serving step's rows
(``ops/pallas/ssd.py``): one call per state-space layer per engine step.

A row of the step is ``(new, context)``: ``new`` tokens of one sequence
whose state holds ``context`` tokens (0: it starts from zero). What the
update needs, whatever implements it: each live sequence's state read and
written once (the recurrent state ``H x P x N`` and the convolution's last
``(width - 1) x channels`` inputs), the rows' inputs read once (``x``,
``dt``, ``B``, ``C``) and ``y`` written once, in the model's dtype; and
the recurrence in its sequential form, ``4 H P N`` operations a row (the
decay and the outer product into ``h``, ``h C`` out). The chunked form's
extra products, rows of padding and a state row read for a sequence that
starts from zero are the implementation's and are not counted.
"""
#: the kernel's device op in a trace (``ssd.N custom-call``)
TRACE_PATTERN = r"^ssd(\.\d+)? custom-call"


def required(rows, heads, head_dim, state_dim, conv_width, conv_dim,
             state_itemsize=2, itemsize=2):
    """``(flops, bytes)`` of one layer's update for the step's ``rows``."""
    hpn = heads * head_dim * state_dim
    state = hpn + (conv_width - 1) * conv_dim
    per_row = 2 * heads * head_dim + heads + 2 * state_dim
    flops = nbytes = 0.0
    for new, _ in rows:
        flops += 4.0 * hpn * new
        nbytes += 2.0 * state * state_itemsize + new * per_row * itemsize
    return flops, nbytes
