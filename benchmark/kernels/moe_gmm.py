"""The grouped products of a dropless expert layer (``HeldExpertsLayer``:
the step's (token, chosen expert) rows sorted by expert, each expert
multiplying exactly its rows by its gate, up and down matrices). Today
that is ``jax.lax.ragged_dot``, three calls a layer, ``ragged-dot-*.N
custom-call`` in a trace; the yardstick is the work, whatever implements it.

What the algorithm needs for a layer and step in which ``rows`` rows fell
on ``experts_hit`` experts: three products of ``d x f`` a row (2
operations a multiply-add); the three matrices of every expert that took a
row read once; the sorted rows read twice and written once ``d`` wide, and
the gated activation written and read once ``f`` wide. An expert without a
row is not read; sorting, gathering and combining are other ops'.
"""
#: the grouped product's device op in a trace; a later kernel of the
#: repo's own is to be named ``moe_gmm``
TRACE_PATTERN = r"^(ragged-dot|moe_gmm)\S* custom-call"


def required(rows, experts_hit, d, f, itemsize=2):
    """``(flops, bytes)`` of the three grouped products for ``rows``
    sorted rows over ``experts_hit`` (layer, expert) pairs that took one
    (both summed over the layers and steps in question)."""
    flops = 2.0 * 3.0 * d * f * rows
    nbytes = itemsize * (3.0 * d * f * experts_hit
                         + rows * (3.0 * d + 3.0 * f))
    return flops, nbytes
