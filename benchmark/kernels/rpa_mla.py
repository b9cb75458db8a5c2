"""The ragged-paged-attention kernel over a latent pool (``rpa_mla``,
``ops/pallas/ragged_paged_attention.py`` with ``v_pool`` None): one call per
layer per engine step over a token-packed batch, the cache read
**absorbed**.

A row of the step is ``(new, context)``: ``new`` query tokens of one
sequence that already holds ``context`` latent rows. What the absorbed
algorithm needs for it: for every (query, key) pair the token may see
(causal inside the new tokens) and every head, a ``kd``-wide product for
the score and a ``vd``-wide one for the output (``kd`` the latent row,
``kv_lora_rank + qk_rope_head_dim``; ``vd`` its value columns,
``kv_lora_rank``); the sequence's latent pages read once (one pool: the
values are columns of the key page), the absorbed queries read and the
output written once. Dead grid steps, pages fetched once per q tile, the
padding rows of a tile and the up-projections around the kernel are the
implementation's or another op's and are not counted.
"""
#: the kernel's device op in a trace (``rpa_mla.N custom-call``)
TRACE_PATTERN = r"^rpa_mla\S* custom-call"


def required(rows, heads, kd, vd, itemsize=2):
    """``(flops, bytes)`` of one layer's call for the step's ``rows``."""
    flops = nbytes = 0.0
    for new, context in rows:
        seen = new * context + new * (new + 1) / 2.0
        flops += 2.0 * heads * (kd + vd) * seen
        nbytes += kd * (context + new) * itemsize \
            + new * heads * (kd + vd) * itemsize
    return flops, nbytes
