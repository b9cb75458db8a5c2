"""Operations a ``phi4flash`` decoder needs, from its sizes alone, in the
published form: 2 operations a multiply-add, every projection a token passes
counted once. The self-decoder and the full layer (layers ``0 .. h + 1``)
run on every row; the cross-decoder (the GMUs, the cross layers' query and
output projections, their MLPs) and the tied head on the rows that choose a
token. A Mamba layer's recurrence is ``6 N D`` a row
(``kernels/selective_scan.py``) and its convolution ``2 x width`` a
channel. Differential attention is counted a (query, key) pair the mask
lets through, in its published form: ``QK^T`` at the half-head width for
each of the ``2H`` half-heads (``2 x 2H x hd``) and each softmax times the
pair head's values ``[v_1 | v_2]`` (``2 x 2H x 2 hd``). The zero halves of
an implementation's wide queries, padding rows and recomputed work are
never counted.
"""
from benchmark import weights_phi4flash as W


def matmul_params(cfg: dict) -> dict:
    """Weights that multiply a token, by part: a Mamba layer's
    projections, a window or full layer's, a cross layer's, a GMU's, a
    layer's MLP, and the head (the tied embedding)."""
    s, z = W.leaf_shapes(cfg), W.sizes(cfg)
    prod = lambda n: s[n][0] * s[n][1]   # noqa: E731
    return {"mamba": sum(prod(n) for n in ("in_proj", "x_proj", "dt_w",
                                           "out_proj")),
            "attention": prod("wqkv") + prod("wo"),
            "cross": prod("wq") + prod("wo"),
            "gmu": prod("gmu_in") + prod("gmu_out"),
            "mlp": prod("fc1") + prod("fc2"),
            "head": z["vocab"] * z["d"]}


def layer_kinds(cfg: dict) -> dict:
    """Layers of each kind in the depth the file holds."""
    kinds = W.sizes(cfg)["kinds"]
    return {k: kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                        "cross")}


def recurrence_flops(cfg: dict) -> float:
    """One token, one Mamba layer: the recurrence and the convolution."""
    z = W.sizes(cfg)
    return 6.0 * z["N"] * z["di"] + 2.0 * z["K"] * z["di"]


def self_row_flops(cfg: dict) -> float:
    """One row through layers ``0 .. h + 1`` (attention's pairs apart)."""
    m, n = matmul_params(cfg), layer_kinds(cfg)
    attn = n["window"] + n["full"]
    return 2.0 * (n["mamba"] * m["mamba"] + attn * m["attention"]
                  + (n["mamba"] + attn) * m["mlp"]) \
        + n["mamba"] * recurrence_flops(cfg)


def cross_row_flops(cfg: dict) -> float:
    """One row through the cross-decoder (its pairs apart), without the
    head."""
    m, n = matmul_params(cfg), layer_kinds(cfg)
    return 2.0 * (n["gmu"] * m["gmu"] + n["cross"] * m["cross"]
                  + (n["gmu"] + n["cross"]) * m["mlp"])


def pair_flops(cfg: dict) -> float:
    """One (query, key) pair, one differential attention layer."""
    z = W.sizes(cfg)
    return 2.0 * z["heads"] * (z["hd"] + 2 * z["hd"])


def key_bytes(cfg: dict, itemsize=2) -> float:
    """A key token's K and V in one layer's pages."""
    z = W.sizes(cfg)
    return 2.0 * z["kv"] * z["hd"] * itemsize


def row_bytes(cfg: dict, itemsize=2) -> float:
    """A query row's queries in and output out, one layer."""
    z = W.sizes(cfg)
    return 2.0 * z["heads"] * z["hd"] * itemsize


def visible_pairs(rows, window=None) -> float:
    """(query, key) pairs the mask lets through for the step rows ``(new,
    context)``: causal, or the last ``window`` keys of each query."""
    total = 0.0
    for n, ctx in rows:
        if window is None:
            total += n * ctx + n * (n + 1) / 2.0
        else:
            total += sum(min(ctx + i + 1, window) for i in range(n))
    return total


def attention_required(cfg: dict, rows, window=None, itemsize=2):
    """``(flops, bytes)`` of one self-decoder attention layer over the
    step's ``rows``: the pairs the mask lets through, the K/V rows any of
    a row's tokens can see read once, each query row's q and output
    once."""
    nbytes = 0.0
    for new, context in rows:
        keys = context + new if window is None \
            else min(context + new, window + new - 1)
        nbytes += keys * key_bytes(cfg, itemsize) \
            + new * row_bytes(cfg, itemsize)
    return pair_flops(cfg) * visible_pairs(rows, window), nbytes


def cross_required(cfg: dict, cross_rows, cross_keys, itemsize=2):
    """``(flops, bytes)`` of one cross layer: ``cross_rows`` query rows that
    see ``cross_keys`` keys in all, each key's K/V and each row's q and
    output read or written once."""
    return pair_flops(cfg) * cross_keys, \
        cross_keys * key_bytes(cfg, itemsize) \
        + cross_rows * row_bytes(cfg, itemsize)


def forward_flops_per_token(cfg: dict, context: float, head: bool = True):
    """Forward operations for one token that samples and attends
    ``context`` keys (a window layer at most ``window`` of them)."""
    n, z = layer_kinds(cfg), W.sizes(cfg)
    return self_row_flops(cfg) + cross_row_flops(cfg) + pair_flops(cfg) * (
        n["window"] * min(context, z["window"])
        + (n["full"] + n["cross"]) * context) \
        + (2.0 * matmul_params(cfg)["head"] if head else 0.0)
