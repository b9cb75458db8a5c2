"""Operations a ``smallthinker`` decoder needs, from its sizes alone, in the
published form: 2 operations a multiply-add, every projection a token
passes counted once, the router once a layer, the experts by the rows that
fell on them (``top_k`` a token and layer), attention as QK^T and PV per
head and per (query, key) pair the mask lets through: every earlier key in
a full layer, at most ``sliding_window_size`` of them (the query's own
among them) in a window layer. Masked pairs a kernel computes all the same,
padding rows and recomputed work are never counted.
"""
from benchmark import weights_smallthinker as W


def matmul_params(cfg: dict) -> dict:
    """Weights that multiply a token: a layer's attention projections and
    router (every token), one expert, and the head."""
    n = W.n_params(cfg)
    return {"attention": n["attention"], "router": n["router"],
            "expert": n["expert"], "head": n["head"]}


def layer_kinds(cfg: dict):
    """``(full layers, window layers)`` of the depth the file holds."""
    z = W.sizes(cfg)
    windowed = sum(z["windowed"])
    return z["layers"] - windowed, windowed


def attention_flops_per_pair(cfg: dict) -> float:
    """One (query token, key token) pair, one layer, all heads."""
    z = W.sizes(cfg)
    return 4.0 * z["heads"] * z["hd"]


def visible_pairs(rows, window=None) -> float:
    """(query, key) pairs the mask lets through for the step rows
    ``(new, context)``: token ``t`` of a row sees ``context + t + 1`` keys,
    under a window at most ``window`` of them."""
    if window is None:
        return sum(n * ctx + n * (n + 1) / 2.0 for n, ctx in rows)
    pairs = 0.0
    for n, ctx in rows:
        # tokens whose whole past still fits the window, then the rest
        short = int(min(n, max(0, window - ctx)))
        pairs += short * ctx + short * (short + 1) / 2.0 + (n - short) * window
    return pairs


def forward_flops_per_token(cfg: dict, context: float, head: bool = True,
                            expert_rows: float = None):
    """Forward operations for one token that attends ``context`` keys (a
    window layer the last ``sliding_window_size`` of them) and that
    ``expert_rows`` experts take in each layer (default ``top_k``)."""
    z, m = W.sizes(cfg), matmul_params(cfg)
    full, windowed = layer_kinds(cfg)
    if expert_rows is None:
        expert_rows = z["top_k"]
    pair = attention_flops_per_pair(cfg)
    flops = z["layers"] * 2.0 * (m["attention"] + m["router"]
                                 + expert_rows * m["expert"]) \
        + pair * (full * context + windowed * min(context, z["window"]))
    return flops + (2.0 * m["head"] if head else 0.0)
