"""Operations a ``granite_hybrid`` decoder needs, from its sizes alone, in
the published form: 2 operations a multiply-add, every projection a token
passes counted once (a Mamba layer's in- and out-projections, an attention
layer's four, every layer's MLP), the recurrence's ``4 H P N`` a token and
Mamba layer (``kernels/ssd.py``), the depthwise convolution's ``2 x width``
a channel, attention as QK^T and PV per head and per (query, key) pair the
causal mask lets through, and the tied head once a token that chooses one.
Padding rows and recomputed work are never counted.
"""
from benchmark import weights_granite_hybrid as W


def matmul_params(cfg: dict) -> dict:
    """Weights that multiply a token: a Mamba layer's projections, an
    attention layer's, a layer's MLP, and the head (the tied embedding)."""
    s, z = W.leaf_shapes(cfg), W.sizes(cfg)
    prod = lambda n: s[n][0] * s[n][1]   # noqa: E731
    return {"mamba": prod("in_proj") + prod("out_proj"),
            "attention": sum(prod(n) for n in W.ATTENTION_LEAVES),
            "mlp": prod("mlp_in") + prod("mlp_out"),
            "head": z["vocab"] * z["d"]}


def layer_kinds(cfg: dict):
    """``(mamba layers, attention layers)`` of the depth the file holds."""
    z = W.sizes(cfg)
    mamba = sum(1 for k in z["kinds"] if k == "mamba")
    return mamba, z["layers"] - mamba


def recurrence_flops(cfg: dict) -> float:
    """One token, one Mamba layer: the recurrence and the convolution."""
    z = W.sizes(cfg)
    return 4.0 * z["mh"] * z["mp"] * z["N"] + 2.0 * z["K"] * z["conv"]


def row_flops(cfg: dict) -> float:
    """One token through every layer's projections, MLP and recurrence
    (attention's pairs and the head apart)."""
    m, (mamba, attn) = matmul_params(cfg), layer_kinds(cfg)
    return 2.0 * (mamba * m["mamba"] + attn * m["attention"]
                  + (mamba + attn) * m["mlp"]) \
        + mamba * recurrence_flops(cfg)


def attention_flops_per_pair(cfg: dict) -> float:
    """One (query token, key token) pair, one attention layer."""
    z = W.sizes(cfg)
    return 4.0 * z["heads"] * z["hd"]


def visible_pairs(rows) -> float:
    """(query, key) pairs the causal mask lets through for the step rows
    ``(new, context)``."""
    return sum(n * ctx + n * (n + 1) / 2.0 for n, ctx in rows)


def forward_flops_per_token(cfg: dict, context: float, head: bool = True):
    """Forward operations for one token that attends ``context`` keys."""
    _, attn = layer_kinds(cfg)
    return row_flops(cfg) \
        + attn * attention_flops_per_pair(cfg) * context \
        + (2.0 * matmul_params(cfg)["head"] if head else 0.0)
