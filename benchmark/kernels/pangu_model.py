"""Operations a ``pangu_ultra_moe`` decoder needs, from its sizes alone, in
the **published (expanded) form**: 2 operations a multiply-add, every
projection a token passes counted once (its latent's up-projection through
``W_kvb`` once, when the token is processed, not once per query that later
attends it), attention as QK^T over ``nope + rope`` and PV over ``v`` per
head and per (query, key) pair. Recomputed or regrouped work (the absorbed
read multiplies 576- and 512-wide rows where the published form multiplies
192- and 128-wide ones) is never counted.
"""
from benchmark import weights_pangu as W


def matmul_params(cfg: dict) -> dict:
    """Weights that multiply a token: a layer's attention projections, a
    dense layer's SwiGLU, what every token passes of an expert layer (the
    router and the shared expert), one routed expert, and the head."""
    n = W.n_params(cfg)
    return {"attention": n["attention"], "dense_mlp": n["dense_mlp"],
            "expert_fixed": n["router"] + n["shared"], "expert": n["expert"],
            "head": n["head"]}


def attention_flops_per_pair(cfg: dict) -> float:
    """One (query token, key token) pair, one layer, all heads."""
    z = W.sizes(cfg)
    return 2.0 * z["heads"] * (z["nope"] + z["rope"] + z["v"])


def forward_flops_per_token(cfg: dict, context: float, head: bool = True,
                            expert_rows: float = None):
    """Forward operations for one token that attends ``context`` keys and
    that ``expert_rows`` of the held routed experts take in each expert
    layer (default: its expected share, ``top_k * held / experts``)."""
    z, m = W.sizes(cfg), matmul_params(cfg)
    if expert_rows is None:
        expert_rows = z["top_k"] * len(z["held"]) / z["experts"]
    moe_layers = z["layers"] - z["dense"]
    flops = z["layers"] * (2.0 * m["attention"]
                           + attention_flops_per_pair(cfg) * context) \
        + z["dense"] * 2.0 * m["dense_mlp"] \
        + moe_layers * 2.0 * (m["expert_fixed"] + expert_rows * m["expert"])
    return flops + (2.0 * m["head"] if head else 0.0)
