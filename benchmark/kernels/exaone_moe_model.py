"""Operations an ``exaone_moe`` decoder and its drafter need, from the sizes
alone, in the published form: 2 operations a multiply-add, every projection
a token passes counted once, the router and the shared expert once an
expert layer, the held experts by the rows that fell on them, attention as
QK^T and PV per head and per (query, key) pair the mask lets through
(every earlier key in a full layer, at most the window in a window layer).
The drafter is one block more (full attention, an expert layer) behind a
``2d x d`` projection, and the head a second time for the row whose guess
is kept. A draft's row is a row like any other: verifying it is what the
step was asked to do, whether the draft is accepted or not. Masked pairs a
kernel computes all the same, padding rows and recomputed work are never
counted.
"""
from benchmark import weights_exaone_moe as W
from benchmark.kernels.smallthinker_model import visible_pairs  # noqa: F401


def matmul_params(cfg: dict) -> dict:
    """Weights that multiply a token: a block's attention projections, the
    dense layer's SwiGLU, what every token passes of an expert layer (the
    router and the shared expert), one routed expert, the head and the
    drafter's projection."""
    n = W.n_params(cfg)
    return {"attention": n["attention"], "dense_mlp": n["dense_mlp"],
            "expert_fixed": n["router"] + n["shared"], "expert": n["expert"],
            "head": n["head"], "mtp_proj": n["mtp_proj"]}


def layer_kinds(cfg: dict, drafter: bool = True):
    """``(full blocks, window blocks)`` of the depth the file holds, the
    drafter's block among them."""
    z = W.sizes(cfg)
    windows = z["windows"] + (z["mtp_windows"][:z["mtp"]] if drafter else ())
    windowed = sum(1 for w in windows if w)
    return len(windows) - windowed, windowed


def window(cfg: dict) -> int:
    return max(W.sizes(cfg)["windows"])


def attention_flops_per_pair(cfg: dict) -> float:
    """One (query token, key token) pair, one block, all heads."""
    z = W.sizes(cfg)
    return 4.0 * z["heads"] * z["hd"]


def row_flops(cfg: dict, drafter: bool = True) -> float:
    """What every row of a step passes whatever it attends and wherever it
    is routed: the blocks' projections, the dense layer, the routers and
    shared experts, and (``drafter``) the drafter's projection and block."""
    z, m = W.sizes(cfg), matmul_params(cfg)
    blocks = z["layers"] + (z["mtp"] if drafter else 0)
    moe = blocks - z["dense"]
    return 2.0 * (blocks * m["attention"] + z["dense"] * m["dense_mlp"]
                  + moe * m["expert_fixed"]
                  + (m["mtp_proj"] if drafter else 0))


def forward_flops_per_token(cfg: dict, context: float, head: bool = True,
                            expert_rows: float = None, drafter: bool = True):
    """Forward operations for one row that attends ``context`` keys and
    that ``expert_rows`` of the held routed experts take in each expert
    layer (default: its expected share, ``top_k * held / experts``)."""
    z, m = W.sizes(cfg), matmul_params(cfg)
    if expert_rows is None:
        expert_rows = z["top_k"] * len(z["held"]) / z["experts"]
    full, windowed = layer_kinds(cfg, drafter)
    moe = full + windowed - z["dense"]
    flops = row_flops(cfg, drafter) + moe * 2.0 * expert_rows * m["expert"] \
        + attention_flops_per_pair(cfg) * (
            full * context + windowed * min(context, window(cfg)))
    heads = (1 + (z["mtp"] if drafter else 0)) if head else 0
    return flops + heads * 2.0 * m["head"]
