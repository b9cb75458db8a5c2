"""Operations a Mistral-shaped decoder needs, from its sizes alone.

The arithmetic of ``bench.py``'s ``bench_full_model`` (2 operations a
multiply-add, causal attention at half the square), kept here so that no
later PR can change the yardstick. Recomputed work is never counted.
"""
from benchmark import weights as W


def matmul_params(cfg: dict) -> dict:
    """Weights that multiply every token: per layer, and the head."""
    n = W.n_params(cfg)
    return {"layer": n["attention"] + n["mlp"], "head": n["head"]}


def forward_flops_per_token(cfg: dict, context: float, head: bool = True):
    """Forward operations for one token that attends ``context`` keys:
    2 per weight, plus QK^T and PV (4 * heads * head_size per key)."""
    z, m = W.sizes(cfg), matmul_params(cfg)
    attn = 4.0 * z["heads"] * z["hd"] * context
    return z["layers"] * (2.0 * m["layer"] + attn) + (2.0 * m["head"]
                                                      if head else 0.0)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) for a token of a causal row of
    ``seq_len``: the mean token attends (seq_len + 1) / 2 keys."""
    return 3.0 * forward_flops_per_token(cfg, (seq_len + 1) / 2.0)
