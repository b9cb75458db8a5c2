"""Model ``exaone_moe``: the EXAONE-MoE family (window and full
grouped-query attention layers with q/k norms, a leading dense layer, then
sigmoid-routed SwiGLU experts beside a shared expert, and a
multi-token-prediction module that drafts) run through the program's
``models/exaone_moe.py``. It serves only: there is no ``Trainer`` (no cut
of it fits one chip beside AdamW's state).

As ``models/mistral.py``, this module only names what exists:

- ``build_engine(cfg, seed, overrides=None)``: the served system
  (``sut_exaone_moe.py``)
- ``serve_logits(seed, cfg, tokens, rows, cols, mode=...)``: the plain
  float32 reference and its int8 control (``reference/exaone_moe.py``,
  over the seeded leaves of ``weights_exaone_moe.py``)
- ``forward_flops_per_token``, ``matmul_params``: the operations the model
  needs in the published form, from its sizes
  (``kernels/exaone_moe_model.py``)
"""
from benchmark.kernels.exaone_moe_model import (  # noqa: F401
    forward_flops_per_token, matmul_params)
from benchmark.reference.exaone_moe import serve_logits  # noqa: F401
from benchmark.sut_exaone_moe import build_engine  # noqa: F401
