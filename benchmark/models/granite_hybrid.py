"""Model ``granite_hybrid``: the Granite 4.0-H family (Mamba-2 state-space
layers and full attention layers without position encoding, a SwiGLU MLP in
every layer, the head tied) run through the program's
``models/granite_hybrid.py``. It serves only: there is no ``Trainer`` (the
scan's backward pass is not written, ROADMAP M4).

As ``models/mistral.py``, this module only names what exists:

- ``build_engine(cfg, seed, overrides=None)``: the served system
  (``sut_granite_hybrid.py``)
- ``serve_logits(seed, cfg, tokens, rows, cols, mode=...)``: the plain
  float32 reference and its int8 control (``reference/granite_hybrid.py``,
  over the seeded leaves of ``weights_granite_hybrid.py``)
- ``forward_flops_per_token``, ``matmul_params``: the operations the model
  needs in the published form, from its sizes
  (``kernels/granite_hybrid_model.py``)
"""
from benchmark.kernels.granite_hybrid_model import (  # noqa: F401
    forward_flops_per_token, matmul_params)
from benchmark.reference.granite_hybrid import serve_logits  # noqa: F401
from benchmark.sut_granite_hybrid import build_engine  # noqa: F401
