"""Model ``pangu_ultra_moe``: the openPangu-Ultra-MoE family (latent
attention, sandwich-norm blocks, sigmoid-routed gated experts of which the
configuration may hold a chip's share, one shared expert) run through the
program's ``models/pangu_moe.py``. It serves only: there is no ``Trainer``
(16 bytes a parameter fit no cut within the guide's floors).

As ``models/mistral.py``, this module only names what exists:

- ``build_engine(cfg, seed, overrides=None)``: the served system
  (``sut_pangu.py``)
- ``serve_logits(seed, cfg, tokens, rows, cols, mode=...)``: the plain
  float32 reference, expanded attention, and its int8 control
  (``reference/pangu_ultra_moe.py``, over the seeded leaves of
  ``weights_pangu.py``)
- ``forward_flops_per_token``, ``matmul_params``: the operations the model
  needs in the published form, from its sizes (``kernels/pangu_model.py``)
"""
from benchmark.kernels.pangu_model import (  # noqa: F401
    forward_flops_per_token, matmul_params)
from benchmark.reference.pangu_ultra_moe import serve_logits  # noqa: F401
from benchmark.sut_pangu import build_engine  # noqa: F401
