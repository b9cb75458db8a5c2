"""Model ``phi4flash``: Phi-4-mini-flash, the SambaY decoder-hybrid-decoder
(Mamba-1 layers and window attention in a self-decoder, one full-attention
layer, a cross-decoder of gated memory units and cross-attention layers that
read the full layer's K/V; differential attention; LayerNorm; the head tied)
run through the program's ``models/phi4flash.py``. It serves only: there is
no ``Trainer`` (the scan's backward pass is not written, ROADMAP M4).

As ``models/mistral.py``, this module only names what exists:

- ``build_engine(cfg, seed, overrides=None)``: the served system
  (``sut_phi4flash.py``)
- ``serve_logits(seed, cfg, tokens, rows, cols, mode=...)``: the plain
  float32 reference and its int8 control (``reference/phi4flash.py``, over
  the seeded leaves of ``weights_phi4flash.py``)
- ``forward_flops_per_token``, ``matmul_params``: the operations the model
  needs in the published form, from its sizes
  (``kernels/phi4flash_model.py``)
"""
from benchmark.kernels.phi4flash_model import (  # noqa: F401
    forward_flops_per_token, matmul_params)
from benchmark.reference.phi4flash import serve_logits  # noqa: F401
from benchmark.sut_phi4flash import build_engine  # noqa: F401
