"""Model ``smallthinker``: the SmallThinker family (window and full NoPE
attention layers mixed, softmax-routed ReGLU experts whose router reads the
layer's input) run through the program's ``models/smallthinker.py``. It
serves only: there is no ``Trainer`` (ROADMAP M1: the gated dropless
experts' backward and flash attention under a window).

As ``models/mistral.py``, this module only names what exists:

- ``build_engine(cfg, seed, overrides=None)``: the served system
  (``sut_smallthinker.py``)
- ``serve_logits(seed, cfg, tokens, rows, cols, mode=...)``: the plain
  float32 reference and its int8 control (``reference/smallthinker.py``,
  over the seeded leaves of ``weights_smallthinker.py``)
- ``forward_flops_per_token``, ``matmul_params``: the operations the model
  needs in the published form, from its sizes
  (``kernels/smallthinker_model.py``)
"""
from benchmark.kernels.smallthinker_model import (  # noqa: F401
    forward_flops_per_token, matmul_params)
from benchmark.reference.smallthinker import serve_logits  # noqa: F401
from benchmark.sut_smallthinker import build_engine  # noqa: F401
