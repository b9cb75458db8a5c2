"""Model ``mistral``: a Mistral-shaped dense decoder (pre-norm blocks, RMSNorm,
grouped-query attention with rotary embedding, SwiGLU, untied head) run
through the program's ``models/llama.py``.

A model module is the one place that says what a model brings; the kinds,
``control.py`` and the tests' flows take all of it from here, found by the
configuration file's ``"model"`` key (``harness.model_of``). This one only
names what exists; nothing is defined here.

- ``build_engine(cfg, seed, overrides=None)``: the served system (``sut.py``)
- ``Trainer(cfg, optimizer, seed)``: the compiled step with its state; a
  model that serves only leaves it out
- ``serve_logits(seed, cfg, tokens, rows, cols, mode=...)`` and
  ``train_steps(seed, cfg, optimizer, batches, mode=..., weight_dtype=...)``:
  the plain reference and its lower-precision controls
  (``reference/mistral.py``, over the seeded leaves of ``weights.py``)
- ``forward_flops_per_token``, ``train_flops_per_token``, ``matmul_params``:
  the operations the model needs, from its sizes (``kernels/model.py``)
"""
from benchmark.kernels.model import (  # noqa: F401
    forward_flops_per_token, matmul_params, train_flops_per_token)
from benchmark.reference.mistral import serve_logits, train_steps  # noqa: F401
from benchmark.sut import Trainer, build_engine  # noqa: F401
