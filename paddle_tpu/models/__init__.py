"""Model zoo — the BASELINE.md benchmark families, built on paddle_tpu.nn.

Reference capability surface: PaddleNLP/paddle model zoos (the reference repo
ships vision models under ``python/paddle/vision/models``; its LLM recipes
live in PaddleNLP). BASELINE.json names the concrete configs this framework
must run: Llama-3 8B/70B, ERNIE, DeepSeekMoE/Qwen2-MoE, DiT/SD-3, PP-OCRv4.

Every family here is TPU-first: attention routes through the Pallas flash
kernel, MoE uses the expert-parallel MoELayer, and each config exposes
``tensor_parallel=True`` construction that builds with the mpu sharded
layers so the same model code runs 1-chip or SPMD over a mesh.
"""
from . import llama  # noqa: F401
from . import ernie  # noqa: F401
from . import moe  # noqa: F401
from . import pangu_moe  # noqa: F401
from . import smallthinker  # noqa: F401
from . import exaone_moe  # noqa: F401
from . import dit  # noqa: F401
from . import ppocr  # noqa: F401
from .llama import LlamaConfig, LlamaModel, LlamaForCausalLM  # noqa: F401
from .ernie import ErnieConfig, ErnieModel, ErnieForSequenceClassification  # noqa: F401
from .moe import MoeConfig, MoeForCausalLM  # noqa: F401
from .pangu_moe import PanguMoeConfig, PanguMoeForCausalLM  # noqa: F401
from .smallthinker import SmallThinkerConfig, SmallThinkerForCausalLM  # noqa: F401
from .exaone_moe import ExaoneMoeConfig, ExaoneMoeForCausalLM  # noqa: F401
from .dit import DiTConfig, DiT  # noqa: F401
from .ppocr import PPOCRRecConfig, PPOCRRecModel  # noqa: F401

__all__ = [
    "llama", "ernie", "moe", "pangu_moe", "smallthinker", "exaone_moe", "dit",
    "ppocr",
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "ErnieConfig", "ErnieModel", "ErnieForSequenceClassification",
    "MoeConfig", "MoeForCausalLM", "PanguMoeConfig", "PanguMoeForCausalLM",
    "SmallThinkerConfig", "SmallThinkerForCausalLM",
    "ExaoneMoeConfig", "ExaoneMoeForCausalLM",
    "DiTConfig", "DiT",
    "PPOCRRecConfig", "PPOCRRecModel",
]
