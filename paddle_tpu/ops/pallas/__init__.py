"""Hand-written Pallas TPU kernels — the analog of the reference's fused
kernel zoo (``paddle/phi/kernels/fusion``, ``operators/fused``; SURVEY.md
§2.10 item 6): flash attention and ragged paged attention. Everything
else rides XLA fusion by design (SURVEY.md §7)."""
from .flash_attention import flash_attention_bshd  # noqa: F401
from .ragged_paged_attention import ragged_paged_attention  # noqa: F401
