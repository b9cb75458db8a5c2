"""chip_smoke.py's legs at ``LlamaConfig.tiny`` size on the CPU, and the
guards that keep a missing chip or a failing kernel from hiding (ISSUE 21).

A CPU run checks values and counts only; the script itself refuses to run
here (``main()`` exits non-zero on any backend but ``tpu``).
"""
import dataclasses
import os
import sys
import types

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

from paddle_tpu.models.llama import LlamaConfig  # noqa: E402

TINY = dataclasses.asdict(LlamaConfig.tiny(tie_word_embeddings=True,
                                           num_hidden_layers=1))
TINY_ENGINE = dict(max_batch=4, max_blocks=32, block_size=4,
                   prefill_chunk=8)


def test_trainer_leg_tiny():
    out = chip_smoke.trainer_leg(TINY, batch=2, seq=16, steps=5)
    assert len(out["losses"]) == 5 and out["losses"][-1] < out["losses"][0]
    assert out["mosaic"] == []  # the CPU compiles no Mosaic call


def test_kernel_check_tiny_interpret():
    """The Pallas kernel (interpret mode) against the gather reader, GQA
    and MHA, through the script's own batch builder."""
    for n_kv in (2, 4):
        err = chip_smoke.kernel_check(
            4, n_kv, 16, [(5, 9), (1, 3), (1, 17)], max_blocks_per_seq=8,
            dtype="float32", **TINY_ENGINE)
        assert err < 1e-5
    with pytest.raises(ValueError, match="exceeds"):
        chip_smoke.kernel_check(4, 2, 16, [(1, 400)], max_blocks_per_seq=8,
                                dtype="float32", **TINY_ENGINE)


def test_server_leg_tiny_interpret():
    """The HTTP leg end to end with the kernel reader in interpret mode,
    pinned through the engine's arguments (the chip run leaves the choice
    to the engine)."""
    _, health = chip_smoke.server_leg(
        TINY, dict(TINY_ENGINE, attn_impl="rpa"),
        [(6, False), (20, True), (24, True)],
        prefix_len=8, new_tokens=4, dtype="float32")
    assert health["step_compiles"] == 1 and health["kv_blocks_in_use"] == 0
    assert health["prefix_cache"]["hits"] >= 2


def test_drafted_leg_tiny():
    """The leg's own pass-or-fail at a tiny size: equal streams with and
    without drafts under the run loop, drafts verified."""
    drafts = chip_smoke.drafted_leg(
        dict(vocab_size=8, hidden_size=64, intermediate_size=96,
             num_hidden_layers=5, num_attention_heads=4,
             num_key_value_heads=1, head_dim=16, moe_intermediate_size=32,
             num_experts=8, num_experts_per_tok=2,
             max_position_embeddings=256),
        dict(max_batch=4, max_blocks={"window": 24, "full": 48},
             block_size=8, prefill_chunk=16), (5, 23, 40), 16,
        dtype="float32")
    assert drafts["drafted"] > 0 and drafts["draft_tokens"] == 1
    # (the family of expert rows is one a process: leave none behind)
    from paddle_tpu.serving.engine import serving_metrics
    serving_metrics()["moe_rows"].clear()


def test_main_refuses_a_cpu(monkeypatch, capsys):
    """No accelerator: non-zero exit code, the platform named, no result
    line, and no compile cache placed."""
    before = jax.config.jax_compilation_cache_dir
    assert chip_smoke.main() != 0
    io = capsys.readouterr()
    assert "platform='cpu'" in io.err and "platform=cpu" in io.out
    assert '"ok"' not in io.out
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_placed_from_outside(monkeypatch):
    from paddle_tpu.device import use_compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert use_compile_cache() == "/some/dir"
    assert updates == []  # the machine placed it: config untouched
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    here = os.path.join(ROOT, ".jax_cache")
    assert use_compile_cache() == here
    assert updates == [("jax_compilation_cache_dir", here)]


def test_peak_flops_refuses_an_unknown_tpu():
    from paddle_tpu.observability.step_timer import peak_flops
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert peak_flops(v5e) == 197e12
    assert peak_flops(jax.devices()[0]) == 0.0  # CPU: MFU not meaningful
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops(types.SimpleNamespace(platform="tpu",
                                         device_kind="TPU v9"))


def test_sdpa_kernel_error_raises_on_tpu(monkeypatch):
    """On a TPU backend a failing flash kernel must raise — never return
    the S x S composite (the blanket except that did is gone)."""
    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    import paddle_tpu.ops.pallas.flash_attention as fa

    def boom(*a, **kw):
        raise RuntimeError("mosaic says no")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "flash_attention_bshd", boom)
    q = pt.to_tensor(np.ones((1, 128, 2, 16), np.float32))
    with pytest.raises(RuntimeError, match="mosaic says no"):
        F.scaled_dot_product_attention(q, q, q, is_causal=True)
    monkeypatch.undo()
    assert F.scaled_dot_product_attention(q, q, q, is_causal=True).shape \
        == [1, 128, 2, 16]


def test_launcher_refuses_several_trainers_on_a_tpu_host(monkeypatch,
                                                         tmp_path):
    """A chip belongs to one process: on a TPU host the launcher refuses
    ``nproc_per_node > 1`` before it spawns anything (nothing partitions
    the chips between children; the second would hang on a held chip)."""
    from paddle_tpu.distributed.launch import launch
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="belongs to one process"):
        launch(str(tmp_path / "never_run.py"), nproc_per_node=2)
