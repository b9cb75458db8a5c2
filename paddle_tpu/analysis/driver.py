"""Committed audit geometries — ONE definition for CLI, bench and tests.

The audit's regression value comes from pinning numbers on a *fixed*
program; these builders are that fixture. Three programs cover the
contracts:

- :func:`dp8_bucketed_step`: the bucketed-dp ``TrainStep`` whose HLO
  must carry exactly ``buckets + 1`` all-reduces (needs an 8-device
  mesh — virtual on CPU, real on chip).
- :func:`tiny_llama_step`: a single-device causal-LM train step — the
  donation-coverage and giant-intermediate ([B, seq, vocab] logits)
  subject.
- :func:`tiny_serving_engine`: the unified serving step behind
  ``ServingEngine.compiled_hlo()``.

Everything is sized for the 1-CPU smoke box (a few seconds per
compile); ``bench.py --audit`` swaps in the committed bench geometry on
a real TPU.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

__all__ = ["ensure_cpu_mesh", "dp8_bucketed_step", "tiny_llama_step",
           "tiny_serving_engine", "run_default_audit", "run_commplan",
           "COMMPLAN_GEOMETRIES"]


def ensure_cpu_mesh(devices: int = 8) -> bool:
    """Arm an N-virtual-device CPU platform when no TPU is selected
    (same discipline as tests/conftest.py / BENCH_FORCE_CPU: the env
    must be set before the jax backend initializes). Returns whether
    the CPU override was applied."""
    env = os.environ
    from paddle_tpu.device import tpu_selected
    if tpu_selected(env):
        return False
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    return True


def dp8_bucketed_step(dp: Optional[int] = None, seed_typo: bool = False):
    """(step, (x, y)) — pure-dp ``DataParallel`` MLP with the bucketed
    collective path active (the PR 7 HLO-contract geometry).

    ``seed_typo`` plants the accidental-all-gather defect the commplan
    auditor exists to catch: one bias declared sharded over ``dp`` (a
    one-token sharding-spec mistake), which forces GSPMD to all-gather
    that parameter every step. Used by ``commplan --seed-typo`` and the
    regression tests — never by a real audit."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu import nn

    if dp is None:
        import jax
        dp = jax.device_count()
    mesh = dist.init_mesh({"dp": dp})
    pt.seed(3)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    if seed_typo:
        from paddle_tpu.distributed import P
        net[0].bias._sharding_spec = P("dp")
    m = dist.DataParallel(net, mesh=mesh)
    o = pt.optimizer.AdamW(learning_rate=0.01, parameters=m.parameters())

    def loss_fn(model, x, y):
        return ((model(x) - y) ** 2).mean()

    step = pt.jit.TrainStep(m, loss_fn, o)
    rng = np.random.RandomState(0)
    X = rng.randn(8 * dp, 16).astype(np.float32)
    Y = X @ rng.randn(16, 4).astype(np.float32)
    return step, (pt.to_tensor(X), pt.to_tensor(Y))


def _tiny_llama(bf16: bool = False, cfg=None):
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if cfg is None:
        cfg = LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=448,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=512,
            tie_word_embeddings=True)
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    if bf16:
        model.bfloat16()
    return model, cfg


def tiny_llama_step(bf16: bool = False, donate: bool = True,
                    batch: Tuple[int, int] = (2, 64), cfg=None):
    """(step, (tokens,)) — single-device causal-LM ``TrainStep``, by
    default on the CPU-smoke geometry (the donation /
    giant-intermediate subject); ``bench.py --audit`` passes the
    committed bench config on chip."""
    import numpy as np

    import paddle_tpu as pt

    model, cfg = _tiny_llama(bf16, cfg)
    opt = pt.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        multi_precision=bf16,
        grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
    step = pt.jit.TrainStep(model, lambda m, t: m(t, labels=t)[1], opt,
                            donate=donate)
    B, S = batch
    rng = np.random.RandomState(0)
    x = pt.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                     .astype(np.int64))
    return step, (x,)


def tiny_serving_engine(attn_impl: Optional[str] = None):
    """A small real ``ServingEngine`` (gather path off-TPU) for the
    serving-step audit."""
    from paddle_tpu.serving import ServingEngine

    model, _ = _tiny_llama()
    return ServingEngine(model, max_batch=2, max_blocks=16, block_size=4,
                         prefill_chunk=8, attn_impl=attn_impl)


def run_default_audit(include_serving: bool = True,
                      dp: Optional[int] = None, bf16: bool = False,
                      batch: Tuple[int, int] = (2, 64),
                      llama_cfg=None) -> dict:
    """The full committed-geometry audit: every report's summary plus
    the three headline numbers ``bench.py --audit`` emits. ``dp`` None
    = all local devices (dp census skipped when fewer than 2); the
    llama kwargs let the bench swap in the committed chip geometry."""
    import jax

    from .audit import audit_serving_engine, audit_train_step

    out = {"reports": [], "findings": []}
    n_dev = jax.device_count()
    if dp is None:
        dp = n_dev if n_dev >= 2 else 0

    if dp >= 2:
        step, dp_batch = dp8_bucketed_step(dp)
        rep = audit_train_step(step, *dp_batch,
                               label=f"train_step[dp{dp}]")
        assert step._comm_buckets is not None, (
            "bucketed path ineligible on the committed geometry: "
            f"{step._bucketed_reason}")
        out["reports"].append(rep.summary())
        out["findings"].extend(rep.findings)
        out["train_step_allreduce_count"] = rep.all_reduce_count
        out["expected_allreduce_count"] = len(step._comm_buckets) + 1
    else:
        out["train_step_allreduce_count"] = None

    step, batch = tiny_llama_step(bf16=bf16, batch=batch, cfg=llama_cfg)
    rep = audit_train_step(step, *batch)
    out["reports"].append(rep.summary())
    out["findings"].extend(rep.findings)
    out["train_step_undonated_bytes"] = rep.undonated_bytes
    out["train_step_donation_coverage"] = round(rep.donation_coverage, 4)
    out["train_step_largest_intermediate_bytes"] = \
        rep.largest_intermediate_bytes
    # runtime-truth counterpart from XLA's buffer assignment
    # (observability.memory.MemoryReport; rides the same cached
    # executable, so no extra trace)
    mr = step.memory_report(*batch)
    out["train_step_peak_hbm_bytes"] = \
        None if mr is None else mr.total_bytes

    if include_serving:
        engine = tiny_serving_engine()
        rep = audit_serving_engine(engine)
        out["reports"].append(rep.summary())
        out["findings"].extend(rep.findings)
    return out


# -- commplan geometries ----------------------------------------------------
#
# One tiny committed program per MULTICHIP parallelism segment, lowered
# through the same RNG-neutral ``compiled_hlo`` seam the audits use.
# The per-axis comm ledgers these produce are pinned in baseline.json —
# the budget-drift gate compares every run against them.

def _lower_train_step(step, *args):
    """(hlo_text, leaf_names) via the RNG-neutral ``_prepare`` seam —
    leaf names aligned to entry-parameter numbers so the
    implicit-reshard pass can name the gathered leaf."""
    from paddle_tpu.core import generator as _gen

    from .audit import TRAIN_STEP_ARGS, _align_params, _leaf_names
    from .hlo import parse_entry_params

    rng_state = _gen.get_rng_state()
    try:
        _, compiled, call_args = step._prepare(args, {})
        lowered = compiled.lower(*call_args)
        hlo_text = lowered.compile().as_text()
        args_info = lowered.args_info
    finally:
        _gen.set_rng_state(rng_state)
    leaves = _leaf_names(args_info, TRAIN_STEP_ARGS)
    aligned = _align_params(parse_entry_params(hlo_text), leaves)
    return hlo_text, [name for name, *_ in aligned]


def _geo_dp8(seed_typo: bool = False):
    step, (x, y) = dp8_bucketed_step(seed_typo=seed_typo)
    hlo, names = _lower_train_step(step, x, y)
    import paddle_tpu.distributed as dist
    return {"hlo": hlo, "mesh": dist.get_mesh(), "leaf_names": names,
            "gather_ok": False}


def _geo_dpxmp():
    """Data x tensor parallel: the zoo Llama with Megatron-style mpu
    layers over {dp: 4, mp: 2} (graft-entry segment (a))."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    mesh = dist.init_mesh({"dp": 4, "mp": 2})
    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=True))
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, toks):
        _, loss = m(toks, labels=toks)
        return loss

    step = pt.jit.TrainStep(model, loss_fn, o, mesh=mesh,
                            input_spec=P("dp"))
    rng = np.random.RandomState(0)
    toks = pt.to_tensor(rng.randint(0, 256, (8, 8)).astype(np.int32))
    hlo, names = _lower_train_step(step, toks)
    return {"hlo": hlo, "mesh": mesh, "leaf_names": names,
            "gather_ok": False}


def _pp_train_step(mesh):
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed.fleet as fleet
    import paddle_tpu.optimizer as opt
    from paddle_tpu import nn

    pt.seed(4)
    layer = fleet.SpmdPipelineLayer(
        lambda: nn.Sequential(nn.Linear(8, 8), nn.Tanh()),
        num_virtual_stages=2, mesh=mesh)
    mse = nn.MSELoss()

    def loss_fn(m, xs, ys):
        out = m(xs)
        return mse(pt.reshape(out, [-1, 8]), pt.reshape(ys, [-1, 8]))

    o = opt.AdamW(learning_rate=1e-3, parameters=layer.parameters())
    rng = np.random.RandomState(0)
    return layer, loss_fn, o, rng


def _geo_pp():
    """SPMD pipeline over a pure {pp: 8} mesh — stage hops are compiled
    ppermutes (collective-permute in the ledger)."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import P

    mesh = dist.init_mesh({"pp": 8})
    layer, loss_fn, o, rng = _pp_train_step(mesh)
    step = pt.jit.TrainStep(layer, loss_fn, o, mesh=mesh, input_spec=P())
    X = pt.to_tensor(rng.randn(8, 2, 8).astype(np.float32))
    Y = pt.to_tensor(rng.randn(8, 2, 8).astype(np.float32))
    hlo, names = _lower_train_step(step, X, Y)
    return {"hlo": hlo, "mesh": mesh, "leaf_names": names,
            "gather_ok": False}


def _geo_dpxpp():
    """Data x pipeline over {dp: 2, pp: 4} — the partial-manual
    shard_map geometry. On jax builds whose shard_map cannot mix a
    manual pp axis with an auto dp axis this raises and the runner
    records the geometry as skipped (capability-gated, not silently
    dropped)."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import P

    mesh = dist.init_mesh({"dp": 2, "pp": 4})
    layer, loss_fn, o, rng = _pp_train_step(mesh)
    step = pt.jit.TrainStep(layer, loss_fn, o, mesh=mesh,
                            input_spec=P(None, "dp"))
    X = pt.to_tensor(rng.randn(4, 4, 8).astype(np.float32))
    Y = pt.to_tensor(rng.randn(4, 4, 8).astype(np.float32))
    hlo, names = _lower_train_step(step, X, Y)
    return {"hlo": hlo, "mesh": mesh, "leaf_names": names,
            "gather_ok": False}


def _geo_zero():
    """ZeRO stage-3 (p_g_os) over {sharding: 8}. ``gather_ok``: the
    whole POINT of ZeRO is re-gathering sharded params every step, so
    the implicit-reshard pass must stay quiet here."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu import nn
    from paddle_tpu.distributed import P

    mesh = dist.init_mesh({"sharding": 8})
    pt.seed(5)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 16))
    o = opt.AdamW(learning_rate=1e-3, parameters=net.parameters())
    m, o, _ = dist.group_sharded_parallel(net, o, level="p_g_os")

    def loss_fn(model, x, y):
        return ((model(x) - y) ** 2).mean()

    step = pt.jit.TrainStep(m, loss_fn, o, mesh=mesh,
                            input_spec=P("sharding"))
    rng = np.random.RandomState(0)
    X = pt.to_tensor(rng.randn(16, 16).astype(np.float32))
    Y = pt.to_tensor(rng.randn(16, 16).astype(np.float32))
    hlo, names = _lower_train_step(step, X, Y)
    return {"hlo": hlo, "mesh": mesh, "leaf_names": names,
            "gather_ok": True}


def _geo_sp():
    """Sequence-parallel ring attention over {sp: 8} — a pure
    collective-permute ring (no TrainStep; the kernel is a function, so
    the lowering goes through a plain jax.jit)."""
    import jax
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    import paddle_tpu.distributed.fleet as fleet

    mesh = dist.init_mesh({"sp": 8})

    def fn(q, k, v):
        out = fleet.ring_attention(pt.to_tensor(q), pt.to_tensor(k),
                                   pt.to_tensor(v), mesh=mesh, axis="sp",
                                   causal=True)
        return out.data

    rng = np.random.RandomState(0)
    args = [rng.randn(2, 32, 2, 8).astype(np.float32) for _ in range(3)]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return {"hlo": hlo, "mesh": mesh, "leaf_names": None,
            "gather_ok": False}


def _geo_ep():
    """Expert-parallel MoE (GShard gate) over {ep: 8} — token dispatch
    is the all-to-all pair. Activations legitimately reshard around the
    expert boundary; parameters must not, so gather_ok stays False."""
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    import paddle_tpu.distributed.fleet as fleet
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import P

    mesh = dist.init_mesh({"ep": 8})
    pt.seed(6)
    moe = fleet.MoELayer(16, 32, num_experts=8, gate="gshard",
                         mesh=mesh, axis="ep")
    o = opt.AdamW(learning_rate=1e-3, parameters=moe.parameters())

    def loss_fn(model, x, y):
        out = model(x)
        return ((out - y) ** 2).mean() + 0.01 * model.l_aux

    step = pt.jit.TrainStep(moe, loss_fn, o, mesh=mesh, input_spec=P("ep"))
    rng = np.random.RandomState(0)
    X = pt.to_tensor(rng.randn(8, 4, 16).astype(np.float32))
    Y = pt.to_tensor(rng.randn(8, 4, 16).astype(np.float32))
    hlo, names = _lower_train_step(step, X, Y)
    return {"hlo": hlo, "mesh": mesh, "leaf_names": names,
            "gather_ok": False}


def _geo_serving():
    """The unified serving step (single device off-TPU — an empty
    ledger is itself the pinned fact: serving must not grow collectives
    without review)."""
    engine = tiny_serving_engine()
    lowered = engine._lowered_step()
    return {"hlo": lowered.compile().as_text(), "mesh": None,
            "leaf_names": None, "gather_ok": False}


def _geo_serving_mp2():
    """The tensor-parallel unified serving step (ISSUE 15): the tiny
    zoo Llama built with mpu layers, engine ``mesh=`` over {mp: 2} —
    weights and KV pools sharded, ONE step executable. The pinned
    per-axis ledger is the reshard-storm tripwire: a sharding
    annotation regression in the serving path shows up as new mp-axis
    collectives and fails the budget gate."""
    import jax

    import paddle_tpu.distributed as dist
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving import ServingEngine

    if jax.device_count() < 2:
        raise RuntimeError("needs >= 2 devices for the mp=2 mesh")
    mesh = dist.init_mesh({"mp": 2}, devices=jax.devices()[:2])
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=448,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=512,
        tie_word_embeddings=True, tensor_parallel=True)
    model, _ = _tiny_llama(cfg=cfg)
    engine = ServingEngine(model, max_batch=2, max_blocks=16,
                           block_size=4, prefill_chunk=8,
                           attn_impl="gather", mesh=mesh)
    lowered = engine._lowered_step()
    return {"hlo": lowered.compile().as_text(), "mesh": mesh,
            "leaf_names": None, "gather_ok": False}


def _geo_serving_mp2_int8():
    """The quantized tensor-parallel unified serving step (ISSUE 20):
    same tiny mpu Llama and {mp: 2} mesh as ``serving_mp2`` but with
    ``quantize="int8_wo"`` — int8 weight values + f32 scales sharded in
    place of the bf16 leaves, dequantized inside the trace. The pinned
    fact: dequant is LOCAL, so the mp-axis comm bytes must NOT grow
    over the bf16 geometry's ledger."""
    import jax

    import paddle_tpu.distributed as dist
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving import ServingEngine

    if jax.device_count() < 2:
        raise RuntimeError("needs >= 2 devices for the mp=2 mesh")
    mesh = dist.init_mesh({"mp": 2}, devices=jax.devices()[:2])
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=448,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=512,
        tie_word_embeddings=True, tensor_parallel=True)
    model, _ = _tiny_llama(cfg=cfg)
    engine = ServingEngine(model, max_batch=2, max_blocks=16,
                           block_size=4, prefill_chunk=8,
                           attn_impl="gather", mesh=mesh,
                           quantize="int8_wo")
    lowered = engine._lowered_step()
    return {"hlo": lowered.compile().as_text(), "mesh": mesh,
            "leaf_names": None, "gather_ok": False}


#: label -> builder; labels are baseline keys — NEVER rename casually
#: (a rename orphans the pinned ledger and reports everything as new)
COMMPLAN_GEOMETRIES = (
    ("dp8", _geo_dp8),
    ("dpxmp", _geo_dpxmp),
    ("pp", _geo_pp),
    ("dpxpp", _geo_dpxpp),
    ("zero", _geo_zero),
    ("sp", _geo_sp),
    ("ep", _geo_ep),
    ("serving", _geo_serving),
    ("serving_mp2", _geo_serving_mp2),
    ("serving_mp2_int8", _geo_serving_mp2_int8),
)


def run_commplan(seed_typo: bool = False, only=None) -> dict:
    """Lower every committed geometry and run the comm-plan audit.

    Returns ``{"reports": {label: summary}, "ledgers": {label: ledger},
    "findings": [...], "skipped": {label: reason}}``. A geometry whose
    *construction* is unsupported on the running jax (the partial-manual
    dp x pp shard_map) lands in ``skipped`` with the error string —
    visible, not silently absent. ``seed_typo`` swaps in the defective
    dp8 variant (the accidental-all-gather regression fixture)."""
    import paddle_tpu.distributed as dist

    from .commplan import audit_comm

    prev_mesh = dist.get_mesh()
    out = {"reports": {}, "ledgers": {}, "findings": [], "skipped": {}}
    try:
        for label, build in COMMPLAN_GEOMETRIES:
            if only and label not in only:
                continue
            try:
                geo = build(seed_typo=True) if (
                    seed_typo and label == "dp8") else build()
            except Exception as e:  # capability gate, not error-hiding:
                # the skip reason is part of the report and the tests
                # assert the supported set
                out["skipped"][label] = f"{type(e).__name__}: {e}"
                continue
            rep = audit_comm(geo["hlo"], label, mesh=geo["mesh"],
                             leaf_names=geo["leaf_names"],
                             gather_ok=geo["gather_ok"])
            out["reports"][label] = rep.summary()
            out["ledgers"][label] = rep.ledger
            out["findings"].extend(rep.findings)
    finally:
        dist.set_mesh(prev_mesh)
    return out
