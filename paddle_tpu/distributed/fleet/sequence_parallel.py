"""Sequence/context parallelism + ring attention.

Greenfield capability (SURVEY.md §5: the reference snapshot has NO sequence
parallelism — no ring attention, no Ulysses; SURVEY.md §7 directs designing
it GSPMD-natively for the Llama long-context north star).

Design: activations shard the *sequence* dim on the ``sp`` mesh axis. For
attention — the one op that mixes sequence positions — K/V shards rotate
around the ring with ``lax.ppermute`` (one ICI hop per step) while each
rank's resident Q block folds the incoming block into an online-softmax
accumulator. Peak memory per rank is O((S/n)^2) scores and the K/V transfer
overlaps the block matmuls (async ICI DMA), which is exactly the RingAttention
schedule. Causal masking skips rotations that are entirely in the future.

``ulysses_attention`` offers the all-to-all alternative (head-scatter):
re-shard [B, S/n, H, D] -> [B, S, H/n, D], run any attention (the Pallas
flash kernel on chip), and shard back — two all-to-alls on ICI.
"""
from __future__ import annotations

import math
from typing import Optional

from jax.sharding import PartitionSpec as P

from paddle_tpu.core.autograd import apply_op
from paddle_tpu.core.tensor import Tensor
from ..mesh import get_mesh
from ..sharding_api import with_sharding_constraint

__all__ = ["ring_attention", "ulysses_attention", "scatter_sequence",
           "gather_sequence"]


def scatter_sequence(x: Tensor, mesh=None, axis: str = "sp",
                     seq_dim: int = 1) -> Tensor:
    """Annotate the sequence dim sharded on the sp axis."""
    mesh = mesh or get_mesh()
    spec = [P.UNCONSTRAINED] * x.ndim
    spec[seq_dim] = axis
    return with_sharding_constraint(x, P(*spec), mesh)


def gather_sequence(x: Tensor, mesh=None, seq_dim: int = 1) -> Tensor:
    """Constrain the sequence dim replicated (an all-gather over sp)."""
    mesh = mesh or get_mesh()
    spec = [P.UNCONSTRAINED] * x.ndim
    spec[seq_dim] = None
    return with_sharding_constraint(x, P(*spec), mesh)


def _ring_attention_arrays(q, k, v, mesh, axis, causal, sm_scale):
    """Pure-array ring attention over a seq-sharded [B, S, H, D] triple."""
    import jax
    import jax.numpy as jnp

    n = mesh.shape[axis]

    def per_rank(ql, kl, vl):
        # local shards [B, Sq, H, D]
        b, sq, h, d = ql.shape
        rank = jax.lax.axis_index(axis)
        qt = jnp.swapaxes(ql, 1, 2).astype(jnp.float32)  # [B, H, Sq, D]
        scale = sm_scale

        def step(r, carry):
            m, l, acc, kc, vc = carry
            src = (rank - r) % n  # origin rank of the current K/V block

            def compute(m, l, acc):
                kt = jnp.swapaxes(kc, 1, 2).astype(jnp.float32)
                vt = jnp.swapaxes(vc, 1, 2).astype(jnp.float32)
                s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
                if causal:
                    q_pos = rank * sq + jnp.arange(sq)
                    k_pos = src * sq + jnp.arange(sq)
                    mask = q_pos[:, None] >= k_pos[None, :]
                    s = jnp.where(mask[None, None], s, -jnp.inf)
                m_cur = jnp.max(s, axis=-1)
                m_new = jnp.maximum(m, m_cur)
                # guard fully-masked rows (exp(-inf - -inf))
                safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
                p = jnp.exp(s - safe_m[..., None])
                p = jnp.where(jnp.isneginf(m_new)[..., None], 0.0, p)
                alpha = jnp.where(jnp.isneginf(m), 0.0,
                                  jnp.exp(m - safe_m))
                l_new = l * alpha + p.sum(axis=-1)
                acc_new = acc * alpha[..., None] + \
                    jnp.einsum("bhqk,bhkd->bhqd", p, vt)
                return m_new, l_new, acc_new

            if causal:
                # blocks entirely in the future (src > rank) skip the
                # matmuls — the ring still rotates so later steps see the
                # right K/V
                m, l, acc = jax.lax.cond(
                    src <= rank, compute, lambda m_, l_, a_: (m_, l_, a_),
                    m, l, acc)
            else:
                m, l, acc = compute(m, l, acc)
            perm = [(i, (i + 1) % n) for i in range(n)]
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return m, l, acc, kc, vc

        m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, sq), jnp.float32)
        a0 = jnp.zeros((b, h, sq, d), jnp.float32)
        # mark the replicated initializers device-varying so the scan carry
        # type matches the rank-dependent outputs (shard_map vma rule)
        from .utils import mark_varying
        m0, l0, a0 = (mark_varying(x, axis) for x in (m0, l0, a0))
        m, l, acc, _, _ = jax.lax.fori_loop(0, n, step,
                                            (m0, l0, a0, kl, vl))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return jnp.swapaxes(out, 1, 2).astype(ql.dtype)

    spec = P(None, axis, None, None)
    return jax.shard_map(per_rank, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


def _ring_flash_arrays(q, k, v, mesh, axis, causal, sm_scale):
    """Ring attention with the Pallas flash kernel per block.

    The jnp formulation materializes [Sq/n, Sk/n] score blocks per ring
    step; at pod-scale contexts those blocks are themselves huge. Here
    each step runs the flash FORWARD kernel on the resident Q against the
    incoming K/V shard (O(block) VMEM) and merges the per-step normalized
    outputs through their log-sum-exps; the backward is the ring-flash
    rule — one flash BACKWARD kernel per step with the GLOBAL lse (the
    flash-2 identity: p = exp(s - lse_global) reproduces each block's true
    softmax slice), dq accumulating locally while dk/dv ride the ring home.
    The ring loop is python-unrolled (n is static), so the diagonal step
    compiles the causal kernel and off-diagonal steps the full kernel,
    with `lax.cond` skipping entirely-future blocks at runtime."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def per_rank(ql, kl, vl):
        B, Sq, H, D = ql.shape
        Sk = kl.shape[1]
        bq = fa._pick_block(fa._DEF_BLOCK_Q, Sq)
        bk = fa._pick_block(fa._DEF_BLOCK_K, Sk)
        # same guards the public wrapper applies (we call the kernel
        # internals directly): an indivisible shard would leave grid-
        # uncovered output rows silently uninitialized, and an over-VMEM
        # forced block would die in a long Mosaic compile
        if Sq % bq or Sk % bk:
            raise ValueError(
                f"ring-flash requires the per-rank shard lengths "
                f"({Sq}, {Sk}) divisible by the kernel blocks ({bq}, {bk})"
                "; pad the sequence to a multiple of 128 x ring size")
        if bq > fa._MAX_BLOCK or bk > fa._MAX_BLOCK:
            raise ValueError(
                f"no VMEM-safe block tiling for ring shard lengths "
                f"({Sq}, {Sk}); pad the sequence to a multiple of "
                f"128 x ring size")
        rank = jax.lax.axis_index(axis)

        def to_k(x):  # [B, S, H, D] -> [B*H, S, D] kernel layout
            return jnp.swapaxes(x, 1, 2).reshape(B * H, x.shape[1], D)

        def from_k(x, s):
            return jnp.swapaxes(x.reshape(B, H, s, D), 1, 2)

        def fwd_block(qk, kk, vk, blk_causal):
            return fa._fwd(qk, kk, vk, None, None, None, None, blk_causal,
                           sm_scale, bq, bk, 1, 1, None, 0.0)

        def bwd_block(qk, kk, vk, o, lse, do, blk_causal):
            return fa._bwd(qk, kk, vk, o, lse, do, None, None, None, None,
                           blk_causal, sm_scale, bq, bk, 1, 1, None, 0.0)

        def merge(o, lse, o_s, lse_s):
            # lse layout is the kernel's [BH, 1, Sq]. The accumulator
            # stays f32 across ring steps (a per-step cast to bf16 would
            # re-quantize n times); per_rank casts once at the end.
            m = jnp.maximum(lse, lse_s)
            new_lse = m + jnp.log(jnp.exp(lse - m) + jnp.exp(lse_s - m))
            w_a = jnp.swapaxes(jnp.exp(lse - new_lse), 1, 2)  # [BH, Sq, 1]
            w_b = jnp.swapaxes(jnp.exp(lse_s - new_lse), 1, 2)
            return w_a * o + w_b * o_s.astype(jnp.float32), new_lse

        def ring_fwd(qk, kk, vk):
            # step 0 is always the resident (diagonal) shard; the output
            # accumulator is f32 until the final cast. Steps 1..n-1 are
            # IDENTICAL non-causal kernels, so they run as ONE lax.scan
            # body — program size and compile time stay O(1) in ring size
            # (a python unroll at sp=64+ would emit hundreds of kernels)
            o, lse = fwd_block(qk, kk, vk, causal)
            o = o.astype(jnp.float32)

            def step(carry, s):
                o_, lse_, kc, vc = carry
                kc = jax.lax.ppermute(kc, axis, perm)
                vc = jax.lax.ppermute(vc, axis, perm)
                if causal:
                    # src = rank - s (mod n) is a PAST shard iff rank >= s
                    def hit(args):
                        oo, ll, kc_, vc_ = args
                        o_s, lse_s = fwd_block(qk, kc_, vc_, False)
                        return merge(oo, ll, o_s, lse_s)

                    o_, lse_ = jax.lax.cond(
                        rank >= s, hit,
                        lambda args: (args[0], args[1]),
                        (o_, lse_, kc, vc))
                else:
                    o_s, lse_s = fwd_block(qk, kc, vc, False)
                    o_, lse_ = merge(o_, lse_, o_s, lse_s)
                return (o_, lse_, kc, vc), None

            if n > 1:
                (o, lse, _, _), _ = jax.lax.scan(
                    step, (o, lse, kk, vk), jnp.arange(1, n))
            return o, lse

        @jax.custom_vjp
        def ring(qk, kk, vk):
            return ring_fwd(qk, kk, vk)[0]

        def ring_f(qk, kk, vk):
            o, lse = ring_fwd(qk, kk, vk)
            return o, (qk, kk, vk, o, lse)

        def ring_b(res, do):
            qk, kk, vk, o, lse = res
            zq = jnp.zeros(qk.shape, jnp.float32)
            zk = jnp.zeros(kk.shape, jnp.float32)
            # diagonal step
            dq_s, dk_s, dv_s = bwd_block(qk, kk, vk, o, lse, do, causal)
            dq = zq + dq_s
            dk_acc = zk + dk_s
            dv_acc = zk + dv_s

            def step(carry, s):
                dq_, dka, dva, kc, vc = carry
                kc = jax.lax.ppermute(kc, axis, perm)
                vc = jax.lax.ppermute(vc, axis, perm)
                # dk/dv accumulators ride the SAME ring so each
                # contribution lands on its shard's row; after the full n
                # rotations they are home again
                dka = jax.lax.ppermute(dka, axis, perm)
                dva = jax.lax.ppermute(dva, axis, perm)
                if causal:
                    def hit(args):
                        d_, ka_, va_, kc_, vc_ = args
                        g_q, g_k, g_v = bwd_block(qk, kc_, vc_, o, lse,
                                                  do, False)
                        return d_ + g_q, ka_ + g_k, va_ + g_v

                    dq_, dka, dva = jax.lax.cond(
                        rank >= s, hit, lambda args: args[:3],
                        (dq_, dka, dva, kc, vc))
                else:
                    g_q, g_k, g_v = bwd_block(qk, kc, vc, o, lse, do,
                                              False)
                    dq_ = dq_ + g_q
                    dka = dka + g_k
                    dva = dva + g_v
                return (dq_, dka, dva, kc, vc), None

            if n > 1:
                (dq, dk_acc, dv_acc, _, _), _ = jax.lax.scan(
                    step, (dq, dk_acc, dv_acc, kk, vk), jnp.arange(1, n))
            # one final rotation completes the cycle (n rotations total)
            dk_acc = jax.lax.ppermute(dk_acc, axis, perm)
            dv_acc = jax.lax.ppermute(dv_acc, axis, perm)
            return (dq.astype(qk.dtype), dk_acc.astype(kk.dtype),
                    dv_acc.astype(vk.dtype))

        ring.defvjp(ring_f, ring_b)
        out = ring(to_k(ql), to_k(kl), to_k(vl))
        return from_k(out, Sq).astype(ql.dtype)

    spec = P(None, axis, None, None)
    # check_vma off: pallas_call's output avals carry no vma annotation,
    # which the checker (not the semantics) rejects inside shard_map
    return jax.shard_map(per_rank, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _ring_flash_tileable(S: int, n: int) -> bool:
    """True when the per-rank shard length admits a VMEM-legal kernel
    tiling (the auto path falls back to the jnp composite otherwise, so
    flipping the default can never reject a previously-working shape)."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    if S % n:
        return False
    local = S // n
    bq = fa._pick_block(fa._DEF_BLOCK_Q, local)
    bk = fa._pick_block(fa._DEF_BLOCK_K, local)
    return (local % bq == 0 and local % bk == 0
            and bq <= fa._MAX_BLOCK and bk <= fa._MAX_BLOCK)


def ring_attention(query, key, value, mesh=None, axis: str = "sp",
                   causal: bool = False, sm_scale: Optional[float] = None,
                   use_flash: Optional[bool] = None):
    """Ring attention over a sequence-sharded [B, S, H, D] triple
    (Tensor-in/Tensor-out, taped).

    ``use_flash=None`` routes each ring step through the Pallas flash
    kernel on TPU (O(block) VMEM per step — the jnp composite would
    materialize [Sq/n, Sk/n] score blocks, themselves enormous at
    pod-scale contexts) and keeps the jnp composite elsewhere; pass
    True/False to force a path (True works in interpret mode for tests).
    """
    import jax as _jax
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise RuntimeError(f"ring_attention needs a mesh with axis {axis!r}")
    if sm_scale is None:
        d = query.shape[-1]
        sm_scale = 1.0 / math.sqrt(d)
    if use_flash is None:
        # auto mode must not NARROW accepted shapes vs the composite:
        # only take the kernel path when the per-rank shard tiles
        use_flash = _jax.default_backend() == "tpu" and \
            _ring_flash_tileable(query.shape[1], mesh.shape[axis])
    impl = _ring_flash_arrays if use_flash else _ring_attention_arrays
    return apply_op(
        lambda q, k, v: impl(q, k, v, mesh, axis, causal, sm_scale),
        query, key, value, op_name="ring_attention")


def ulysses_attention(query, key, value, mesh=None, axis: str = "sp",
                      causal: bool = False):
    """Ulysses/DeepSpeed-style SP: all-to-all heads<->sequence so each rank
    holds full sequences for a head subset, then ordinary attention."""
    from paddle_tpu.nn import functional as F
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise RuntimeError(
            f"ulysses_attention needs a mesh with axis {axis!r}")
    # re-shard: seq-sharded -> head-sharded (GSPMD emits the all-to-all)
    head_spec = P(None, None, axis, None)

    def reshard(t, spec):
        return with_sharding_constraint(t, spec, mesh)

    q = reshard(query, head_spec)
    k = reshard(key, head_spec)
    v = reshard(value, head_spec)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=causal)
    return reshard(out, P(None, axis, None, None))
