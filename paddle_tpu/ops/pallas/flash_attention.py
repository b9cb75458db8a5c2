"""Flash attention as Pallas TPU kernels.

Capability parity with the reference's FlashAttention integration
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu`` — ``FlashAttnKernel`` and
``FlashAttnUnpaddedKernel`` wrapping the external CUDA lib, plus
``paddle/fluid/operators/fused/fused_attention_op.cc`` which takes arbitrary
additive masks): O(S) memory attention with online softmax and the standard
recompute-based flash backward (dq and dk/dv kernels), wired into the tape
via ``jax.custom_vjp``.

Supported generality (all combinations compose):
  * causal masking with a key/query length offset (chunked prefill, decode);
  * cross attention: ``kv_len != q_len``;
  * native GQA/MQA: ``num_kv_heads < num_q_heads`` served by grid index maps
    — each query head streams its shared KV head straight from HBM, no
    KV replication materialized (the reference replicates KV for its
    non-flash path);
  * segment ids (the TPU-idiomatic form of the reference's
    varlen/unpadded seam): per-token integer ids for q and kv; tokens
    attend only within equal ids. Padding masks are segment ids with a
    sentinel. Fully-masked *tiles* are skipped dynamically — padding-heavy
    batches don't pay for dead FLOPs. Fully-masked rows produce 0 output
    and 0 gradient (exactly, via the l==0 guard).
  * arbitrary additive bias/mask, streamed tile-by-tile from HBM
    ([B|1, H|1, Sq, Sk] broadcasting): O(S) VMEM still holds, and the
    backward is the fused flash backward. Bias is treated as a constant
    (zero gradient) — it serves attention *masks*, which never train.
  * post-softmax dropout, in-kernel: a murmur-style position hash of
    (head, q_pos, k_pos, seed) generates the keep mask — pure integer
    jnp ops (works in interpret mode, unlike pltpu.prng) and identical
    by construction across the forward and both backward kernels
    whatever their grid layouts. ``l`` keeps the raw softmax
    denominator; only value contributions drop (standard semantics).

Kernel shape: q flattens to [B*Hq, Sq, D], kv to [B*Hkv, Sk, D]; every
kernel walks a (flat heads, outer blocks, inner blocks) grid with the inner
dimension marked "arbitrary" so K/V (or Q) blocks stream HBM→VMEM with
double buffering. Softmax statistics are carried across inner steps in fp32
VMEM scratch, lane-replicated to honor the (8, 128) tile rule. Causal tiles
above the diagonal are skipped with static ``pl.when`` predication;
segment-dead tiles with dynamic predication.

Off-TPU the kernels run in Pallas interpret mode so the numerics are
testable on the CPU mesh (the reference cannot test its CUDA kernel without
a GPU; SURVEY.md §4 calls out this improvement).
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_bshd", "flash_attention_bhsd",
           "flash_tileable", "spmd_mesh"]

_DEF_BLOCK_Q = 1024  # swept on v5e: 1024/1024 beats 512/512 by ~16% fwd+bwd
_DEF_BLOCK_K = 1024
_BIAS_BLOCK = 512    # bias tiles are f32 [bq, bk]: cap so VMEM double-buffers
_LANES = 128
# refuse block sizes that can't double-buffer in ~16MB VMEM instead of
# paying a doomed Mosaic compile (hit by odd kv lengths — e.g. decode at
# long context — that force block == seq); SDPA routes such shapes to the
# composite up front (flash_tileable)
_MAX_BLOCK = 2048
# finite stand-in for -inf (the official TPU flash kernels use the same
# trick): keeps m/l/alpha arithmetic NaN-free when a tile is fully masked
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# candidate (block_q, block_k) pairs for the runtime autotuner; the
# hand-swept default stays first so a sweep that ties keeps it
_BLOCK_CANDIDATES = [(1024, 1024), (512, 512), (512, 1024), (1024, 512),
                     (2048, 1024), (256, 1024), (1024, 256)]


def _auto_blocks(b, sq, sk, d, hq, hkv, dtype, causal, bias_kind, has_seg,
                 has_drop):
    """(block_q, block_k) for this call signature: the hand-swept default,
    or — with ``FLAGS_use_autotune`` — the winner of an on-chip sweep over
    ``_BLOCK_CANDIDATES``, measured once per signature with synthetic
    operands (fwd+bwd, the full kernel trio) and cached (the reference's
    ``AutoTuneBase::Run`` + ``AutoTuneCache`` shape, phi/kernels/autotune).

    ``bias_kind``: None | "row" (a [.., 1, Sk] key-padding mask — streams
    uncapped) | "full" (full-tile bias — block sizes get the _BIAS_BLOCK
    cap). The two kinds tile differently, so they are distinct signatures
    and the synthetic bias reproduces the caller's kind; candidates are
    deduped AFTER clamping so a short sequence never times the same
    effective tiling twice.
    """
    default = (_DEF_BLOCK_Q, _DEF_BLOCK_K)
    if _interpret():
        return default  # interpret mode: timing a sweep is meaningless
    from paddle_tpu.core.flags import flag
    if not flag("use_autotune"):
        # fast exit BEFORE any candidate bookkeeping: the default path
        # (eager dispatch included) must not pay for a disabled feature
        return default
    from .autotune import autotune

    sig = (b, sq, sk, d, hq, hkv, dtype, causal, bias_kind, has_seg,
           has_drop)

    def effective(cand):
        bq, bk = cand
        if bias_kind == "full":
            bq, bk = min(bq, _BIAS_BLOCK), min(bk, _BIAS_BLOCK)
        return (_pick_block(bq, sq), _pick_block(bk, sk))

    seen, cands = set(), []
    for cand in _BLOCK_CANDIDATES:
        eff = effective(cand)
        if sq % eff[0] or sk % eff[1] or eff in seen:
            continue
        if eff[0] > _MAX_BLOCK or eff[1] > _MAX_BLOCK:
            # the shape forces seq-sized tiles beyond VMEM — let the
            # normal path raise its cheap early error instead of paying
            # (and re-paying: failures are uncached) doomed Mosaic
            # compiles in the sweep
            continue
        seen.add(eff)
        cands.append(eff)

    def build(cand):
        from .autotune import aot_runner
        bq, bk = cand
        # operands created CONCRETE even under an enclosing trace
        # (ensure_compile_time_eval), committed to device once by the
        # aot_runner
        with jax.ensure_compile_time_eval():
            dt = jnp.dtype(dtype)
            q0 = jnp.zeros((b, hq, sq, d), dt)
            k0 = jnp.zeros((b, hkv, sk, d), dt)
            v0 = jnp.zeros((b, hkv, sk, d), dt)
            kw = dict(causal=causal, block_q=bq, block_k=bk)
            if bias_kind == "row":
                kw["bias"] = jnp.zeros((1, 1, 1, sk), jnp.float32)
            elif bias_kind == "full":
                kw["bias"] = jnp.zeros((1, 1, sq, sk), jnp.float32)
            if has_seg:
                kw["q_segment_ids"] = jnp.zeros((b, sq), jnp.int32)
                kw["kv_segment_ids"] = jnp.zeros((b, sk), jnp.int32)
            if has_drop:
                kw["dropout_p"] = 0.1
                kw["dropout_seed"] = jnp.zeros((1,), jnp.int32)

        return aot_runner(jax.value_and_grad(
            lambda qa, ka, va: flash_attention_bhsd(
                qa, ka, va, **kw).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), q0, k0, v0)

    return autotune("flash_attention", sig, cands, build, default)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _masked_scores(q, k, bias_ref, seg, j, i, *, sm_scale, causal, offset,
                   block_q, block_k):
    """Scaled q·kᵀ for one tile with causal/segment/bias masking applied,
    clamped finite. Shared verbatim by forward and both backward kernels so
    the recomputed probabilities match the forward bit-for-bit."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    if bias_ref is not None:
        s = s + bias_ref[...].astype(jnp.float32)
    if seg is not None:
        s = jnp.where(seg, s, _MASK_VALUE)
    if causal:
        qi = j * block_q + offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        ki = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qi >= ki, s, _MASK_VALUE)
    return jnp.maximum(s, _MASK_VALUE)


def _threshold(dropout_p: float) -> int:
    """uint32 drop threshold: bits below it drop (P = dropout_p)."""
    return min(int(dropout_p * 2**32), 2**32 - 1)


def _dropout_keep(seed_ref, bh, j, i, *, block_q, block_k, threshold):
    """Deterministic keep-mask for one tile from GLOBAL (head, q, k)
    positions — murmur3-style integer hash, pure jnp ops (portable to
    interpret mode, identical in forward and both backward kernels
    regardless of their different grid layouts)."""
    qi = j * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    ki = i * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    # fold q and k positions separately — a qi*sk+ki linearization would
    # alias rows once sq*sk exceeds 2^32 at extreme context lengths
    x = qi.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    x = x ^ (ki.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    x = x ^ (bh.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    x = x ^ seed_ref[0].astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x >= jnp.uint32(threshold)


def _qflat(b, t, *, hq, hkv, group, nq):
    """Flat (batch, Q head) index for the dkv grid's (b over B*Hkv, t over
    group*nq) coordinates. The dropout mask AND the q/do/lse BlockSpecs
    must use this SAME mapping — one definition, used by both."""
    return (b // hkv) * hq + (b % hkv) * group + t // nq


def _causal_live(j, i, *, offset, block_q, block_k):
    """Static tile-liveness: any (q row, k col) in tile satisfies
    q_abs >= k_abs, where q_abs = q + offset (offset = Sk - Sq)."""
    return i * block_k < (j + 1) * block_q + offset


def _segments(qseg_ref, kvseg_ref):
    if qseg_ref is None:
        return None
    qs = qseg_ref[0, :]   # [block_q] (stored lane-tiled as [1, block_q])
    ks = kvseg_ref[0, :]  # [block_k]
    return qs[:, None] == ks[None, :]


# =========================== forward =========================================
def _fwd_kernel(*refs, sm_scale, causal, offset, block_q, block_k, nk,
                has_bias, has_seg, dropout_p, sk, threshold):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kvseg_ref = next(it) if has_seg else None
    seed_ref = next(it) if dropout_p > 0 else None
    o_ref, lse_ref = next(it), next(it)
    m_sc, l_sc, acc_sc = next(it), next(it), next(it)

    bh = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    live = _causal_live(j, i, offset=offset, block_q=block_q,
                        block_k=block_k) if causal else True

    def _compute(seg):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = _masked_scores(q, k, bias_ref, seg, j, i, sm_scale=sm_scale,
                           causal=causal, offset=offset, block_q=block_q,
                           block_k=block_k)
        m_prev = m_sc[:, :1]  # [bq, 1] (lane-replicated storage)
        l_prev = l_sc[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if seg is not None:
            # rows with no live key in THIS tile would otherwise contribute
            # p = exp(MASK - MASK) = 1 per column; zeroing them keeps l == 0
            # for fully-masked rows so the finish-guard emits exact 0
            p = jnp.where(jnp.any(seg, axis=-1, keepdims=True), p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p_acc = p
        if dropout_p > 0:
            # l keeps the RAW softmax denominator; only the value
            # contributions drop (standard post-softmax dropout)
            keep = _dropout_keep(seed_ref, bh, j, i, block_q=block_q,
                                 block_k=block_k, threshold=threshold)
            p_acc = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(live)
    def _outer():
        if has_seg:
            seg = _segments(qseg_ref, kvseg_ref)

            @pl.when(jnp.any(seg))
            def _inner():
                _compute(seg)
        else:
            _compute(None)

    @pl.when(i == nk - 1)
    def _finish():
        l = l_sc[:, :1]
        # rows that saw no live tile (fully-masked padding rows): exact 0
        # output and a sentinel lse of 0 so the backward's
        # p = exp(MASK - lse) underflows to 0 — zero grads, no NaN
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l_sc[:, 0] == 0.0, 0.0,
                        m_sc[:, 0] + jnp.log(l_safe[:, 0]))
        lse_ref[0, :] = lse


def _build_specs(block_q, block_k, d, hq, hkv, bias_bh):
    """Input block specs for the (bhq, nq, nk) grids (forward and dq); the
    dkv kernel's (bhkv, nk, group*nq) grid builds its own maps in _bwd."""
    group = hq // hkv

    def kv_of(b):
        return (b // hq) * hkv + (b % hq) // group

    def batch_of(b):
        return b // hq

    specs = {
        "q": pl.BlockSpec((None, block_q, d), lambda b, j, i: (b, j, 0)),
        "kv": pl.BlockSpec((None, block_k, d),
                           lambda b, j, i: (kv_of(b), i, 0)),
        "row_q": pl.BlockSpec((None, 1, block_q),
                              lambda b, j, i: (b, 0, j)),
        "qseg": pl.BlockSpec((None, 1, block_q),
                             lambda b, j, i: (batch_of(b), 0, j)),
        "kvseg": pl.BlockSpec((None, 1, block_k),
                              lambda b, j, i: (batch_of(b), 0, i)),
    }
    if bias_bh is not None:
        bb_n, hb_n, row_bcast = bias_bh

        def bias_of(b):
            bb = (b // hq) if bb_n > 1 else 0
            hh = (b % hq) if hb_n > 1 else 0
            return bb * hb_n + hh
        if row_bcast:  # [.., 1, Sk] key-padding mask: one row per tile
            specs["bias"] = pl.BlockSpec((None, 1, block_k),
                                         lambda b, j, i: (bias_of(b), 0, i))
        else:
            specs["bias"] = pl.BlockSpec(
                (None, block_q, block_k),
                lambda b, j, i: (bias_of(b), j, i))
    return specs


def _fwd(q, k, v, bias, q_seg, kv_seg, seed, causal, sm_scale, block_q,
         block_k, hq, hkv, bias_bh, dropout_p):
    bhq, sq, d = q.shape
    _, sk, _ = k.shape
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq
    has_bias = bias is not None
    has_seg = q_seg is not None
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, offset=offset,
        block_q=block_q, block_k=block_k, nk=nk, has_bias=has_bias,
        has_seg=has_seg, dropout_p=dropout_p, sk=sk,
        threshold=_threshold(dropout_p))
    sp = _build_specs(block_q, block_k, d, hq, hkv, bias_bh)
    in_specs = [sp["q"], sp["kv"], sp["kv"]]
    inputs = [q, k, v]
    if has_bias:
        in_specs.append(sp["bias"])
        inputs.append(bias)
    if has_seg:
        in_specs += [sp["qseg"], sp["kvseg"]]
        inputs += [q_seg, kv_seg]
    if dropout_p > 0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(seed)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bhq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, j, i: (b, j, 0)),
            sp["row_q"],
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bhq, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*inputs)
    return o, lse


# =========================== backward ========================================
def _dq_kernel(*refs, sm_scale, causal, offset, block_q, block_k, nk,
               has_bias, has_seg, dropout_p, sk, threshold):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref = next(it), next(it), next(it), next(it)
    lse_ref, delta_ref = next(it), next(it)
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kvseg_ref = next(it) if has_seg else None
    seed_ref = next(it) if dropout_p > 0 else None
    dq_ref = next(it)
    dq_sc = next(it)

    bh = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc[...])

    live = _causal_live(j, i, offset=offset, block_q=block_q,
                        block_k=block_k) if causal else True

    def _compute(seg):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = _masked_scores(q, k, bias_ref, seg, j, i, sm_scale=sm_scale,
                           causal=causal, offset=offset, block_q=block_q,
                           block_k=block_k)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0:
            keep = _dropout_keep(seed_ref, bh, j, i, block_q=block_q,
                                 block_k=block_k, threshold=threshold)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout_p))
        ds = (p * (dp - delta[:, None]) * sm_scale).astype(k.dtype)
        dq_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live)
    def _outer():
        if has_seg:
            seg = _segments(qseg_ref, kvseg_ref)

            @pl.when(jnp.any(seg))
            def _inner():
                _compute(seg)
        else:
            _compute(None)

    @pl.when(i == nk - 1)
    def _finish():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, causal, offset, block_q, block_k, nq,
                group, has_bias, has_seg, dropout_p, sk, threshold, hq,
                hkv):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref = next(it), next(it), next(it), next(it)
    lse_ref, delta_ref = next(it), next(it)
    bias_ref = next(it) if has_bias else None
    qseg_ref = next(it) if has_seg else None
    kvseg_ref = next(it) if has_seg else None
    seed_ref = next(it) if dropout_p > 0 else None
    dk_ref, dv_ref = next(it), next(it)
    dk_sc, dv_sc = next(it), next(it)

    b = pl.program_id(0)   # flat (batch, kv head)
    i = pl.program_id(1)   # k block
    t = pl.program_id(2)   # fused (query head in group, q block)
    j = t % nq
    gnq = group * nq
    # flat (batch, Q head) index — the dropout mask is defined per q-head
    bh_q = _qflat(b, t, hq=hq, hkv=hkv, group=group, nq=nq)

    @pl.when(t == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc[...])
        dv_sc[...] = jnp.zeros_like(dv_sc[...])

    live = _causal_live(j, i, offset=offset, block_q=block_q,
                        block_k=block_k) if causal else True

    def _compute(seg):
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = _masked_scores(q, k, bias_ref, seg, j, i, sm_scale=sm_scale,
                           causal=causal, offset=offset, block_q=block_q,
                           block_k=block_k)
        p = jnp.exp(s - lse[:, None])  # [bq, bk] f32
        p_v = p
        if dropout_p > 0:
            keep = _dropout_keep(seed_ref, bh_q, j, i, block_q=block_q,
                                 block_k=block_k, threshold=threshold)
            p_v = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
        dv_sc[...] += jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0:
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout_p))
        ds = (p * (dp - delta[:, None]) * sm_scale).astype(q.dtype)
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live)
    def _outer():
        if has_seg:
            seg = _segments(qseg_ref, kvseg_ref)

            @pl.when(jnp.any(seg))
            def _inner():
                _compute(seg)
        else:
            _compute(None)

    @pl.when(t == gnq - 1)
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, bias, q_seg, kv_seg, seed, causal, sm_scale,
         block_q, block_k, hq, hkv, bias_bh, dropout_p):
    bhq, sq, d = q.shape
    bhkv, sk, _ = k.shape
    group = hq // hkv
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq
    has_bias = bias is not None
    has_seg = q_seg is not None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [bhq, 1, sq]

    sp = _build_specs(block_q, block_k, d, hq, hkv, bias_bh)
    dq_kernel = functools.partial(
        _dq_kernel, sm_scale=sm_scale, causal=causal, offset=offset,
        block_q=block_q, block_k=block_k, nk=nk, has_bias=has_bias,
        has_seg=has_seg, dropout_p=dropout_p, sk=sk,
        threshold=_threshold(dropout_p))
    in_specs = [sp["q"], sp["kv"], sp["kv"], sp["q"], sp["row_q"],
                sp["row_q"]]
    inputs = [q, k, v, do, lse, delta]
    if has_bias:
        in_specs.append(sp["bias"])
        inputs.append(bias)
    if has_seg:
        in_specs += [sp["qseg"], sp["kvseg"]]
        inputs += [q_seg, kv_seg]
    if dropout_p > 0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(seed)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bhq, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda b, j, i: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*inputs)

    # dk/dv at KV-head resolution: grid (B*Hkv, nk, group*nq) — the inner
    # fused dimension walks every (query head in the group, q block) pair,
    # accumulating into one [block_k, d] scratch. GQA head reduction happens
    # in-kernel; dk/dv never inflate to Hq.
    def qflat(b, t):
        return _qflat(b, t, hq=hq, hkv=hkv, group=group, nq=nq)

    dkv_in_specs = [
        pl.BlockSpec((None, block_q, d),
                     lambda b, i, t: (qflat(b, t), t % nq, 0)),       # q
        pl.BlockSpec((None, block_k, d), lambda b, i, t: (b, i, 0)),  # k
        pl.BlockSpec((None, block_k, d), lambda b, i, t: (b, i, 0)),  # v
        pl.BlockSpec((None, block_q, d),
                     lambda b, i, t: (qflat(b, t), t % nq, 0)),       # do
        pl.BlockSpec((None, 1, block_q),
                     lambda b, i, t: (qflat(b, t), 0, t % nq)),       # lse
        pl.BlockSpec((None, 1, block_q),
                     lambda b, i, t: (qflat(b, t), 0, t % nq)),       # delta
    ]
    dkv_inputs = [q, k, v, do, lse, delta]
    if has_bias:
        bb_n, hb_n, row_bcast = bias_bh

        def bias_of(b, t):
            bb = (b // hkv) if bb_n > 1 else 0
            hh = ((b % hkv) * group + t // nq) if hb_n > 1 else 0
            return bb * hb_n + hh
        if row_bcast:
            dkv_in_specs.append(pl.BlockSpec(
                (None, 1, block_k),
                lambda b, i, t: (bias_of(b, t), 0, i)))
        else:
            dkv_in_specs.append(pl.BlockSpec(
                (None, block_q, block_k),
                lambda b, i, t: (bias_of(b, t), t % nq, i)))
        dkv_inputs.append(bias)
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((None, 1, block_q),
                         lambda b, i, t: (b // hkv, 0, t % nq)),
            pl.BlockSpec((None, 1, block_k),
                         lambda b, i, t: (b // hkv, 0, i)),
        ]
        dkv_inputs += [q_seg, kv_seg]
    if dropout_p > 0:
        dkv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_inputs.append(seed)

    dkv_kernel = functools.partial(
        _dkv_kernel, sm_scale=sm_scale, causal=causal, offset=offset,
        block_q=block_q, block_k=block_k, nq=nq, group=group,
        has_bias=has_bias, has_seg=has_seg, dropout_p=dropout_p, sk=sk,
        threshold=_threshold(dropout_p), hq=hq, hkv=hkv)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bhkv, nk, group * nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, t: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*dkv_inputs)
    return dq, dk, dv


# =========================== custom-vjp wrapper ==============================
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, bias, q_seg, kv_seg, seed, causal, sm_scale, block_q,
           block_k, hq, hkv, bias_bh, dropout_p):
    o, _ = _fwd(q, k, v, bias, q_seg, kv_seg, seed, causal, sm_scale,
                block_q, block_k, hq, hkv, bias_bh, dropout_p)
    return o


def _flash_fwd(q, k, v, bias, q_seg, kv_seg, seed, causal, sm_scale,
               block_q, block_k, hq, hkv, bias_bh, dropout_p):
    o, lse = _fwd(q, k, v, bias, q_seg, kv_seg, seed, causal, sm_scale,
                  block_q, block_k, hq, hkv, bias_bh, dropout_p)
    return o, (q, k, v, bias, q_seg, kv_seg, seed, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, hq, hkv, bias_bh,
               dropout_p, res, do):
    q, k, v, bias, q_seg, kv_seg, seed, o, lse = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, bias, q_seg, kv_seg, seed,
                      causal, sm_scale, block_q, block_k, hq, hkv, bias_bh,
                      dropout_p)
    # bias is an attention mask: constant by contract (zero grad); segment
    # ids are carried as f32 so integer-cotangent (float0) plumbing never
    # enters the picture; the seed is integer state (no grad)
    dbias = None if bias is None else jnp.zeros_like(bias)
    dqs = None if q_seg is None else jnp.zeros_like(q_seg)
    dks = None if kv_seg is None else jnp.zeros_like(kv_seg)
    return dq, dk, dv, dbias, dqs, dks, None


_flash.defvjp(_flash_fwd, _flash_bwd)

# module-level jit so EAGER calls hit the compile cache: without this,
# every eager flash_attention re-traces and re-compiles the pallas_call
# (~1s/call on chip vs ~1ms steady-state — measured). Under an outer
# jit/TrainStep trace this inlines and changes nothing. None-valued
# optional inputs are empty pytrees — one jitted callable serves every
# bias/segment combination.
_flash_cached = functools.partial(
    jax.jit, static_argnums=(7, 8, 9, 10, 11, 12, 13, 14))(_flash)


def _pick_block(requested, seq):
    """Largest lane-multiple block <= requested that divides seq, else the
    smallest lane-multiple divisor above it, else the whole sequence (always
    a legal tile). The (1, block) rows (lse, segment ids) must satisfy the
    TPU tile rule: last dim a multiple of 128 or equal to the array dim.
    Interpret mode (CPU tests) keeps the raw clamp so indivisible shapes
    still surface as ValueError."""
    block = min(requested, seq)
    if _interpret():
        return block
    if seq % block == 0 and (block % _LANES == 0 or block == seq):
        return block
    cands = [b for b in range(_LANES, block + 1, _LANES) if seq % b == 0]
    if cands:
        return cands[-1]
    bigger = [b for b in range(_LANES, seq, _LANES) if seq % b == 0]
    return bigger[0] if bigger else seq


def flash_tileable(sq, sk, full_bias=False) -> bool:
    """Whether the default blocks tile ``(sq, sk)`` inside VMEM — the
    shape test SDPA routes on before it calls the kernel. False only for
    a length with no lane-multiple divisor that is too long to stream as
    one tile (odd kv lengths: eager decode at long context), which the
    kernel itself refuses with a ValueError."""
    cap = _BIAS_BLOCK if full_bias else _MAX_BLOCK
    return (_pick_block(min(_DEF_BLOCK_Q, cap), sq) <= _MAX_BLOCK
            and _pick_block(min(_DEF_BLOCK_K, cap), sk) <= _MAX_BLOCK)


def _norm_bias(bias, b, hq, sq, sk):
    """Normalize bias to (flat [Bb*Hb, Sq|1, Sk], (Bb, Hb, row_bcast)) with
    Bb in {1,B}, Hb in {1,Hq}. A size-1 q dim (the [B, 1, 1, Sk]
    key-padding-mask shape) is served by a one-row BlockSpec — never
    broadcast to Sq in HBM."""
    bias = jnp.asarray(bias)
    if bias.dtype == jnp.bool_:  # bool convention: True = attend
        bias = jnp.where(bias, 0.0,
                         jnp.float32(jnp.finfo(jnp.float32).min))
    if bias.ndim == 2:
        bias = bias[None, None]
    elif bias.ndim == 3:  # [B|H ambiguous, Sq, Sk] — treat as per-head
        bias = bias[None]
    if bias.ndim != 4:
        raise ValueError(f"bias must be 2/3/4-D, got shape {bias.shape}")
    bb, hb = bias.shape[0], bias.shape[1]
    if bb not in (1, b) or hb not in (1, hq):
        raise ValueError(
            f"bias batch/head dims {bias.shape[:2]} must be 1 or match "
            f"(batch={b}, heads={hq})")
    rows = bias.shape[2]
    if rows not in (1, sq) or bias.shape[3] != sk:
        raise ValueError(
            f"bias tail {bias.shape[2:]} must equal (q_len|1, kv_len)="
            f"({sq}|1, {sk})")
    return (bias.reshape(bb * hb, rows, sk), (bb, hb, rows == 1))


def _norm_seg(seg, b, s, name):
    seg = jnp.asarray(seg)
    if seg.ndim == 1:
        seg = seg[None]
    if seg.shape != (b, s):
        raise ValueError(f"{name} must have shape [batch={b}, {s}], got "
                         f"{tuple(seg.shape)}")
    # f32 carrier: exact for ids < 2^24 and sidesteps integer cotangents
    return seg.astype(jnp.float32).reshape(b, 1, s)


def flash_attention_bhsd(q, k, v, causal=False, sm_scale=None, bias=None,
                         q_segment_ids=None, kv_segment_ids=None,
                         dropout_p=0.0, dropout_seed=None,
                         block_q=None, block_k=None):
    """Flash attention on arrays in [B, H, S, D] (or [BH, S, D]) layout.

    GQA: 4-D ``k``/``v`` may carry fewer heads than ``q`` (``Hq % Hkv == 0``)
    — the kernel maps each query head onto its shared KV head; nothing is
    replicated. Cross attention: ``kv_len`` may differ from ``q_len``; with
    ``causal=True`` query i attends keys <= i + (kv_len - q_len) (the
    chunked-prefill/decode convention). ``bias`` is an additive mask
    broadcastable to [B, Hq, Sq, Sk]. Segment ids ([B, Sq]/[B, Sk] ints)
    restrict attention to equal ids; for 3-D inputs their batch dim is BH.
    """
    squeeze = None
    if q.ndim == 4:
        b, hq, sq, d = q.shape
        _, hkv, sk, _ = k.shape
        if k.shape != (b, hkv, sk, d) or v.shape != (b, hkv, sk, d):
            raise ValueError(f"k/v shapes {k.shape}/{v.shape} inconsistent")
        if hq % hkv:
            raise ValueError(
                f"q heads {hq} must be a multiple of kv heads {hkv}")
        q = q.reshape(b * hq, sq, d)
        k = k.reshape(b * hkv, sk, d)
        v = v.reshape(b * hkv, sk, d)
        squeeze = (b, hq)
    else:
        b, hq, hkv = q.shape[0], 1, 1
        if (k.shape[0] != b or k.shape[2] != q.shape[2]
                or v.shape != k.shape):
            raise ValueError(
                f"3-D flash attention requires matching batch*heads and "
                f"head_dim (and v matching k), got "
                f"{q.shape}/{k.shape}/{v.shape}")
        sq, sk, d = q.shape[1], k.shape[1], q.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # validate BEFORE block resolution: an invalid call must fail in
    # microseconds, not after a ~24 s autotune sweep
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be given for both q and kv")
    dropout_p = float(dropout_p)
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError(
            "dropout_p > 0 requires dropout_seed (an int or int32 "
            "array) so forward and recompute-backward agree")
    if block_q is None or block_k is None:
        bias_kind = None
        if bias is not None:
            rows = bias.shape[-2] if bias.ndim >= 2 else 1
            bias_kind = "row" if rows == 1 else "full"
        tq, tk = _auto_blocks(b, sq, sk, d, hq, hkv, str(q.dtype), causal,
                              bias_kind, q_segment_ids is not None,
                              dropout_p > 0.0)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    bias_bh = None
    if bias is not None:
        bias, bias_bh = _norm_bias(bias, b, hq, sq, sk)
        if not bias_bh[2]:  # full [bq, bk] f32 tiles: cap for VMEM; the
            # one-row key-padding shape streams [1, bk] and keeps the
            # swept-fast 1024 blocks
            block_q = min(block_q, _BIAS_BLOCK)
            block_k = min(block_k, _BIAS_BLOCK)
    req_q, req_k = block_q, block_k
    block_q = _pick_block(block_q, sq)
    block_k = _pick_block(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash attention requires q_len {sq} / kv_len {sk} divisible "
            f"by block sizes ({block_q}, {block_k}); pad the sequence")
    if (block_q > max(req_q, _MAX_BLOCK)
            or block_k > max(req_k, _MAX_BLOCK)):
        # seq has no lane-multiple divisor (odd lengths) and is too long to
        # stream as one tile — cheap early error, no Mosaic compile attempt
        raise ValueError(
            f"no VMEM-safe block tiling for q_len {sq} / kv_len {sk} "
            f"(forced blocks ({block_q}, {block_k}) exceed {_MAX_BLOCK}); "
            "pad the sequence to a multiple of 128")

    q_seg = kv_seg = None
    if q_segment_ids is not None:
        q_seg = _norm_seg(q_segment_ids, b, sq, "q_segment_ids")
        kv_seg = _norm_seg(kv_segment_ids, b, sk, "kv_segment_ids")
    seed = None
    if dropout_p > 0.0:
        seed = jnp.atleast_1d(jnp.asarray(dropout_seed)).astype(
            jnp.int32)[:1]

    out = _flash_cached(q, k, v, bias, q_seg, kv_seg, seed, causal,
                        float(sm_scale), block_q, block_k, hq, hkv,
                        bias_bh, dropout_p)
    if squeeze:
        b, hq = squeeze
        out = out.reshape(b, hq, sq, d)
    return out


# thread-local: two TrainSteps may trace concurrently on their own threads
_spmd_local = threading.local()


@contextlib.contextmanager
def spmd_mesh(mesh):
    """Pin the mesh a GSPMD program is being traced for (``None`` =
    single device, a no-op). A ``pallas_call`` is opaque to the
    partitioner: under a multi-device jit, jax 0.9.0 refuses to lower it
    at all ("Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map"). ``TrainStep`` wraps its GSPMD trace
    in this and :func:`flash_attention_bshd` reads it to ``shard_map``
    the kernel over the batch (``dp``) and head (``mp``) axes, so each
    chip runs the kernel on its own ``[B/dp, S, H/mp, D]`` shard. A
    thread-local because ``TrainStep`` traces a user's ``forward``, which
    calls ``F.flash_attention`` with no object to carry the mesh (the
    serving route's rides on its cache)."""
    prev = getattr(_spmd_local, "mesh", None)
    _spmd_local.mesh = mesh
    try:
        yield
    finally:
        _spmd_local.mesh = prev


def _spmd_axes(b, hq, hkv):
    """(mesh, batch_axis|None, head_axis|None) for the pinned mesh: the
    ``dp`` axis when it divides the batch, the model axis when it divides
    both head counts (whole GQA groups stay together). None when no mesh
    is pinned or neither axis splits anything."""
    mesh = getattr(_spmd_local, "mesh", None)
    if mesh is None:
        return None
    batch = "dp" if mesh.shape.get("dp", 1) > 1 \
        and b % mesh.shape["dp"] == 0 else None
    head = None
    for cand in ("mp", "model", "tp"):
        n = mesh.shape.get(cand, 1)
        if n > 1 and hq % n == 0 and hkv % n == 0:
            head = cand
            break
    if batch is None and head is None:
        return None
    return mesh, batch, head


def flash_attention_bshd(query, key, value, causal=False, sm_scale=None,
                         bias=None, q_segment_ids=None, kv_segment_ids=None,
                         dropout_p=0.0, dropout_seed=None,
                         block_q=None, block_k=None):
    """Flash attention with paddle's [batch, seq, heads, head_dim] layout,
    Tensor-in/Tensor-out, recorded on the autograd tape. ``key``/``value``
    may carry fewer heads (GQA) and a different sequence length (cross
    attention) than ``query``. ``bias``/segment ids are mask constants —
    closed over, not taped. Under a pinned :func:`spmd_mesh` the kernel
    runs per shard of the batch and head axes."""
    from paddle_tpu.core.autograd import apply_op

    def _raw(x):
        return x.data if hasattr(x, "data") else jnp.asarray(x)

    bias_arr = None if bias is None else _raw(bias)
    qseg_arr = None if q_segment_ids is None else _raw(q_segment_ids)
    kvseg_arr = None if kv_segment_ids is None else _raw(kv_segment_ids)
    seed_arr = None if dropout_seed is None else \
        jnp.atleast_1d(jnp.asarray(_raw(dropout_seed))).astype(jnp.int32)[:1]

    def kernel(q, k, v, bias_, qseg, kvseg, seed):
        o = flash_attention_bhsd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=causal, sm_scale=sm_scale,
            bias=bias_, q_segment_ids=qseg, kv_segment_ids=kvseg,
            dropout_p=dropout_p, dropout_seed=seed,
            block_q=block_q, block_k=block_k)
        return jnp.swapaxes(o, 1, 2)

    def f(q, k, v):
        spmd = _spmd_axes(q.shape[0], q.shape[2], k.shape[2])
        if spmd is None:
            return kernel(q, k, v, bias_arr, qseg_arr, kvseg_arr, seed_arr)
        from jax.sharding import PartitionSpec as P
        mesh, bax, hax = spmd
        qkv = P(bax, None, hax, None)
        seg = P(bax)
        bias4 = bias_arr
        bias_spec = None
        if bias4 is not None:
            # normalize to 4-D so the batch/head dims are addressable; a
            # broadcast (size-1) dim stays replicated
            bias4 = bias4.reshape((1,) * (4 - bias4.ndim) + bias4.shape)
            bias_spec = P(bax if bias4.shape[0] > 1 else None,
                          hax if bias4.shape[1] > 1 else None, None, None)

        def shard(q, k, v, bias_, qseg, kvseg, seed):
            if seed is not None:
                # the in-kernel dropout hash sees shard-LOCAL head
                # indices: fold the shard's mesh position into the seed
                # so shards do not repeat one another's pattern
                for ax in (bax, hax):
                    if ax is not None:
                        seed = seed * jnp.int32(1000003) \
                            + jax.lax.axis_index(ax)
            return kernel(q, k, v, bias_, qseg, kvseg, seed)

        # check_vma off: pallas_call's output avals carry no vma
        # annotation, which the checker (not the semantics) rejects
        return jax.shard_map(
            shard, mesh=mesh,
            in_specs=(qkv, qkv, qkv, bias_spec, seg, seg, P()),
            out_specs=qkv, check_vma=False)(
            q, k, v, bias4, qseg_arr, kvseg_arr, seed_arr)
    return apply_op(f, query, key, value, op_name="flash_attention")
