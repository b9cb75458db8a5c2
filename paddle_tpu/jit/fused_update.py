"""Multi-tensor (fused) optimizer update for the compiled train step.

The reference framework ships hand-written fused kernels
(``multi_tensor_adam``, ``paddle/phi/kernels/gpu/multi_tensor_*``) because a
per-parameter optimizer loop dispatches hundreds of tiny kernels. The XLA
analog of that problem survives jit: tracing ``_update`` once per parameter
emits ~100s of small elementwise subgraphs plus N small reductions for the
global-norm clip, and the TPU pays scheduling + tiling overhead on every one
of them. BENCH r03–r05 attribute ~5% of the full step to exactly this glue.

This module precomputes a **flat-buffer layout** on the host: trainable
parameters are grouped into buckets by everything that must be uniform for a
single shape-polymorphic update call —

- param-group index (carries the group's lr/decay/kwargs),
- array dtype,
- master-weight-ness (``multi_precision`` bf16/f16 params update in f32),
- sharding (only replicated params fuse; TP/ZeRO-sharded ones keep the
  per-param path so their PartitionSpecs survive),
- host-resolved per-param scalars: AdamW's ``lr_ratio`` and decoupled decay
  coefficient (``apply_decay_param_fun``) — resolved here, once, instead of
  through the removed ``opt._cur_param`` trace-time side channel,
- scalar accumulator values (``beta1_pow`` …) so params that joined the
  optimizer at different steps never share a bucket,

and inside the trace each bucket runs ONE ``opt._update`` over concatenated
1-D param/grad/moment buffers. Global-norm grad clip becomes one dot product
per bucket instead of N per-param reductions. The per-parameter state layout
is preserved at the boundary: inputs are the optimizer's normal per-param
accumulators and outputs are split back per param, so ``state_dict()``,
checkpointing/reshard (PR 3) and ZeRO accumulator sharding are untouched.

Numerics: the fused update applies bitwise the same elementwise operations to
every element as the per-param loop, so it is bit-exact in f32 — except under
``ClipGradByGlobalNorm``, where summing one dot per bucket instead of N
per-param partial sums changes the floating-point reduction order of the
norm (≈1 ulp on the scale factor; docs/PERFORMANCE.md#numerics).

Disable with ``PADDLE_TPU_FUSED_OPTIMIZER=0`` or ``TrainStep(fused=False)``.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["FlatLayout", "Bucket", "build_layout", "fused_clip_and_update",
           "fused_enabled"]


def fused_enabled() -> bool:
    """Process default for the fused path (``TrainStep(fused=...)`` wins)."""
    return os.environ.get("PADDLE_TPU_FUSED_OPTIMIZER", "1") != "0"


def _replicated(spec) -> bool:
    """True when a ``_sharding_spec`` annotation means fully replicated
    (absent, empty ``P()``, or all-None axes)."""
    return spec is None or all(s is None for s in spec)


@dataclass
class Bucket:
    """One fused-update group: every field that feeds the update rule is
    uniform across ``names`` (enforced by the bucket key)."""
    names: Tuple[str, ...]
    shapes: Tuple[tuple, ...]
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]
    group_index: int
    master: bool
    lr_ratio: Optional[float]       # None -> no per-param scaling (bit-exact)
    decay_coeff: float              # decoupled (AdamW) coefficient
    decay: object                   # non-decoupled regularizer to fold, or None
    kwargs: dict                    # _update keyword args (betas, eps, ...)
    vector_keys: Tuple[str, ...]    # state entries with the param's shape
    scalar_keys: Tuple[str, ...]    # 0-d state entries shared bucket-wide


@dataclass
class FlatLayout:
    """Host-side plan: fusable buckets + the residue that keeps the
    per-param loop (sharded params, exotic state shapes, unhashable
    kwargs). Built once per TrainStep compile key."""
    buckets: List[Bucket] = field(default_factory=list)
    residue: List[str] = field(default_factory=list)

    @property
    def fused_names(self) -> List[str]:
        return [n for b in self.buckets for n in b.names]


# regularizers known to be elementwise/shape-polymorphic, safe to fold into
# a concatenated grad buffer; anything else sends the param to the residue
def _decay_fusable(decay) -> bool:
    if decay is None:
        return True
    from paddle_tpu.regularizer import L1Decay, L2Decay
    return isinstance(decay, (L1Decay, L2Decay))


def build_layout(opt, params: Dict[str, object],
                 train_names: Sequence[str]) -> Optional[FlatLayout]:
    """Plan the fused update for ``train_names`` (TrainStep's train subset,
    in registration order). Returns None when the optimizer cannot fuse at
    all (no ``_fusable_update`` rule, or ZeRO accumulator sharding is
    active — flat buffers would break the per-accumulator PartitionSpecs).
    """
    if not getattr(opt, "_fusable_update", False):
        return None
    if getattr(opt, "_shard_states_axis", None) is not None:
        return None

    group_index = {id(p): gi for gi, g in enumerate(opt._param_groups)
                   for p in g["params"]}
    layout = FlatLayout()
    groups: Dict[tuple, list] = {}

    for name in train_names:
        p = params[name]
        gi = group_index.get(id(p))
        if gi is None or not _replicated(getattr(p, "_sharding_spec", None)):
            layout.residue.append(name)
            continue
        group = opt._param_groups[gi]
        decay = group.get("weight_decay", opt.regularization)
        if opt._decoupled_decay:
            dcoeff = float(opt._decay_coeff_for(p, decay))
            fold_decay = None
        else:
            dcoeff = 0.0
            fold_decay = decay
            if not _decay_fusable(decay):
                layout.residue.append(name)
                continue
        # host-resolved per-param lr scaling (AdamW lr_ratio); None when
        # the hook is the identity so the traced multiply is skipped and
        # the unscaled path stays bit-exact with the eager loop
        ratio = float(opt._param_lr(p, 1.0))
        lr_ratio = None if ratio == 1.0 else ratio

        st = opt._ensure_state(p)
        vector_keys, scalar_keys, scalar_vals = [], [], []
        fusable = True
        for k, v in st.items():
            if k == "master_weight":
                continue
            shape = getattr(v, "shape", None)
            if shape == tuple(p.data.shape):
                vector_keys.append(k)
            elif shape == ():
                scalar_keys.append(k)
                scalar_vals.append((k, float(np.asarray(v))))
            else:
                fusable = False  # exotic state shape: keep per-param
                break
        if not fusable:
            layout.residue.append(name)
            continue
        try:
            kw = opt._param_group_kwargs(p, group)
            kw_key = tuple(sorted(kw.items()))
            hash(kw_key)
        except TypeError:
            layout.residue.append(name)
            continue
        key = (gi, str(p.data.dtype), "master_weight" in st, lr_ratio,
               dcoeff, tuple(scalar_vals), kw_key)
        groups.setdefault(key, []).append(
            (name, tuple(p.data.shape), kw, fold_decay,
             tuple(vector_keys), tuple(scalar_keys)))

    for (gi, dtype_s, master, lr_ratio, dcoeff, _svals, _kwk), members \
            in groups.items():
        names, shapes, sizes, offsets = [], [], [], []
        off = 0
        for name, shape, _kw, _dec, _vk, _sk in members:
            names.append(name)
            shapes.append(shape)
            size = int(np.prod(shape)) if shape else 1
            sizes.append(size)
            offsets.append(off)
            off += size
        first = members[0]
        layout.buckets.append(Bucket(
            names=tuple(names), shapes=tuple(shapes), sizes=tuple(sizes),
            offsets=tuple(offsets), group_index=gi, master=master,
            lr_ratio=lr_ratio, decay_coeff=dcoeff, decay=first[3],
            kwargs=first[2], vector_keys=first[4], scalar_keys=first[5]))
    return layout


def _flat(jnp, arrs):
    if len(arrs) == 1:
        return jnp.reshape(arrs[0], (-1,))
    return jnp.concatenate([jnp.reshape(a, (-1,)) for a in arrs])


def build_flat_states(opt, layout: FlatLayout, params,
                      consume: bool = False) -> list:
    """Concatenate the per-parameter accumulators into one flat buffer per
    (bucket, state-key) — the persistent hot-path representation the
    compiled step updates IN PLACE via buffer donation (no per-step
    concat/split of optimizer state; that round trip measured ~2x the
    step's memory traffic). Runs once per layout (or after an external
    ``set_state_dict`` invalidates the cache).

    Each flat is one jitted concatenate (eager, every reshape is a device
    copy of its own). ``consume=True`` (the TrainStep, which releases the
    per-parameter arrays anyway) pops the arrays a flat was built from
    before the next is built, so the peak is one flat buffer above the
    state — not a second copy of all of it, which the 0.7B bench model's
    8.4 GB of accumulators cannot afford on a 16 GB chip. The dicts keep
    their identity (the caller's invalidation record) and get their
    contents back from ``_flush_flat``."""
    import jax
    import jax.numpy as jnp
    concat = jax.jit(lambda arrs: _flat(jnp, arrs))
    flats = []
    for b in layout.buckets:
        sts = [opt._ensure_state(params[n]) for n in b.names]
        f = {k: sts[0][k] for k in b.scalar_keys}
        keys = list(b.vector_keys) + (["master_weight"] if b.master else [])
        for k in keys:
            f[k] = concat([st.pop(k) if consume else st[k] for st in sts])
        flats.append(f)
    return flats


def split_flat_states(layout: FlatLayout, flats) -> list:
    """Inverse of :func:`build_flat_states`: per-bucket lists of
    per-parameter state dicts (slice + reshape — values bitwise equal to
    what the per-param loop would have stored). Used by the flush seam
    that keeps ``opt.state_dict()`` / checkpoints on the per-parameter
    layout."""
    import jax.numpy as jnp
    out = []
    for b, f in zip(layout.buckets, flats):
        per = []
        for name, off, size, shape in zip(b.names, b.offsets, b.sizes,
                                          b.shapes):
            st = {}
            for k in b.vector_keys:
                st[k] = jnp.reshape(f[k][off:off + size], shape)
            for k in b.scalar_keys:
                # one DISTINCT buffer per param: a shared scalar would be
                # donated once per param by a consuming looped TrainStep
                # (double-donate rejection)
                st[k] = f[k].copy()
            if b.master:
                st["master_weight"] = jnp.reshape(
                    f["master_weight"][off:off + size], shape)
            per.append(st)
        out.append(per)
    return out


def fused_clip_and_update(opt, layout: FlatLayout, train, grads, flats,
                          group_lrs, clip_pure):
    """Traced body: clip + update for the fused buckets.

    Returns ``(new_train_fused, new_flats, res_grads, global_norm)`` —
    per-param new parameter arrays for the fused names, the updated flat
    state buffers (same structure as ``flats``, donated/aliased by the
    caller), the residue gradients for the per-param fallback loop
    (already clipped, whichever strategy applied), and the pre-clip
    global gradient norm when the strategy is ``ClipGradByGlobalNorm``
    (None otherwise) — already reduced for the scale, surfaced so
    TrainStep can publish it instead of throwing it away.

    ``clip_pure`` is TrainStep's per-param clip fallback, used verbatim
    for strategies that are inherently per-tensor (``ClipGradByNorm``).

    Shape of the math (and why): gradients concatenate once per bucket;
    the rule's ``_update_delta`` runs ONE shape-polymorphic call per
    bucket over the flat grad + flat state (a handful of large elementwise
    kernels instead of ~100s of per-param ones); the new flat states are
    emitted as whole outputs (materialized once — donation aliases them
    onto the inputs); only the per-parameter *parameter* update touches
    slices, each a cheap read of the materialized delta / master buffer.
    """
    import jax.numpy as jnp
    from paddle_tpu.nn.clip import ClipGradByGlobalNorm, ClipGradByValue

    clip = opt._grad_clip
    pre_clipped = False
    if clip is not None and not isinstance(
            clip, (ClipGradByGlobalNorm, ClipGradByValue)):
        grads = clip_pure(grads)   # per-tensor strategy: clip first
        pre_clipped = True

    # concatenate raw grads once per bucket (original dtype — clip sees
    # the same values/order as the eager path)
    flat_gs = [_flat(jnp, [grads[n] for n in b.names])
               for b in layout.buckets]
    res_grads = {n: grads[n] for n in layout.residue}

    global_norm = None
    if not pre_clipped and isinstance(clip, ClipGradByGlobalNorm):
        # one dot per bucket instead of one small reduction per param
        # (changes the norm's float summation order vs eager — the one
        # documented non-bit-exact spot, docs/PERFORMANCE.md#numerics)
        sq = [jnp.sum(jnp.square(f.astype(jnp.float32))) for f in flat_gs]
        sq += [jnp.sum(jnp.square(g.astype(jnp.float32)))
               for g in res_grads.values()]
        global_norm = jnp.sqrt(sum(sq))
        scale = clip.clip_norm / jnp.maximum(global_norm, clip.clip_norm)
        flat_gs = [f * scale.astype(f.dtype) for f in flat_gs]
        res_grads = {n: g * scale.astype(g.dtype)
                     for n, g in res_grads.items()}
    elif not pre_clipped and isinstance(clip, ClipGradByValue):
        flat_gs = [jnp.clip(f, clip.min, clip.max) for f in flat_gs]
        res_grads = {n: jnp.clip(g, clip.min, clip.max)
                     for n, g in res_grads.items()}

    new_train, new_flats = {}, []
    for b, f, flat_g in zip(layout.buckets, flats, flat_gs):
        eff_lr = group_lrs[b.group_index]
        if b.lr_ratio is not None:
            eff_lr = eff_lr * b.lr_ratio
        if b.master:
            flat_g = flat_g.astype(jnp.float32)
        if b.decay is not None:          # non-decoupled: fold into the grad
            psrc = f["master_weight"] if b.master \
                else _flat(jnp, [train[n] for n in b.names])
            flat_g = b.decay(psrc, flat_g)
        flat_state = {k: f[k] for k in b.vector_keys}
        for k in b.scalar_keys:
            flat_state[k] = f[k]
        delta, new_fs = opt._update_delta(flat_g, flat_state, eff_lr,
                                          **b.kwargs)
        wd = b.decay_coeff
        if b.master:
            fm = f["master_weight"]
            if wd:
                fm = fm * (1.0 - eff_lr * wd)
            new_master = fm - delta.astype(jnp.float32)
            new_fs = dict(new_fs)
            new_fs["master_weight"] = new_master
            for name, off, size, shape in zip(b.names, b.offsets, b.sizes,
                                              b.shapes):
                seg = jnp.reshape(new_master[off:off + size], shape)
                new_train[name] = seg.astype(train[name].dtype)
        else:
            for name, off, size, shape in zip(b.names, b.offsets, b.sizes,
                                              b.shapes):
                p = train[name]
                if wd:
                    p = p * (1.0 - eff_lr * wd)
                seg = jnp.reshape(delta[off:off + size], shape)
                new_train[name] = p - seg.astype(p.dtype)
        new_flats.append(new_fs)
    return new_train, new_flats, res_grads, global_norm
