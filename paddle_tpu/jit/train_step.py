"""TrainStep — one fully-compiled training iteration.

forward + loss + backward + grad-clip + optimizer update as ONE jitted XLA
program with donated buffers. This is the hot path SURVEY.md:633 calls
mandatory ("per-op eager dispatch is untenable; lazy/compiled execution is
the top risk") and the TPU answer to the reference's static-graph executor
(``InterpreterCore``) + fused optimizer kernels: XLA fuses the whole step,
overlaps collectives with compute, and updates parameters in place via buffer
donation.

Usage::

    step = paddle_tpu.jit.TrainStep(model, loss_fn, optimizer)
    loss = step(x, y)          # loss_fn(model, x, y) -> scalar loss Tensor

Parameters, optimizer accumulators and batch-norm buffers are updated in
place (storage replacement) after each call; the LR is threaded as a runtime
scalar so schedulers never retrigger compilation.

Step-glue fast paths (docs/PERFORMANCE.md):

- **Fused multi-tensor optimizer** (``jit.fused_update``): instead of
  tracing the update rule once per parameter (~100s of tiny elementwise
  kernels + N small clip reductions), a precomputed flat-buffer layout runs
  one update per (group, dtype, master, sharding) bucket over concatenated
  1-D buffers, with global-norm clip as one dot per bucket. Per-parameter
  state layout is preserved at the step boundary. ``fused=False`` or
  ``PADDLE_TPU_FUSED_OPTIMIZER=0`` restores the per-param loop.
- **Bucketed dp gradient collectives** (``jit.bucketing``): for a pure-dp
  ``DataParallel`` model the step computes per-shard gradients under
  ``shard_map`` and reduces them in size-targeted buckets (one ``pmean``
  per bucket, reverse registration order) instead of GSPMD's one
  all-reduce per parameter — giving the latency-hiding scheduler a handful
  of large, early-issuable async collectives to overlap with the rest of
  backward. ``bucketed=False`` or ``PADDLE_TPU_BUCKETED_GRADS=0`` restores
  pure GSPMD.
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import numpy as np

from paddle_tpu.core import generator as _gen
from paddle_tpu.core.autograd import no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn.layer_base import Layer
from .functional import functional_state, swap_state
from .api import _sig_of, _unwrap, _wrap
from .fused_update import (_flat, build_flat_states, build_layout,
                           fused_clip_and_update, fused_enabled,
                           split_flat_states)
from .bucketing import (bucketed_eligibility, bucketed_enabled,
                        plan_comm_buckets)

#: key under which the fused buckets' flat state rides the compiled step's
#: ``states`` pytree (cannot collide with parameter names, which are
#: dotted attribute paths)
FUSED_KEY = "__fused__"

__all__ = ["TrainStep"]


class TrainStep:
    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate: bool = True, mesh=None, input_spec=None,
                 fused=None, bucketed=None):
        """``mesh``/``input_spec`` activate SPMD compilation: every batch
        leaf is placed with ``input_spec`` (a PartitionSpec, default: shard
        dim 0 on the mesh's ``dp`` axis; a ``DataParallel`` wrapper supplies
        its ``batch_spec``), parameters keep their ``_sharding_spec``
        annotations (replicated when unannotated — plain DP; sharded for
        TP/ZeRO), and XLA inserts all gradient/activation collectives.

        ``fused``/``bucketed`` override the env defaults for the fused
        multi-tensor optimizer and bucketed dp gradient collectives (None
        = follow ``PADDLE_TPU_FUSED_OPTIMIZER`` /
        ``PADDLE_TPU_BUCKETED_GRADS``)."""
        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._donate = donate
        self._cache = {}
        self._fused = fused_enabled() if fused is None else bool(fused)
        self._bucketed = bucketed_enabled() if bucketed is None \
            else bool(bucketed)
        # per-compile-key plan: (FlatLayout|None, comm buckets|None, reason)
        self._plans = {}
        # fused flat optimizer state: (layout_sig, layout, [per-bucket
        # {state_key: flat array}], [per-bucket {name: id(installed
        # per-param dict)}]). Keyed by the layout's STRUCTURE (not the
        # compile key) so compile keys that share a trainable set — e.g.
        # alternating batch signatures — reuse one set of flats instead of
        # flushing/rebuilding per step. The flats are the authoritative
        # hot-path state between steps; _flush_flat re-materializes the
        # per-parameter layout on demand (state_dict, eager step, another
        # TrainStep) — see docs/PERFORMANCE.md.
        self._flat_cache = None
        from paddle_tpu.distributed.parallel import DataParallel
        if mesh is None and isinstance(model, DataParallel):
            mesh = model._mesh
        if input_spec is None and isinstance(model, DataParallel):
            input_spec = model.batch_spec
        self._mesh = mesh
        self._input_spec = input_spec
        self._params = {name: p for name, p in model.named_parameters()}
        # only parameters handed to the optimizer are trained — params the
        # user excluded (freeze-by-exclusion fine-tuning) stay frozen,
        # matching eager step() semantics
        self._opt_param_ids = {id(p) for p in optimizer._parameter_list}
        self._group_index = {id(p): gi
                             for gi, g in enumerate(optimizer._param_groups)
                             for p in g["params"]}
        if mesh is not None:
            # place every parameter and buffer by its annotation NOW:
            # layers stamp ``_sharding_spec`` but initialize on the
            # default device, and a model left there (with the
            # accumulators created from it below) parks the whole
            # training state on the first chip until the first call
            # reshards it — 9.8 GB of a 16 GB chip for the 0.7B bench
            # model. Avals carry the mesh, so it would also make the
            # second call's inputs a different type from the first's:
            # one full recompile.
            from jax.sharding import NamedSharding
            from paddle_tpu.distributed import spec_of
            for t in list(self._params.values()) + [
                    b for _, b in model.named_buffers() if b is not None]:
                t._data = jax.device_put(
                    t._data, NamedSharding(mesh, spec_of(t)))
        # Accumulators must exist before the first trace. Donated buffers
        # must be distinct: cloned layers (set_value's no-op astype) and
        # cached constants can silently share device buffers, which the
        # donation path rejects as a double-donate — uniquify by buffer.
        seen = set()

        def uniquify(arr):
            try:
                key = arr.unsafe_buffer_pointer()
            except Exception:
                key = id(arr)
            if key in seen:
                arr = arr.copy()
                try:
                    key = arr.unsafe_buffer_pointer()
                except Exception:
                    key = id(arr)
            seen.add(key)
            return arr

        for p in self._params.values():
            if not p.stop_gradient:
                if donate:
                    p._data = uniquify(p._data)
                st = optimizer._ensure_state(p)
                if donate:
                    for k, v in st.items():
                        if hasattr(v, "copy"):
                            st[k] = uniquify(v)
        self._register_memory_owners()

    def _register_memory_owners(self):
        """Hand the HBM ledger (docs/OBSERVABILITY.md#memory) the two
        trees this step owns for its lifetime: the parameters and the
        optimizer accumulators (per-param dicts plus the fused flats —
        whichever currently holds the authoritative copies). Weakref
        closures: a registration must not keep a discarded TrainStep —
        and its buffers — alive, and returning None after death lets
        the ledger drop the entry itself."""
        import weakref

        from paddle_tpu.observability import memory as _obs_memory

        wself = weakref.ref(self)

        def _param_buffers():
            s = wself()
            if s is None:
                return None
            return [p._data for p in s._params.values()]

        def _opt_state_buffers():
            s = wself()
            if s is None:
                return None
            trees = list(s._opt._state.values())
            if s._flat_cache is not None:
                trees.append(s._flat_cache[2])
            return trees

        _obs_memory.register("model_params", _param_buffers)
        _obs_memory.register("optimizer_state", _opt_state_buffers)

    # -- pure helpers ---------------------------------------------------------
    def _clip_pure(self, grads: Dict[str, object]) -> Dict[str, object]:
        clipped, _ = self._clip_pure_with_norm(grads)
        return clipped

    def _clip_pure_with_norm(self, grads):
        """``(clipped, global_norm)`` — the norm is a free byproduct of
        ``ClipGradByGlobalNorm`` (None for other strategies / no clip);
        surfaced so the step can publish ``train_grad_norm`` instead of
        recomputing the reduction it already paid for."""
        from paddle_tpu.nn.clip import ClipGradByGlobalNorm
        clip = self._opt._grad_clip
        if clip is None:
            return grads, None
        names = list(grads.keys())
        pairs = [(self._params[n], Tensor(grads[n])) for n in names]
        if isinstance(clip, ClipGradByGlobalNorm):
            clipped, gnorm = clip._clip_with_norm(pairs)
        else:
            clipped, gnorm = clip(pairs), None
        return {n: c.data for n, (_, c) in zip(names, clipped)}, gnorm

    def _update_loop(self, names, train, grads, states, group_lrs):
        """The classic per-parameter update (same rule the eager step()
        runs). ``group_lrs`` holds one traced effective-LR scalar per param
        group (scheduler values are resolved host-side each call, never
        baked into the trace); per-param kwargs come from the host-side
        ``_param_group_kwargs`` hook — nothing on ``opt`` is mutated
        inside the trace."""
        opt = self._opt
        new_train, new_states = {}, {}
        for name in names:
            p = self._params[name]
            p_arr = train[name]
            g = grads[name]
            state = states[name]
            gi = self._group_index[id(p)]
            group = opt._param_groups[gi]
            decay = group.get("weight_decay", opt.regularization)
            eff_lr = group_lrs[gi]
            if "master_weight" in state:
                g = g.astype(jax.numpy.float32)
                p_arr = state["master_weight"]
            if decay is not None and not opt._decoupled_decay:
                g = decay(p_arr, g)
            dcoeff = opt._decay_coeff_for(p, decay) \
                if opt._decoupled_decay else 0.0
            kw = opt._param_group_kwargs(p, group)
            new_p, new_s = opt._update(p_arr, g, state,
                                       opt._param_lr(p, eff_lr),
                                       weight_decay=dcoeff, **kw)
            if "master_weight" in state:
                new_s["master_weight"] = new_p
                new_p = new_p.astype(self._params[name].data.dtype)
            new_train[name] = new_p
            new_states[name] = new_s
        return new_train, new_states

    def _apply_updates(self, train, grads, states, group_lrs, layout):
        """Clip + optimizer update for every train param: fused buckets
        through ``fused_update`` (flat state rides ``states[FUSED_KEY]``),
        everything else (or ``fused=False``) through the per-param loop.
        Returns ``(new_train, new_states, global_norm)`` — the clip
        path's global gradient norm (None unless ``ClipGradByGlobalNorm``
        is active)."""
        if layout is None or not layout.buckets:
            grads, gnorm = self._clip_pure_with_norm(grads)
            new_train, new_states = self._update_loop(
                list(train), train, grads, states, group_lrs)
            return new_train, new_states, gnorm
        new_train, new_flats, res_grads, gnorm = fused_clip_and_update(
            self._opt, layout, train, grads, states[FUSED_KEY], group_lrs,
            self._clip_pure)
        new_states = {FUSED_KEY: new_flats}
        if layout.residue:
            rt, rs = self._update_loop(layout.residue, train, res_grads,
                                       states, group_lrs)
            new_train.update(rt)
            new_states.update(rs)
        return new_train, new_states, gnorm

    # -- fused flat-state lifecycle -------------------------------------------
    @staticmethod
    def _layout_sig(layout):
        """Structural identity of a layout: two layouts with the same
        signature index identical flat buffers (bucket membership, order,
        state keys), so their compile keys can share one flat cache."""
        return tuple((b.names, b.vector_keys, b.scalar_keys, b.master)
                     for b in layout.buckets)

    def _flat_ids_ok(self, layout, src_ids):
        opt = self._opt
        return all(id(opt._state.get(id(self._params[n]))) == ids[n]
                   for b, ids in zip(layout.buckets, src_ids)
                   for n in b.names)

    def _release_per_param(self, layout):
        """Drop the per-parameter accumulator arrays while the flats are
        authoritative (dict identity preserved — the ids-based
        invalidation still works; the arrays themselves would otherwise
        duplicate the whole optimizer state in device memory). Readers
        always come back through ``_flush_flat``, which re-installs full
        dicts first."""
        opt = self._opt
        for b in layout.buckets:
            for n in b.names:
                d = opt._state.get(id(self._params[n]))
                if d:
                    d.clear()

    def _flat_states_for(self, layout):
        """The per-bucket flat state buffers for this layout — reused
        while nothing external rewrote the per-parameter entries
        (identity check against the dicts recorded at the last
        build/flush), rebuilt from ``opt._state`` otherwise."""
        opt = self._opt
        sig = self._layout_sig(layout)
        if self._flat_cache is not None:
            csig, clayout, flats, src_ids = self._flat_cache
            ids_ok = self._flat_ids_ok(clayout, src_ids)
            if csig == sig and ids_ok:
                self._release_per_param(clayout)
                return flats
            if ids_ok:
                # layout changed (e.g. a param unfroze) with our flats
                # still the newest values: persist them, then rebuild
                self._flush_flat()
            else:
                # something external (set_state_dict, rollback restore,
                # another TrainStep's flush) replaced per-param entries
                # AFTER our last flush — those values win; flushing now
                # would clobber them with stale flats
                self._flat_cache = None
        flats = build_flat_states(opt, layout, self._params, consume=True)
        src_ids = [{n: id(opt._state[id(self._params[n])])
                    for n in b.names} for b in layout.buckets]
        self._flat_cache = (sig, layout, flats, src_ids)
        self._release_per_param(layout)
        opt._register_state_sync(self)
        return flats

    def _flush_flat(self):
        """Materialize the flat buffers back into ``opt._state``'s
        per-parameter layout (slice + reshape — bitwise the values the
        per-param loop would have stored). Invoked through the
        optimizer's ``_sync_state`` seam by ``state_dict`` /
        ``set_state_dict`` / eager ``step()`` / other TrainSteps; cheap
        no-op when no fused step ran since the last flush. When the
        per-param entries were replaced externally AFTER our last flush
        (an eager step's own writes, a restore), those values are newer —
        the cache is dropped instead of installed."""
        if self._flat_cache is None:
            return
        sig, layout, flats, src_ids = self._flat_cache
        opt = self._opt
        if not self._flat_ids_ok(layout, src_ids):
            self._flat_cache = None
            return
        # eval_context: a flush can fire at GC time (__del__) WHILE some
        # other function is being traced — under omnistaging the split's
        # jnp ops would then stage into that trace and leak tracers into
        # opt._state (observed: poisoned state_dict after test-ordered
        # GC). Escape to the eval trace so the split always runs eagerly.
        with jax.core.eval_context():
            per = split_flat_states(layout, flats)
        new_ids = []
        for b, dicts in zip(layout.buckets, per):
            ids = {}
            for n, st in zip(b.names, dicts):
                opt._state[id(self._params[n])] = st
                ids[n] = id(st)
            new_ids.append(ids)
        # flats stay valid (flush is a read) — re-anchor the identity
        # record to the dicts just installed
        self._flat_cache = (sig, layout, flats, new_ids)

    def __del__(self):
        # a TrainStep discarded without a final state read must not take
        # the only copy of the fused accumulators with it
        try:
            self._flush_flat()
        except Exception:
            pass

    # -- compile --------------------------------------------------------------
    def _grads_gspmd(self, treedef, instrument=False, tap_order=None):
        """Gradient closure for the default path: one value_and_grad over
        the global batch; GSPMD inserts whatever collectives the shardings
        imply (per-param grad all-reduces under dp). ``instrument`` arms
        the numerics tap seam for this trace: activation-health scalars
        collected during the forward ride out through the aux channel
        (values only — ``value_and_grad`` never differentiates aux).
        Disarmed, the collect() is a no-op yielding an empty dict — zero
        extra pytree leaves, bit-identical HLO. ``tap_order`` (a list
        cell) receives the taps' EXECUTION order at trace time — jax
        pytrees iterate dicts key-sorted, so the topological order NaN
        provenance scans by must leave the trace out-of-band."""
        from paddle_tpu.observability import numerics
        from paddle_tpu.ops.pallas.flash_attention import spmd_mesh

        model, loss_fn = self._model, self._loss_fn

        def run(train, frozen, buffers, rng, flat_batch):
            args = jax.tree_util.tree_unflatten(treedef, flat_batch)
            args = _wrap(args)
            # the step key folds from (base, count) INSIDE the program —
            # same key next_key() would produce, without the eager
            # per-step dispatch (measurable step-glue on small steps)
            rng_key = jax.random.fold_in(rng[0], rng[1])

            def loss_of(train_arrs):
                state = {**train_arrs, **frozen, **buffers}
                # spmd_mesh: GSPMD cannot partition the flash kernel, so
                # it shard_maps itself over this program's dp/mp axes
                with no_grad(), _gen.rng_guard(rng_key), \
                        swap_state(model, state) as out_bufs, \
                        numerics.collect(instrument) as col, \
                        spmd_mesh(self._mesh):
                    loss = loss_fn(model, *args[0], **args[1])
                    val = loss.data if isinstance(loss, Tensor) else loss
                if tap_order is not None:
                    tap_order[:] = list(col.taps)
                return val, (out_bufs, col.taps)

            (loss_val, (new_bufs, taps)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train)
            return loss_val, grads, new_bufs, taps
        return run

    def _grads_bucketed(self, treedef, comm, flat_example,
                        instrument=False, tap_order=None):
        """Gradient closure for the bucketed-collective path: shard_map
        over ``dp`` computes per-shard gradients with no implicit
        collectives, then reduces them as ONE ``pmean`` per planned bucket
        (reverse registration order — first-complete grads reduce first)
        plus one for the scalar loss. The resulting HLO carries
        ``len(comm) + 1`` all-reduces whose explicit dependencies let the
        latency-hiding scheduler overlap them with remaining backward
        compute (the flags ``paddle_tpu.device`` enables on TPU)."""
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.observability import numerics

        model, loss_fn = self._model, self._loss_fn
        mesh = self._mesh
        seg = {}  # name -> (size, shape) for the post-reduce split
        for names in comm:
            for n in names:
                shape = tuple(self._params[n].data.shape)
                seg[n] = (int(np.prod(shape)) if shape else 1, shape)

        def local(train, frozen, rng, flat_batch):
            # step key folds from (base, count) in-program; each dp shard
            # additionally folds its axis index so per-shard randomness
            # (dropout) decorrelates
            key = jax.random.fold_in(
                jax.random.fold_in(rng[0], rng[1]),
                jax.lax.axis_index("dp"))
            args = jax.tree_util.tree_unflatten(treedef, flat_batch)
            args = _wrap(args)

            def loss_of(train_arrs):
                state = {**train_arrs, **frozen}
                with no_grad(), _gen.rng_guard(key), \
                        swap_state(model, state) as out_bufs, \
                        numerics.collect(instrument) as col:
                    loss = loss_fn(model, *args[0], **args[1])
                    val = loss.data if isinstance(loss, Tensor) else loss
                if tap_order is not None:
                    tap_order[:] = list(col.taps)
                return val, (out_bufs, col.taps)

            # the replicated parameters are cast to varying over dp
            # BEFORE the grad: differentiating through the implicit cast
            # would have autodiff psum each parameter's gradient over dp
            # (the cast's transpose), and the buckets' pmean below would
            # reduce a second time what is already the SUM over shards:
            # per-parameter all-reduces back in the program, and
            # gradients dp times too large. check_vma stays on, so what
            # leaves under out_specs=P() is proven replicated.
            train = jax.tree_util.tree_map(
                lambda a: jax.lax.pcast(a, "dp", to="varying"), train)
            (loss_val, (new_bufs, taps)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train)
            if instrument:
                # out_specs is P() (replicated): per-shard tap stats must
                # leave shard_map as globals — max/mean/sum across dp
                taps = {n: numerics.reduce_stats(st, "dp")
                        for n, st in taps.items()}
            flats = []
            for names in comm:
                flat = _flat(jnp, [grads[n] for n in names])
                flats.append(jax.lax.pmean(flat, "dp"))
            loss_val = jax.lax.pmean(loss_val, "dp")
            return loss_val, flats, new_bufs, taps

        def batch_spec(leaf):
            return P("dp") if getattr(leaf, "ndim", 0) > 0 else P()

        sm = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P(), [batch_spec(a) for a in flat_example]),
            out_specs=P())

        def run(train, frozen, buffers, rng, flat_batch):
            loss_val, flats, new_bufs, taps = sm(train, frozen, rng,
                                                 flat_batch)
            grads = {}
            for names, flat in zip(comm, flats):
                off = 0
                for n in names:
                    size, shape = seg[n]
                    grads[n] = jnp.reshape(flat[off:off + size], shape)
                    off += size
            # restore registration order so clip/update see the same
            # iteration order as the GSPMD path
            grads = {n: grads[n] for n in train}
            return loss_val, grads, new_bufs, taps
        return run

    def _numerics_grad_stats(self, grads, layout):
        """Per-parameter-bucket gradient (L2 norm, non-finite count),
        riding the FlatLayout buckets so the per-param kernel storm the
        fused optimizer killed does not return through telemetry; params
        outside a fused bucket fall back to per-param-group aggregates.
        Also returns the total sum-of-squares so the observatory gets a
        global grad norm even when no global-norm clip computes one."""
        import jax.numpy as jnp

        def agg(names):
            sq = sum(jnp.sum(jnp.square(grads[n].astype(jnp.float32)))
                     for n in names)
            nonf = sum(jnp.sum(jnp.logical_not(
                jnp.isfinite(grads[n])).astype(jnp.int32)) for n in names)
            return sq, nonf

        out, total = {}, jnp.float32(0.0)
        rest = list(grads)
        if layout is not None and layout.buckets:
            for i, b in enumerate(layout.buckets):
                sq, nonf = agg(b.names)
                out[f"bucket{i}:{b.names[0]}"] = (jnp.sqrt(sq), nonf)
                total = total + sq
            rest = list(layout.residue)
        groups = {}
        for n in rest:
            gi = self._group_index[id(self._params[n])]
            groups.setdefault(gi, []).append(n)
        for gi in sorted(groups):
            sq, nonf = agg(groups[gi])
            out[f"group{gi}"] = (jnp.sqrt(sq), nonf)
            total = total + sq
        return out, total

    def _numerics_update_stats(self, train, new_train, layout):
        """Per-bucket (update_norm, param_norm) from the optimizer deltas
        actually applied this step — the observatory publishes their
        ratio (the classic 1e-3-ish LR-health signal)."""
        import jax.numpy as jnp

        def agg(names):
            us = sum(jnp.sum(jnp.square(new_train[n].astype(jnp.float32)
                                        - train[n].astype(jnp.float32)))
                     for n in names)
            ps = sum(jnp.sum(jnp.square(train[n].astype(jnp.float32)))
                     for n in names)
            return jnp.sqrt(us), jnp.sqrt(ps)

        out = {}
        rest = list(new_train)
        if layout is not None and layout.buckets:
            for i, b in enumerate(layout.buckets):
                out[f"bucket{i}:{b.names[0]}"] = agg(b.names)
            rest = list(layout.residue)
        groups = {}
        for n in rest:
            gi = self._group_index[id(self._params[n])]
            groups.setdefault(gi, []).append(n)
        for gi in sorted(groups):
            out[f"group{gi}"] = agg(groups[gi])
        return out

    def _compile(self, treedef, layout, comm, flat_example,
                 instrument=False, tap_order=None):
        grads_of = self._grads_bucketed(treedef, comm, flat_example,
                                        instrument=instrument,
                                        tap_order=tap_order) \
            if comm is not None else self._grads_gspmd(
                treedef, instrument=instrument, tap_order=tap_order)

        def pure(train, frozen, buffers, states, group_lrs, rng_key,
                 flat_batch):
            loss_val, grads, new_bufs, taps = grads_of(
                train, frozen, buffers, rng_key, flat_batch)
            gstats = total_sq = None
            if instrument:
                gstats, total_sq = self._numerics_grad_stats(grads, layout)
            new_train, new_states, gnorm = self._apply_updates(
                train, grads, states, group_lrs, layout)
            nums = None
            if instrument:
                import jax.numpy as jnp
                nums = {
                    "taps": taps,
                    "grads": gstats,
                    "updates": self._numerics_update_stats(
                        train, new_train, layout),
                    "grad_norm": gnorm if gnorm is not None
                    else jnp.sqrt(total_sq),
                }
            return loss_val, new_train, new_states, new_bufs, gnorm, nums

        donate = (0, 3) if self._donate else ()
        if self._mesh is None:
            return jax.jit(pure, donate_argnums=donate)

        # SPMD: per-argument shardings; GSPMD propagates through the step
        # and emits the collectives (grad psum for DP, activation
        # all-gathers for TP, ...)
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self._mesh

        def ns(spec):
            return NamedSharding(mesh, spec)

        rep = ns(PartitionSpec())

        def param_spec(name):
            s = getattr(self._params[name], "_sharding_spec", None)
            return ns(s) if s is not None else rep

        train, frozen, buffers = self._split_state()
        train_sh = {n: param_spec(n) for n in train}
        frozen_sh = {n: param_spec(n) for n in frozen}
        buf_sh = {n: rep for n in buffers}
        # per-param states only for the residue when a fused layout is
        # active — bucket flats ride states[FUSED_KEY], always replicated
        # (build_layout only fuses replicated params, and ZeRO disables
        # the layout entirely so accumulator sharding is untouched)
        per_param_names = layout.residue if layout is not None \
            and layout.buckets else list(train)
        states_sh = {
            n: {k: ns(self._state_spec(self._params[n], v))
                for k, v in self._opt._ensure_state(self._params[n]).items()}
            for n in per_param_names}
        if layout is not None and layout.buckets:
            bucket_keys = []
            for b in layout.buckets:
                keys = list(b.vector_keys) + list(b.scalar_keys)
                if b.master:
                    keys.append("master_weight")
                bucket_keys.append({k: rep for k in keys})
            states_sh[FUSED_KEY] = bucket_keys
        in_spec = self._input_spec
        if in_spec is None and "dp" in mesh.axis_names:
            in_spec = PartitionSpec("dp")

        def batch_sharding(arr):
            if in_spec is None or not hasattr(arr, "ndim") or arr.ndim == 0:
                return rep
            return ns(in_spec)

        batch_sh = [batch_sharding(a) for a in flat_example]
        lr_sh = [rep] * len(self._opt._param_groups)
        in_shardings = (train_sh, frozen_sh, buf_sh, states_sh, lr_sh, rep,
                        batch_sh)
        # trailing rep prefixes cover the grad-norm scalar and the
        # numerics sample tree (both replicated; empty subtrees — None —
        # when the executable is not instrumented)
        out_shardings = (rep, train_sh, states_sh, buf_sh, rep, rep)
        return jax.jit(pure, donate_argnums=donate,
                       in_shardings=in_shardings,
                       out_shardings=out_shardings)

    def _state_spec(self, p, leaf):
        """PartitionSpec of one accumulator leaf of parameter ``p`` under
        the mesh: the parameter's own spec for a same-shaped leaf; for a
        replicated parameter the ZeRO axis over dim 0 when
        ``group_sharded_parallel`` marked the optimizer (stage 1/2);
        replicated otherwise (scalars, odd shapes)."""
        from jax.sharding import PartitionSpec
        shape = getattr(leaf, "shape", None)
        if shape != p.data.shape:
            return PartitionSpec()
        pspec = getattr(p, "_sharding_spec", None)
        if pspec is not None:
            return pspec
        zero_axis = getattr(self._opt, "_shard_states_axis", None)
        zero_n = self._mesh.shape.get(zero_axis, 1) if zero_axis in \
            getattr(self._mesh, "axis_names", ()) else 1
        if zero_n > 1 and shape and shape[0] % zero_n == 0:
            return PartitionSpec(zero_axis, *([None] * (len(shape) - 1)))
        return PartitionSpec()

    def _place_states(self, names):
        """Put every accumulator leaf where the compiled step's
        in_shardings say (no-op for a leaf already there). They are born
        from mesh-resident parameters, i.e. committed: jit refuses a
        committed argument whose sharding differs from its in_sharding,
        and a leaf left off the mesh retypes the second call."""
        from jax.sharding import NamedSharding
        for n in names:
            p = self._params[n]
            st = self._opt._ensure_state(p)
            for k, v in st.items():
                if hasattr(v, "shape"):
                    st[k] = jax.device_put(v, NamedSharding(
                        self._mesh, self._state_spec(p, v)))

    def _split_state(self):
        """(train, frozen, buffers) arrays — train restricted to params the
        optimizer owns AND that are currently trainable."""
        train, frozen, buffers = functional_state(self._model)
        for name in list(train.keys()):
            if id(self._params[name]) not in self._opt_param_ids:
                frozen[name] = train.pop(name)
        return train, frozen, buffers

    def _group_lrs(self):
        """Effective LR per param group, resolved host-side (mirrors eager
        step(): group lr — scheduler or float — scales the optimizer lr)."""
        from paddle_tpu.optimizer import lr as lr_mod
        base = self._opt.get_lr()
        out = []
        for g in self._opt._param_groups:
            glr = g.get("learning_rate")
            if isinstance(glr, lr_mod.LRScheduler):
                out.append(np.float32(glr() * base))
            elif glr is not None:
                out.append(np.float32(glr * base))
            else:
                out.append(np.float32(base))
        return out

    # -- call -----------------------------------------------------------------
    def _prepare(self, args, kwargs, instrument=False):
        """Resolve (compile if needed) the executable for this batch
        signature and assemble its call arguments. ``instrument=True``
        resolves the numerics-instrumented twin — its own compile-cache
        entry (compile-once per signature, exactly like train/eval), so
        arming numerics mid-run costs one compile and disarming is a
        cache hit on the original program."""
        model, opt = self._model, self._opt
        # other holders of flat state (another TrainStep on this
        # optimizer) must flush before we read accumulators; our own
        # flats stay authoritative
        opt._sync_state(exclude=self)
        treedef, sig = _sig_of((args, kwargs))
        train, frozen, buffers = self._split_state()
        # the trainable-name set keys the cache too: unfreezing a param
        # changes the train pytree (and, under a mesh, the shardings)
        key = (treedef, sig, model.training, tuple(sorted(train)),
               bool(instrument))
        if key not in self._cache:
            # only shapes/dtypes are needed for sharding decisions — never
            # pin the concrete batch for the object's lifetime
            self._example_batch = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                if hasattr(a, "shape") and hasattr(a, "dtype") else a,
                _unwrap((args, kwargs)))
            flat_example, _ = jax.tree_util.tree_flatten(self._example_batch)
            # accumulators (incl. any param unfrozen after construction)
            # must exist — with their real contents, not the released
            # husks the flat cache leaves behind — before the layout
            # reads their shapes and scalar values
            self._flush_flat()
            for name in train:
                opt._ensure_state(self._params[name])
            if self._mesh is not None:
                self._place_states(train)
            layout = build_layout(opt, self._params, list(train)) \
                if self._fused else None
            comm, reason = None, "disabled"
            if self._bucketed:
                reason = bucketed_eligibility(
                    model, opt, self._mesh, self._input_spec, self._params,
                    buffers, flat_example)
                if reason is None:
                    comm = plan_comm_buckets(train)
            self._plans[key] = (layout, comm, reason)
            # filled at trace time (first execution): the taps' real
            # execution order, which the sorted-key output dict loses
            tap_order = [] if instrument else None
            if not hasattr(self, "_tap_orders"):
                self._tap_orders = {}
            self._tap_orders[key] = tap_order
            self._cache[key] = self._compile(treedef, layout, comm,
                                             flat_example,
                                             instrument=instrument,
                                             tap_order=tap_order)
            # jax.jit compiles lazily on the first concrete call — mark
            # this executable fresh so __call__ stamps that call's wall
            # into the goodput ledger's compile bin
            self._fresh_executable = True
        layout, comm, reason = self._plans[key]
        self._layout, self._comm_buckets, self._bucketed_reason = \
            layout, comm, reason
        self._active_tap_order = self._tap_orders.get(key) \
            if hasattr(self, "_tap_orders") else None

        if layout is not None and layout.buckets:
            states = {name: opt._ensure_state(self._params[name])
                      for name in layout.residue}
            states[FUSED_KEY] = self._flat_states_for(layout)
        else:
            states = {name: opt._ensure_state(self._params[name])
                      for name in train}
        flat_batch, _ = jax.tree_util.tree_flatten(_unwrap((args, kwargs)))
        base_key, count = _gen.next_key_parts()
        return train, self._cache[key], (
            train, frozen, buffers, states, self._group_lrs(),
            (base_key, np.uint32(count)), flat_batch)

    def __call__(self, *args, **kwargs):
        model, opt = self._model, self._opt
        from paddle_tpu.observability import numerics
        instrument = numerics.sample_this_step(opt._step_count + 1)
        train, compiled, call_args = self._prepare(args, kwargs,
                                                   instrument=instrument)
        if numerics.provenance_enabled():
            # the batch is never donated, so its buffers survive the
            # step — stash it (plus this step's rng parts) for the
            # NaN-provenance replay; overwritten every step, dropped
            # leaves the previous batch to the GC
            self._last_batch = (args, kwargs, call_args[5])

        from paddle_tpu.observability.comm import compute_scope
        from paddle_tpu.profiler import RecordEvent
        # one host span per compiled step; the compute_scope marks this
        # window for the comm tracer's exposure accounting — a collective
        # running concurrently (bucketed async all-reduce) is overlapped,
        # one serialized after it is exposed
        # first call of a freshly built executable carries the real XLA
        # compile (jit is lazy): time it for the goodput ledger. The
        # wall includes one execution — negligible next to the compile,
        # and exactly how fleet goodput accounting bins warmup steps.
        fresh = getattr(self, "_fresh_executable", False)
        self._fresh_executable = False
        t_compile0 = time.perf_counter() if fresh else 0.0
        with RecordEvent("TrainStep"), compute_scope():
            try:
                loss_val, new_train, new_states, new_bufs, gnorm, nums = \
                    compiled(*call_args)
            except Exception as e:
                # RESOURCE_EXHAUSTED gets one postmortem (ledger owners +
                # this executable's memory report) before re-raising;
                # anything else passes straight through
                from paddle_tpu.observability import memory as _obs_memory
                _obs_memory.handle_oom(
                    e, source="train_step",
                    report_fn=lambda: _obs_memory.MemoryReport.from_compiled(
                        compiled.lower(*call_args).compile(),
                        source="train_step"))
                raise

        if fresh:
            from paddle_tpu.observability import goodput
            goodput.record_compile(time.perf_counter() - t_compile0)

        # write back (storage replacement — same semantics as eager step())
        opt._step_count += 1
        for name, arr in new_train.items():
            p = self._params[name]
            p._data = arr
            p._version += 1
            if name in new_states:
                opt._state[id(p)] = new_states[name]
        if FUSED_KEY in new_states:
            # fused accumulators stay flat between steps (donated buffers
            # updated in place); per-param opt._state entries are
            # re-materialized lazily by _flush_flat when something reads
            # them — identity record unchanged, the flats stay newest
            sig, layout, _, src_ids = self._flat_cache
            self._flat_cache = (sig, layout, new_states[FUSED_KEY],
                                src_ids)
        named_bufs = dict(model.named_buffers())
        for name, arr in new_bufs.items():
            b = named_bufs.get(name)
            if b is not None:
                b._data = arr
        # device scalar (or None without a global-norm clip) — hapi's fit
        # loop floats it into the per-step logs, which feeds the console
        # line, the train_grad_norm gauge and NaNGuard's grad_nan check
        self.last_grad_norm = gnorm
        if nums is not None:
            try:
                self.last_numerics = numerics.host_sample(
                    nums, loss_val, tap_order=self._active_tap_order)
                numerics.get_observatory().record_sample(
                    opt._step_count, self.last_numerics)
            except Exception:
                # telemetry must never fail the step it observes
                import warnings
                warnings.warn("[numerics] sample publication failed",
                              RuntimeWarning, stacklevel=2)
        return Tensor(loss_val)

    def compiled_hlo(self, *args, **kwargs) -> str:
        """Compiled-HLO text of the step for this batch (inspection seam:
        the bucketed-collective acceptance test counts ``all-reduce`` ops
        here instead of guessing from timings). RNG-neutral: the step is
        never executed, so the key _prepare drew is handed back — an
        inspection must not shift the subsequent training key stream
        (resume == uninterrupted digest equality depends on it)."""
        rng_state = _gen.get_rng_state()
        try:
            _, compiled, call_args = self._prepare(args, kwargs)
            return compiled.lower(*call_args).compile().as_text()
        finally:
            _gen.set_rng_state(rng_state)

    def memory_report(self, *args, **kwargs):
        """XLA's memory accounting of the compiled step for this batch
        (``observability.memory.MemoryReport``; None when the backend
        doesn't report): argument/output/temp/alias/generated-code
        bytes — the runtime-truth counterpart to the static audit's
        ``largest_intermediate_bytes``, cross-checked by a tier-1 test.
        Same contract as :meth:`compiled_hlo`: RNG-neutral (the key
        ``_prepare`` drew is handed back) and retrace-free (``lower``
        shares the jit trace cache with real calls)."""
        from paddle_tpu.observability.memory import MemoryReport
        rng_state = _gen.get_rng_state()
        try:
            _, compiled, call_args = self._prepare(args, kwargs)
            return MemoryReport.from_compiled(
                compiled.lower(*call_args).compile(), source="train_step")
        finally:
            _gen.set_rng_state(rng_state)

    def numerics_probe_last(self):
        """NaN-provenance replay (docs/OBSERVABILITY.md#numerics): re-run
        forward + backward over the last stashed batch with that step's
        exact rng parts, fully instrumented, against the CURRENT
        model/optimizer state — the caller (NaNGuard) restores the last
        committed checkpoint first, so the replay answers "does the state
        training resumes from still blow up on this batch, and where
        first". No clip, no update, NOTHING donated — a probe must never
        perturb the state it inspects. Returns the host sample dict (tap
        stats + grad bucket stats + loss/grad-norm) or None when no
        batch was stashed. Compiled once per batch signature into a side
        cache (never counted by the compile-once guards on ``_cache``);
        RNG-neutral like :meth:`compiled_hlo`. The bucketed-dp path is
        replayed through the GSPMD closure (same math, global batch) —
        per-shard dropout decorrelation is the one approximation."""
        stash = getattr(self, "_last_batch", None)
        if stash is None:
            return None
        args, kwargs, rng_parts = stash
        from paddle_tpu.observability import numerics
        rng_state = _gen.get_rng_state()
        try:
            self._opt._sync_state(exclude=self)
            treedef, sig = _sig_of((args, kwargs))
            train, frozen, buffers = self._split_state()
            key = (treedef, sig, self._model.training,
                   tuple(sorted(train)))
            if not hasattr(self, "_probe_cache"):
                self._probe_cache = {}
            if key not in self._probe_cache:
                # the layout only names the grad buckets here; reuse the
                # step's plan when one exists for this signature
                plan = self._plans.get(key + (True,)) \
                    or self._plans.get(key + (False,))
                layout = plan[0] if plan is not None else None
                order = []
                grads_of = self._grads_gspmd(treedef, instrument=True,
                                             tap_order=order)

                def probe(train_, frozen_, buffers_, rng, flat_batch):
                    import jax.numpy as jnp
                    loss_val, grads, _bufs, taps = grads_of(
                        train_, frozen_, buffers_, rng, flat_batch)
                    gstats, total_sq = self._numerics_grad_stats(
                        grads, layout)
                    return {"taps": taps, "grads": gstats,
                            "grad_norm": jnp.sqrt(total_sq),
                            "loss": loss_val}

                self._probe_cache[key] = (jax.jit(probe), order)
            flat_batch, _ = jax.tree_util.tree_flatten(
                _unwrap((args, kwargs)))
            fn, order = self._probe_cache[key]
            out = fn(train, frozen, buffers, rng_parts, flat_batch)
            loss_val = out.pop("loss")
            return numerics.host_sample(out, loss_val, tap_order=order)
        finally:
            _gen.set_rng_state(rng_state)

    def clear_cache(self):
        self._flush_flat()
        self._flat_cache = None
        self._cache.clear()
        self._plans.clear()
        if hasattr(self, "_probe_cache"):
            self._probe_cache.clear()
