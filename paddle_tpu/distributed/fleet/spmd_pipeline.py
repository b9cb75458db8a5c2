"""SPMD (collective) pipeline parallelism: the whole 1F1B/interleave
schedule inside ONE compiled XLA program.

Why a second pipeline engine: ``fleet/pipeline.py``'s list scheduler moves
micro-batch activations with single-controller ``jax.device_put`` — legal
only across devices addressable by one process, so its pipeline cannot span
hosts. The reference spans nodes with per-rank send_v2/recv_v2 loops
(``fleet/meta_parallel/pp_utils/p2p_communication.py:298``,
``pipeline_parallel.py:117``). The TPU-native equivalent of those p2p ops is
``lax.ppermute`` over a ``pp`` mesh axis inside a compiled program: XLA
lowers every stage hop to an ICI/DCN collective-permute, so the same
program runs unmodified on a v5p pod where stages sit on different hosts
(multi-controller: every process executes the same jitted step).

Design (the "How to Scale Your Model" pipelining recipe, done natively):

- Stage bodies are HOMOGENEOUS (the transformer trunk): one ``body_fn``
  applied by every stage to its own parameter slice. Parameters are stacked
  ``[v, S, ...]`` (virtual chunk r, stage s ⇒ pipeline chunk ``c = r*S+s``,
  the Megatron round-robin placement) and sharded ``P(None, 'pp', ...)`` —
  each stage holds exactly its ``v`` chunks. Embedding/head stay OUTSIDE
  the pipelined region (replicated over pp, sharded over dp/mp), which is
  how production TPU pipelining divides labor.

- The schedule is a ``lax.scan`` over clock ticks. At tick ``t`` stage
  ``s`` decomposes ``u = t - s`` as ``u = g·vS + r·S + i`` (mixed radix):
  it runs virtual chunk ``r`` on micro-batch ``m = g·S + i`` iff
  ``u ≥ 0 and m < M``. Boundary activations rotate one stage per tick via
  a ``ppermute`` ring (stage S-1 wraps to stage 0 carrying the next
  virtual round — the circular/interleaved pipeline). Inactive ticks
  compute on zeros and are masked: that idle compute IS the bubble,
  ``(S-1)/(v·M + S-1)`` of the span — the same fraction the list
  scheduler measures for the interleaved schedule.

- Backward needs no scheduler: ``jax.grad`` through scan + ppermute
  generates the reverse pipeline (transpose of a permute is the reverse
  permute), and ``jax.checkpoint`` around the body gives 1F1B-grade
  memory: only boundary activations are saved per tick, chunk internals
  are rematerialized.

Boundaries are pytrees: ``body_fn`` may thread tuples/dicts of tensors
between stages (the reference's ``_p2p_helper`` handshakes arbitrary tensor
tuples — here the pytree structure is static so no meta handshake is
needed).
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from paddle_tpu.core.autograd import apply_op, no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn.layer_base import Layer
from ..mesh import get_mesh

__all__ = ["pipeline_spmd", "spmd_schedule_stats", "SpmdPipelineLayer",
           "SpmdPipelineParallel", "pipeline_spmd_hetero",
           "SpmdHeteroPipelineLayer"]


def _completion_ticks(S: int, v: int, M: int) -> np.ndarray:
    """Tick at which micro-batch m's LAST chunk (stage S-1, round v-1)
    executes: t_m = (S-1) + (m//S)·vS + (v-1)·S + (m%S)."""
    m = np.arange(M)
    return (S - 1) + (m // S) * v * S + (v - 1) * S + (m % S)


def spmd_schedule_stats(num_stages: int, num_virtual_stages: int,
                        n_micro: int) -> dict:
    """Analytic schedule accounting in forward-tick units (the compiled
    schedule is exact, so no simulation is needed; the backward pipeline
    autodiff generates mirrors it). Matches the list scheduler's keys."""
    S, v, M = num_stages, num_virtual_stages, n_micro
    span = int(_completion_ticks(S, v, M)[-1]) + 1
    busy = v * M  # ticks each stage actually computes
    return {
        "slots_span": span,
        "busy": {s: busy for s in range(S)},
        "bubble_fraction": round(1.0 - busy / span, 4) if span else 0.0,
        "n_micro": M,
        "n_chunks": S * v,
    }


def pipeline_spmd(body_fn: Callable, stacked_params, micro_inputs,
                  mesh=None, axis: str = "pp",
                  num_virtual_stages: int = 1, remat: bool = True):
    """Run the collective pipeline on raw jax pytrees.

    ``body_fn(chunk_params, x) -> y``: one pipeline chunk. ``x``/``y`` are
    pytrees of identical structure/shape/dtype (the ring carry).
    ``stacked_params``: pytree with leaves ``[v, S, ...]``.
    ``micro_inputs``: pytree with leaves ``[M, ...]`` (micro-batch leading).
    Returns the last chunk's outputs, leaves ``[M, ...]``, replicated over
    ``axis``. Differentiable; all stage hops are compiled ppermutes.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise RuntimeError(f"pipeline_spmd needs a mesh with axis {axis!r}")
    S = mesh.shape[axis]
    v = num_virtual_stages
    leaves = jax.tree_util.tree_leaves(micro_inputs)
    M = leaves[0].shape[0]
    for lf in jax.tree_util.tree_leaves(stacked_params):
        if lf.shape[:2] != (v, S):
            raise ValueError(
                f"stacked param leaf {lf.shape} must lead with "
                f"[v={v}, S={S}]")
    t_idx = _completion_ticks(S, v, M)
    span = int(t_idx[-1]) + 1
    body = jax.checkpoint(body_fn) if remat else body_fn

    from .utils import mark_varying

    def _pvary(x):
        return mark_varying(x, axis)

    def per_stage(params, xs):
        # params leaves [v, 1, ...] (stage slice); xs leaves [M, ...]
        params = jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 1), params)
        s = jax.lax.axis_index(axis)
        vS = v * S
        perm = [(j, (j + 1) % S) for j in range(S)]

        def tick(carry, t):
            u = t - s
            g = u // vS
            rem = u % vS
            r = rem // S
            i = rem % S
            m = g * S + i
            active = (u >= 0) & (m < M)
            m_safe = jnp.clip(m, 0, M - 1)
            inject = active & (s == 0) & (r == 0)

            def pick(buf, ix):
                return jax.lax.dynamic_index_in_dim(buf, ix, 0,
                                                    keepdims=False)

            x_new = jax.tree_util.tree_map(
                lambda b: pick(b, m_safe), xs)
            x_in = jax.tree_util.tree_map(
                lambda new, c: jnp.where(
                    active,
                    jnp.where(inject, _pvary(new), c),
                    jnp.zeros_like(c)),
                x_new, carry)
            cp = jax.tree_util.tree_map(
                lambda a: pick(a, jnp.clip(r, 0, v - 1)), params)
            y = body(cp, x_in)
            # inactive stages computed on zeros: mask so garbage can never
            # reach an active consumer (and grads through the masked side
            # are exact zeros)
            y = jax.tree_util.tree_map(
                lambda a: jnp.where(active, a, jnp.zeros_like(a)), y)
            y_next = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, axis, perm), y)
            return y_next, y

        x0 = jax.tree_util.tree_map(
            lambda b: _pvary(jnp.zeros(b.shape[1:], b.dtype)), xs)
        _, ys = jax.lax.scan(tick, x0, jnp.arange(span))
        # micro m's final-chunk output was emitted on stage S-1 at tick
        # t_idx[m]; everywhere else the buffer holds zeros, so a psum over
        # the pp ring is a pure selection (no arithmetic mixing)
        is_last = (s == S - 1)
        sel = jnp.asarray(t_idx)

        def collect(buf):
            out = jnp.take(buf, sel, axis=0)
            out = jnp.where(is_last, out, jnp.zeros_like(out))
            return jax.lax.psum(out, axis)

        return jax.tree_util.tree_map(collect, ys)

    pspec = jax.tree_util.tree_map(
        lambda a: P(None, axis), stacked_params)
    xspec = jax.tree_util.tree_map(lambda a: P(), micro_inputs)
    ospec = jax.tree_util.tree_map(lambda a: P(), micro_inputs)
    return jax.shard_map(per_stage, mesh=mesh, in_specs=(pspec, xspec),
                         out_specs=ospec,
                         axis_names={axis})(stacked_params, micro_inputs)


class SpmdPipelineLayer(Layer):
    """Homogeneous-trunk pipeline Layer over a ``pp`` mesh axis.

    ``block_factory()`` builds one trunk chunk (e.g. a run of transformer
    blocks); ``S * num_virtual_stages`` independent instances are built,
    their parameters stacked into ``[v, S, ...]`` Parameters sharded
    ``P(None, 'pp', ...)``. The forward takes micro-batched input
    ``[M, B, ...]`` and returns ``[M, B, ...]`` — every stage hop is a
    compiled ppermute, so the layer trains across hosts under a
    multi-controller mesh (the multi-host path the device_put engine in
    ``fleet/pipeline.py`` cannot take).

    Blocks must be stateless apart from parameters (no BN running stats):
    the chunk body runs under functional parameter swap.
    """

    def __init__(self, block_factory: Callable[[], Layer],
                 num_virtual_stages: int = 1, mesh=None, axis: str = "pp",
                 remat: bool = True, loss_fn: Optional[Callable] = None):
        super().__init__()
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.core.tensor import Parameter

        self._mesh = mesh or get_mesh()
        if self._mesh is None or axis not in self._mesh.axis_names:
            raise RuntimeError(
                f"SpmdPipelineLayer needs a mesh with axis {axis!r}")
        self.axis = axis
        self.num_stages = self._mesh.shape[axis]
        self.num_virtual_stages = num_virtual_stages
        self.num_chunks = self.num_stages * num_virtual_stages
        self.remat = remat
        self._loss_fn = loss_fn

        blocks = [block_factory() for _ in range(self.num_chunks)]
        template = blocks[0]
        names = [n for n, _ in template.named_parameters()]
        for b in blocks[1:]:
            got = [n for n, _ in b.named_parameters()]
            if got != names:
                raise ValueError(
                    "block_factory must build identical parameter "
                    f"structures (got {got} vs {names})")
        if any(b is not None for _, b in template.named_buffers()):
            raise ValueError(
                "SpmdPipelineLayer blocks must be stateless (no buffers/"
                "running stats); use the host-scheduled PipelineParallel "
                "for stateful stages")
        # template kept OUT of the sublayer registry: its (chunk-0 copy)
        # parameters must not appear next to the stacked ones
        self.__dict__["_template"] = template
        self._param_names = names
        S, v = self.num_stages, num_virtual_stages
        by_name = [dict(b.named_parameters()) for b in blocks]
        for name in names:
            # chunk c = r*S + s sits at index [r, s]
            arr = jnp.stack([by_name[c][name].data
                             for c in range(self.num_chunks)])
            arr = arr.reshape((v, S) + arr.shape[1:])
            p = Parameter(arr, trainable=not by_name[0][name].stop_gradient)
            p._sharding_spec = P(None, self.axis,
                                 *([None] * (arr.ndim - 2)))
            self.add_parameter(name.replace(".", "__"), p)

    def _stacked(self):
        return {n: getattr(self, n.replace(".", "__"))
                for n in self._param_names}

    def schedule_stats(self, n_micro: int) -> dict:
        return spmd_schedule_stats(self.num_stages, self.num_virtual_stages,
                                   n_micro)

    def forward(self, micro_x):
        """``micro_x``: Tensor ``[M, B, ...]`` (or pytree of such) ->
        same-structure ``[M, B, ...]`` outputs of the final chunk."""
        import jax
        template = self.__dict__["_template"]
        names = self._param_names
        stacked = self._stacked()
        mesh, axis, v, remat = (self._mesh, self.axis,
                                self.num_virtual_stages, self.remat)

        def f(xs, *param_arrays):
            params = dict(zip(names, param_arrays))

            def body_fn(chunk_params, x):
                from paddle_tpu.jit.functional import swap_state
                with no_grad(), swap_state(template, chunk_params,
                                           collect_buffers=False):
                    y = template(Tensor(x, stop_gradient=True))
                return y.data if isinstance(y, Tensor) else \
                    jax.tree_util.tree_map(
                        lambda t: t.data if isinstance(t, Tensor) else t, y)

            return pipeline_spmd(body_fn, params, xs, mesh=mesh, axis=axis,
                                 num_virtual_stages=v, remat=remat)

        return apply_op(f, micro_x, *[stacked[n] for n in names],
                        op_name="pipeline_spmd")


class SpmdPipelineParallel(Layer):
    """``train_batch`` engine over an :class:`SpmdPipelineLayer` — the
    multi-host counterpart of :class:`PipelineParallel` (same contract:
    reference ``pipeline_parallel.py:228 train_batch``). The schedule lives
    inside the compiled program, so ``last_schedule_stats`` is the exact
    analytic accounting of that program rather than a simulation."""

    def __init__(self, layers: SpmdPipelineLayer,
                 accumulate_steps: Optional[int] = None):
        super().__init__()
        self._layers = layers
        self.accumulate_steps = accumulate_steps or layers.num_stages
        self._loss_fn = layers._loss_fn
        self.last_schedule_stats: dict = {}

    def parameters(self, include_sublayers=True):
        return self._layers.parameters(include_sublayers)

    def named_parameters(self, prefix="", include_sublayers=True):
        return self._layers.named_parameters(prefix, include_sublayers)

    def forward(self, micro_x):
        return self._layers(micro_x)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        from paddle_tpu import ops

        inputs, labels = data
        M = self.accumulate_steps
        B = inputs.shape[0]
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by accumulate_steps {M}")
        micro_x = ops.reshape(inputs, [M, B // M] + list(inputs.shape[1:]))
        out = self._layers(micro_x)  # [M, b, ...]
        merged = ops.reshape(out, [B] + list(out.shape[2:]))
        loss = self._loss_fn(merged, labels)
        if scaler is not None:
            scaler.scale(loss).backward()
            scaler.step(optimizer)
            scaler.update()
        else:
            loss.backward()
            optimizer.step()
        optimizer.clear_grad(set_to_zero=False)
        if lr_scheduler is not None:
            lr_scheduler.step()
        self.last_schedule_stats = self._layers.schedule_stats(M)
        return loss

    @no_grad()
    def eval_batch(self, data, compute_loss=True):
        from paddle_tpu import ops
        inputs, labels = data
        M = self.accumulate_steps
        B = inputs.shape[0]
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by accumulate_steps {M}")
        micro_x = ops.reshape(inputs, [M, B // M] + list(inputs.shape[1:]))
        out = self._layers(micro_x)
        merged = ops.reshape(out, [B] + list(out.shape[2:]))
        if compute_loss and self._loss_fn is not None:
            return self._loss_fn(merged, labels)
        return merged


# ===================== heterogeneous + tied-weight stages ====================
# The homogeneous engine above stacks ONE body's params [v, S, ...]. The
# reference additionally pipelines arbitrary per-stage bodies and ties
# weights across stages with a grad allreduce (SharedLayerDesc,
# fleet/meta_parallel/parallel_layers/pp_layers.py:77; segmentation :209).
# TPU-native equivalents:
#
#   * HETEROGENEOUS chunks — each chunk's param pytree is flattened and
#     concatenated into ONE vector, padded to the longest chunk, stacked
#     [v, S, Lmax] and sharded P(None, 'pp'): every stage holds exactly
#     its own chunks' weights (the "padded stacked param superset"). The
#     tick body dispatches over the chunk index with ``lax.switch`` —
#     each branch statically unflattens ITS chunk's slice (shapes are
#     compile-time metadata), so heterogeneity costs program size, not
#     memory or transfers. Boundary activations must still share one
#     pytree structure (the ring carry is a fixed-shape collective).
#
#   * TIED weights — ``shared_params`` ride into every stage REPLICATED
#     over pp; any chunk may consume them (chunk 0's embedding, chunk
#     C-1's head). The transpose of a replicated shard_map input is a
#     psum over the axis: XLA inserts the exact grad allreduce
#     SharedLayerDesc implements by hand.


def pipeline_spmd_hetero(chunk_bodies, chunk_params, micro_inputs,
                         mesh=None, axis: str = "pp",
                         num_virtual_stages: int = 1,
                         shared_params=None, remat: bool = True):
    """Heterogeneous collective pipeline on raw jax pytrees.

    ``chunk_bodies``: list of ``v*S`` callables; chunk ``c`` computes
    ``chunk_bodies[c](params_c, shared_params, x) -> y`` where ``x``/``y``
    share one pytree structure across ALL chunks (the ring carry).
    ``chunk_params``: list of ``v*S`` per-chunk pytrees (shapes may differ
    arbitrarily between chunks). ``shared_params``: optional pytree
    visible to every chunk (tied weights) — grads sum over the pp axis.
    Returns the last chunk's outputs ``[M, ...]``; differentiable.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise RuntimeError(
            f"pipeline_spmd_hetero needs a mesh with axis {axis!r}")
    S = mesh.shape[axis]
    v = num_virtual_stages
    C = v * S
    if len(chunk_bodies) != C or len(chunk_params) != C:
        raise ValueError(
            f"need {C} chunk bodies/params (S={S} x v={v}); got "
            f"{len(chunk_bodies)}/{len(chunk_params)}")

    # flatten each chunk to one vector; remember the static recipe
    treedefs, shapes_list, sizes, dtype = [], [], [], None
    flats = []
    for c, p in enumerate(chunk_params):
        leaves, td = jax.tree_util.tree_flatten(p)
        for lf in leaves:
            if dtype is None:
                dtype = lf.dtype
            elif lf.dtype != dtype:
                raise ValueError(
                    "heterogeneous pipeline params must share one dtype "
                    f"(chunk {c} mixes {lf.dtype} with {dtype})")
        treedefs.append(td)
        shapes_list.append([lf.shape for lf in leaves])
        flat = jnp.concatenate([lf.reshape(-1) for lf in leaves]) \
            if leaves else jnp.zeros((0,), dtype or jnp.float32)
        sizes.append(flat.size)
        flats.append(flat)
    Lmax = max(max(sizes), 1)
    padded = jnp.stack([jnp.pad(f, (0, Lmax - f.size)) for f in flats])
    padded = padded.reshape(v, S, Lmax)
    if shared_params is None:
        shared_params = {}

    def unflatten(c, vec):
        out, off = [], 0
        for shp in shapes_list[c]:
            n = int(np.prod(shp)) if shp else 1
            out.append(vec[off:off + n].reshape(shp))
            off += n
        return jax.tree_util.tree_unflatten(treedefs[c], out)

    def make_branch(c):
        body = chunk_bodies[c]

        def branch(vec, shared, x):
            return body(unflatten(c, vec), shared, x)
        return branch

    branches = [make_branch(c) for c in range(C)]
    return _hetero_schedule(branches, padded, shared_params, micro_inputs,
                            mesh, axis, v, remat)


def _hetero_schedule(branches, padded, shared_params, micro_inputs,
                     mesh, axis, num_virtual_stages, remat=True):
    """Schedule core over the ALREADY padded-stacked [v, S, Lmax] param
    array: ``branches[c](vec, shared, x)`` unflattens its own chunk's
    slice via static metadata. Split out so SpmdHeteroPipelineLayer can
    feed its stored stacked Parameter directly — routing a per-step
    slice/re-pad/re-stack round trip over the whole trunk through the
    public list-of-pytrees API wasted HBM bandwidth every step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    v = num_virtual_stages
    S = mesh.shape[axis]
    C = v * S
    if remat:
        branches = [jax.checkpoint(b) for b in branches]
    if shared_params is None:
        shared_params = {}

    leaves = jax.tree_util.tree_leaves(micro_inputs)
    M = leaves[0].shape[0]
    t_idx = _completion_ticks(S, v, M)
    span = int(t_idx[-1]) + 1

    from .utils import mark_varying

    def _pvary(x):
        return mark_varying(x, axis)

    def per_stage(stage_vecs, shared, xs):
        # stage_vecs [v, 1, Lmax] -> [v, Lmax]
        stage_vecs = jnp.squeeze(stage_vecs, 1)
        # pvary the shared (tied) params HERE, uniformly on every device:
        # left implicit, the cast happens inside whichever switch branch
        # consumes them — a collective only SOME pp ranks execute
        # (deadlock). Outside the switch, every rank runs it in lockstep.
        shared = jax.tree_util.tree_map(_pvary, shared)
        s = jax.lax.axis_index(axis)
        vS = v * S
        perm = [(j, (j + 1) % S) for j in range(S)]

        def tick(carry, t):
            u = t - s
            g = u // vS
            rem = u % vS
            r = rem // S
            i = rem % S
            m = g * S + i
            active = (u >= 0) & (m < M)
            m_safe = jnp.clip(m, 0, M - 1)
            inject = active & (s == 0) & (r == 0)

            def pick(buf, ix):
                return jax.lax.dynamic_index_in_dim(buf, ix, 0,
                                                    keepdims=False)

            x_new = jax.tree_util.tree_map(lambda b: pick(b, m_safe), xs)
            x_in = jax.tree_util.tree_map(
                lambda new, cr: jnp.where(
                    active,
                    jnp.where(inject, _pvary(new), cr),
                    jnp.zeros_like(cr)),
                x_new, carry)
            r_safe = jnp.clip(r, 0, v - 1)
            vec = pick(stage_vecs, r_safe)
            # this stage's chunk at round r is c = r*S + s: every branch
            # is compiled, ONE executes per tick (program size buys
            # heterogeneity; weights stay stage-local)
            cidx = jnp.clip(r_safe * S + s, 0, C - 1)
            y = jax.lax.switch(cidx, branches, vec, shared, x_in)
            y = jax.tree_util.tree_map(
                lambda a: jnp.where(active, a, jnp.zeros_like(a)), y)
            y_next = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, axis, perm), y)
            return y_next, y

        x0 = jax.tree_util.tree_map(
            lambda b: _pvary(jnp.zeros(b.shape[1:], b.dtype)), xs)
        _, ys = jax.lax.scan(tick, x0, jnp.arange(span))
        is_last = (s == S - 1)
        sel = jnp.asarray(t_idx)

        def collect(buf):
            out = jnp.take(buf, sel, axis=0)
            out = jnp.where(is_last, out, jnp.zeros_like(out))
            return jax.lax.psum(out, axis)

        return jax.tree_util.tree_map(collect, ys)

    xspec = jax.tree_util.tree_map(lambda a: P(), micro_inputs)
    sspec = jax.tree_util.tree_map(lambda a: P(), shared_params)
    # FULL-manual over every mesh axis (unlike the homogeneous engine's
    # partial-manual {axis}): ``lax.switch`` branch selection varies per
    # pp rank, and under partial-manual GSPMD would auto-partition branch
    # INTERNALS over the other axes — inserting per-branch collectives
    # whose schedules then differ across pp ranks (deadlock). Full-manual
    # keeps branch bodies collective-free; the pipeline is replicated
    # over non-pp axes. Blocks whose forward builds fresh scan carries
    # (RNNs) must vma-match them to their inputs — see
    # ``fleet.utils.match_vma`` (nn.RNN does this natively).
    return jax.shard_map(
        per_stage, mesh=mesh, in_specs=(P(None, axis, None), sspec, xspec),
        out_specs=xspec,
        axis_names=set(mesh.axis_names))(padded, shared_params,
                                         micro_inputs)


class SpmdHeteroPipelineLayer(Layer):
    """Heterogeneous-trunk pipeline Layer: per-chunk bodies + optional
    tied (shared) sublayer, over a ``pp`` mesh axis.

    ``block_factories``: list of ``S * num_virtual_stages`` callables,
    each building that chunk's Layer (structures may differ arbitrarily;
    chunk boundaries must exchange one fixed pytree shape). The chunks'
    parameters live in ONE stacked-padded Parameter ``[v, S, Lmax]``
    sharded ``P(None, 'pp')`` — each stage stores only its own chunks.

    ``shared_factory`` builds a Layer replicated over pp whose forward
    any chunk may call: chunk bodies receive ``(x, shared)`` when their
    forward takes two arguments, ``(x)`` otherwise. Its gradient is the
    SUM of every chunk's contribution (psum over pp — the
    SharedLayerDesc tied-weight semantics, pp_layers.py:77)."""

    def __init__(self, block_factories, num_virtual_stages: int = 1,
                 mesh=None, axis: str = "pp", remat: bool = True,
                 loss_fn: Optional[Callable] = None, shared_factory=None):
        super().__init__()
        import inspect

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.core.tensor import Parameter

        self._mesh = mesh or get_mesh()
        if self._mesh is None or axis not in self._mesh.axis_names:
            raise RuntimeError(
                f"SpmdHeteroPipelineLayer needs a mesh with axis {axis!r}")
        self.axis = axis
        self.num_stages = self._mesh.shape[axis]
        self.num_virtual_stages = num_virtual_stages
        self.num_chunks = self.num_stages * num_virtual_stages
        self.remat = remat
        self._loss_fn = loss_fn
        if len(block_factories) != self.num_chunks:
            raise ValueError(
                f"need {self.num_chunks} block factories "
                f"(S={self.num_stages} x v={num_virtual_stages}); got "
                f"{len(block_factories)}")

        blocks = [f() for f in block_factories]
        for c, b in enumerate(blocks):
            if any(buf is not None for _, buf in b.named_buffers()):
                raise ValueError(
                    f"chunk {c} has buffers/running stats; hetero spmd "
                    "chunks must be stateless apart from parameters")
        self.__dict__["_blocks"] = blocks

        def wants_shared(b):
            # only REQUIRED positional params opt a block into receiving
            # the shared layer — forward(self, x, mask=None) keeps its
            # default, forward(self, x, shared) gets the tied sublayer
            sig = inspect.signature(b.forward)
            required = [p for p in sig.parameters.values()
                        if p.default is inspect.Parameter.empty
                        and p.kind in (p.POSITIONAL_ONLY,
                                       p.POSITIONAL_OR_KEYWORD)]
            return len(required) >= 2
        self._wants_shared = [wants_shared(b) for b in blocks]
        self._names = [[n for n, _ in b.named_parameters()]
                       for b in blocks]
        self._shapes = [[tuple(p.shape) for _, p in b.named_parameters()]
                        for b in blocks]
        sizes = [int(sum(np.prod(s) or 1 for s in shp)) or 0
                 for shp in self._shapes]
        self._sizes = sizes
        Lmax = max(max(sizes), 1)
        v, S = num_virtual_stages, self.num_stages
        flats = []
        dtype = None
        for c, b in enumerate(blocks):
            ps = [p.data for _, p in b.named_parameters()]
            for p in ps:
                if dtype is None:
                    dtype = p.dtype
                elif p.dtype != dtype:
                    # same contract the function API enforces — a silent
                    # concatenate would promote everything to the widest
                    # dtype (wrong memory footprint, no error)
                    raise ValueError(
                        "hetero pipeline blocks must share one param "
                        f"dtype (chunk {c} mixes {p.dtype} with {dtype})")
            flat = jnp.concatenate([p.reshape(-1) for p in ps]) if ps \
                else jnp.zeros((0,), dtype or jnp.float32)
            flats.append(jnp.pad(flat, (0, Lmax - flat.size)))
        arr = jnp.stack(flats).reshape(v, S, Lmax)
        trainable = any(not p.stop_gradient
                        for b in blocks for p in b.parameters())
        p = Parameter(arr, trainable=trainable)
        p._sharding_spec = P(None, self.axis, None)
        self.add_parameter("trunk_flat", p)
        if shared_factory is not None:
            self.shared = shared_factory()
        else:
            self.shared = None

    def schedule_stats(self, n_micro: int) -> dict:
        return spmd_schedule_stats(self.num_stages,
                                   self.num_virtual_stages, n_micro)

    def chunk_state_dict(self, c: int):
        """Chunk ``c``'s parameters as a plain name->numpy dict (unpadded,
        unflattened) — the serve-elsewhere export path."""
        vec = np.asarray(self.trunk_flat.numpy()).reshape(
            self.num_chunks, -1)[c]
        out, off = {}, 0
        for name, shp in zip(self._names[c], self._shapes[c]):
            n = int(np.prod(shp)) if shp else 1
            out[name] = vec[off:off + n].reshape(shp)
            off += n
        return out

    def forward(self, micro_x):
        import jax
        from paddle_tpu.jit.functional import swap_state

        blocks = self.__dict__["_blocks"]
        wants = self._wants_shared
        mesh, axis = self._mesh, self.axis
        v, remat = self.num_virtual_stages, self.remat
        shared = self.shared
        shared_named = dict(shared.named_parameters()) \
            if shared is not None else {}
        shared_keys = sorted(shared_named)

        def make_body(c):
            block = blocks[c]

            def body(params_c, shared_p, x):
                with no_grad(), swap_state(block, params_c,
                                           collect_buffers=False):
                    if wants[c] and shared is not None:
                        with swap_state(shared, shared_p,
                                        collect_buffers=False):
                            y = block(Tensor(x, stop_gradient=True),
                                      shared)
                    else:
                        y = block(Tensor(x, stop_gradient=True))
                return y.data if isinstance(y, Tensor) else \
                    jax.tree_util.tree_map(
                        lambda t: t.data if isinstance(t, Tensor) else t,
                        y)
            return body

        bodies = [make_body(c) for c in range(self.num_chunks)]
        shapes, nm = self._shapes, self._names
        C = self.num_chunks

        def f(xs, flat, *shared_leaves):
            shared_p = dict(zip(shared_keys, shared_leaves))

            def make_branch(c):
                body = bodies[c]

                def branch(vec, shared, x):
                    # unflatten THIS chunk's slice of the stacked padded
                    # param (static recipe); the stacked array feeds the
                    # schedule directly — no per-step re-pad/re-stack
                    out, off = {}, 0
                    for name, shp in zip(nm[c], shapes[c]):
                        n = int(np.prod(shp)) if shp else 1
                        out[name] = vec[off:off + n].reshape(shp)
                        off += n
                    return body(out, shared, x)
                return branch

            return _hetero_schedule(
                [make_branch(c) for c in range(C)], flat, shared_p, xs,
                mesh, axis, v, remat)

        return apply_op(f, micro_x, self.trunk_flat,
                        *[shared_named[k] for k in shared_keys],
                        op_name="pipeline_spmd_hetero")
