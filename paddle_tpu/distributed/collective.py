"""Collective communication API.

Parity surface: ``python/paddle/distributed/communication/`` (all_reduce,
all_gather, reduce_scatter, broadcast, all_to_all, send/recv, barrier) and the
C++ ProcessGroup family (SURVEY.md §2.4). TPU-native redesign: a collective is
not a runtime call into NCCL — it is an *XLA op over a named mesh axis*
(psum/all_gather/ppermute compiled onto ICI). Per-rank semantics (each rank
holding different data) exist inside :func:`spmd` (shard_map) regions; that is
where these functions are used, exactly as the reference uses them inside a
rank's train script. The reference's process groups become :class:`Group`
objects naming mesh axes.

Example (loss-parity test pattern, SURVEY.md §4)::

    mesh = dist.init_mesh({"dp": 8})

    @dist.spmd(mesh=mesh, in_specs=P("dp"), out_specs=P())
    def global_mean(local_batch):
        s = dist.all_reduce(local_batch.sum(), group=dist.Group(("dp",)))
        return s / total
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import numpy as np

from paddle_tpu.core.autograd import apply_op
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability.comm import (comm_event, comm_scope,
                                           payload_bytes)
from .mesh import get_mesh

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "all_reduce",
           "all_gather", "all_gather_object", "reduce", "reduce_scatter",
           "broadcast", "all_to_all", "scatter", "send", "recv", "barrier",
           "spmd", "shard_map", "P"]

from jax.sharding import PartitionSpec as P  # re-export for specs


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator. Two flavors, matching the two planes the reference's
    ProcessGroup serves:

    - **device groups**: a tuple of mesh axis names — XLA collectives
      inside shard_map regions (the ring-id, reduced to its essence).
    - **host groups**: an explicit list of global host-process ``ranks`` —
      the store-backed OBJECT collectives address processes directly, so
      arbitrary rank subsets are representable there (and only there).
    """

    _registry = {}
    _next_id = 0

    def __init__(self, axes: Union[str, Sequence[str]], mesh=None,
                 ranks: Optional[Sequence[int]] = None):
        self.axes: Tuple[str, ...] = (axes,) if isinstance(axes, str) \
            else tuple(axes)
        self._mesh = mesh
        if ranks is not None:
            ranks = tuple(int(r) for r in ranks)
            if len(set(ranks)) != len(ranks):
                raise ValueError(f"duplicate ranks in group: {ranks}")
        # USER order is the group-rank order (reference new_group
        # semantics): scatter payload gi goes to ranks[gi], gathers return
        # in this order — never silently sorted
        self.ranks: Optional[Tuple[int, ...]] = ranks

    @property
    def mesh(self):
        return self._mesh or get_mesh()

    @property
    def nranks(self) -> int:
        if self.ranks is not None:
            return len(self.ranks)
        m = self.mesh
        if m is None:
            return 1
        return int(np.prod([m.shape[a] for a in self.axes]))

    @property
    def axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def __repr__(self):
        return f"Group(axes={self.axes}, nranks={self.nranks})"


def new_group(ranks=None, axes=None, mesh=None) -> Group:
    """Create a communicator. On a mesh, DEVICE groups are axis-aligned:
    pass ``axes``. An explicit ``ranks`` subset builds a HOST group —
    usable by the store-backed object collectives (which address host
    processes directly); arbitrary rank lists still have no XLA analog, so
    a host group inside a shard_map region raises."""
    g = None
    if axes is None:
        m = mesh or get_mesh()
        full = int(np.prod(list(m.shape.values()))) if m is not None \
            else None
        if ranks is not None and (
                m is None or list(ranks) != list(range(full))):
            # anything but the identity covering of the mesh — a subset, a
            # permutation, no mesh at all — is a host-rank group for the
            # object-collective plane (order/dups validated by Group)
            g = Group((), mesh, ranks=ranks)
        else:
            axes = tuple(m.axis_names) if m is not None else ("dp",)
    if g is None:
        g = Group(axes, mesh)
    gid = Group._next_id
    Group._next_id += 1
    Group._registry[gid] = g
    g.id = gid
    return g


def get_group(gid: int) -> Optional[Group]:
    return Group._registry.get(gid)


def _linear_rank(axes):
    """Group-linear rank inside a mapped context (axes[0] major — the
    same flattening order jax collectives use for axis tuples)."""
    import jax
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _axes(group) -> Tuple[str, ...]:
    if group is None:
        m = get_mesh()
        return tuple(m.axis_names) if m is not None else ()
    if isinstance(group, Group):
        if group.ranks is not None:
            raise RuntimeError(
                "host-rank groups (new_group(ranks=[...])) serve the "
                "store-backed OBJECT collectives; device collectives need "
                "an axis-aligned group (new_group(axes=('dp',)))")
        return group.axes
    if isinstance(group, str):
        return (group,)
    return tuple(group)


def _in_mapped_context(axes) -> bool:
    """True when the named axes are bound (i.e. we are inside shard_map)."""
    import jax
    try:
        for a in axes:
            jax.lax.axis_size(a)
        return True
    except NameError:  # jax's unbound-axis-name error
        return False


def _collective(fn, t, op_name):
    if isinstance(t, Tensor):
        return apply_op(fn, t, op_name=op_name)
    return fn(t)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reduce across the group; every rank gets the result
    (reference: communication/all_reduce.py → ProcessGroup::AllReduce)."""
    import jax
    axes = _axes(group)
    if not axes or not _in_mapped_context(axes):
        if group is None or Group(axes).nranks == 1:
            return tensor  # single-rank: identity, matching paddle
        raise RuntimeError(
            "per-rank collectives run inside dist.spmd/shard_map regions; "
            "outside, arrays are global and all_reduce has no meaning")
    red = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
           ReduceOp.MIN: jax.lax.pmin, ReduceOp.AVG: jax.lax.pmean}
    if op == ReduceOp.PROD:
        def f(x):
            import jax.numpy as jnp
            logs = jax.lax.psum(jnp.log(jnp.abs(x)), axes)
            sign = jax.lax.psum((x < 0).astype(jnp.int32), axes)
            return jnp.exp(logs) * jnp.where(sign % 2 == 1, -1.0, 1.0)
    else:
        def f(x):
            return red[op](x, axes)
    with comm_scope("all_reduce", axes, payload=tensor,
                    extra={"reduce_op": op}):
        return _collective(f, tensor, f"all_reduce_{op}")


def all_gather(tensor_or_list, tensor=None, group=None, sync_op=True,
               axis=0):
    """Gather shards from every rank (concatenated along ``axis``).

    Supports both call shapes: paddle's ``all_gather(out_list, t)`` and the
    functional ``out = all_gather(t)``.
    """
    import jax
    out_list = None
    if tensor is None:
        t = tensor_or_list
    else:
        out_list, t = tensor_or_list, tensor
    axes = _axes(group)
    if not axes or not _in_mapped_context(axes):
        if group is None or Group(axes).nranks == 1:
            result, n = t, 1  # identity: the "gather" holds one copy
        else:
            raise RuntimeError("all_gather outside a dist.spmd region")
    else:
        def f(x):
            return jax.lax.all_gather(x, axes, axis=axis, tiled=True)
        with comm_scope("all_gather", axes, payload=t):
            result = _collective(f, t, "all_gather")
        n = Group(axes).nranks
    if out_list is not None:
        from paddle_tpu import ops
        out_list.extend(ops.split(result, n, axis=axis)
                        if n > 1 else [result])
        return None
    return result


_obj_seq: dict = {}  # (kind, group-tag) -> per-call sequence counter


def _multi_host_world():
    """(rank, world) of HOST PROCESSES — launcher env when present, else
    the PJRT process view. Deliberately not get_world_size(): that falls
    back to the device count, and object collectives move host objects
    between processes, not chips. The jax fallback is only touched when
    the env vars are absent (calling it would initialize the backend)."""
    import os
    rank = os.environ.get("PADDLE_TRAINER_ID")
    world = os.environ.get("PADDLE_TRAINERS_NUM")
    if rank is not None and world is not None:
        return int(rank), int(world)
    import jax
    return (int(rank) if rank is not None else jax.process_index(),
            int(world) if world is not None else jax.process_count())


def _group_members(group, what: str):
    """(member ranks, my global rank, store tag) for an object collective.

    ``group=None`` → the full world. A host-rank group
    (``new_group(ranks=[...])``) scopes the collective to its members —
    store keys are namespaced by the member tuple so concurrent groups
    never collide. Axis (device) groups are rejected: they partition
    chips, not host processes."""
    rank, world = _multi_host_world()
    if group is None:
        return tuple(range(world)), rank, "w"
    ranks = getattr(group, "ranks", None)
    if ranks is None:
        if getattr(group, "nranks", None) in (None, world):
            return tuple(range(world)), rank, "w"
        raise NotImplementedError(
            f"{what}: device (axis) groups do not scope host-object "
            "collectives; build a host group with new_group(ranks=[...])")
    bad = [r for r in ranks if not 0 <= r < world]
    if bad:
        raise ValueError(f"{what}: ranks {bad} outside world {world}")
    return ranks, rank, "-".join(map(str, ranks))


def _reaped_barrier(store, name: str, world: int):
    """barrier_via_store + key reaping: the LAST process to leave deletes
    the barrier namespace (counter/done/left keys), so per-call barriers
    don't grow the store without bound."""
    import os
    from .tcp_store import barrier_via_store
    barrier_via_store(store, name, world)
    epoch = os.environ.get("PADDLE_RESTART_EPOCH", "0")
    if store.add(f"__barrier/{epoch}/{name}/left", 1) == world:
        store.delete_prefix(f"__barrier/{epoch}/{name}")


def _obj_key(kind: str, tag: str = "w") -> str:
    """Unique per-call store namespace. All MEMBER processes issue a
    group's collectives in the same program order, so a per-(kind, group)
    counter is consistent; the member-tuple tag keeps concurrent groups
    apart and the elastic restart epoch prevents reuse across
    relaunches."""
    import os
    epoch = os.environ.get("PADDLE_RESTART_EPOCH", "0")
    seq = _obj_seq.get((kind, tag), 0)
    _obj_seq[(kind, tag)] = seq + 1
    return f"__objcol/{epoch}/{tag}/{kind}{seq}"


def all_gather_object(object_list, obj, group=None):
    """Host-object gather (reference: communication/all_gather.py
    all_gather_object). Single process: trivial. Multi-process (DCN): each
    rank publishes its pickled object to the job's TCPStore and reads the
    others — the store-backed control plane the reference implements over
    its gloo/TCP store.

    Non-member contract: on ranks OUTSIDE ``group`` this is a no-op and
    ``object_list`` is left untouched (empty if passed empty) — matching
    the reference's non-member pass-through. Symmetric caller code that
    indexes ``object_list`` on every rank must guard on membership."""
    import pickle
    members, rank, tag = _group_members(group, "all_gather_object")
    if rank not in members:
        return None  # non-members pass through (reference semantics)
    if len(members) <= 1:
        object_list.append(obj)
        return None
    from .tcp_store import job_store
    store = job_store()
    key = _obj_key("ag", tag)
    blob = pickle.dumps(obj)
    with comm_scope("all_gather_object", (), nbytes=len(blob),
                    extra={"members": len(members)}):
        store.set(f"{key}/{rank}", blob)
        for r in members:
            object_list.append(pickle.loads(store.wait(f"{key}/{r}")))
        # every member has read everything: safe to drop our slot
        _reaped_barrier(store, key, len(members))
        store.delete_key(f"{key}/{rank}")
    return None


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """psum then keep (XLA has no single-dst reduce cheaper than allreduce
    on ICI; the reference's reduce is NCCL Reduce — result equal on dst,
    undefined elsewhere; we return the reduced value everywhere)."""
    return all_reduce(tensor, op, group, sync_op)


def reduce_scatter(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
                   axis=0):
    """Reduce + scatter shards (reference: communication/reduce_scatter.py).
    Input per-rank shape [N, ...] -> output [N/world, ...]."""
    import jax
    axes = _axes(group)
    if not axes or not _in_mapped_context(axes):
        if group is None or Group(axes).nranks == 1:
            return tensor
        raise RuntimeError("reduce_scatter outside a dist.spmd region")

    def f(x):
        return jax.lax.psum_scatter(x, axes, scatter_dimension=axis,
                                    tiled=True)
    with comm_scope("reduce_scatter", axes, payload=tensor,
                    extra={"reduce_op": op}):
        return _collective(f, tensor, "reduce_scatter")


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Replicate src's value across the group. On a mesh this is a
    psum of a rank-masked select (memory-lean collective-select)."""
    import jax
    import jax.numpy as jnp
    axes = _axes(group)
    if not axes or not _in_mapped_context(axes):
        if group is None or Group(axes).nranks == 1:
            return tensor
        raise RuntimeError("broadcast outside a dist.spmd region")
    n = Group(axes).nranks
    if not 0 <= src < n:
        # the masked-select psum would silently yield zeros for an absent
        # src rank — keep the old all_gather+index failure mode
        raise ValueError(f"broadcast src {src} out of range for group "
                         f"of {n}")

    def f(x):
        # psum of a masked select: peak memory 2x the tensor, not the
        # world-size x of an all_gather+index — this is how large params
        # broadcast over the mesh
        idx = _linear_rank(axes)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        if jnp.issubdtype(x.dtype, jnp.bool_):
            return jax.lax.psum(masked.astype(jnp.int8), axes).astype(
                x.dtype)
        return jax.lax.psum(masked, axes)
    with comm_scope("broadcast", axes, payload=tensor,
                    extra={"src": src}):
        return _collective(f, tensor, "broadcast")


def all_to_all(in_tensor_list, out_tensor_list=None, group=None,
               sync_op=True, split_axis=0, concat_axis=0):
    """All-to-all over the group (reference: communication/all_to_all.py →
    the MoE dispatch primitive ``global_scatter``). Functional form: pass a
    single tensor whose ``split_axis`` divides by world size."""
    import jax
    axes = _axes(group)
    single = not isinstance(in_tensor_list, (list, tuple))
    if not axes or not _in_mapped_context(axes):
        if group is None or Group(axes).nranks == 1:
            return in_tensor_list
        raise RuntimeError("all_to_all outside a dist.spmd region")
    axis_name = axes if len(axes) > 1 else axes[0]
    if single:
        def f(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                      concat_axis=concat_axis, tiled=True)
        with comm_scope("all_to_all", axes, payload=in_tensor_list):
            return _collective(f, in_tensor_list, "all_to_all")
    # list form: stack -> all_to_all -> unstack into out_tensor_list
    from paddle_tpu import ops
    stacked = ops.stack(list(in_tensor_list), axis=0)

    def f(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                                  tiled=False)
    with comm_scope("all_to_all", axes, payload=stacked):
        out = _collective(f, stacked, "all_to_all")
    outs = [out[i] for i in range(len(in_tensor_list))]
    if out_tensor_list is not None:
        out_tensor_list.extend(outs)
        return None
    return outs


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Take src's i-th shard on rank i (reference: communication/scatter)."""
    import jax
    axes = _axes(group)
    if not axes or not _in_mapped_context(axes):
        if group is None or Group(axes).nranks == 1:
            return tensor
        raise RuntimeError("scatter outside a dist.spmd region")

    def f(x):
        # all_to_all then keep src's lane: src's slice i reaches rank i
        # with peak memory 2x the tensor, not the world-size x of the old
        # all_gather+index formulation
        axis = axes[0] if len(axes) == 1 else axes
        n = jax.lax.axis_size(axis)
        chunk = x.shape[0] // n
        recv = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        return jax.lax.dynamic_slice_in_dim(recv, src * chunk, chunk, 0)
    if tensor_list is not None:
        from paddle_tpu import ops
        tensor = ops.concat(list(tensor_list), axis=0)
    with comm_scope("scatter", axes, payload=tensor, extra={"src": src}):
        return _collective(f, tensor, "scatter")


def send(tensor, dst=0, group=None, sync_op=True):
    """P2P send — on a mesh this is a collective_permute (ppermute) to the
    destination; pair with :func:`recv` in the same spmd program. The
    reference's send_v2/recv_v2 (PP micro-batch transfer) maps to
    :func:`p2p_shift` which is what the pipeline engine uses."""
    # record the attempt: a flight-recorder postmortem should show which
    # rank tried an unsupported raw P2P before the crash
    comm_event("send", (), payload=tensor, extra={"dst": dst})
    raise NotImplementedError(
        "raw send/recv have no XLA analog; use dist.p2p_shift (ppermute) "
        "inside an spmd region — the PP engine does")


def recv(tensor, src=0, group=None, sync_op=True):
    comm_event("recv", (), payload=tensor, extra={"src": src})
    raise NotImplementedError(
        "raw send/recv have no XLA analog; use dist.p2p_shift (ppermute) "
        "inside an spmd region — the PP engine does")


def p2p_shift(tensor, group=None, shift: int = 1):
    """Shift values along a mesh axis ring: rank i's data goes to rank
    (i+shift) % n — the ICI-native form of send/recv used for pipeline
    micro-batch handoff (reference: p2p_communication.py _p2p_helper)."""
    import jax
    axes = _axes(group)
    axis = axes[0] if len(axes) == 1 else axes

    def f(x):
        n = jax.lax.axis_size(axis)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return jax.lax.ppermute(x, axis, perm)
    with comm_scope("p2p_shift", axes, payload=tensor,
                    extra={"shift": shift}):
        return _collective(f, tensor, "p2p_shift")


def barrier(group=None):
    """Device-level barriers are implicit in XLA program boundaries; this
    synchronizes the host on outstanding work (paddle barrier blocks the
    host the same way)."""
    import jax
    axes = getattr(group, "axes", ()) if group is not None else ()
    with comm_scope("barrier", axes):
        jax.effects_barrier()
    return None


def shard_map(fn, mesh=None, in_specs=None, out_specs=None,
              check_rep=False):
    """Thin wrapper over jax shard_map operating on Tensors."""
    import jax
    from jax.sharding import PartitionSpec

    mesh = mesh or get_mesh()

    def unwrap(x):
        return x.data if isinstance(x, Tensor) else x

    def run(*args):
        inner = jax.shard_map(
            lambda *a: jax.tree_util.tree_map(
                unwrap, fn(*[Tensor(x) if hasattr(x, "dtype") else x
                             for x in a]),
                is_leaf=lambda v: isinstance(v, Tensor)),
            mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_rep)
        out = inner(*[unwrap(a) for a in args])
        return jax.tree_util.tree_map(
            lambda x: Tensor(x) if hasattr(x, "dtype") else x, out)
    return run


def spmd(fn=None, mesh=None, in_specs=None, out_specs=None):
    """Decorator form of :func:`shard_map` — the region where per-rank
    (paddle-style) collective semantics hold."""
    def wrap(f):
        return shard_map(f, mesh, in_specs, out_specs)
    return wrap(fn) if fn is not None else wrap
