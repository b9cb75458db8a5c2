"""Process/environment bootstrap.

Parity with the reference's ``init_parallel_env``
(``python/paddle/distributed/parallel.py:919``: read PADDLE_TRAINER_* env,
TCPStore rendezvous, default process group, barrier). On TPU the runtime
(jax.distributed / PJRT) owns rendezvous: multi-host jobs call
``jax.distributed.initialize`` with a coordinator address — the TCPStore
analog — after which every host sees the global device set and SPMD programs
span the slice. Single-process (incl. the 8-device CPU test mesh) needs no
rendezvous at all.
"""
from __future__ import annotations

import os
from typing import Optional

from .mesh import get_mesh, init_mesh

__all__ = ["init_parallel_env", "get_rank", "get_world_size", "ParallelEnv"]

_initialized = {"done": False}


def init_parallel_env(mesh_shape: Optional[dict] = None):
    """Bootstrap distributed state and the default mesh.

    Honors the reference's env-variable protocol where present
    (PADDLE_TRAINER_ID → process index, PADDLE_MASTER/MASTER_ADDR →
    coordinator) and maps it onto jax.distributed for multi-host TPU.
    """
    import jax

    if _initialized["done"]:
        return ParallelEnv()
    coord = os.environ.get("PADDLE_MASTER") or os.environ.get("MASTER_ADDR")
    n_proc = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    proc_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if coord and n_proc > 1 and not jax.distributed.is_initialized():
        explicit = os.environ.get("PADDLE_JAX_COORDINATOR")
        if explicit:
            addr = explicit
        elif os.environ.get("PADDLE_STORE_PORT"):
            # under the launcher PADDLE_MASTER is the TCPStore endpoint —
            # a DIFFERENT protocol than jax's gRPC coordinator. Negotiate
            # a separate coordinator port through the store, namespaced by
            # the elastic restart epoch (a relaunched attempt must never
            # read a dead coordinator's address).
            from .tcp_store import free_port, job_store
            store = job_store()
            host = coord.split(":")[0]
            epoch = os.environ.get("PADDLE_RESTART_EPOCH", "0")
            key = f"__jax_coordinator/{epoch}"
            if proc_id == 0:
                # the coordinator service runs INSIDE proc 0, so the
                # advertised host must be proc 0's reachable address. When
                # proc 0 owns the PADDLE_MASTER address (the common
                # single-node / master-on-rank-0 layout) advertise that;
                # otherwise (explicit --master on another node) advertise
                # this machine's hostname instead of crashing on the bind.
                try:
                    port = free_port(host)
                    adv = host
                except OSError:
                    # rank 0 doesn't own the master address: advertise the
                    # IP of the interface that reaches it (UDP connect
                    # sends nothing, just resolves routing) — a bare
                    # gethostname() is often unresolvable cluster-wide
                    import socket as _socket
                    s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
                    try:
                        s.connect((host, 1))
                        adv = s.getsockname()[0]
                    except OSError:
                        adv = _socket.gethostname()
                    finally:
                        s.close()
                    port = free_port("")
                store.set(key, f"{adv}:{port}".encode())
            addr = store.wait(key).decode()
        else:
            port = os.environ.get("MASTER_PORT", "8476")
            addr = coord if ":" in coord else f"{coord}:{port}"
        jax.distributed.initialize(coordinator_address=addr,
                                   num_processes=n_proc,
                                   process_id=proc_id)
    if get_mesh() is None:
        init_mesh(mesh_shape)
    _initialized["done"] = True
    return ParallelEnv()


def get_rank(group=None) -> int:
    """Host process rank (reference: paddle.distributed.get_rank).

    The launcher/spawn env contract wins when present (PADDLE_TRAINER_ID,
    exactly like the reference reads it); otherwise the PJRT process
    index. Under SPMD one process drives many devices; device-level rank
    only exists inside shard_map, via lax.axis_index.
    """
    import os
    env = os.environ.get("PADDLE_TRAINER_ID")
    if env is not None:
        return int(env)
    import jax
    return jax.process_index()


def get_world_size(group=None) -> int:
    """Total worker count: the launcher env contract (PADDLE_TRAINERS_NUM)
    when present, else the device count (paddle world-size semantics map
    to chips on TPU — each chip was a paddle "rank")."""
    if group is not None and hasattr(group, "nranks"):
        return group.nranks
    import os
    env = os.environ.get("PADDLE_TRAINERS_NUM")
    if env is not None:
        return int(env)
    import jax
    return jax.device_count()


class ParallelEnv:
    """Reference: ``python/paddle/fluid/dygraph/parallel.py`` ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def nranks(self):
        return get_world_size()

    @property
    def local_rank(self):
        return get_rank()
