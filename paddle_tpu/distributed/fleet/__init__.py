"""fleet facade (reference: ``python/paddle/distributed/fleet/``).

``fleet.init`` builds the 4-D topology and the mesh;
``distributed_model``/``distributed_optimizer`` wrap by strategy — on TPU the
wrapping is sharding annotation (DataParallel spec, mpu layer shardings)
rather than NCCL group plumbing (reference: fleet.py:168, model.py:30).
"""
from __future__ import annotations

from typing import Optional

from ..mesh import get_mesh
from ..parallel import DataParallel
from ..topology import CommunicateTopology, HybridCommunicateGroup
from . import mpu  # noqa: F401
from . import pipeline  # noqa: F401
from . import moe  # noqa: F401
from .moe import (HeldExpertsLayer, MoELayer, NaiveGate,  # noqa: F401
                  SwitchGate, GShardGate)
from . import sequence_parallel  # noqa: F401
from .sequence_parallel import (  # noqa: F401
    ring_attention, ulysses_attention, scatter_sequence, gather_sequence,
)
from .pipeline import (  # noqa: F401
    LayerDesc, SharedLayerDesc, PipelineLayer, PipelineParallel,
)
from . import spmd_pipeline  # noqa: F401
from .spmd_pipeline import (  # noqa: F401
    pipeline_spmd, spmd_schedule_stats, SpmdPipelineLayer,
    SpmdPipelineParallel, pipeline_spmd_hetero, SpmdHeteroPipelineLayer,
)
from .mpu import (  # noqa: F401
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy,
)
from . import utils  # noqa: F401
from .utils import recompute  # noqa: F401

__all__ = ["init", "DistributedStrategy", "distributed_model",
           "distributed_optimizer", "get_hybrid_communicate_group",
           "worker_num", "worker_index", "mpu", "ColumnParallelLinear",
           "RowParallelLinear", "VocabParallelEmbedding",
           "ParallelCrossEntropy", "LayerDesc", "SharedLayerDesc", "PipelineLayer", "PipelineParallel", "pipeline_spmd", "spmd_schedule_stats", "SpmdPipelineLayer", "SpmdPipelineParallel", "pipeline_spmd_hetero", "SpmdHeteroPipelineLayer", "MoELayer", "HeldExpertsLayer", "NaiveGate", "SwitchGate", "GShardGate", "ring_attention", "ulysses_attention", "scatter_sequence", "gather_sequence", "utils", "recompute"]

_state = {"hcg": None, "strategy": None}


class DistributedStrategy:
    """Reference: ``fleet/base/distributed_strategy.py`` — the switchboard.
    Only the knobs with TPU meaning are consumed; the rest are accepted for
    API compatibility and recorded."""

    def __init__(self):
        self.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 1}
        self.amp = False
        self.amp_configs = {}
        self.recompute = False
        self.recompute_configs = {}
        self.sharding = False
        self.sharding_configs = {}
        self.pipeline = False
        self.pipeline_configs = {}
        self.tensor_parallel = False
        self.tensor_parallel_configs = {}
        self.gradient_merge = False
        self.gradient_merge_configs = {}
        self.find_unused_parameters = False


def init(role_maker=None, is_collective=True, strategy: Optional[
        DistributedStrategy] = None):
    """Reference: fleet.py:168 — build topology + communicators (here: the
    mesh) from the strategy's hybrid_configs."""
    strategy = strategy or DistributedStrategy()
    hc = strategy.hybrid_configs
    topo = CommunicateTopology(
        ["data", "pipe", "sharding", "model"],
        [hc.get("dp_degree", 1), hc.get("pp_degree", 1),
         hc.get("sharding_degree", 1), hc.get("mp_degree", 1)])
    hcg = HybridCommunicateGroup(topo)
    _state["hcg"] = hcg
    _state["strategy"] = strategy
    return hcg


def get_hybrid_communicate_group() -> HybridCommunicateGroup:
    if _state["hcg"] is None:
        raise RuntimeError("call fleet.init() first")
    return _state["hcg"]


def distributed_model(model):
    """Reference: model.py:30 — wrap by mode. DP wrapping covers the pure
    data-parallel case; TP/PP models are built from mpu/pipeline layers and
    pass through (their parallelism already lives in the shardings).
    ``strategy.recompute`` is honored for models that expose a
    ``cfg.recompute`` switch (the zoo models do)."""
    hcg = get_hybrid_communicate_group()
    strategy = _state.get("strategy")
    if strategy is not None and strategy.recompute:
        cfg = getattr(model, "cfg", None)
        if cfg is not None and hasattr(cfg, "recompute"):
            cfg.recompute = True
    if hcg.get_data_parallel_world_size() > 1 and \
            hcg.get_model_parallel_world_size() == 1 and \
            hcg.get_pipe_parallel_world_size() == 1:
        return DataParallel(model, mesh=get_mesh())
    return model


def distributed_optimizer(optimizer, strategy=None):
    """Reference: fleet.py distributed_optimizer → HybridParallelOptimizer.
    Under GSPMD the gradient collectives live inside the compiled step, so
    the optimizer passes through unchanged."""
    return optimizer


def worker_num() -> int:
    from ..env import get_world_size
    return get_world_size()


def worker_index() -> int:
    from ..env import get_rank
    return get_rank()
