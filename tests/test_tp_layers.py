"""Tensor-parallel mpu layer tests: loss parity vs the non-parallel layers
on the 8-device CPU mesh (the reference's own test pattern, SURVEY.md §4)."""
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.distributed as dist
import paddle_tpu.distributed.fleet as fleet
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import P


@pytest.fixture()
def mesh_mp8():
    return dist.init_mesh({"mp": 8})


@pytest.fixture()
def mesh_dp2mp4():
    return dist.init_mesh({"dp": 2, "mp": 4})


def t(x):
    return pt.to_tensor(np.asarray(x, dtype=np.float32))


class TestColumnParallel:
    def test_forward_matches_dense(self, mesh_mp8):
        rng = np.random.RandomState(0)
        col = fleet.ColumnParallelLinear(16, 32, has_bias=True)
        x = rng.randn(4, 16).astype(np.float32)
        got = col(t(x)).numpy()
        ref = x @ col.weight.numpy() + col.bias.numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        # weight is actually feature-sharded across 8 devices
        assert col.weight._sharding_spec == P(None, "mp")
        assert len({str(s.device)
                    for s in col.weight.data.addressable_shards}) == 8

    def test_default_has_no_bias(self, mesh_mp8):
        # reference parity: has_bias defaults falsy (mp_layers.py:282)
        assert fleet.ColumnParallelLinear(4, 8).bias is None

    def test_gather_output_false_keeps_sharded(self, mesh_mp8):
        col = fleet.ColumnParallelLinear(16, 32, gather_output=False)
        out = col(t(np.zeros((4, 16))))
        assert out.shape == [4, 32]  # logically full; physically sharded


class TestRowParallel:
    def test_forward_matches_dense(self, mesh_mp8):
        rng = np.random.RandomState(1)
        row = fleet.RowParallelLinear(32, 16)
        x = rng.randn(4, 32).astype(np.float32)
        got = row(t(x)).numpy()
        ref = x @ row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        assert row.weight._sharding_spec == P("mp", None)

    def test_col_row_pair(self, mesh_mp8):
        """The Megatron MLP pattern: column-parallel up, row-parallel down
        with input_is_parallel — one allreduce total."""
        rng = np.random.RandomState(2)
        up = fleet.ColumnParallelLinear(16, 64, has_bias=True,
                                        gather_output=False)
        down = fleet.RowParallelLinear(64, 16, input_is_parallel=True)
        x = rng.randn(4, 16).astype(np.float32)
        got = down(nn.functional.relu(up(t(x)))).numpy()
        h = np.maximum(x @ up.weight.numpy() + up.bias.numpy(), 0)
        ref = h @ down.weight.numpy() + down.bias.numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


class TestVocabParallelEmbedding:
    def test_lookup_matches_dense(self, mesh_mp8):
        emb = fleet.VocabParallelEmbedding(64, 16)
        toks = np.array([[0, 5, 63], [10, 20, 40]], dtype=np.int64)
        got = emb(pt.to_tensor(toks)).numpy()
        ref = emb.weight.numpy()[toks]
        np.testing.assert_allclose(got, ref, rtol=1e-5)
        assert emb.weight._sharding_spec == P("mp", None)


class TestParallelCrossEntropy:
    def test_matches_dense_ce(self, mesh_mp8):
        rng = np.random.RandomState(3)
        logits = rng.randn(8, 64).astype(np.float32)
        labels = rng.randint(0, 64, 8).astype(np.int64)
        pce = fleet.ParallelCrossEntropy()
        got = pce(t(logits), pt.to_tensor(labels)).numpy()
        assert got.shape == (8, 1)  # reference keeps the trailing-1 dim
        ref = nn.functional.cross_entropy(
            t(logits), pt.to_tensor(labels), reduction="none").numpy()
        np.testing.assert_allclose(got[:, 0], ref, rtol=1e-4, atol=1e-5)


class TestTPTrainingParity:
    def test_tp_mlp_matches_dense_training(self, mesh_dp2mp4):
        """Megatron MLP trained compiled on (dp=2, mp=4) must track the
        dense single-logical-device run step for step."""
        rng = np.random.RandomState(0)
        X = rng.randn(32, 16).astype(np.float32)
        Y = X @ rng.randn(16, 16).astype(np.float32)

        class DenseMLP(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = nn.Linear(16, 64)
                self.down = nn.Linear(64, 16)

            def forward(self, x):
                return self.down(nn.functional.relu(self.up(x)))

        class TPMLP(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = fleet.ColumnParallelLinear(
                    16, 64, has_bias=True, gather_output=False)
                self.down = fleet.RowParallelLinear(64, 16,
                                                    input_is_parallel=True)

            def forward(self, x):
                return self.down(nn.functional.relu(self.up(x)))

        pt.seed(7)
        dense = DenseMLP()
        pt.seed(7)
        tp = TPMLP()
        np.testing.assert_allclose(dense.up.weight.numpy(),
                                   tp.up.weight.numpy(), rtol=1e-6)

        loss_fn = lambda m, a, b: nn.MSELoss()(m(a), b)
        od = opt.AdamW(learning_rate=0.01, parameters=dense.parameters())
        ot = opt.AdamW(learning_rate=0.01, parameters=tp.parameters())
        sd = pt.jit.TrainStep(dense, loss_fn, od)
        st = pt.jit.TrainStep(tp, loss_fn, ot, mesh=mesh_dp2mp4,
                              input_spec=P("dp"))
        for i in range(10):
            ld = float(sd(t(X), t(Y)).numpy())
            lt = float(st(t(X), t(Y)).numpy())
            assert abs(ld - lt) / max(abs(ld), 1e-8) < 5e-3, (i, ld, lt)
        # weights stayed sharded through the compiled updates
        assert len({str(s.device)
                    for s in tp.up.weight.data.addressable_shards}) == 8


class TestTrainStepPlacement:
    def test_state_on_the_mesh_before_the_first_call(self, mesh_dp2mp4):
        """Layers initialize on the default device and only stamp a spec.
        TrainStep must place parameters and accumulators by that spec up
        front: left off the mesh they park the whole training state on
        the first chip, and — avals carry the mesh — retype the second
        call, one full recompile (ISSUE 21: the parent compiled twice)."""
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        pt.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(
            num_hidden_layers=1, tie_word_embeddings=True,
            tensor_parallel=True))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        step = pt.jit.TrainStep(model, lambda m, x: m(x, labels=x)[1], o,
                                mesh=mesh_dp2mp4, input_spec=P("dp"))
        w = model.model.layers[0].self_attn.q_proj.weight
        assert w.data.sharding.spec == P(None, "mp")
        assert o._ensure_state(w)["moment1"].sharding.spec == P(None, "mp")
        x = pt.to_tensor(np.random.RandomState(0).randint(
            0, 256, (4, 16)).astype(np.int64))
        for _ in range(3):
            step(x)
        (compiled,) = step._cache.values()
        assert compiled._cache_size() == 1


class TestFleetFacade:
    def test_init_and_wrap(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                                   "pp_degree": 1, "sharding_degree": 1}
        hcg = fleet.init(strategy=strategy)
        assert hcg.get_model_parallel_world_size() == 4
        assert dist.get_mesh().shape == {"dp": 2, "pp": 1, "sharding": 1,
                                         "mp": 4}
        m = nn.Linear(4, 4)
        wrapped = fleet.distributed_model(m)
        assert wrapped is m  # mp>1: parallelism lives in the layers

        o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
        assert fleet.distributed_optimizer(o) is o

    def test_dp_only_wraps_dataparallel(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1,
                                   "pp_degree": 1, "sharding_degree": 1}
        fleet.init(strategy=strategy)
        m = nn.Linear(4, 4)
        wrapped = fleet.distributed_model(m)
        assert isinstance(wrapped, dist.DataParallel)
