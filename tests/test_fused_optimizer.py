"""Fused multi-tensor optimizer + bucketed dp gradient collectives.

Covers ISSUE 7's acceptance matrix: fused-vs-eager parity (bit-exact at the
update-rule level where the same gradients are fed; tight-tolerance end to
end, where XLA's differing backward fusion injects ~1-ulp gradient noise —
docs/PERFORMANCE.md#numerics), per-parameter ``state_dict`` preservation and
CheckpointManager round trips across the fused/eager boundary, compile-once
guards, HLO-verified bucketed (not per-param, not monolithic) dp gradient
reductions with the env-tunable bucket size, the flat-state flush protocol,
the XLA tuning flag gate, and the bench report-gate wiring.
"""

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.jit.fused_update import (build_flat_states, build_layout,
                                         fused_clip_and_update,
                                         split_flat_states)
from paddle_tpu.jit.bucketing import plan_comm_buckets


def t(x):
    return pt.to_tensor(np.asarray(x, dtype=np.float32))


def _mlp(seed=0):
    pt.seed(seed)
    return nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))


def _data():
    rng = np.random.RandomState(0)
    X = rng.randn(32, 8).astype(np.float32)
    W = rng.randn(8, 4).astype(np.float32)
    return X, X @ W


def _loss(mm, a, b):
    return nn.MSELoss()(mm(a), b)


OPTIMIZERS = {
    "adamw": lambda ps, **kw: opt.AdamW(learning_rate=0.01, parameters=ps,
                                        **kw),
    "adam": lambda ps, **kw: opt.Adam(learning_rate=0.01, parameters=ps,
                                      **kw),
    "sgd": lambda ps, **kw: opt.SGD(learning_rate=0.05, parameters=ps,
                                    **kw),
    "momentum": lambda ps, **kw: opt.Momentum(
        learning_rate=0.01, momentum=0.9, parameters=ps, **kw),
}


def _run_pair(make_opt, fused, steps=5, seed=7, bf16=False):
    X, Y = _data()
    pt.seed(seed)
    m = _mlp(seed)
    if bf16:
        m.bfloat16()
    o = make_opt(m.parameters())
    s = pt.jit.TrainStep(m, _loss, o, fused=fused)
    losses = [float(s(t(X), t(Y)).numpy()) for _ in range(steps)]
    return m, o, losses


def _assert_state_dicts_match(sd1, sd2, rtol=0.0, atol=0.0):
    assert set(sd1) == set(sd2)
    for k in sd2:
        a, b = sd1[k], sd2[k]
        if not hasattr(b, "data"):
            assert a == b, k
            continue
        a, b = np.asarray(a.data), np.asarray(b.data)
        assert a.dtype == b.dtype and a.shape == b.shape, k
        if rtol == 0.0 and atol == 0.0:
            np.testing.assert_array_equal(a, b, err_msg=k)
        else:
            np.testing.assert_allclose(
                a.astype(np.float64), b.astype(np.float64), rtol=rtol,
                atol=atol, err_msg=k)


class TestRuleLevelBitExact:
    """Same gradients in -> the fused bucket update and the per-param loop
    produce bitwise identical parameters and accumulators (f32, no clip:
    the update math itself reorders nothing)."""

    @pytest.mark.parametrize("name", sorted(OPTIMIZERS))
    def test_fused_update_bitwise(self, name):
        m = _mlp()
        o = OPTIMIZERS[name](m.parameters())
        params = dict(m.named_parameters())
        names = list(params)
        rng = np.random.RandomState(3)
        grads = {n: np.asarray(
            rng.randn(*params[n].shape).astype(np.float32))
            for n in names}
        import jax.numpy as jnp
        grads = {n: jnp.asarray(g) for n, g in grads.items()}
        layout = build_layout(o, params, names)
        assert layout is not None and layout.buckets and not layout.residue
        flats = build_flat_states(o, layout, params)
        train = {n: params[n].data for n in names}
        lrs = [np.float32(o.get_lr())]

        new_train, new_flats, _, _ = fused_clip_and_update(
            o, layout, train, grads, flats, lrs, lambda g: g)
        per = split_flat_states(layout, new_flats)

        # reference: the optimizer's own rule, one param at a time
        for b, dicts in zip(layout.buckets, per):
            for n, fused_state in zip(b.names, dicts):
                p = params[n]
                st = o._ensure_state(p)
                ref_p, ref_s = o._update(
                    train[n], grads[n], st, np.float32(o.get_lr()),
                    weight_decay=b.decay_coeff, **b.kwargs)
                np.testing.assert_array_equal(
                    np.asarray(new_train[n]), np.asarray(ref_p), err_msg=n)
                for k, v in ref_s.items():
                    np.testing.assert_array_equal(
                        np.asarray(fused_state[k]), np.asarray(v),
                        err_msg=f"{n}.{k}")


class TestTrainStepParity:
    """End-to-end fused-vs-looped TrainStep: identical state layout, and
    values equal to float ulp noise (XLA compiles two different programs;
    their backward reductions fuse differently)."""

    TOL = dict(rtol=5e-6, atol=1e-7)

    @pytest.mark.parametrize("name", sorted(OPTIMIZERS))
    def test_plain_f32(self, name):
        m1, o1, l1 = _run_pair(OPTIMIZERS[name], fused=True)
        m2, o2, l2 = _run_pair(OPTIMIZERS[name], fused=False)
        np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-7)
        for a, b in zip(m1.parameters(), m2.parameters()):
            np.testing.assert_allclose(a.numpy(), b.numpy(), **self.TOL)
        _assert_state_dicts_match(o1.state_dict(), o2.state_dict(),
                                  rtol=1e-5, atol=1e-7)

    def test_global_norm_clip(self):
        mk = lambda ps: opt.AdamW(learning_rate=0.01, parameters=ps,
                                  grad_clip=nn.ClipGradByGlobalNorm(0.5))
        m1, o1, _ = _run_pair(mk, fused=True)
        m2, o2, _ = _run_pair(mk, fused=False)
        for a, b in zip(m1.parameters(), m2.parameters()):
            np.testing.assert_allclose(a.numpy(), b.numpy(), **self.TOL)

    def test_clip_by_value_and_by_norm(self):
        for clip in (nn.ClipGradByValue(0.01),
                     nn.ClipGradByNorm(0.05)):  # per-tensor: pre-clip path
            mk = lambda ps: opt.SGD(learning_rate=0.05, parameters=ps,
                                    grad_clip=clip)
            m1, _, _ = _run_pair(mk, fused=True)
            m2, _, _ = _run_pair(mk, fused=False)
            for a, b in zip(m1.parameters(), m2.parameters()):
                np.testing.assert_allclose(a.numpy(), b.numpy(), **self.TOL)

    def test_master_weights_bf16(self):
        mk = lambda ps: opt.AdamW(learning_rate=0.01, parameters=ps,
                                  multi_precision=True)
        m1, o1, _ = _run_pair(mk, fused=True, bf16=True)
        m2, o2, _ = _run_pair(mk, fused=False, bf16=True)
        for a, b in zip(m1.parameters(), m2.parameters()):
            assert str(a.data.dtype) == "bfloat16"
            np.testing.assert_allclose(
                a.numpy().astype(np.float32), b.numpy().astype(np.float32),
                rtol=2e-2, atol=1e-3)  # bf16 tolerance (issue acceptance)
        sd1, sd2 = o1.state_dict(), o2.state_dict()
        assert any(k.endswith(".master_weight") for k in sd1)
        _assert_state_dicts_match(sd1, sd2, rtol=1e-4, atol=1e-5)

    def test_param_groups_per_group_lr_and_decay(self):
        X, Y = _data()

        def mk(m):
            sched = opt.lr.StepDecay(0.5, step_size=1, gamma=0.1)
            return opt.AdamW(learning_rate=0.01, parameters=[
                {"params": [m[0].weight, m[0].bias], "weight_decay": 0.1},
                {"params": [m[2].weight, m[2].bias],
                 "learning_rate": sched, "weight_decay": 0.0},
            ])

        outs = []
        for fused in (True, False):
            pt.seed(7)
            m = _mlp(7)
            o = mk(m)
            s = pt.jit.TrainStep(m, _loss, o, fused=fused)
            for _ in range(4):
                s(t(X), t(Y))
            if fused:
                # the two groups must not share a bucket (distinct
                # group lr/decay feed the fused kernel as constants)
                assert len(s._layout.buckets) == 2
            outs.append(m)
        for a, b in zip(outs[0].parameters(), outs[1].parameters()):
            np.testing.assert_allclose(a.numpy(), b.numpy(), **self.TOL)

    def test_adamw_lr_ratio_and_decay_mask(self):
        """Per-param host-resolved hooks (the old opt._cur_param side
        channel): lr_ratio and apply_decay_param_fun split buckets and
        match the eager loop."""
        X, Y = _data()

        def mk(m):
            names_no_decay = {m[0].bias.name, m[2].bias.name}
            return opt.AdamW(
                learning_rate=0.01, parameters=m.parameters(),
                weight_decay=0.1,
                lr_ratio=lambda p: 0.1 if p.ndim == 1 else 1.0,
                apply_decay_param_fun=lambda n: n not in names_no_decay)

        outs = []
        for fused in (True, False):
            pt.seed(7)
            m = _mlp(7)
            s = pt.jit.TrainStep(m, _loss, mk(m), fused=fused)
            for _ in range(3):
                s(t(X), t(Y))
            if fused:
                assert len(s._layout.buckets) >= 2  # ratio/mask split
            outs.append(m)
        for a, b in zip(outs[0].parameters(), outs[1].parameters()):
            np.testing.assert_allclose(a.numpy(), b.numpy(), **self.TOL)

    def test_frozen_subset_stays_frozen(self):
        X, Y = _data()
        pt.seed(3)
        m = _mlp(3)
        head = [m[2].weight, m[2].bias]
        o = opt.AdamW(learning_rate=0.05, parameters=head)
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        backbone_before = m[0].weight.numpy().copy()
        head_before = m[2].weight.numpy().copy()
        s(t(X), t(Y))
        assert s._layout is not None and s._layout.buckets
        np.testing.assert_array_equal(m[0].weight.numpy(), backbone_before)
        assert not np.allclose(m[2].weight.numpy(), head_before)

    def test_lamb_exclude_fn_without_cur_param(self):
        """Lamb is unfusable (trust-ratio norms) but must keep its
        per-param decay exclusion through the host-resolved kwargs hook —
        the traced body no longer writes opt._cur_param."""
        X, Y = _data()
        m = _mlp(5)
        bias_ids = {id(m[0].bias), id(m[2].bias)}
        o = opt.Lamb(learning_rate=0.01, lamb_weight_decay=0.5,
                     parameters=m.parameters(),
                     exclude_from_weight_decay_fn=lambda p: id(p) in
                     bias_ids)
        s = pt.jit.TrainStep(m, _loss, o)
        assert s is not None
        s(t(X), t(Y))
        assert s._layout is None  # Lamb never fuses
        assert not hasattr(o, "_cur_param")
        kw = o._param_group_kwargs(m[0].bias, o._param_groups[0])
        assert kw["lamb_weight_decay"] == 0.0
        kw = o._param_group_kwargs(m[0].weight, o._param_groups[0])
        assert kw["lamb_weight_decay"] == 0.5


class TestCompileOnceAndLayoutStability:
    def test_scheduler_tick_no_retrace_no_relayout(self, monkeypatch):
        import paddle_tpu.jit.train_step as ts_mod
        builds = []
        orig = ts_mod.build_layout
        monkeypatch.setattr(ts_mod, "build_layout",
                            lambda *a, **k: builds.append(1) or orig(*a, **k))
        X, Y = _data()
        m = _mlp()
        sched = opt.lr.StepDecay(0.1, step_size=1, gamma=0.5)
        o = opt.AdamW(learning_rate=sched, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        for _ in range(4):
            s(t(X), t(Y))
            sched.step()
        assert len(s._cache) == 1          # LR tick never retraces
        assert len(builds) == 1            # bucket layout built once
        assert len(s._plans) == 1

    def test_flat_state_not_rebuilt_across_steps(self, monkeypatch):
        import paddle_tpu.jit.train_step as ts_mod
        rebuilds = []
        orig = ts_mod.build_flat_states
        monkeypatch.setattr(
            ts_mod, "build_flat_states",
            lambda *a, **k: rebuilds.append(1) or orig(*a, **k))
        X, Y = _data()
        m = _mlp()
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        for _ in range(4):
            s(t(X), t(Y))
        assert len(rebuilds) == 1  # donated flats round-trip, no concat


class TestFlushProtocol:
    def test_state_dict_reflects_fused_steps(self):
        X, Y = _data()
        m = _mlp()
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        for _ in range(2):
            s(t(X), t(Y))
        sd = o.state_dict()
        moments = [np.abs(np.asarray(v.data)).max()
                   for k, v in sd.items() if k.endswith(".moment1")]
        assert moments and all(mv > 0 for mv in moments)

    def test_set_state_dict_wins_over_flat_cache(self):
        X, Y = _data()
        m = _mlp()
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        for _ in range(3):
            s(t(X), t(Y))
        zeroed = {}
        for k, v in o.state_dict().items():
            if hasattr(v, "data") and "pow" not in k:
                zeroed[k] = pt.to_tensor(np.zeros_like(np.asarray(v.data)))
            else:
                zeroed[k] = v
        o.set_state_dict(zeroed)
        s(t(X), t(Y))  # must rebuild flats from the restored zeros
        sd = o.state_dict()
        # one step from zeroed moments: |moment1| == (1-beta1)*|g| — far
        # smaller than 3 accumulated steps would leave behind
        m1 = [np.asarray(v.data) for k, v in sd.items()
              if k.endswith(".moment1")]
        assert all(np.isfinite(a).all() for a in m1)

    def test_mixed_fused_then_eager_steps(self):
        X, Y = _data()
        m1, o1, _ = _run_pair(OPTIMIZERS["momentum"], fused=True, steps=2)
        m2, o2, _ = _run_pair(OPTIMIZERS["momentum"], fused=False, steps=2)
        # TWO extra EAGER steps on both: the first flushes the fused
        # run's flat velocity; the second's _sync_state must NOT
        # re-install the now-stale flats over the first eager step's
        # writes (regression: flush clobbered newer external state)
        for m, o in ((m1, o1), (m2, o2)):
            for _ in range(2):
                loss = _loss(m, t(X), t(Y))
                loss.backward()
                o.step()
                o.clear_grad()
        for a, b in zip(m1.parameters(), m2.parameters()):
            np.testing.assert_allclose(a.numpy(), b.numpy(), rtol=5e-6,
                                       atol=1e-7)

    def test_per_param_arrays_released_while_flat(self):
        """No duplicate accumulator memory: while the flats are
        authoritative the per-param dicts are empty (identity kept),
        and state reads re-materialize through the flush."""
        X, Y = _data()
        m = _mlp()
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        s(t(X), t(Y))
        assert all(not o._state[id(p)] for p in m.parameters())
        sd = o.state_dict()  # flush reinstalls full per-param dicts
        assert any(k.endswith(".moment1") for k in sd)
        s(t(X), t(Y))  # the next step releases them again
        assert all(not o._state[id(p)] for p in m.parameters())

    def test_dropped_trainstep_flushes_on_del(self):
        import gc
        X, Y = _data()
        m = _mlp()
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        s(t(X), t(Y))
        del s
        gc.collect()
        sd = o.state_dict()  # the flat state must have been flushed
        vals = [np.abs(np.asarray(v.data)).max()
                for k, v in sd.items() if k.endswith(".moment1")]
        assert vals and all(v > 0 for v in vals)
        # and the dead holder's weakref hook is pruned on next register
        assert all(r() is None for r in o._state_sync_hooks)

    def test_alternating_batch_shapes_share_flats(self, monkeypatch):
        """Two compile keys (different batch signatures) over one
        trainable set reuse ONE flat cache — no per-step flush/rebuild
        round trip (regression: single-slot cache keyed by compile
        key)."""
        import paddle_tpu.jit.train_step as ts_mod
        rebuilds = []
        orig = ts_mod.build_flat_states
        monkeypatch.setattr(
            ts_mod, "build_flat_states",
            lambda *a, **k: rebuilds.append(1) or orig(*a, **k))
        X, Y = _data()
        m = _mlp()
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, fused=True)
        for _ in range(3):
            s(t(X), t(Y))            # full batch
            s(t(X[:8]), t(Y[:8]))    # tail batch: second compile key
        assert len(s._cache) == 2
        assert len(rebuilds) == 1

    def test_two_trainsteps_one_optimizer_stay_coherent(self):
        X, Y = _data()
        pt.seed(7)
        m = _mlp(7)
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        sa = pt.jit.TrainStep(m, _loss, o, fused=True)
        sb = pt.jit.TrainStep(m, _loss, o, fused=True)
        la = float(sa(t(X), t(Y)).numpy())
        lb = float(sb(t(X), t(Y)).numpy())
        assert lb < la  # second step saw the first step's accumulators
        m2, o2, losses2 = _run_pair(OPTIMIZERS["adamw"], fused=True,
                                    steps=2)
        np.testing.assert_allclose([la, lb], losses2, rtol=1e-5, atol=1e-7)


class TestCheckpointRoundTrip:
    """Optimizer state crosses the fused/eager boundary through
    CheckpointManager with the per-parameter layout intact."""

    def _ckpt(self, tmp_path, o):
        from paddle_tpu.checkpoint import CheckpointManager
        mgr = CheckpointManager(str(tmp_path), async_=False)
        mgr.save(0, {"optimizer": o.state_dict()})
        return mgr

    def test_save_fused_restore_eager(self, tmp_path):
        X, Y = _data()
        m1, o1, _ = _run_pair(OPTIMIZERS["adamw"], fused=True, steps=3)
        mgr = self._ckpt(tmp_path, o1)
        state = mgr.restore()["optimizer"]

        # an EAGER continuation from the checkpoint == the fused run's own
        # eager continuation (state crossed the boundary losslessly)
        pt.seed(11)
        m2 = _mlp(11)
        for p2, p1 in zip(m2.parameters(), m1.parameters()):
            p2.set_value(p1.numpy())
        o2 = OPTIMIZERS["adamw"](m2.parameters())
        o2.set_state_dict(state)
        _assert_state_dicts_match(o1.state_dict(), o2.state_dict())
        for m, o in ((m1, o1), (m2, o2)):
            loss = _loss(m, t(X), t(Y))
            loss.backward()
            o.step()
            o.clear_grad()
        for a, b in zip(m1.parameters(), m2.parameters()):
            np.testing.assert_array_equal(a.numpy(), b.numpy())

    def test_save_eager_restore_fused(self, tmp_path):
        X, Y = _data()
        # eager-trained state restored into a fused TrainStep
        pt.seed(9)
        m1 = _mlp(9)
        o1 = OPTIMIZERS["adamw"](m1.parameters())
        for _ in range(3):
            loss = _loss(m1, t(X), t(Y))
            loss.backward()
            o1.step()
            o1.clear_grad()
        mgr = self._ckpt(tmp_path, o1)
        state = mgr.restore()["optimizer"]

        pt.seed(9)
        m2 = _mlp(9)
        for p2, p1 in zip(m2.parameters(), m1.parameters()):
            p2.set_value(p1.numpy())
        o2 = OPTIMIZERS["adamw"](m2.parameters())
        o2.set_state_dict(state)
        s = pt.jit.TrainStep(m2, _loss, o2, fused=True)
        s(t(X), t(Y))
        # the fused step consumed the restored accumulators: state_dict
        # advanced from the checkpoint, layout still per-parameter
        sd = o2.state_dict()
        assert set(sd) == set(state)
        for k in state:
            if hasattr(state[k], "data") and k.endswith(".moment1"):
                assert not np.array_equal(np.asarray(sd[k].data),
                                          np.asarray(state[k].data))

    def test_per_parameter_layout_byte_identical(self, tmp_path):
        """The checkpoint written after fused steps has the same keys,
        dtypes and shapes as one written by the eager loop — the PR 3
        manager sees no layout difference at all."""
        m1, o1, _ = _run_pair(OPTIMIZERS["adamw"], fused=True, steps=2)
        m2, o2, _ = _run_pair(OPTIMIZERS["adamw"], fused=False, steps=2)
        sd1, sd2 = o1.state_dict(), o2.state_dict()
        assert set(sd1) == set(sd2)
        for k in sd1:
            a, b = sd1[k], sd2[k]
            if hasattr(a, "data"):
                assert np.asarray(a.data).dtype == np.asarray(b.data).dtype
                assert np.asarray(a.data).shape == np.asarray(b.data).shape


@pytest.fixture()
def dp8():
    import paddle_tpu.distributed as dist
    return dist.init_mesh({"dp": 8})


def _count_all_reduce(hlo_text):
    """Buffers all-reduced in the compiled module: counted per payload
    (``analysis.hlo.collective_census``), because XLA's combiner packs
    the independent all-reduces of a program this small into one variadic
    instruction, and the contract is about what travels."""
    from paddle_tpu.analysis.hlo import collective_census
    return collective_census(hlo_text)["all-reduce"]


class TestBucketedCollectives:
    def _dp_step(self, mesh, fused=True, bucketed=None, seed=3,
                 optimizer=opt.AdamW):
        import paddle_tpu.distributed as dist
        pt.seed(seed)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        m = dist.DataParallel(net, mesh=mesh)
        o = optimizer(learning_rate=0.01, parameters=m.parameters())
        return m, o, pt.jit.TrainStep(m, _loss, o, fused=fused,
                                      bucketed=bucketed)

    def _batch(self):
        rng = np.random.RandomState(0)
        X = rng.randn(64, 16).astype(np.float32)
        return X, X @ rng.randn(16, 4).astype(np.float32)

    def test_hlo_reductions_equal_bucket_count(self, dp8):
        X, Y = self._batch()
        m, o, s = self._dp_step(dp8)
        hlo = s.compiled_hlo(t(X), t(Y))
        assert s._bucketed_reason is None
        n_buckets = len(s._comm_buckets)
        # default 25MB target: one bucket for this model — bucketed, not
        # per-param (4 trainable tensors), not a per-param count
        assert n_buckets == 1
        # + 1 is the scalar loss pmean
        assert _count_all_reduce(hlo) == n_buckets + 1

    def test_bucket_size_env_changes_count(self, dp8, monkeypatch):
        X, Y = self._batch()
        monkeypatch.setenv("PADDLE_TPU_COMM_BUCKET_MB", "0.000001")
        m, o, s = self._dp_step(dp8)
        hlo = s.compiled_hlo(t(X), t(Y))
        n_buckets = len(s._comm_buckets)
        assert n_buckets == 4  # one per parameter at a ~1-byte target
        assert _count_all_reduce(hlo) == n_buckets + 1

    def test_gspmd_fallback_emits_per_param_reductions(self, dp8):
        X, Y = self._batch()
        m, o, s = self._dp_step(dp8, bucketed=False)
        hlo = s.compiled_hlo(t(X), t(Y))
        assert s._comm_buckets is None
        # per-param grads + loss: strictly more reductions than the
        # bucketed step's 2
        assert _count_all_reduce(hlo) > 2

    @pytest.mark.parametrize("optimizer", [opt.AdamW, opt.SGD])
    def test_bucketed_matches_single_device(self, dp8, optimizer):
        """SGD beside AdamW: Adam divides the gradient's scale out, so
        only a plain update shows a gradient summed over the shards where
        it should be their mean."""
        X, Y = self._batch()
        pt.seed(3)
        m1 = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        o1 = optimizer(learning_rate=0.01, parameters=m1.parameters())
        s1 = pt.jit.TrainStep(m1, _loss, o1)
        base = [float(s1(t(X), t(Y)).numpy()) for _ in range(6)]
        m2, o2, s2 = self._dp_step(dp8, optimizer=optimizer)
        got = [float(s2(t(X), t(Y)).numpy()) for _ in range(6)]
        assert s2._bucketed_reason is None
        np.testing.assert_allclose(got, base, rtol=2e-4, atol=1e-6)
        # params stay replicated across the mesh after bucketed steps
        p = m2.parameters()[0]
        assert len({str(sh.device)
                    for sh in p.data.addressable_shards}) == 8

    def test_buckets_reverse_order_and_size_target(self):
        import jax.numpy as jnp
        train = {f"p{i}": jnp.zeros((256,), jnp.float32) for i in range(6)}
        # 1KB per tensor; 2KB target -> 3 buckets of 2, reverse order
        buckets = plan_comm_buckets(train, target_bytes=2048)
        assert buckets == [("p5", "p4"), ("p3", "p2"), ("p1", "p0")]
        # mixed dtypes never share a payload
        train["p6"] = jnp.zeros((256,), jnp.bfloat16)
        buckets = plan_comm_buckets(train, target_bytes=10 ** 9)
        assert buckets[0] == ("p6",)

    def test_eligibility_reasons(self, dp8):
        import paddle_tpu.distributed as dist
        X, Y = self._batch()
        # plain (non-DataParallel) mesh step keeps GSPMD
        pt.seed(0)
        m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        s = pt.jit.TrainStep(m, _loss, o, mesh=dp8,
                             input_spec=pt.distributed.P("dp"))
        s(t(X), t(Y))
        assert s._comm_buckets is None
        assert "DataParallel" in s._bucketed_reason

    def test_zero_keeps_gspmd_and_sharded_states(self):
        """ZeRO stage 1: fused layout disabled, bucketed path disabled,
        accumulators still shard over the mesh exactly as before."""
        import paddle_tpu.distributed as dist
        mesh = dist.init_mesh({"sharding": 8})
        rng = np.random.RandomState(0)
        X = rng.randn(32, 16).astype(np.float32)
        Y = X @ rng.randn(16, 8).astype(np.float32)
        pt.seed(3)
        m = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 8))
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        m, o, _ = dist.group_sharded_parallel(m, o, level="os")
        s = pt.jit.TrainStep(m, _loss, o, mesh=mesh,
                             input_spec=dist.P("sharding"))
        s(t(X), t(Y))
        assert s._layout is None and s._comm_buckets is None
        w = m[0].weight
        moment = o._state[id(w)]["moment1"]
        assert len({str(sh.device)
                    for sh in moment.addressable_shards}) == 8


class TestCompiledHloInspection:
    def test_rng_neutral(self):
        """Inspecting the program mid-training must not shift the key
        stream (resume == uninterrupted digest equality rides on it)."""
        X, Y = _data()

        def run(inspect):
            pt.seed(7)
            m = _mlp(7)
            o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
            s = pt.jit.TrainStep(m, _loss, o, fused=True)
            out = [float(s(t(X), t(Y)).numpy())]
            if inspect:
                s.compiled_hlo(t(X), t(Y))
            out += [float(s(t(X), t(Y)).numpy()) for _ in range(2)]
            return out

        np.testing.assert_array_equal(run(True), run(False))


class TestXlaTuning:
    def test_flags_appended_to_libtpu_init_args(self):
        from paddle_tpu.device import apply_xla_tuning, XLA_TUNING_FLAGS
        # the chip machine's image pre-sets one libtpu flag: keep it
        env = {"LIBTPU_INIT_ARGS": " --xla_tpu_foo=1"}
        applied = apply_xla_tuning(env)
        assert len(applied) == len(XLA_TUNING_FLAGS)
        assert env["LIBTPU_INIT_ARGS"].startswith("--xla_tpu_foo=1 ")
        for name in XLA_TUNING_FLAGS:
            assert name + "=" in env["LIBTPU_INIT_ARGS"]
        # jaxlib's own parser aborts on every one of them
        assert "XLA_FLAGS" not in env

    def test_user_setting_wins(self):
        from paddle_tpu.device import apply_xla_tuning
        user = "--xla_tpu_enable_latency_hiding_scheduler=false"
        env = {"LIBTPU_INIT_ARGS": user}
        apply_xla_tuning(env)
        assert env["LIBTPU_INIT_ARGS"].count(
            "--xla_tpu_enable_latency_hiding_scheduler") == 1
        assert user in env["LIBTPU_INIT_ARGS"]

    def test_longer_user_flag_does_not_shadow_prefix_flag(self):
        """Exact flag-name matching: a user flag whose name merely
        CONTAINS a tuning flag's name must not suppress it."""
        from paddle_tpu.device import apply_xla_tuning
        env = {"LIBTPU_INIT_ARGS":
               "--xla_tpu_enable_async_collective_fusion_fuse_all_gather"
               "=false"}
        applied = apply_xla_tuning(env)
        assert "--xla_tpu_enable_async_collective_fusion=true" in applied
        # and the user's longer flag stays exactly once, untouched
        assert env["LIBTPU_INIT_ARGS"].count("fuse_all_gather=false") == 1
        assert "fuse_all_gather=true" not in env["LIBTPU_INIT_ARGS"]

    def test_disable_env(self):
        from paddle_tpu.device import apply_xla_tuning
        env = {"PADDLE_TPU_NO_XLA_TUNING": "1"}
        assert apply_xla_tuning(env) == []
        assert "LIBTPU_INIT_ARGS" not in env

    def test_flags_documented(self):
        from paddle_tpu.device import XLA_TUNING_FLAGS
        for name, (value, why) in XLA_TUNING_FLAGS.items():
            assert name.startswith("--xla")
            assert value and why and len(why) > 10


class TestReportGateWiring:
    def test_optimizer_phase_gates_lower_better(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench", __file__.replace(
                "tests/test_fused_optimizer.py", "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        for metric in ("optimizer_phase_seconds",
                       "train_step_exposed_collective_seconds"):
            assert metric in bench.REPORT_LOWER_BETTER
            worse = bench.report_compare({metric: 1.0}, {metric: 1.5}, 3.0)
            assert worse["failures"] == [metric]
            better = bench.report_compare({metric: 1.0}, {metric: 0.5}, 3.0)
            assert not better["failures"]
