"""Training numerics observatory (paddle_tpu.observability.numerics).

Coverage contract (ISSUE 14): the disarmed-tap bit-identity guarantee
(tap-on-but-disarmed program == never-instrumented program, compiled-HLO
text AND loss bits), arming mid-run compiles exactly ONE instrumented
twin (then compile-once), sampled-step tap/grad/update stat sanity plus
the ``numerics_*`` gauge families, sampling cadence
(``PADDLE_TPU_NUMERICS_EVERY``), the NaN-provenance probe (poisoned
layer named as the FIRST non-finite tap in topological order, end to
end through a NaNGuard rollback in ``Model.fit``), the host-side-only
corruption counterexample (``verdict: "finite_in_graph"``), calibration
sketch accumulation + checkpoint round-trip (``FitResilience``), the
``grad_norm`` fit-log / ``train_grad_norm`` gauge satellite, and the
serving decode-path drift gauges.
"""
import json
import os
import re
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import numerics
from paddle_tpu.observability.metrics import get_registry

NUM_VARS = ("PADDLE_TPU_NUMERICS", "PADDLE_TPU_NUMERICS_EVERY",
            "PADDLE_TPU_NUMERICS_PROVENANCE", "PADDLE_TPU_TRACE_DIR",
            "PADDLE_TPU_CHAOS_CORRUPT_LOSS")


@pytest.fixture(autouse=True)
def _numerics_clean():
    """Numerics env and the observatory singleton must never leak
    between tests (sketches accumulate per process)."""
    saved = {k: os.environ.get(k) for k in NUM_VARS}
    yield
    for k, v in saved.items():
        os.environ.pop(k, None) if v is None \
            else os.environ.__setitem__(k, v)
    numerics._observatory = None
    from paddle_tpu.resilience import chaos
    chaos.refresh()


def _tiny_lm(seed=0):
    pt.seed(seed)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=True))


def _lm_step(seed=0, clip=True):
    """(TrainStep, batch) on the tap-instrumented tiny llama."""
    model = _tiny_lm(seed)
    opt = pt.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(),
        grad_clip=pt.nn.ClipGradByGlobalNorm(1.0) if clip else None)
    step = pt.jit.TrainStep(model, lambda m, t: m(t, labels=t)[1], opt)
    rng = np.random.RandomState(0)
    x = pt.to_tensor(rng.randint(0, 64, (2, 16)).astype(np.int64))
    return model, step, (x,)


def _lm_batches(n=4, bs=2, seqlen=16, vocab=64):
    rng = np.random.RandomState(1)
    out = []
    for _ in range(n):
        ids = rng.randint(0, vocab, (bs, seqlen)).astype(np.int64)
        out.append({"input_ids": ids, "labels": ids.copy()})
    return out


# ---------------------------------------------------------------------------
# disarmed-tap contract: bit-identical program, zero extra compiles
# ---------------------------------------------------------------------------

def _instructions(hlo: str) -> str:
    """Compiled-HLO text without its call sites: the ``metadata={...}``
    attribute of each instruction and the file, function, location and
    stack-frame tables at the head of the module."""
    hlo = re.sub(r',? metadata=\{(?:[^{}"]|"[^"]*")*\}', "", hlo)
    return re.sub(r"(?m)^(?:FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n(?:.+\n)*\n", "", hlo)


class TestDisarmedContract:
    def test_disarmed_program_bit_identical_to_never_instrumented(
            self, monkeypatch):
        """The tap seam disarmed must cost NOTHING: the same compiled
        instructions and bit-equal losses as a build where the seam never
        existed (taps monkeypatched to bare identity). The two builds are
        traced from different lines of this file and through different
        frames (``numerics.tap`` against a lambda here), so the texts are
        compared without their call sites: the contract is the program,
        not where it was called from."""
        os.environ.pop("PADDLE_TPU_NUMERICS", None)

        _, step_a, batch = _lm_step(seed=3)
        hlo_a = step_a.compiled_hlo(*batch)
        losses_a = [float(step_a(*batch).numpy())]

        # a build whose model code never had the seam: tap is identity,
        # scope/suppress are inert context managers
        from contextlib import contextmanager

        @contextmanager
        def _null(*a, **k):
            yield

        monkeypatch.setattr(numerics, "tap", lambda name, x: x)
        monkeypatch.setattr(numerics, "scope", _null)
        monkeypatch.setattr(numerics, "suppress", _null)
        _, step_b, batch_b = _lm_step(seed=3)
        hlo_b = step_b.compiled_hlo(*batch_b)
        losses_b = [float(step_b(*batch_b).numpy())]

        assert "metadata=" in hlo_a and "metadata=" not in _instructions(
            hlo_a)
        assert _instructions(hlo_a) == _instructions(hlo_b), \
            "disarmed tap seam changed the compiled program"
        assert losses_a == losses_b, \
            "disarmed tap seam changed the training math"
        assert len(step_a._cache) == len(step_b._cache) == 1

    def test_arming_mid_run_compiles_exactly_one_twin(self):
        os.environ.pop("PADDLE_TPU_NUMERICS", None)
        _, step, batch = _lm_step(seed=4)
        step(*batch)
        step(*batch)
        assert len(step._cache) == 1
        os.environ["PADDLE_TPU_NUMERICS"] = "1"
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "1"
        step(*batch)
        assert len(step._cache) == 2, \
            "arming must add exactly ONE instrumented executable"
        step(*batch)
        step(*batch)
        assert len(step._cache) == 2, "instrumented twin must be cached"
        # disarming goes back to the plain executable, no new compiles
        os.environ["PADDLE_TPU_NUMERICS"] = "0"
        step(*batch)
        assert len(step._cache) == 2


# ---------------------------------------------------------------------------
# sampled-step stats
# ---------------------------------------------------------------------------

class TestSampledStats:
    def test_sample_contents_and_gauges(self):
        os.environ["PADDLE_TPU_NUMERICS"] = "1"
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "1"
        _, step, batch = _lm_step(seed=5)
        step(*batch)
        s = step.last_numerics
        assert s is not None
        # taps in topological (execution) order, all stats finite
        names = list(s["taps"])
        assert names[0] == "embed" and names[-1] == "logits"
        assert names.index("layers.0.attn") < names.index("layers.1.attn")
        assert len(names) == 11  # embed + 2x(attn,mlp_act,mlp,resid) + 2
        for name, (absmax, mean, rms, nonfinite) in s["taps"].items():
            assert np.isfinite((absmax, mean, rms)).all(), name
            assert nonfinite == 0, name
            assert absmax >= rms >= 0, name
        # fused-bucket grad stats + update/param norms + global norm
        assert s["grads"] and s["updates"]
        for norm, nonfinite in s["grads"].values():
            assert np.isfinite(norm) and nonfinite == 0
        for unorm, pnorm in s["updates"].values():
            assert np.isfinite(unorm) and pnorm > 0
        assert np.isfinite(s["grad_norm"]) and np.isfinite(s["loss"])
        # observatory published the gauge families
        doc = get_registry().to_json()
        assert any(v["labels"].get("tap") == "embed"
                   for v in doc["numerics_tap_absmax"]["samples"])
        assert doc["numerics_grad_norm"]["samples"]
        assert doc["numerics_update_ratio"]["samples"]

    def test_sampling_cadence_every_n(self):
        """The cadence decision function alone — the EVERY=1 publication
        path through a real compiled twin is pinned above."""
        os.environ["PADDLE_TPU_NUMERICS"] = "1"
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "3"
        sampled = [i for i in range(1, 13)
                   if numerics.sample_this_step(i)]
        assert sampled == [1, 3, 6, 9, 12]  # step 1 always sampled
        # malformed / non-positive periods fall back to the default
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "banana"
        assert numerics.every() == 32
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "-3"
        assert numerics.every() == 32
        os.environ["PADDLE_TPU_NUMERICS"] = "0"
        assert not numerics.sample_this_step(1)


# ---------------------------------------------------------------------------
# NaN provenance
# ---------------------------------------------------------------------------

def _poison(model, value=float("nan")):
    """NaN-poison layer 1's down_proj weight: the first tap to go
    non-finite in topological order is layers.1.mlp."""
    w = model.model.layers[1].mlp.down_proj.weight
    arr = w.numpy().copy()
    arr[0, 0] = value
    w.set_value(pt.to_tensor(arr))


class TestNaNProvenance:
    def test_probe_names_first_nonfinite_tap(self, tmp_path):
        os.environ["PADDLE_TPU_NUMERICS_PROVENANCE"] = "1"
        os.environ["PADDLE_TPU_TRACE_DIR"] = str(tmp_path)
        model, step, batch = _lm_step(seed=7)
        step(*batch)  # stashes the batch + rng parts
        _poison(model)
        # neutrality pins around the probe: weights, the rng stream and
        # the compile-once guard on ``_cache`` must all be untouched (a
        # probe that perturbs what it inspects breaks resume digests)
        from paddle_tpu.core import generator
        state0 = {k: v.numpy().copy()
                  for k, v in model.state_dict().items()}
        rng0 = generator.get_rng_state()
        cache0 = len(step._cache)
        path = numerics.write_provenance(step, step=1,
                                         trip_kind="loss_nan")
        doc = json.load(open(path))
        assert doc["schema"] == "nan_provenance_v1"
        assert doc["verdict"] == "nonfinite_in_graph"
        assert doc["first_nonfinite"]["kind"] == "tap"
        assert doc["first_nonfinite"]["name"] == "layers.1.mlp"
        # upstream of the poison stays finite in the replay record
        taps = doc["replay"]["taps"]
        assert taps["layers.1.mlp_act"]["nonfinite"] == 0
        assert taps["layers.1.mlp"]["nonfinite"] > 0
        assert generator.get_rng_state() == rng0
        assert len(step._cache) == cache0
        for k, v in model.state_dict().items():
            np.testing.assert_array_equal(v.numpy(), state0[k])

    def test_fit_nan_drill_end_to_end(self, tmp_path):
        """Acceptance drill: poison committed INTO the checkpoint (the
        poison callback runs before FitResilience's save), next step's
        loss goes NaN, the guard rolls back and the forced replay names
        the poisoned layer."""
        from paddle_tpu.resilience import FitResilience
        os.environ["PADDLE_TPU_NUMERICS_PROVENANCE"] = "1"
        os.environ["PADDLE_TPU_TRACE_DIR"] = str(tmp_path / "trace")
        lm = _tiny_lm(seed=9)
        model = pt.hapi.Model(lm)
        model.prepare(pt.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=model.parameters()))

        # poison at the END of step 2 (before FitResilience's save of
        # step 2 — the poison is committed INTO the checkpoint); step 3
        # is the last batch, so the guard trips exactly once
        class Poison(pt.callbacks.Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 2:
                    _poison(lm)

        fr = FitResilience(checkpoint_dir=str(tmp_path / "ckpt"),
                           save_every_steps=1, nan_guard=True,
                           preemption=False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            model.fit(_lm_batches(n=3), epochs=1, verbose=0, shuffle=False,
                      callbacks=[Poison(), fr])
        assert fr.nan_guard.rollbacks == 1
        files = [f for f in os.listdir(tmp_path / "trace")
                 if f.startswith("nan_provenance_")]
        assert len(files) == 1
        doc = json.load(open(tmp_path / "trace" / files[0]))
        assert doc["trip_kind"] == "loss_nan"
        assert doc["verdict"] == "nonfinite_in_graph"
        assert doc["first_nonfinite"]["name"] == "layers.1.mlp"

    def test_host_side_corruption_replays_finite(self, tmp_path):
        """A chaos-injected host-side NaN loss replays all-finite: the
        provenance document must say so instead of inventing a layer."""
        from paddle_tpu.resilience import FitResilience
        os.environ["PADDLE_TPU_NUMERICS_PROVENANCE"] = "1"
        os.environ["PADDLE_TPU_TRACE_DIR"] = str(tmp_path / "trace")
        os.environ["PADDLE_TPU_CHAOS_CORRUPT_LOSS"] = "2"
        model = pt.hapi.Model(nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                            nn.Linear(16, 1)))
        model.prepare(pt.optimizer.SGD(learning_rate=0.01,
                                       parameters=model.parameters()),
                      nn.MSELoss())
        rng = np.random.RandomState(0)
        data = [(rng.randn(4, 8).astype(np.float32),
                 rng.randn(4, 1).astype(np.float32)) for _ in range(4)]
        fr = FitResilience(checkpoint_dir=str(tmp_path / "ckpt"),
                           save_every_steps=1, nan_guard=True,
                           preemption=False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            model.fit(data, epochs=1, verbose=0, callbacks=[fr])
        assert fr.nan_guard.rollbacks == 1
        files = [f for f in os.listdir(tmp_path / "trace")
                 if f.startswith("nan_provenance_")]
        assert len(files) == 1
        doc = json.load(open(tmp_path / "trace" / files[0]))
        assert doc["verdict"] == "finite_in_graph"
        assert doc["first_nonfinite"] is None


# ---------------------------------------------------------------------------
# calibration sketches + checkpoint aux state
# ---------------------------------------------------------------------------

class TestCalibration:
    def test_sketch_accumulates_and_merges(self):
        sk = numerics._Sketch()
        for v in (0.5, 1.5, 3.0, 100.0):
            sk.add(v)
        s = sk.summary()
        assert s["n"] == 4 and s["absmax"] == 100.0
        assert s["p99"] >= 100.0  # bucket upper edge covers the max
        other = numerics._Sketch()
        other.merge(s)
        other.add(200.0)
        assert other.absmax == 200.0 and other.summary()["n"] == 5

    def test_fit_commits_and_restores_calibration(self, tmp_path):
        from paddle_tpu.resilience import FitResilience
        os.environ["PADDLE_TPU_NUMERICS"] = "1"
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "1"
        lm = _tiny_lm(seed=10)
        model = pt.hapi.Model(lm)
        model.prepare(pt.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=model.parameters()))
        fr = FitResilience(checkpoint_dir=str(tmp_path),
                           save_every_steps=1, preemption=False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            model.fit(_lm_batches(n=2), epochs=1, verbose=0,
                      callbacks=[fr])
        state = fr.manager.restore()
        assert "numerics" in state
        taps = state["numerics"]["taps"]
        assert "final_norm" in taps and taps["final_norm"]["n"] >= 1
        # a fresh process (serving calibration load) merges the summary
        numerics._observatory = None
        obs = numerics.get_observatory()
        obs.load_summary(state["numerics"])
        assert obs.sketches["final_norm"].absmax == \
            taps["final_norm"]["absmax"]


# ---------------------------------------------------------------------------
# satellites: fit-log grad_norm, flight-recorder appendix, serving drift
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_grad_norm_in_fit_logs_and_gauge(self):
        seen = []

        class Grab(pt.callbacks.Callback):
            def on_train_batch_end(self, step, logs=None):
                seen.append((logs or {}).get("grad_norm"))

        model = pt.hapi.Model(nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                            nn.Linear(16, 1)))
        model.prepare(
            pt.optimizer.SGD(learning_rate=0.01,
                             parameters=model.parameters(),
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0)),
            nn.MSELoss())
        rng = np.random.RandomState(0)
        data = [(rng.randn(4, 8).astype(np.float32),
                 rng.randn(4, 1).astype(np.float32)) for _ in range(3)]
        model.fit(data, epochs=1, verbose=0,
                  callbacks=[pt.callbacks.StepTelemetry(peak=0), Grab()])
        assert len(seen) == 3
        assert all(g is not None and np.isfinite(g) for g in seen)
        doc = get_registry().to_json()
        assert doc["train_grad_norm"]["samples"]

    def test_flight_recorder_appendix_carries_last_sample(self):
        os.environ["PADDLE_TPU_NUMERICS"] = "1"
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "1"
        _, step, batch = _lm_step(seed=11)
        step(*batch)
        from paddle_tpu.observability import flight_recorder as fr
        appendix = fr._ledger_appendix()
        assert appendix.get("numerics", {}).get("step") == 1
        assert "taps" in appendix["numerics"]

    def test_serving_decode_drift_gauges(self):
        os.environ["PADDLE_TPU_NUMERICS"] = "1"
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "2"
        from paddle_tpu.serving import ServingEngine
        lm = _tiny_lm(seed=12)
        lm.eval()
        # a training calibration sketch makes the drift ratio computable
        obs = numerics.get_observatory()
        obs.load_summary({"version": 1, "taps": {
            "final_norm": {"n": 1, "absmax": 1.0, "p50": 1.0,
                           "p99": 1.0, "buckets": {}}}})
        eng = ServingEngine(lm, max_batch=2, max_blocks=16, block_size=4,
                            prefill_chunk=4)
        h = eng.submit([1, 2, 3], max_new_tokens=4, temperature=0.0)
        eng.start()
        h.result(timeout=60)
        eng.shutdown()
        assert eng.step_traces == 2  # plain + the instrumented twin
        doc = get_registry().to_json()
        assert any(v["labels"].get("tap") == "final_norm"
                   for v in doc["numerics_decode_absmax"]["samples"])
        assert any(v["labels"].get("tap") == "final_norm" and v["value"] > 0
                   for v in doc["numerics_decode_drift_ratio"]["samples"])

    def test_disarmed_serving_engine_untouched(self):
        os.environ.pop("PADDLE_TPU_NUMERICS", None)
        from paddle_tpu.serving import ServingEngine
        lm = _tiny_lm(seed=13)
        lm.eval()
        eng = ServingEngine(lm, max_batch=2, max_blocks=16, block_size=4,
                            prefill_chunk=4)
        h = eng.submit([1, 2, 3], max_new_tokens=3, temperature=0.0)
        eng.start()
        h.result(timeout=60)
        eng.shutdown()
        assert eng.step_traces == 1 and eng._numerics_step is None
