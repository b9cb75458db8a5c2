"""``correct`` through the whole run flow at a tiny size: sound runs pass
under the cells' own limits; the control (the reference one precision lower
in the program's place) and each planted fault come out as not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny as tiny
from benchmark import control, harness, sut

LIMITS = {c: harness.load_cell(harness.load_manifest(), c)[3]
          for c in ("train-4k", "serve-chat", "serve-flood")}


# --------------------------------------------------------------- training --
def _train(**hooks):
    ctx = tiny.context("train-4k", tiny.train_mix(), hooks=hooks)
    out = harness.run_cell(ctx)
    return tiny.result("train-4k", out, LIMITS["train-4k"]), out


def test_sound_training_run_is_correct():
    line, out = _train()
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"loss_step1", "loss_step2", "loss_step3",
                                     "grad_worst_leaf", "grad_sketch_gap",
                                     "change_worst_leaf"}
    assert line["attempted"] == len(out.run["step_done_s"]) > 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(line)[-1] == "compared"


class _StateUnchanged(sut.Trainer):
    """A step that hands back the loss and leaves its state as it was."""

    def __init__(self, *a):
        super().__init__(*a)
        # host copies: the step donates the device buffers it is given
        self._start = {n: np.asarray(p._data)
                       for n, p in self.model.named_parameters()}

    def __call__(self, x):
        loss = super().__call__(x)
        sig, layout, flats, ids = self.step._flat_cache
        flats = [{k: jnp.zeros_like(v) if k.startswith("moment") else v
                  for k, v in f.items()} for f in flats]
        self.step._flat_cache = (sig, layout, flats, ids)
        for n, p in self.model.named_parameters():
            p._data = jnp.asarray(self._start[n])
        return loss


class _HalfBatch(sut.Trainer):
    """Half of the batch left out, the mean taken over the rest."""

    def feed(self, batch):
        return super().feed(np.asarray(batch)[: len(batch) // 2])


@pytest.mark.parametrize("fault", [_StateUnchanged, _HalfBatch],
                         ids=["state_unchanged", "half_batch"])
def test_training_faults_are_not_correct(fault):
    line, _ = _train(trainer=fault)
    assert line["correct"] is False
    over = [n for n, c in line["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over
    if fault is _StateUnchanged:
        # a state left unchanged reads 1 by the measure, whatever the size
        for n in ("grad_worst_leaf", "grad_sketch_gap", "change_worst_leaf"):
            assert line["compared"][n]["value"] == pytest.approx(1, abs=1e-3)


def test_training_control_is_not_correct():
    """The reference in fp8, put in the program's place, fails a limit."""
    readings = control.train_readings(tiny.CFG, tiny.train_mix(), 7,
                                      modes=("fp8", "half_batch"))
    for mode in ("fp8", "half_batch"):
        numbers = {k: v for k, v in readings[mode].items()
                   if not k.endswith("_leaf") or k.endswith("worst_leaf")}
        ok, _ = harness.judge(numbers, LIMITS["train-4k"])
        assert not ok, (mode, numbers)


def test_worst_leaf_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    gap, leaf = harness.worst_leaf({"a": 1.1, "b": 2.0, "c": 2e-6}, ref)
    assert (round(gap, 6), leaf) == (0.1, "a")     # c is held to the median
    gap, leaf = harness.worst_leaf({"a": 1.0, "b": 2.0, "c": 0.5}, ref)
    assert leaf == "c" and gap == pytest.approx(0.5, rel=1e-3)
    assert harness.moving_leaves({"a": 1.0, "b": 2.0, "c": 1e-6}) == ["a", "b"]
    assert harness.judge({"x": float("nan")}, {"x": 1.0})[0] is False
    assert harness.judge({}, {})[0] is False


# ---------------------------------------------------------------- serving --
def _serve(cell, mix, reference_mode="exact", **hooks):
    ctx = tiny.context(cell, mix, reference_mode=reference_mode, hooks=hooks)
    out = harness.run_cell(ctx)
    return tiny.result(cell, out, LIMITS[cell]), out


@pytest.mark.parametrize("cell,mix", [("serve-chat", tiny.chat_mix),
                                      ("serve-flood", tiny.flood_mix)])
def test_sound_serving_run_is_correct(cell, mix):
    line, out = _serve(cell, mix())
    assert line["correct"] is True, (line["compared"], out.notes)
    assert line["attempted"] > 0 and line["failed"] == 0
    expect = {"serve-chat": {"itl_p95_ms", "setup_s"},
              "serve-flood": {"serve_tokens_per_s", "setup_s"}}[cell]
    assert set(line["metrics"]) == expect
    assert all(v["value"] > 0 for v in line["metrics"].values())
    traced = tiny.result(cell, out, LIMITS[cell], traced=True)
    assert traced["correct"] is True
    tag = cell.split("-")[1]
    assert "engine_step_ms." + tag in traced["metrics"]
    # no trace on a CPU: the trace's readers return nothing, never 0
    assert "rpa_roofline." + tag not in traced["metrics"]
    assert "device_idle_pct." + tag not in traced["metrics"]
    if cell == "serve-flood":
        assert traced["metrics"]["cached_prompt_pct"]["value"] > 20


def _altered_tokens(cfg, seed):
    """An engine whose sampler hands back another token every fifth time."""
    engine = sut.build_engine(cfg, seed)
    sample, calls = engine._sample, [0]

    def altered(logits_row, seq):
        calls[0] += 1
        tok = sample(logits_row, seq)
        return (tok + 1) % cfg["vocab_size"] if calls[0] % 5 == 0 else tok
    engine._sample = altered
    return engine


def test_an_altered_token_is_not_correct():
    line, _ = _serve("serve-chat", tiny.chat_mix(), engine=_altered_tokens)
    c = line["compared"]["served_gap_max"]
    assert line["correct"] is False and c["value"] > c["limit"]


#: the tiny model's logits are an eighth of the cell's, so its limit is its
#: own, set by the cell's rule from tiny readings on four seeds: sound runs
#: read at most 0.00085, the int8 control 0.0017 to 0.0057
TINY_SERVE_LIMIT = {"served_gap_max": 0.0015}


def test_serving_control_is_not_correct():
    """The reference in int8 in the program's place: the token it puts
    first lies further below the reference's best than the limit allows,
    at the same prompts and tokens where the program itself passes."""
    mix = tiny.chat_mix()
    mix.update(check_requests=12, check_positions=400,
               answer={"dist": "lognormal", "median": 16, "sigma": 0.5,
                       "min": 4, "max": 32})
    ctx = tiny.context("serve-chat", mix, reference_mode="int8")
    out = harness.run_cell(ctx)
    sound = {"served_gap_max": out.numbers["served_gap_max"]}
    assert harness.judge(sound, TINY_SERVE_LIMIT)[0] is True
    as_program = {"served_gap_max": out.numbers["control_gap_max"]}
    assert harness.judge(as_program, TINY_SERVE_LIMIT)[0] is False


def test_a_compile_inside_the_window_is_not_correct():
    line, out = _serve("serve-chat", tiny.chat_mix())
    out.must_hold = False
    assert tiny.result("serve-chat", out, LIMITS["serve-chat"])["correct"] is False
