"""Traffic kind ``train_job``: one compiled train step called in a loop on
batches drawn from the seed, as a trainer's inner loop calls it.

The mix's file gives the batch (rows x tokens), how many distinct batches
the feed cycles through, how many steps may be in flight (the loop waits
for step ``i - in_flight`` before it enqueues step ``i``: some seconds of
steps are queued ahead, so the device stays fed while the host stands
still, and the host never runs minutes ahead of the clock), and how many
first steps the output check follows. Losses are read ``in_flight`` steps
late. When the window's time is up the loop sends nothing more, waits for
all it sent and reads the clock after that wait: all of that work counts,
over all of that time, so the window is longer than asked by what was
queued.
"""
from __future__ import annotations

import json
import math
import time

import numpy as np


def batches(mix: dict, seed: int, vocab: int, count: int) -> list:
    """``count`` batches of token ids ``[rows, seq_len]``; every row of
    every batch differs."""
    rng = np.random.default_rng(int(seed))
    return [rng.integers(0, vocab, (int(mix["batch"]), int(mix["seq_len"])))
            for _ in range(count)]


def loop(step, feeds, seconds: float, in_flight: int,
         clock=time.perf_counter, on_step=None):
    """Call ``step(feed)`` round-robin over ``feeds`` until ``seconds`` have
    passed, then wait for the last step. Returns ``(completion times of
    every step on the window's clock, losses, window seconds)``; a step's
    completion is read when the loop waits on it, ``in_flight`` steps
    later, so only the last few are exact."""
    pending, done_s, losses = [], [], []
    t0 = clock()

    def retire(loss):
        losses.append(float(loss.numpy()))       # blocks until the step ends
        done_s.append(clock() - t0)

    i = 0
    while clock() - t0 < seconds:
        if on_step is not None:
            on_step(i, clock() - t0)
        pending.append(step(feeds[i % len(feeds)]))
        i += 1
        if len(pending) > in_flight:
            retire(pending.pop(0))
    for loss in pending:
        retire(loss)
    return done_s, losses, clock() - t0


def run(ctx):
    """Set-up builds the one compiled step with its state and drives it from
    the seed through its first steps, through the window's own call and
    feed; the window then takes that same object on. The reference follows
    those first steps once the window has closed, the peak has been read
    and the trainer's state is gone. Trainer, reference and operation counts
    are those of the configuration's model (``harness.model_of``)."""
    from benchmark import harness, sut

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    model = harness.model_of(cfg)
    make_trainer = ctx.hooks.get("trainer", getattr(model, "Trainer", None))
    if make_trainer is None:
        raise ValueError(
            f"model {cfg['model']!r} supplies no Trainer (it serves only): "
            f"a train_job cell needs benchmark/models/{cfg['model']}.py to "
            f"bring one")
    n_check = int(mix["check_steps"])
    pool = batches(mix, seed, int(cfg["vocab_size"]),
                   max(int(mix["batch_pool"]), n_check))
    trainer = make_trainer(cfg, mix["optimizer"], seed)
    feeds = [trainer.feed(b) for b in pool]
    notes = []

    first = {"loss": []}
    for i in range(n_check):
        first["loss"].append(float(trainer(feeds[i]).numpy()))
        if i == 0:
            first["grad_norms"] = trainer.first_grad_norms()
            first["grad_sketches"] = trainer.first_grad_sketches(seed)
    first["change_norms"] = trainer.change_norms(seed)
    compiles0 = trainer.compiles()
    setup_s = time.perf_counter() - ctx.t_process_start

    tw = None
    if ctx.traced:
        tw = harness.TraceWindow(ctx.trace_dir, mix["trace_start_s"],
                                 min(mix["trace_seconds"], ctx.seconds))
        tw.start()
    done_s, losses, window_s = loop(trainer, feeds, ctx.seconds,
                                    int(mix["in_flight"]))
    trace = tw.reduced() if tw else None
    if tw and tw.error:
        notes.append(f"trace failed: {tw.error}")

    compiles = trainer.compiles()
    notes.append(f"compiles: {compiles0} before the window, "
                 f"{compiles - compiles0} inside it")
    peak = sut.memory_peak_bytes()
    trainer.release()

    tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])
    steps = len(done_s)
    failed = sum(1 for v in losses if not math.isfinite(v))
    tokens_per_s = steps * tokens_per_step / window_s
    notes.append(f"window: {steps} steps of {tokens_per_step} tokens in "
                 f"{window_s:.3f} s; first losses {first['loss']}")

    ref = ctx.hooks.get("train_reference", model.train_steps)(
        seed, cfg, mix["optimizer"], pool[:n_check], mode=ctx.reference_mode,
        weight_dtype=cfg.get("dtype", "bfloat16"))
    numbers = compare(first, ref)
    notes.append(f"worst sketch leaf: {numbers.pop('grad_sketch_leaf')}")
    for k in ("grad", "change"):
        leaf = numbers.pop(k + "_leaf")
        notes.append(f"worst {k} leaf: {leaf} program "
                     f"{first[k + '_norms'][leaf]!r} reference "
                     f"{ref[k + '_norms'][leaf]!r}")
    notes.append("leaf norms [program grad, reference grad, program change, "
                 "reference change]: " + json.dumps(
                     {n: [first["grad_norms"][n], ref["grad_norms"][n],
                          first["change_norms"][n], ref["change_norms"][n]]
                      for n in ref["grad_norms"]}))

    run_facts = {
        "kind": "train_job", "cfg": cfg, "mix": mix, "peaks": ctx.peaks,
        "window_s": window_s, "steps": steps, "step_done_s": done_s,
        "tokens_per_s": tokens_per_s,
        "flops_per_token": model.train_flops_per_token(
            cfg, int(mix["seq_len"])),
        "trace": trace,
        "trace_span_s": (tw.end_s - tw.begin_s) if tw else math.nan,
        "trace_begin_s": tw.begin_s if tw else math.nan,
        "trace_end_s": tw.end_s if tw else math.nan,
    }
    return harness.Outcome(
        attempted=steps, failed=failed,
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        run=run_facts, numbers=numbers, memory_peak_bytes=peak, trace=trace,
        trace_window_s=run_facts["trace_span_s"], notes=notes,
        must_hold=failed == 0 and compiles == compiles0)


def compare(program: dict, ref: dict) -> dict:
    """The numbers ``correct`` compares for a training cell: each followed
    step's loss (relative gap), the first gradient's and the parameters'
    change's norms by the worst leaf."""
    from benchmark import sketch
    from benchmark.harness import moving_leaves, worst_leaf
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], ref["loss"]), start=1):
        out[f"loss_step{i}"] = abs(a - b) / abs(b) if math.isfinite(a) \
            else math.inf
    out["grad_worst_leaf"], out["grad_leaf"] = worst_leaf(
        program["grad_norms"], ref["grad_norms"])
    out["grad_sketch_gap"], out["grad_sketch_leaf"] = sketch.worst_leaf(
        program["grad_sketches"], ref["grad_sketches"])
    out["change_worst_leaf"], out["change_leaf"] = worst_leaf(
        program["change_norms"], ref["change_norms"],
        moving_leaves(ref["grad_norms"]))
    return out
