#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` (not part of a benchmark
run; see README.md, "How a limit is set"):

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 [--seconds S]
                                 [--program-quantize int8_wo]

Training cell: no window is needed. For each seed the reference follows the
first steps three times: as it is; in the precision below the
configuration's (``fp8``: the control); and with half of the batch left out
(a fault). Each is compared with the first exactly as a run compares the
program with it.

Serving cell: a short window at the cell's own load in one process for all
seeds. Each seed gives the program's own reading (``served_gap_max``: a
lower reading) and the control's (``control_gap_max``: the reference in
int8 put in the program's place, read at the same prompts and tokens). With
``--program-quantize`` the program's own weight-only path is switched on
instead, and its ``served_gap_max`` is a control reading.

With ``--rates`` a serving cell's mix is offered at each rate in turn (one
seed, no output check): the sweep that finds its capacity.

Everything model-shaped (the engine, the trainer's reference, the control
modes) comes from the module the cell's configuration names under
``"model"`` (``harness.model_of``), so the limits and the rates of a later
model's cells are set by these same commands.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def train_readings(cfg, mix, seed, modes=("fp8", "half_batch")):
    from benchmark import harness
    from benchmark.kinds import train_job
    model = harness.model_of(cfg)
    n = int(mix["check_steps"])
    pool = train_job.batches(mix, seed, int(cfg["vocab_size"]), n)
    dtype = cfg.get("dtype", "bfloat16")
    ref = model.train_steps(seed, cfg, mix["optimizer"], pool,
                            weight_dtype=dtype)
    out = {}
    for mode in modes:
        if mode == "half_batch":
            half = [b[: max(1, len(b) // 2)] for b in pool]
            got = model.train_steps(seed, cfg, mix["optimizer"], half,
                                    weight_dtype=dtype)
        else:
            got = model.train_steps(seed, cfg, mix["optimizer"], pool,
                                    mode=mode, weight_dtype=dtype)
        out[mode] = train_job.compare(got, ref)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--program-quantize", default=None)
    ap.add_argument("--rates", default=None,
                    help="serving cell: sweep sessions_per_s over these "
                         "rates (one seed, no output check) and print each "
                         "window's backlog and end-to-end numbers")
    args = ap.parse_args(argv)

    from benchmark import harness
    manifest = harness.load_manifest()
    wl, cfg, mix, _ = harness.load_cell(manifest, args.workload)
    try:
        device, peaks = harness.require_chip(int(wl["chips"]))
    except harness.NoChip as e:
        print(f"benchmark/control.py: {e}", file=sys.stderr)
        return 3
    harness.place_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for rate in (float(r) for r in (args.rates or "").split(",") if r):
        ctx = harness.Context(
            cell=args.workload, cfg=cfg, mix=dict(mix, sessions_per_s=rate),
            seed=seeds[0], seconds=args.seconds, traced=False,
            peaks=peaks, t_process_start=time.perf_counter(), trace_dir="",
            hooks={"skip_check": True})
        out = harness.run_cell(ctx)
        print(json.dumps({"workload": args.workload, "sessions_per_s": rate,
                          "seed": seeds[0], "attempted": out.attempted,
                          "failed": out.failed, "notes": out.notes}))
        sys.stdout.flush()
    if args.rates:
        return 0
    for seed in seeds:
        if mix["kind"] == "train_job":
            row = train_readings(cfg, mix, seed)
        else:
            hooks = {}
            if args.program_quantize:
                build = harness.model_of(cfg).build_engine
                hooks["engine"] = lambda c, s: build(
                    c, s, {"quantize": args.program_quantize})
            ctx = harness.Context(
                cell=args.workload, cfg=cfg, mix=mix, seed=seed, seconds=args.seconds, traced=False, peaks=peaks,
                t_process_start=time.perf_counter(), trace_dir="",
                reference_mode="exact" if args.program_quantize else "int8",
                hooks=hooks)
            out = harness.run_cell(ctx)
            row = dict(out.numbers, notes=out.notes)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program_quantize": args.program_quantize,
                          "device": device, "readings": row}))
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
