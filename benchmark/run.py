#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: loads the cell's files, builds the system under test with
weights made from the seed, warms the cell's own shapes (set-up), measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of stdout. It exits
non-zero, with no result, where JAX finds no TPU it knows or too few chips.
"""
import time
_T0 = time.perf_counter()          # set-up counts from the process's start

import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None, metavar="DIR",
                    help="with --trace 1: keep the .xplane.pb and every "
                         "op's seconds there (for looking at a trace by hand)")
    args = ap.parse_args(argv)

    from benchmark import harness
    manifest = harness.load_manifest()
    wl, cfg, mix, limits = harness.load_cell(manifest, args.workload)
    try:
        device, peaks = harness.require_chip(int(wl["chips"]))
    except harness.NoChip as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 3
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"device_count={device['count']}")
    print(f"compile_cache_dir={harness.place_compile_cache()}")
    trace_dir = os.path.join(harness.ROOT, ".bench_trace", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = harness.Context(
        cell=args.workload, cfg=cfg, mix=mix, seed=args.seed,
        seconds=args.seconds, traced=bool(args.trace), peaks=peaks,
        t_process_start=_T0, trace_dir=trace_dir)
    outcome = harness.run_cell(ctx)
    line = harness.result_line(manifest, args.workload, outcome, device,
                               limits, bool(args.trace))
    if args.dump_trace and outcome.trace is not None:
        harness.dump_trace(outcome.trace, trace_dir, args.dump_trace)
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.emit(line, outcome.notes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
