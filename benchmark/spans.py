#!/usr/bin/env python3
"""The program's own spans in a traced span's ``.xplane.pb``, beside the
device plane: which of the program's phases the host was in while the
device sat idle.

``paddle_tpu.profiler.RecordEvent`` writes every span of the program into
the profiler's trace (the serving step's leaves ``serving.lock`` ..
``serving.gauges``, ``TrainStep``). ``xplane.Reduced`` keeps no host event,
so this module reads the file again, once per process:

- **the clock.** The device plane and the host plane of one file are not on
  one clock to the millisecond. Causality bounds the difference: a program
  starts on the device no earlier than the host began to enqueue it
  (``DoEnqueueProgram`` with the program's ``run_id``), and ends no later
  than the host began the callbacks of its completion (``CompleteCallbacks``
  with that ``run_id``). Over every program of the span that is a bracket
  ``[lo, hi]`` for the shift that puts the device plane on the host's
  clock; the midpoint is applied, and an empty bracket reads as nothing.
- **the split.** Idle intervals are the gaps of the union of device-op
  intervals between the first op and the last, as ``xplane.reduce`` takes
  them, shifted. Each is cut at span boundaries: a span is given the idle
  seconds that fall inside its own intervals, not the whole gap.

    python3 benchmark/spans.py <file.xplane.pb> [name prefix ...]

prints the bracket and, per span name, calls, host seconds and the idle
seconds inside (for a trace kept with ``run.py --dump-trace``; the
prefixes add host events of other names to the table).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import statistics
import sys
from dataclasses import dataclass, field

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import xplane

#: the leaves that tile one engine step, in order (``serving/engine.py``)
STEP_LEAVES = ("serving.lock", "serving.plan", "serving.pack",
               "serving.dispatch", "serving.fetch", "serving.commit",
               "serving.gauges")
#: leaves in which the host waits and does no work of its own: for the
#: device's result, for a request
WAITS = ("serving.fetch", "serving.idle_wait")
SERVING = "serving."
TRAIN_STEP = "TrainStep"
#: the line of a device plane that holds whole programs, with ``run_id``
MODULE_LINE = "XLA Modules"
LAUNCH, DONE = "DoEnqueueProgram", "CompleteCallbacks"


@dataclass
class Events:
    """What this module reads of one file (times in ns, as recorded)."""
    ops: dict = field(default_factory=dict)      # device plane -> [(s, e)]
    modules: list = field(default_factory=list)  # (run_id, s, e), device clock
    host: list = field(default_factory=list)     # (name, s, e, stats)


def read(path: str, also=()) -> Events:
    """The device's ops and programs, and of the host's events the
    program's spans (``serving.*``, ``TrainStep``), the runtime's launch
    and completion events, and names that start with one of ``also``."""
    from jax.profiler import ProfileData
    ev = Events()
    prefixes = (SERVING,) + tuple(also)
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in xplane.OP_LINES:
                    ev.ops.setdefault(plane.name, []).extend(
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                        for e in line.events)
                elif line.name == MODULE_LINE:
                    for e in line.events:
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            s = float(e.start_ns)
                            ev.modules.append(
                                (rid, s, s + float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name in (LAUNCH, DONE, TRAIN_STEP) \
                            or name.startswith(prefixes):
                        s = float(e.start_ns)
                        ev.host.append((name, s, s + float(e.duration_ns),
                                        dict(e.stats)))
    ev.host.sort(key=lambda h: h[1])
    return ev


@functools.lru_cache(maxsize=4)
def load(path: str) -> Events:
    return read(path)


def find_path(run: dict):
    """``run["xplane_path"]`` where a test gives one, else the newest file
    under ``.bench_trace/<cell>/`` (where ``run.py`` has the profiler write
    it; it is there until the result line has been made)."""
    if run.get("xplane_path"):
        return run["xplane_path"]
    from benchmark.harness import ROOT
    files = glob.glob(os.path.join(ROOT, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


# ---------------------------------------------------------------- the clock --
def offset_bracket(modules, host):
    """``(lo, hi)`` in ns: every shift of the device plane that lets each
    program start after its launch began and end before its completion
    was seen. None where no program has both, or no shift does."""
    launch, done = {}, {}
    for name, s, _, stats in host:
        rid = stats.get("run_id")
        if rid is None:
            continue
        if name == LAUNCH:
            launch[rid] = s
        elif name == DONE:
            done[rid] = s
    lo = max((launch[r] - s for r, s, _ in modules if r in launch),
             default=None)
    hi = min((done[r] - e for r, _, e in modules if r in done), default=None)
    if lo is None or hi is None or lo > hi:
        return None
    return lo, hi


def offset_bracket_ns(path: str):
    ev = load(path)
    return offset_bracket(ev.modules, ev.host)


# ---------------------------------------------------------------- the split --
class Idle:
    """The device's idle intervals on the host's clock: per device the gaps
    of the union of its op intervals between its first op and its last,
    shifted by ``shift``; seconds are means over the devices that ran."""

    def __init__(self, ops: dict, shift: float = 0.0):
        self.devices = []               # (gap starts, gap ends, cumulative)
        for intervals in ops.values():
            merged = xplane.union(intervals)
            starts = [e0 + shift for _, e0 in merged[:-1]]
            ends = [s1 + shift for s1, _ in merged[1:]]
            cum = [0.0]
            for a, b in zip(starts, ends):
                cum.append(cum[-1] + (b - a))
            if merged:
                self.devices.append((starts, ends, cum))

    @property
    def total_s(self) -> float:
        return sum(c[-1] for _, _, c in self.devices) \
            / max(len(self.devices), 1) / 1e9

    def inside_s(self, s: float, e: float) -> float:
        """Idle seconds inside ``[s, e]``: a gap is cut at the ends."""
        total = 0.0
        for starts, ends, cum in self.devices:
            i = bisect.bisect_right(ends, s)      # first gap ending after s
            j = bisect.bisect_left(starts, e)     # first gap starting at/after e
            if i >= j:
                continue
            total += cum[j] - cum[i]
            total -= max(0.0, s - starts[i]) + max(0.0, ends[j - 1] - e)
        return total / max(len(self.devices), 1) / 1e9


@dataclass
class Split:
    bracket: tuple                    # (lo, hi) ns
    idle_s: float                     # all idle of the span
    rows: dict                        # name -> [calls, host s, idle s inside]
    steps: dict                       # step -> {leaf: summed seconds}


def split(ev: Events):
    """The per-span table of one file, or None where the clocks cannot be
    aligned (no bracket) or the device ran nothing."""
    bracket = offset_bracket(ev.modules, ev.host)
    if bracket is None or not ev.ops:
        return None
    idle = Idle(ev.ops, (bracket[0] + bracket[1]) / 2.0)
    rows, steps = {}, {}
    for name, s, e, stats in ev.host:
        if name in (LAUNCH, DONE):
            continue
        row = rows.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e9
        row[2] += idle.inside_s(s, e)
        if name in STEP_LEAVES and "step" in stats:
            leaves = steps.setdefault(stats["step"], {})
            leaves[name] = leaves.get(name, 0.0) + (e - s) / 1e9
    return Split(bracket, idle.total_s, rows, steps)


def split_of(run: dict):
    path = find_path(run)
    return split(load(path)) if path else None


def whole_steps(sp: Split) -> list:
    """The leaves of the steps that lie whole inside the span."""
    return [leaves for leaves in sp.steps.values()
            if all(k in leaves for k in STEP_LEAVES)]


# ------------------------------------------------------------ the readers --
def step_host_ms(run):
    """Median over the span's whole steps of the host's own work a step:
    the summed leaves other than the waits."""
    sp = split_of(run)
    if sp is None:
        return None
    work = [1e3 * sum(v for k, v in leaves.items() if k not in WAITS)
            for leaves in whole_steps(sp)]
    return statistics.median(work) if work else None


def idle_named_pct(run):
    """Of the device's idle seconds in the span, the share that falls
    inside any of the program's ``serving.*`` leaves."""
    sp = split_of(run)
    if sp is None or sp.idle_s <= 0:
        return None
    named = [r[2] for n, r in sp.rows.items() if n.startswith(SERVING)]
    return 100.0 * sum(named) / sp.idle_s if named else None


def train_enqueue_ms(run):
    """Median duration of the ``TrainStep`` span: the host's cost to
    prepare and enqueue a step. Needs no clock: one plane."""
    path = find_path(run)
    if not path:
        return None
    durs = [(e - s) / 1e6 for n, s, e, _ in load(path).host
            if n == TRAIN_STEP]
    return statistics.median(durs) if durs else None


def main(argv):
    ev = read(argv[1], also=argv[2:])
    sp = split(ev)
    if sp is None:
        print("no bracket: the device plane cannot be put on the host's "
              f"clock ({len(ev.modules)} programs, {len(ev.host)} host events)")
        return 1
    lo, hi = sp.bracket
    print(f"offset bracket [{lo / 1e3:.1f}, {hi / 1e3:.1f}] us, width "
          f"{(hi - lo) / 1e3:.1f} us; device idle {sp.idle_s:.6f} s")
    print(f"{'span':<24}{'calls':>7}{'host s':>12}{'idle s inside':>15}")
    for name, (calls, host_s, idle_s) in sorted(sp.rows.items()):
        print(f"{name:<24}{calls:>7}{host_s:>12.6f}{idle_s:>15.6f}")
    named = sum(r[2] for r in sp.rows.values())
    print(f"{'(no span)':<24}{'':>7}{'':>12}{sp.idle_s - named:>15.6f}")
    whole = whole_steps(sp)
    if whole:
        print(f"{len(whole)} whole steps; host ms a step, median: " + ", ".join(
            f"{k.split('.')[1]} {1e3 * statistics.median(s[k] for s in whole):.3f}"
            for k in STEP_LEAVES))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
