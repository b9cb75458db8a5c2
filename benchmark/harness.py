"""What every cell shares: the manifest, the chip check, the compile cache,
the traced sub-window, the per-layer readers, the comparison that decides
``correct`` and the result line.

Nothing in here names a cell, a configuration, a model or a metric: those
are files found by the names ``BENCHMARK.json`` gives (``configs/``,
``traffic/``, ``limits/``, ``layer_metrics/``, ``kinds/``) and, for what is
model-shaped, by the name the configuration's file gives (``models/``).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import statistics
import sys
import threading
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no accelerator this benchmark knows, or too few chips."""


# --------------------------------------------------------------- manifest --
def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(root, "BENCHMARK.json")


def load_cell(manifest: dict, name: str, root=ROOT):
    """``(workload entry, configuration, traffic mix, limits)`` of a cell."""
    wl = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                       f"{[w['name'] for w in manifest['workloads']]}")
    conf = next(c for c in manifest["configs"] if c["name"] == wl["config"])
    cfg = load_json(root, conf["file"])
    here = os.path.join(root, os.path.basename(HERE))
    mix = load_json(here, "traffic", wl["traffic"] + ".json")
    lim_path = os.path.join(here, "limits", name + ".json")
    limits = load_json(lim_path) if os.path.exists(lim_path) else {}
    return wl, cfg, mix, limits


def model_names(here=HERE):
    """The model modules there are: ``models/<name>.py``."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(here, "models"))
                  if f.endswith(".py") and not f.startswith("_"))


def model_of(cfg: dict):
    """The module ``models/<cfg["model"]>.py``: the builder of the system
    under test, the plain reference and the operation counts of the model
    the configuration says it is. There is no default: a file that does not
    name its model, or names one no module answers to, is an error."""
    name, there = cfg.get("model"), model_names()
    if name not in there:
        raise LookupError(
            f"the configuration names model {name!r} under \"model\"; "
            f"benchmark/models/ has {there}")
    return importlib.import_module("benchmark.models." + name)


def metrics_of(manifest: dict, section: str, cell: str):
    """The metrics of ``section`` that ``cell`` reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------------- chip --
def require_chip(chips: int) -> dict:
    """The device as JAX reports it and its peaks; raises ``NoChip`` unless
    the backend is a TPU of a kind in ``peaks.json`` with ``chips`` chips."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    table = load_json(HERE, "peaks.json")
    if dev["platform"] != "tpu":
        raise NoChip(f"needs a TPU, jax found {dev}")
    if dev["kind"] not in table["devices"]:
        raise NoChip(f"device kind {dev['kind']!r} is not in peaks.json")
    if dev["count"] < chips:
        raise NoChip(f"cell needs {chips} chips, jax found {dev['count']}")
    return dev, table["devices"][dev["kind"]]


def place_compile_cache() -> str:
    """The persistent compile cache at the program's fixed place
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    keeping every program, however quickly it compiled, so that a second
    run compiles nothing."""
    import jax
    from paddle_tpu.device import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ------------------------------------------------------------------ trace --
class TraceWindow(threading.Thread):
    """Profile ``[start_s, start_s + seconds)`` of a window from a helper
    thread, so that the window's own thread never waits for the profiler.
    ``snap()`` is read at both ends (counters for the traced span)."""

    def __init__(self, out_dir, start_s, seconds, snap=None):
        super().__init__(name="bench-trace", daemon=True)
        self.out_dir, self.start_s, self.seconds = out_dir, start_s, seconds
        self.snap = snap or (lambda: {})
        self.t0 = time.perf_counter()
        self.begin_s = self.end_s = math.nan     # on the window's clock
        self.snap0 = self.snap1 = {}
        self.error = None

    def run(self):
        import jax
        try:
            time.sleep(max(0.0, self.start_s - (time.perf_counter() - self.t0)))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.snap0 = self.snap()
            self.begin_s = time.perf_counter() - self.t0
            time.sleep(self.seconds)
            self.snap1 = self.snap()
            self.end_s = time.perf_counter() - self.t0
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported by the caller
            self.error = repr(e)

    def reduced(self):
        """The reduced trace, or None if nothing was captured."""
        from benchmark import xplane
        self.join()
        if self.error or math.isnan(self.end_s):
            return None
        return xplane.reduce(xplane.find_xplane(self.out_dir))


def traced_window_s(reduced, host_span_s: float) -> float:
    """The traced window: the span the helper thread timed on the host's
    clock, or first op to last op where the trace itself reaches further
    (the profiler stops a little after the host asked it to)."""
    return max(host_span_s, reduced.window_s)


def dump_trace(reduced, trace_dir, out_dir):
    """Keep a trace for reading by hand: the file, and every op and gap."""
    import shutil
    from benchmark import xplane
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(xplane.find_xplane(trace_dir), out_dir)
    with open(os.path.join(out_dir, "reduced.json"), "w") as f:
        json.dump({"window_s": reduced.window_s, "busy_s": reduced.busy_s,
                   "ops": sorted(([n, s, reduced.op_counts.get(n, 0)]
                                  for n, s in reduced.device_ops.items()),
                                 key=lambda r: -r[1]),
                   "gaps": reduced.top_gaps(50)}, f, indent=1)


# ---------------------------------------------------------------- readers --
def read_layer_metric(name: str, run: dict):
    """Call ``layer_metrics/<name>.py``'s ``read(run)``. A reader that finds
    nothing to read returns None and the metric is left out."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    if value is None or (isinstance(value, float) and not math.isfinite(value)):
        return None
    return float(value)


# ---------------------------------------------------------------- correct --
def worst_leaf(program: dict, reference: dict, leaves=None):
    """The gap between the program's norm and the reference's, by the worst
    leaf, each against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns ``(gap, leaf)``."""
    leaves = list(leaves if leaves is not None else reference)
    floor = statistics.median(reference[n] for n in leaves)
    gap, name = 0.0, ""
    for n in leaves:
        g = abs(program[n] - reference[n]) / max(reference[n], floor, 1e-30)
        if not g <= gap:            # also catches a NaN
            gap, name = g, n
    return float(gap), name


def moving_leaves(ref_grad_norms: dict, share=1e-3):
    """Leaves whose reference gradient is not nought to rounding: at least
    ``share`` of the median leaf's. The others move under Adam by round-off
    alone and are left out of the change."""
    floor = share * statistics.median(ref_grad_norms.values())
    return [n for n, v in ref_grad_norms.items() if v >= floor]


def judge(numbers: dict, limits: dict):
    """``(correct, compared)``: each number beside its limit. A number with
    no limit in the cell's file is shown and not compared."""
    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if limit is not None and not (value <= limit):
            ok = False
    return ok and bool(numbers), compared


# ----------------------------------------------------------------- result --
@dataclass
class Outcome:
    """What a kind's ``run`` hands back."""
    attempted: int
    failed: int
    end_to_end: dict                   # name -> value, every one it can give
    run: dict                          # what the per-layer readers read
    numbers: dict                      # what ``correct`` compares
    memory_peak_bytes: int
    trace: object = None               # xplane.Reduced of the traced span
    trace_window_s: float = math.nan
    notes: list = field(default_factory=list)
    must_hold: bool = True             # False: a check outside the numbers failed


def result_line(manifest, cell, outcome: Outcome, device, limits, traced):
    ok, compared = judge(outcome.numbers, limits)
    ok = ok and outcome.must_hold
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    metrics = {}
    if traced:
        for m in metrics_of(manifest, "per_layer", cell):
            v = read_layer_metric(m["name"], outcome.run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    else:
        for m in metrics_of(manifest, "end_to_end", cell):
            metrics[m["name"]] = {"value": outcome.end_to_end[m["name"]],
                                  "unit": units[m["name"]]}
    dev = dict(device, memory_peak_bytes=int(outcome.memory_peak_bytes))
    line = {"correct": bool(ok), "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics, "device": dev}
    if traced and outcome.trace is not None:
        dev["busy_s"] = outcome.trace.busy_s
        dev["window_s"] = traced_window_s(outcome.trace,
                                          outcome.trace_window_s)
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in outcome.trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in outcome.trace.top_gaps(10)]}
    line["compared"] = compared
    return line


def emit(line: dict, notes):
    """Notes first, the result as the last line of stdout, and each number
    compared beside its limit as the last lines of stderr."""
    for n in notes:
        print(n)
    sys.stdout.flush()
    for name, c in line["compared"].items():
        print(f"compared {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(f"correct={line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()


# -------------------------------------------------------------------- run --
@dataclass
class Context:
    """One run of one cell."""
    cell: str
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    traced: bool
    peaks: dict
    t_process_start: float
    trace_dir: str
    #: the reference's linear mode; a control passes "int8" or "fp8"
    reference_mode: str = "exact"
    #: tests plant faults and tiny presets through these, not the command
    hooks: dict = field(default_factory=dict)


def run_cell(ctx: Context) -> Outcome:
    kind = importlib.import_module("benchmark.kinds." + ctx.mix["kind"])
    return kind.run(ctx)
