"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A device
plane (``/device:TPU:n``) carries a line of XLA ops; host planes carry the
threads' TraceMe events (JAX's own and the benchmark's
``TraceAnnotation``s). From those:

- ``busy_s``: the union of the intervals in which an op ran on a device,
  averaged over the devices that ran any;
- ``device_ops``: per op name, the *self* time: an op's duration less what
  its nested ops (the body of a ``while``, a fusion's parts) cover, so that
  the sums add up to the busy time and nothing counts twice;
- ``idle_gaps``: each gap between busy intervals, named by what the host
  was doing in it, summed by name.

Nothing here knows a model or a kernel: kernels find their events through
``op_seconds(pattern)``.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
#: lines of a device plane that hold ops (others hold steps, modules,
#: framework scopes: spans that cover the ops and would double the time)
OP_LINES = ("XLA Ops",)
#: host events that span whole windows and so explain nothing
_WRAPPERS = ("bench_window", "bench_trace")
NO_HOST = "no_host_event"


@dataclass
class Reduced:
    window_s: float                  # first op start .. last op end
    busy_s: float                    # union of op intervals, mean over devices
    n_devices: int
    device_ops: dict = field(default_factory=dict)   # name -> self seconds
    op_counts: dict = field(default_factory=dict)    # name -> events
    idle_gaps: dict = field(default_factory=dict)    # host activity -> seconds
    gap_count: int = 0

    def op_seconds(self, pattern: str) -> float:
        """Self seconds of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.device_ops.items() if rx.search(n))

    def top_ops(self, n=10):
        return sorted(self.device_ops.items(), key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n=10):
        return sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def short_name(text: str, width: int = 96) -> str:
    """A device op's event name is its whole HLO instruction. Keep the
    instruction's name, its opcode and the start of its result's shape:
    ``fusion.283 fusion (bf16[704663552]{0:T(1024)...``. Operands go: they
    name other instructions, and a pattern must not find a kernel there."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:width]
    depth, cut = 0, len(rest)
    for i, ch in enumerate(rest):              # the end of the result shape
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            cut = i
            break
    shape, tail = rest[:cut], rest[cut + 1:]
    opcode = tail.split("(", 1)[0].strip()
    return f"{head.lstrip('%')} {opcode} {shape}"[:width]


def read_events(path: str):
    """``(device, host)``: per device plane a list of ``(name, start_ns,
    end_ns)`` of its ops, and one list of the host threads' events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [(short_name(e.name), float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns))
                            for e in line.events]
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, float(e.start_ns),
                          float(e.start_ns) + float(e.duration_ns))
                         for e in line.events if e.duration_ns > 0]
    return device, host


def union(intervals):
    """Merged ``[start, end]`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops):
    """Per name, duration less the time covered by nested ops. ``ops``:
    ``(name, start, end)``; nesting is by containment on one timeline."""
    total, count, stack = {}, {}, []     # stack: [name, end, self]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
        count[name] = count.get(name, 0) + 1
    close(float("inf"))
    return total, count


def name_gap(start, end, host):
    """What the host was doing in ``[start, end]``: among host events that
    cover at least half the gap the shortest (the most specific), else the
    one that overlaps it most, else ``no_host_event``."""
    best_cover, best_overlap = None, None
    for name, s, e in host:
        if any(name.startswith(w) for w in _WRAPPERS):
            continue
        ov = min(e, end) - max(s, start)
        if ov <= 0:
            continue
        if ov >= 0.5 * (end - start):
            if best_cover is None or e - s < best_cover[1]:
                best_cover = (name, e - s)
        if best_overlap is None or ov > best_overlap[1]:
            best_overlap = (name, ov)
    if best_cover:
        return best_cover[0]
    return best_overlap[0] if best_overlap else NO_HOST


def reduce(path: str, min_gap_ns: float = 20_000.0) -> Reduced:
    """Reduce one trace file. Gaps shorter than ``min_gap_ns`` (launch
    latency between back-to-back ops) are summed under ``short_gaps``."""
    device, host = read_events(path)
    device = {k: v for k, v in device.items() if v}
    if not device:
        return Reduced(0.0, 0.0, 0)
    busy, ops_total, ops_count, gaps, n_gaps = [], {}, {}, {}, 0
    lo = min(s for ops in device.values() for _, s, _ in ops)
    hi = max(e for ops in device.values() for _, _, e in ops)
    host = [h for h in host if h[2] > lo and h[1] < hi]
    for ops in device.values():
        merged = union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        t, c = self_times(ops)
        for n, v in t.items():
            ops_total[n] = ops_total.get(n, 0.0) + v
            ops_count[n] = ops_count.get(n, 0) + c[n]
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if s1 - e0 < min_gap_ns:
                name = "short_gaps"
            else:
                name = name_gap(e0, s1, host)
                n_gaps += 1
            gaps[name] = gaps.get(name, 0.0) + (s1 - e0)
    nd = len(device)
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / nd / 1e9, n_devices=nd,
        device_ops={n: v / nd / 1e9 for n, v in ops_total.items()},
        op_counts=ops_count,
        idle_gaps={n: v / nd / 1e9 for n, v in gaps.items()},
        gap_count=n_gaps)
