"""paddle.profiler parity — host tracer + chrome-trace export.

Reference three-layer design (SURVEY.md §5): RecordEvent instrumentation at
every op (``platform/profiler/event_tracing.h``), tracers collecting into an
event store (``host_tracer.cc``/``cuda_tracer.cc``), chrome-trace/summary
sinks (``chrometracing_logger.cc``, ``profiler_statistic.py``).

TPU mapping: the host side is rebuilt here (op dispatch emits RecordEvents
when a Profiler is active — zero overhead otherwise); the device side
delegates to jax.profiler's XPlane capture (libtpu's tracer — the CUPTI
analog), written next to the host trace for TensorBoard/xprof.

Observability hooks (docs/OBSERVABILITY.md): events carry an optional
``args`` dict and a category — collective-comm spans (cat ``comm``, tagged
with payload bytes + group axes by ``observability.comm``) render as a
dedicated lane plus cumulative-bytes counter events in the chrome export;
every span also feeds the crash flight recorder's ring when that is on,
profiler active or not.

Every ``RecordEvent`` is also a ``jax.profiler.TraceAnnotation``: while a JAX
profiler session runs (``jax.profiler.start_trace``, a ``Profiler`` with a
TPU target, ``POST /debug/profile``) the span lands in the ``.xplane.pb`` on
the profiler's own clock, beside the device plane, its ``args`` as the
event's stats. With no session it is a flag check.

``trace_gc()`` makes each collection of Python's cyclic collector such a
span (``python.gc``) and an observation of the registry's
``python_gc_pause_seconds``: the pauses in which no span of the program
can advance.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import threading
import time
from typing import Callable, List, Optional

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "make_scheduler",
           "export_chrome_tracing", "load_profiler_result", "trace_gc"]

_state = {"active": None}

#: synthetic chrome-trace lane for collective spans (thread_name metadata
#: names it "collectives" in the viewer)
_COMM_TID = 1 << 20


def _flight():
    """The flight-recorder module (lazy: observability imports profiler,
    so this import must not run at module scope)."""
    global _flight_mod
    if _flight_mod is None:
        from paddle_tpu.observability import flight_recorder
        _flight_mod = flight_recorder
    return _flight_mod


_flight_mod = None


class _NativeTracer:
    """ctypes binding to the C++ lock-free event ring
    (``native/host_tracer.cpp`` — the reference HostEventRecorder analog,
    ``platform/profiler/host_event_recorder.h``). Compiled on first use;
    None when the toolchain is unavailable (pure-Python fallback). The same
    library exposes the flight recorder's wrapping seqlock ring (fr_*)."""

    _lib = None
    _failed = False

    @classmethod
    def load(cls):
        if cls._lib is not None or cls._failed:
            return cls._lib
        import ctypes
        import subprocess
        try:
            here = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            src = os.path.join(os.path.dirname(here), "native",
                               "host_tracer.cpp")
            build = os.path.join(os.path.dirname(src), "build")
            os.makedirs(build, exist_ok=True)
            so = os.path.join(build, "libhost_tracer.so")

            def stale():
                return not os.path.exists(so) or \
                    os.path.getmtime(so) < os.path.getmtime(src)

            if stale():
                # serialize the rebuild across processes (parallel pytest):
                # without the lock two workers can both see a stale mtime
                # and race the compile + os.replace; with it, the second
                # re-stats under the lock and finds the fresh .so
                import fcntl
                with open(so + ".lock", "w") as lf:
                    fcntl.flock(lf, fcntl.LOCK_EX)
                    try:
                        if stale():
                            tmp = so + f".tmp{os.getpid()}"
                            subprocess.run(
                                ["g++", "-O2", "-std=c++17", "-shared",
                                 "-fPIC", src, "-o", tmp],
                                check=True, capture_output=True)
                            os.replace(tmp, so)
                    finally:
                        fcntl.flock(lf, fcntl.LOCK_UN)
            lib = ctypes.CDLL(so)
            u64 = ctypes.c_uint64
            u32 = ctypes.c_uint32
            lib.ht_start.argtypes = [u64]
            lib.ht_start.restype = ctypes.c_int
            lib.ht_record.argtypes = [ctypes.c_char_p, u64, u64, u64]
            lib.ht_count.restype = u64
            lib.ht_capacity.restype = u64
            lib.ht_read.argtypes = [u64, ctypes.c_char_p, u64,
                                    ctypes.POINTER(u64), ctypes.POINTER(u64),
                                    ctypes.POINTER(u64)]
            lib.ht_read.restype = ctypes.c_int
            if hasattr(lib, "fr_start"):  # flight-recorder ring (fr_*)
                lib.fr_start.argtypes = [u64]
                lib.fr_start.restype = ctypes.c_int
                lib.fr_record.argtypes = [u32, ctypes.c_char_p, u64, u64,
                                          u64, u64]
                lib.fr_count.restype = u64
                lib.fr_read.argtypes = [u64, ctypes.POINTER(u32),
                                        ctypes.c_char_p, u64,
                                        ctypes.POINTER(u64),
                                        ctypes.POINTER(u64),
                                        ctypes.POINTER(u64),
                                        ctypes.POINTER(u64)]
                lib.fr_read.restype = ctypes.c_int
            cls._lib = lib
        except Exception:
            cls._failed = True
        return cls._lib

    @classmethod
    def drain(cls, into: list):
        """Copy every recorded event out of the ring and free it."""
        import ctypes
        lib = cls._lib
        if lib is None:
            return
        n = min(lib.ht_count(), lib.ht_capacity())
        buf = ctypes.create_string_buffer(64)
        s = ctypes.c_uint64()
        e = ctypes.c_uint64()
        t = ctypes.c_uint64()
        for i in range(n):
            if lib.ht_read(i, buf, 64, ctypes.byref(s), ctypes.byref(e),
                           ctypes.byref(t)) == 0:
                into.append(_Event(buf.value.decode(errors="replace"),
                                   s.value, e.value, t.value))
        lib.ht_stop()


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    CUSTOM_DEVICE = "custom_device"
    TPU = "tpu"


class _Event:
    __slots__ = ("name", "start", "end", "tid", "args", "cat")

    def __init__(self, name, start, end, tid, args=None, cat="op"):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid
        self.args = args
        self.cat = cat


#: a TraceMe's name carries its stats as ``name#k=v,k=v#``: the decoder
#: splits on these three, so a value may not hold them
_TRACEME_UNSAFE = str.maketrans({",": ";", "#": "_", "=": ":"})
_trace_annotation = None


def _trace_args(args) -> dict:
    """``args`` as TraceMe stats: numbers as they are, anything else (the
    ``comm`` spans' axes, a list) through ``str()``, cut to 256 characters,
    the encoding's separators replaced."""
    return {str(k): v if isinstance(v, (int, float))
            else str(v).translate(_TRACEME_UNSAFE)[:256]
            for k, v in args.items()}


def _bind_trace_annotation():
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation


def annotate(name: str, args=None):
    """An entered ``jax.profiler.TraceAnnotation`` for a span that begins
    now, or None when no profiler session runs. The caller leaves it with
    ``__exit__(None, None, None)``."""
    if not (_trace_annotation or _bind_trace_annotation()).is_enabled():
        return None
    ann = _trace_annotation(name, **_trace_args(args)) if args \
        else _trace_annotation(name)
    ann.__enter__()
    return ann


def _emit_event(name, start, end, tid=None, args=None, cat="op"):
    """Append one finished span to the active profiler (used by the comm
    tracer and any instrumentation that already has its timestamps).
    Python path always: events with args/category bypass the native ring
    (it stores only name/start/end/tid)."""
    prof = _state["active"]
    if prof is None:
        return
    prof._events.append(_Event(
        name, start, end, tid if tid is not None else threading.get_ident(),
        args, cat))


class RecordEvent:
    """RAII host span (reference: ``paddle.profiler.RecordEvent``). Usable
    as context manager or begin()/end() pair. Three sinks, each a no-op
    when off: the active ``Profiler``'s store, the flight recorder's ring,
    and the JAX profiler's trace (a ``TraceAnnotation``, so the span shares
    the ``.xplane.pb`` and its clock with the device plane). ``args`` set
    before ``begin()`` become the trace event's stats at once; keys added
    to ``args`` between ``begin()`` and ``end()`` follow as late metadata.
    A TraceMe is written whole by the thread that ends it, so the trace
    files a pair that crosses threads (nothing in the program does) under
    the thread of its ``end()``, with its true times."""

    def __init__(self, name: str, event_type=None, args=None, cat="op"):
        self.name = name
        self.args = args
        self.cat = cat
        self._t0 = None
        self._ann = None

    def begin(self):
        self._ann = annotate(self.name, self.args)
        if self._ann is not None:
            self._ann_sent = len(self.args) if self.args else 0
        fr = _flight_mod or _flight()
        if _state["active"] is not None or fr._active is not None:
            self._t0 = time.perf_counter_ns()

    def end(self):
        ann = self._ann
        if ann is not None:
            self._ann = None
            if self.args and len(self.args) > self._ann_sent:
                late = dict(list(self.args.items())[self._ann_sent:])
                ann.set_metadata(**_trace_args(late))
            ann.__exit__(None, None, None)
        if self._t0 is None:
            return
        t0, self._t0 = self._t0, None
        t1 = time.perf_counter_ns()
        prof = _state["active"]
        if prof is not None:
            if prof._native_lib is not None and self.args is None and \
                    self.cat == "op":
                prof._native_lib.ht_record(
                    self.name.encode(), t0, t1, threading.get_ident())
            else:
                prof._events.append(_Event(
                    self.name, t0, t1, threading.get_ident(), self.args,
                    self.cat))
        fr = _flight_mod._active
        if fr is not None:
            fr.record(_flight_mod.KIND_OP, self.name, t0, t1,
                      tid=threading.get_ident(), args=self.args)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


#: a collection's span (host clock, any thread) and its pause histogram
GC_SPAN = "python.gc"
GC_PAUSE_FAMILY = "python_gc_pause_seconds"
#: 0.1 ms to 2 s
GC_PAUSE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                    0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0)


class _GcSpans:
    """The ``gc.callbacks`` hook: a ``RecordEvent`` (cat ``host``) from a
    collection's ``start`` to its ``stop``, ``generation`` given at once,
    ``collected`` and ``uncollectable`` as late args, and the pause
    observed in the histogram. Collections never overlap (one at a time a
    process, under the interpreter lock): one open span is all the state."""

    def __init__(self, pauses):
        from paddle_tpu.observability.metrics import label_key
        self.pauses = pauses
        self.key = label_key            # bound here, not inside a collection
        self.span = None
        self.t0 = 0
        self.pending = []               # (generation, seconds) not yet filed

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter_ns()
            self.span = RecordEvent(
                GC_SPAN, args={"generation": info["generation"]}, cat="host")
            self.span.begin()
            return
        span, self.span = self.span, None
        if span is None:                # installed while a collection ran
            return
        span.args["collected"] = info["collected"]
        span.args["uncollectable"] = info["uncollectable"]
        span.end()
        self.pending.append(
            (info["generation"], (time.perf_counter_ns() - self.t0) / 1e9))
        self._file()

    def _file(self):
        """Observe the pending pauses. A collection can begin while its own
        thread holds the histogram's lock (a scrape copying it): the lock
        is only tried, and what it cannot file waits for the next
        collection."""
        lock = self.pauses._lock
        if not lock.acquire(blocking=False):
            return
        try:
            for generation, seconds in self.pending:
                self.pauses._observe_locked(self.key(generation=generation),
                                            seconds)
            self.pending.clear()
        finally:
            lock.release()


_gc_spans: Optional[_GcSpans] = None
_gc_install = threading.Lock()


def trace_gc():
    """Hook Python's collector into the program's tracing, once a process
    however often it is called (``ServingEngine.start()`` calls it). Each
    collection, on any thread, is a ``python.gc`` span and an observation of
    ``python_gc_pause_seconds{generation}``; with no profiler session that
    costs the span's flag checks and the observation. Returns the
    histogram."""
    global _gc_spans
    with _gc_install:
        if _gc_spans is None:
            from paddle_tpu.observability.metrics import get_registry
            pauses = get_registry().histogram(
                GC_PAUSE_FAMILY,
                "pauses of Python's cyclic collector by generation, each "
                "also a python.gc span in a profiler session's trace",
                buckets=GC_PAUSE_BUCKETS)
            # what the hook would otherwise import inside a collection
            _bind_trace_annotation()
            _flight()
            _gc_spans = _GcSpans(pauses)
            gc.callbacks.append(_gc_spans)
    return _gc_spans.pauses


def record_op(name: str, inputs=None):
    """Fast-path hook for the op dispatcher: returns a live RecordEvent or
    None when both the profiler and the flight recorder are off.

    ``inputs`` (the op's operand arrays) feeds ``record_shapes``: with an
    active ``Profiler(record_shapes=True)`` the span's ``args`` carries
    each operand's shape."""
    # hot path: two dict/attribute reads when everything is off (the
    # _flight() call only happens once, to bind the module)
    prof = _state["active"]
    fr = _flight_mod or _flight()
    if prof is None and fr._active is None:
        return None
    args = None
    if prof is not None and prof._record_shapes and inputs is not None:
        args = {"input_shapes": [list(getattr(a, "shape", ()))
                                 for a in inputs]}
    ev = RecordEvent(name, args=args)
    ev.begin()
    return ev


def make_scheduler(closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0) -> Callable[[int],
                                                                     str]:
    """Reference: profiler.py:117 make_scheduler state machine
    (CLOSED/READY/RECORD cycling)."""
    if record < 1:
        raise ValueError("record period must be >= 1")
    if min(closed, ready, repeat, skip_first) < 0:
        raise ValueError("scheduler periods must be non-negative")
    period = closed + ready + record

    def schedule(step: int) -> str:
        if step < skip_first:
            return "closed"
        s = step - skip_first
        if repeat and s >= repeat * period:
            return "closed"
        pos = s % period
        if pos < closed:
            return "closed"
        if pos < closed + ready:
            return "ready"
        return "record"
    return schedule


class Profiler:
    """Reference: ``python/paddle/profiler/profiler.py:344``.

    ``record_shapes`` attaches operand shapes to op spans (forces the
    Python event path — the native ring stores no args). ``timer_only``
    collects no events at all (no native ring, no op instrumentation) and
    keeps only the per-step wall clock exposed by :meth:`step_info`."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, emit_nvtx=False):
        self._targets = targets or [ProfilerTarget.CPU]
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._record_shapes = record_shapes
        self._events: List[_Event] = []
        self._step = 0
        self._recording = False
        self._device_trace_dir: Optional[str] = None
        self._native_lib = None
        self._step_marks: List[int] = []

    # -- lifecycle ------------------------------------------------------------
    def start(self):
        self._step = 0
        self._step_marks = [time.perf_counter_ns()]
        self._apply_state()
        return self

    def stop(self):
        self._step_marks.append(time.perf_counter_ns())
        self._stop_recording()
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        return self

    def step(self, num_samples=None):
        self._step += 1
        self._step_marks.append(time.perf_counter_ns())
        self._apply_state()

    def step_info(self, unit: str = "ms") -> dict:
        """Per-step wall-clock stats from the step() marks — the whole
        output when ``timer_only`` is set."""
        scale = {"ms": 1e6, "us": 1e3, "s": 1e9}[unit]
        durs = [(b - a) / scale for a, b in
                zip(self._step_marks, self._step_marks[1:])]
        if not durs:
            return {"steps": 0}
        return {"steps": len(durs),
                f"avg_{unit}": sum(durs) / len(durs),
                f"min_{unit}": min(durs), f"max_{unit}": max(durs)}

    def _apply_state(self):
        state = "record" if self._scheduler is None \
            else self._scheduler(self._step)
        if state == "record" and not self._recording:
            self._start_recording()
        elif state != "record" and self._recording:
            self._stop_recording()

    def _start_recording(self):
        self._recording = True
        if self._timer_only:
            return  # step timing only: no event capture, no native ring
        lib = _NativeTracer.load()
        if lib is not None and lib.ht_start(1 << 20) == 0:
            self._native_lib = lib
        _state["active"] = self
        if ProfilerTarget.TPU in self._targets or \
                ProfilerTarget.GPU in self._targets:
            try:
                import jax
                self._device_trace_dir = os.environ.get(
                    "PADDLE_TPU_TRACE_DIR", "/tmp/paddle_tpu_trace")
                jax.profiler.start_trace(self._device_trace_dir)
            except Exception:
                self._device_trace_dir = None

    def _stop_recording(self):
        if not self._recording:
            return
        self._recording = False
        if _state["active"] is self:
            _state["active"] = None
        if self._native_lib is not None:
            _NativeTracer.drain(self._events)
            self._native_lib = None
        if self._device_trace_dir is not None:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_trace_dir = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- sinks ----------------------------------------------------------------
    def export_chrome_tracing(self, dir_name: str,
                              worker_name: Optional[str] = None) -> str:
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(
            dir_name, f"{worker_name or 'host'}.pb.trace.json")
        evs = sorted(self._events, key=lambda e: e.start)
        events = []
        if any(e.cat == "comm" for e in evs):
            # name the dedicated collective lane in the viewer
            events.append({"ph": "M", "name": "thread_name", "pid": 0,
                           "tid": _COMM_TID,
                           "args": {"name": "collectives"}})
        comm_cum = 0
        for e in evs:
            d = {
                "name": e.name, "ph": "X", "cat": e.cat or "op",
                "ts": e.start / 1000.0,  # chrome wants microseconds
                "dur": (e.end - e.start) / 1000.0,
                "pid": 0,
                "tid": _COMM_TID if e.cat == "comm" else e.tid,
            }
            if e.args:
                d["args"] = dict(e.args)
            events.append(d)
            if e.cat == "comm":
                # cumulative comm-volume counter track next to the lane
                comm_cum += int((e.args or {}).get("bytes", 0))
                events.append({"name": "comm_bytes", "ph": "C", "pid": 0,
                               "ts": e.start / 1000.0,
                               "args": {"bytes": comm_cum}})
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated per-op table (reference: profiler_statistic.py)."""
        agg = {}
        for e in self._events:
            tot, cnt, mx = agg.get(e.name, (0, 0, 0))
            dur = e.end - e.start
            agg[e.name] = (tot + dur, cnt + 1, max(mx, dur))
        rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
        unit = {"ms": 1e6, "us": 1e3, "s": 1e9}[time_unit]
        lines = [f"{'name':<40}{'calls':>8}{'total':>12}{'max':>12}"
                 f"{'avg':>12}  ({time_unit})"]
        for name, (tot, cnt, mx) in rows:
            lines.append(f"{name[:39]:<40}{cnt:>8}{tot / unit:>12.3f}"
                         f"{mx / unit:>12.3f}{tot / cnt / unit:>12.3f}")
        table = "\n".join(lines)
        print(table)
        return rows

    @property
    def events(self):
        return list(self._events)


def export_chrome_tracing(dir_name: str, worker_name=None):
    """Reference: profiler.py:215 — returns an on_trace_ready callback."""
    def handler(prof: Profiler):
        prof.export_chrome_tracing(dir_name, worker_name)
    return handler


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)
