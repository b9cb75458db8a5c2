"""Step telemetry — per-step time decomposition, throughput, MFU.

:class:`StepTimer` brackets each training step and decomposes wall time
into data / compute / collective components: data time is supplied by the
caller (the hapi fit loop times its loader fetch), collective time is the
delta of the comm tracer's ``comm_seconds_total`` counter across the step,
and compute is the remainder. From a per-model FLOPs hint
(``flops_per_sample``) and the device's peak it derives an MFU estimate;
``samples/sec`` and (given ``tokens_per_sample``) ``tokens/sec`` come for
free. Everything is recorded into the metrics registry (Prometheus /
JSON exposition) and the step lands in the flight recorder's ring.
"""
from __future__ import annotations

import time
from typing import Optional

from . import fleet, flight_recorder, goodput, memory, trace
from .comm import comm_totals
from .metrics import MetricsRegistry, get_registry

__all__ = ["StepTimer", "peak_flops"]


def peak_flops(device) -> float:
    """bf16 peak FLOP/s per chip by device kind (public TPU specs);
    0 on CPU, where MFU is not meaningful. A TPU whose ``device_kind``
    is not in the table raises: a guessed peak makes every MFU built on
    it wrong without a sign."""
    kind = getattr(device, "device_kind", "").lower()
    table = [
        ("v6e", 918e12), ("trillium", 918e12),
        ("v5p", 459e12), ("v5e", 197e12), ("v5 lite", 197e12),
        ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
    ]
    for key, val in table:
        if key in kind:
            return val
    if getattr(device, "platform", "") == "tpu" or "tpu" in kind:
        raise ValueError(
            f"no bf16 peak for TPU device_kind {device.device_kind!r}: "
            "add it to observability.step_timer.peak_flops with its source")
    return 0.0


def _detect_peak() -> float:
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return 0.0  # jax not imported: no device to ask
    return peak_flops(jax.devices()[0])


class StepTimer:
    """Usage (what the hapi ``StepTelemetry`` callback does)::

        timer = StepTimer(flops_per_sample=6 * n_params)
        for batch in loader:                 # fit times this fetch
            timer.begin_step(data_time=fetch_seconds)
            loss = train_step(batch)
            stats = timer.end_step(samples=batch_size)
        # stats: step_time_s, data_time_s, compute_time_s,
        #        collective_time_s, samples_per_sec, [tokens_per_sec, mfu,
        #        comm_bytes]
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 flops_per_sample: Optional[float] = None,
                 tokens_per_sample: Optional[float] = None,
                 peak: Optional[float] = None):
        self.registry = registry or get_registry()
        self.flops_per_sample = flops_per_sample
        self.tokens_per_sample = tokens_per_sample
        self.peak = _detect_peak() if peak is None else float(peak)
        r = self.registry
        self._h_step = r.histogram("train_step_seconds",
                                   "wall time per training step")
        self._g_sps = r.gauge("train_samples_per_sec",
                              "training throughput, samples")
        self._g_tps = r.gauge("train_tokens_per_sec",
                              "training throughput, tokens")
        self._g_mfu = r.gauge("train_mfu_ratio",
                              "model FLOPs utilization estimate (0..1)")
        self._g_data = r.gauge("train_step_data_seconds",
                               "data-loading share of the last step")
        self._g_compute = r.gauge("train_step_compute_seconds",
                                  "compute share of the last step")
        self._g_coll = r.gauge("train_step_collective_seconds",
                               "collective-comm share of the last step")
        self._g_exposed = r.gauge(
            "train_step_exposed_collective_seconds",
            "non-overlapped (exposed) collective share of the last step")
        self._c_steps = r.counter("train_steps_total", "steps completed")
        self._c_samples = r.counter("train_samples_total",
                                    "samples consumed")
        self._g_gnorm = r.gauge(
            "train_grad_norm",
            "global gradient L2 norm (clip path, per step)")
        self._t0 = None
        self._data_time = 0.0
        self._comm0 = None
        self._step_index = 0
        self.last = None
        # birth the process goodput ledger HERE (top of the fit loop),
        # not lazily at the first end_step — the ledger's wall must
        # already be running when step 1's seconds are classified, or
        # the fraction exceeds 1
        try:
            goodput.get_ledger()
        except Exception:
            pass

    def begin_step(self, data_time: float = 0.0):
        self._data_time = float(data_time)
        # comm counters always live in the DEFAULT registry (collectives
        # cannot know their caller's registry), so diff that one even when
        # this timer records into a custom registry
        self._comm0 = comm_totals()
        self._t0 = time.perf_counter()

    def end_step(self, samples: Optional[int] = None,
                 tokens: Optional[int] = None,
                 grad_norm: Optional[float] = None) -> dict:
        if self._t0 is None:
            return {}
        if grad_norm is not None:
            # the clip path computes this every step and used to throw
            # it away — surfaced per docs/OBSERVABILITY.md#numerics
            self._g_gnorm.set(float(grad_norm))
        t1 = time.perf_counter()
        busy = t1 - self._t0
        comm1 = comm_totals()
        coll = max(comm1["comm_seconds_total"] -
                   self._comm0["comm_seconds_total"], 0.0)
        exposed = max(comm1["comm_exposed_seconds_total"] -
                      self._comm0["comm_exposed_seconds_total"], 0.0)
        comm_bytes = comm1["comm_bytes_total"] - \
            self._comm0["comm_bytes_total"]
        total = busy + self._data_time
        compute = max(busy - coll, 0.0)
        stats = {"step_time_s": total, "data_time_s": self._data_time,
                 "compute_time_s": compute, "collective_time_s": coll,
                 "exposed_collective_time_s": exposed}
        if comm_bytes:
            stats["comm_bytes"] = comm_bytes
        self._h_step.observe(total)
        self._g_data.set(self._data_time)
        self._g_compute.set(compute)
        self._g_coll.set(coll)
        self._g_exposed.set(exposed)
        self._c_steps.inc()
        if samples is not None and total > 0:
            sps = samples / total
            stats["samples_per_sec"] = sps
            self._g_sps.set(sps)
            self._c_samples.inc(samples)
            if tokens is None and self.tokens_per_sample:
                tokens = samples * self.tokens_per_sample
            if self.flops_per_sample and self.peak:
                mfu = samples * self.flops_per_sample / total / self.peak
                stats["mfu"] = mfu
                self._g_mfu.set(mfu)
        if tokens is not None and total > 0:
            tps = tokens / total
            stats["tokens_per_sec"] = tps
            self._g_tps.set(tps)
        # goodput classification: every second of this step lands in a
        # ledger bin; the compile/ckpt shares it discovered ride along in
        # the stats (and the trace step span) so the offline
        # `trace merge --goodput` path replays the exact same split
        try:
            g = goodput.on_step(stats)
            stats["compile_s"] = g["compile_s"]
            stats["ckpt_s"] = g["ckpt_s"]
            stats["goodput_fraction"] = g["goodput_fraction"]
        except Exception:
            pass  # the accountant must never fail a step
        flight_recorder.record(
            flight_recorder.KIND_STEP, "train_step",
            int((t1 - total) * 1e9), int(t1 * 1e9),
            aux=int(samples or 0), args=stats)
        # per-step HBM poll: refresh the memory ledger's hbm_* gauges
        # into THIS timer's registry (owners registered by TrainStep,
        # the engine, the data prefetcher — docs/OBSERVABILITY.md#memory)
        try:
            memory.publish(self.registry)
        except Exception:
            pass  # the memory instrument must never fail a step
        self._step_index += 1
        # fleet bus: stamp liveness and publish this step's heartbeat
        # (both are single-attribute-read no-ops when the bus is off)
        fleet.note_step()
        try:
            fleet.publish_step(self._step_index, stats)
        except Exception:
            pass  # telemetry bus must never fail a step
        # the trace layer's step phases: one "step" span carrying the
        # step id (the merge tool's skew/straggler key) plus child phase
        # spans for the data / compute decomposition
        if trace.active() is not None:
            s_ns, e_ns = int((t1 - total) * 1e9), int(t1 * 1e9)
            targs = {"step": self._step_index, **{
                k: round(v, 6) for k, v in stats.items()
                if isinstance(v, float)}}
            trace.span("step", "train_step", s_ns, e_ns, args=targs)
            d_ns = int(self._data_time * 1e9)
            if d_ns > 0:
                trace.span("phase", "data", s_ns, s_ns + d_ns,
                           args={"step": self._step_index})
            trace.span("phase", "compute", s_ns + d_ns, e_ns,
                       args={"step": self._step_index,
                             "collective_s": round(coll, 6),
                             "exposed_collective_s": round(exposed, 6)})
        self.last = stats
        self._t0 = None
        return stats
