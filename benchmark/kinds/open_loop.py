"""Traffic kind ``open_loop``: sessions arrive on a schedule fixed from the
seed before the window opens, whatever the server does; latency counts from
the time a request was *due*.

A mix is a data file of parameters. A session is a shared prefix (drawn
from a small pool, as system prompts are, or unique to the session, as a
document is) asked ``asks_per_session`` times, each ask with a suffix of
its own and an answer length, the asks ``ask_gap_s`` apart. Chat is one ask
a session over a pooled prefix; document questions are several asks over a
unique one.

Steadiness: every seed offers the same work. Sizes and gaps are the
quantiles of their distributions (not draws), shuffled once by the mix's own
``schedule_seed`` into a cycle of ``cycle_sessions`` sessions; the run's seed
makes the token ids (and the weights) and, where the mix says
``"rotate": true``, picks where in the cycle the window starts. Without it
the window walks the cycle from its start: another order of the same
sessions spreads tails and rates far more than two runs of one seed differ
(PERF.md, PR 23).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


# ------------------------------------------------------------ the schedule --
def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles of ``dist``, clipped to its range."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        v = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        v = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "exponential":
        v = -np.log1p(-q) * dist["mean"]
        v *= dist["mean"] / v.mean()          # the grid's mean is the mean
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if kind != "uniform" and ("min" in dist or "max" in dist):
        v = np.clip(v, dist.get("min"), dist.get("max"))
    return v


@dataclass
class Ask:
    due_s: float
    session: int
    prefix_id: int          # pool index, or -1 - session for a unique prefix
    prefix_len: int
    suffix_len: int
    answer_len: int
    tokens: list = field(default_factory=list)


def cycle(mix: dict):
    """The mix's cycle of sessions: arrays of equal length, the same for
    every seed. Returns ``(gaps_s, sessions)``; a session is a dict of its
    prefix and its asks' offsets, suffixes and answers."""
    n, asks = int(mix["cycle_sessions"]), int(mix["asks_per_session"])
    rng = np.random.default_rng(int(mix["schedule_seed"]))

    def grid(dist, count):
        v = quantile_grid(dist, count)
        rng.shuffle(v)
        return v

    gaps = grid({"dist": "exponential", "mean": 1.0 / mix["sessions_per_s"]}, n)
    pre = mix["prefix"]
    pool = int(pre.get("pool", 0))
    with_prefix = np.zeros(n, bool)
    with_prefix[:int(round(pre.get("share", 1.0) * n))] = True
    rng.shuffle(with_prefix)
    prefix_len = grid(pre["tokens"], n).round().astype(int)
    pool_ids = np.arange(n) % max(pool, 1)
    rng.shuffle(pool_ids)
    total = grid(mix["prompt_total"], n * asks).round().astype(int) \
        if "prompt_total" in mix else None
    suffix = grid(mix["suffix"], n * asks).round().astype(int)
    answer = grid(mix["answer"], n * asks).round().astype(int)
    ask_gap = grid(mix.get("ask_gap_s", {"dist": "fixed", "value": 0.0}),
                   n * asks)
    sessions = []
    for i in range(n):
        plen = int(prefix_len[i]) if with_prefix[i] else 0
        one = {"prefix_len": plen,
               "prefix_id": int(pool_ids[i]) if pool else -1, "asks": []}
        off = 0.0
        for a in range(asks):
            j = i * asks + a
            s = int(suffix[j])
            if total is not None:       # the prompt's length covers the prefix
                s = max(int(mix["suffix"]["min"]), int(total[j]) - plen)
            one["asks"].append({"offset_s": off, "suffix_len": s,
                                "answer_len": int(answer[j])})
            off += float(ask_gap[j])
        sessions.append(one)
    return gaps, sessions


def schedule(mix: dict, seed: int, seconds: float, vocab: int):
    """Every ask due inside ``[0, seconds)``, in due order, with its token
    ids, which the seed draws (and, with ``rotate``, the cycle's start)."""
    gaps, sessions = cycle(mix)
    n = len(sessions)
    rng = np.random.default_rng(int(seed))
    start = int(rng.integers(n)) if mix.get("rotate") else 0
    pre = mix["prefix"]
    pool_len = int(round(quantile_grid(pre["tokens"], n).max()))
    pool = [rng.integers(1, vocab, pool_len).tolist()
            for _ in range(int(pre.get("pool", 0)))]
    asks, t, k = [], 0.0, 0
    while True:
        i = (start + k) % n
        t += float(gaps[i])
        if t >= seconds:
            break
        s = sessions[i]
        if s["prefix_len"] == 0:
            prefix, pid = [], -1 - k
        elif s["prefix_id"] >= 0:
            prefix = pool[s["prefix_id"]][:s["prefix_len"]]
            pid = s["prefix_id"]
        else:
            prefix = rng.integers(1, vocab, s["prefix_len"]).tolist()
            pid = -1 - k
        for a in s["asks"]:
            due = t + a["offset_s"]
            tokens = prefix + rng.integers(1, vocab, a["suffix_len"]).tolist()
            if due < seconds:
                asks.append(Ask(due, k, pid, len(prefix), a["suffix_len"],
                                a["answer_len"], tokens))
        k += 1
    asks.sort(key=lambda a: a.due_s)
    return asks


# ---------------------------------------------------------------- the run --
@dataclass
class Record:
    """One request as the client saw it (times on the window's clock)."""
    ask: Ask
    sent_s: float = math.nan
    token_s: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    queue_wait_s: float = math.nan
    slot_s: float = math.nan          # when the engine gave it a slot
    cached_prompt_tokens: int = 0
    error: str = ""
    handle: object = None

    @property
    def done(self):
        return len(self.tokens) >= self.ask.answer_len


def offer(engine, asks, seconds: float, clock=time.perf_counter,
          sleep=time.sleep):
    """Submit each ask when it falls due; never wait for the server.
    Returns ``(records, t0)`` once the window of ``seconds`` has passed.
    Runs in the caller's thread; tokens arrive on the engine's."""
    records = [Record(a) for a in asks]
    t0 = clock()

    def on_token(rec):
        def cb(req, tok):
            now = clock() - t0
            if not rec.token_s:
                # the engine's own reading of the wait for a slot
                rec.slot_s = req.slot_time - t0
                rec.queue_wait_s = req.slot_time - req.arrival_time
                rec.cached_prompt_tokens = int(req.cached_prompt_tokens)
            rec.token_s.append(now)
            rec.tokens.append(int(tok))
        return cb

    for rec in records:
        wait = rec.ask.due_s - (clock() - t0)
        if wait > 0:
            sleep(wait)
        rec.sent_s = clock() - t0
        try:
            rec.handle = engine.submit(
                rec.ask.tokens, max_new_tokens=rec.ask.answer_len,
                temperature=0.0, on_token=on_token(rec))
        except Exception as e:  # noqa: BLE001 — a refusal is a failed request
            rec.error = repr(e)
    left = seconds - (clock() - t0)
    if left > 0:
        sleep(left)
    return records, t0


def wait_all(records, deadline_s: float, t0: float,
             clock=time.perf_counter):
    """Wait for every accepted request, at most until ``deadline_s`` on the
    window's clock."""
    for rec in records:
        if rec.handle is not None and not rec.error:
            rec.handle.wait(max(0.0, deadline_s - (clock() - t0)))


# ------------------------------------------------------------- arithmetic --
def percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else math.nan


def end_to_end(records, seconds: float, horizon_s: float) -> dict:
    """The end-to-end numbers of a window. A request with no first token by
    ``horizon_s`` (failed, refused, unfinished) waits from its due time to
    the horizon: it misses any limit."""
    ttft = [(r.token_s[0] if r.token_s else horizon_s) - r.ask.due_s
            for r in records]
    gaps = [b - a for r in records for a, b in zip(r.token_s, r.token_s[1:])]
    served = 0
    for r in records:
        if r.token_s and r.token_s[0] <= seconds:
            served += len(r.ask.tokens)
        served += sum(1 for t in r.token_s if t <= seconds)
    return {"ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "itl_p95_ms": 1e3 * percentile(gaps, 95),
            "serve_tokens_per_s": served / seconds,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "n_gaps": len(gaps), "served_tokens": served}


def backlog(records, t: float) -> int:
    """Requests due by ``t`` and not finished by ``t``."""
    return sum(1 for r in records if r.ask.due_s <= t
               and not (r.done and r.token_s[-1] <= t))


def step_rows(records, t_lo: float, t_hi: float, prefill_chunk: int):
    """The rows ``(new, context)`` the engine's steps held inside
    ``[t_lo, t_hi)``, rebuilt from the client's log: a decode row for every
    token after a request's first (exact), and the prefill chunks of its
    uncached prompt spread evenly between its slot time and its first token
    (the engine does not say when each chunk ran)."""
    rows = []
    for r in records:
        if not r.token_s:
            continue
        P = len(r.ask.tokens)
        for j, t in enumerate(r.token_s[1:], start=1):
            if t_lo <= t < t_hi:
                rows.append((1, P + j - 1))
        c0 = min(r.cached_prompt_tokens, P - 1)
        n_chunks = -(-(P - c0) // prefill_chunk)
        for k in range(n_chunks):
            t = r.slot_s + (k + 1) / n_chunks * (r.token_s[0] - r.slot_s)
            if t_lo <= t < t_hi:
                ctx = c0 + k * prefill_chunk
                rows.append((min(prefill_chunk, P - ctx), ctx))
    return rows


# ------------------------------------------------------------ the counters --
def read_counters(engine) -> dict:
    """The engine's own counts (process-wide families and its stats)."""
    from paddle_tpu.serving.engine import serving_metrics
    m = serving_metrics()
    pc = engine.stats().get("prefix_cache") or {}
    return {"steps": m["steps"].value(kind="unified"),
            "prompt_tokens": m["tokens"].value(kind="prompt"),
            "generated_tokens": m["tokens"].value(kind="generated"),
            "prefix_hit_tokens": float(pc.get("hit_tokens", 0)),
            "prefix_hits": float(pc.get("hits", 0)),
            "prefix_lookups": float(pc.get("lookups", 0)),
            "step_compiles": float(engine.stats()["step_compiles"]),
            "preemptions": float(engine.stats()["preemptions"])}


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


# ------------------------------------------------------------- the check --
def check_sample(records, seed: int, count: int):
    """Finished requests for the output check: the longest, and others
    drawn from the seed."""
    done = [r for r in records if r.done and not r.error]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.ask.tokens) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    picks = rng.permutation(len(rest))[:max(count - 1, 0)]
    return [longest] + [rest[i] for i in picks]


def served_gaps(sample, seed, cfg, mix, mode, logits_fn):
    """For each served token of the sample, how far its reference logit
    lies below the reference's best at that position; with a control
    ``mode``, the same for the token the lower precision puts first.
    One reference call of fixed shape: rows padded to ``check_pad_to``
    tokens, ``check_requests`` rows, ``check_positions`` positions."""
    R, L, N = (int(mix["check_requests"]), int(mix["check_pad_to"]),
               int(mix["check_positions"]))
    tokens = np.zeros((R, L), np.int32)
    rows, cols, served = [], [], []
    for i, r in enumerate(sample):
        full = r.ask.tokens + r.tokens[:-1]
        tokens[i, :len(full)] = full
        for j, tok in enumerate(r.tokens):
            rows.append(i)
            cols.append(len(r.ask.tokens) - 1 + j)
            served.append(tok)
    n = len(served)
    if n > N:
        raise ValueError(f"{n} served tokens in the sample, the mix's "
                         f"check_positions is {N}")
    pad = N - n
    rows_p, cols_p = rows + [0] * pad, cols + [0] * pad
    ref = np.asarray(logits_fn(seed, cfg, tokens, rows_p, cols_p,
                               mode="exact"))[:n]
    best = ref.max(-1)
    idx = np.arange(n)
    out = {"served": best - ref[idx, np.asarray(served)], "n": n}
    if mode != "exact":
        low = np.asarray(logits_fn(seed, cfg, tokens, rows_p, cols_p,
                                   mode=mode))[:n]
        out["control"] = best - ref[idx, low.argmax(-1)]
    return out


def run(ctx):
    """Build the engine, warm its one executable with one request through
    the served entry, open the window, offer the schedule, then (window
    closed, peak read, engine gone) run the reference over a sample of what
    was served. Engine, reference and operation counts are those of the
    configuration's model (``harness.model_of``)."""
    from benchmark import harness, sut

    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    model = harness.model_of(cfg)
    vocab = int(cfg["vocab_size"])
    engine = ctx.hooks.get("engine", model.build_engine)(cfg, seed)
    engine.start()
    notes = []
    warm_rng = np.random.default_rng(int(seed) + 2)
    warm = engine.submit(
        warm_rng.integers(1, vocab, int(mix["warmup_prompt"])).tolist(),
        max_new_tokens=int(mix["warmup_answer"]))
    warm.result(timeout=1100)
    asks = schedule(mix, seed, ctx.seconds, vocab)
    before = read_counters(engine)
    setup_s = time.perf_counter() - ctx.t_process_start

    tw = None
    if ctx.traced:
        tw = harness.TraceWindow(ctx.trace_dir, mix["trace_start_s"],
                                 min(mix["trace_seconds"],
                                     ctx.seconds - mix["trace_start_s"]),
                                 snap=lambda: read_counters(engine))
        tw.start()
    records, t0 = offer(engine, asks, ctx.seconds)
    after = read_counters(engine)
    drain = mix["backlog"] == "drain"
    if drain:
        wait_all(records, ctx.seconds + float(mix["drain_s"]), t0)
    horizon_s = time.perf_counter() - t0
    stats = engine.stats()
    left = sum(1 for r in records if not r.done and not r.error)
    engine.shutdown(drain=False, timeout=30)
    trace = tw.reduced() if tw else None
    if tw and tw.error:
        notes.append(f"trace failed: {tw.error}")
    peak = sut.memory_peak_bytes()
    end = read_counters(engine)

    e2e = end_to_end(records, ctx.seconds, horizon_s)
    e2e["setup_s"] = setup_s
    errors = sum(1 for r in records if r.error)
    failed = errors + (left if drain else 0)
    window = delta(before, after)
    notes.append(
        f"window: {len(records)} requests due, {errors} refused or failed, "
        f"{left} unfinished at {'the end of the drain' if drain else 'the cut'}"
        f" ({horizon_s:.1f} s); {e2e['served_tokens']} tokens served, "
        f"{e2e['n_gaps']} gaps; engine steps {window['steps']:.0f}, "
        f"preemptions {window['preemptions']:.0f}")
    notes.append("backlog (due, not finished) at 1/3, 2/3 and the end of "
                 "the window: " + ", ".join(
                     str(backlog(records, f * ctx.seconds))
                     for f in (1 / 3, 2 / 3, 1.0)))
    notes.append("end to end: " + ", ".join(
        f"{k}={v:.4f}" for k, v in e2e.items()))
    notes.append(f"compiles: {before['step_compiles']:.0f} before the window, "
                 f"{end['step_compiles'] - before['step_compiles']:.0f} "
                 f"inside it")

    sample = check_sample(records, seed, int(mix["check_requests"]))
    engine_kw = dict(cfg["engine"])
    del engine, warm
    for r in records:
        r.handle = None
    sut.free_device_memory()

    numbers, must_hold = {}, end["step_compiles"] == before["step_compiles"]
    if ctx.hooks.get("skip_check"):          # a rate sweep reads no output
        sample, numbers = [], {"not_checked": 0.0}
    if sample:
        gaps = served_gaps(
            sample, seed, cfg, mix, ctx.reference_mode,
            ctx.hooks.get("serve_logits", model.serve_logits))
        numbers["served_gap_max"] = float(gaps["served"].max())
        numbers["served_gap_mean"] = float(gaps["served"].mean())
        notes.append(
            f"check: {len(sample)} requests, {gaps['n']} served tokens, "
            f"{int((gaps['served'] > 0).sum())} not the reference's best, "
            f"median gap {float(np.median(gaps['served'])):.5f}")
        if "control" in gaps:
            notes.append(
                f"control {ctx.reference_mode}: gap max "
                f"{float(gaps['control'].max())!r}, "
                f"{int((gaps['control'] > 0).sum())} of {gaps['n']} not the "
                f"reference's best")
            numbers["control_gap_max"] = float(gaps["control"].max())
            numbers["control_gap_mean"] = float(gaps["control"].mean())
    elif not numbers:
        must_hold = False
        notes.append("check: no finished request to compare")

    tr = {}
    if tw and trace is not None:
        span = delta(tw.snap0, tw.snap1)
        rows = step_rows(records, tw.begin_s, tw.end_s,
                         int(engine_kw["prefill_chunk"]))
        tr = {"span_s": tw.end_s - tw.begin_s, "counters": span,
              "rows": rows}
    run_facts = {
        "kind": "open_loop", "cfg": cfg, "mix": mix, "peaks": ctx.peaks,
        "window_s": ctx.seconds, "counters": window, "records": records,
        "e2e": e2e, "trace": trace, "traced": tr, "stats": stats,
        "flops": model,
    }
    return harness.Outcome(
        attempted=len(records), failed=failed, end_to_end=e2e,
        run=run_facts, numbers=numbers, memory_peak_bytes=peak, trace=trace,
        trace_window_s=tr.get("span_s", math.nan), notes=notes,
        must_hold=must_hold)
