"""Benchmark: full-model Llama causal-LM pretraining step, bf16, one chip.

Headline metric (the BASELINE.md north star, measured end to end): one
complete compiled ``jit.TrainStep`` — token embedding, L transformer blocks
with Pallas flash attention (causal, GQA, no materialized mask), RMSNorm,
SwiGLU, tied vocab projection (the 128K-vocab matmul), cross-entropy loss,
gradient clip, and AdamW (multi-precision: f32 master weights + moments) —
on a Llama-3-recipe-shaped model sized to a single chip (~0.7B params,
d=2048, 16 heads / 4 KV heads, ffn=7168, vocab=128256, seq 2048).

The bench ASSERTS the Pallas flash kernel is on the hot path by counting
the Mosaic custom calls in the step's compiled HLO (forward and two
backward kernels per layer). A single-block bench (the round-2 metric)
runs alongside as the layer-vs-model breakdown.

The default command measures the chip and refuses to run without one
(non-zero exit naming the platform it found); ``BENCH_FORCE_CPU=1`` runs the
smoke-size configuration on the CPU, whose numbers are not device metrics.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}; extra
detail goes to stderr. FLOP accounting is analytic (2 flops/MAC, causal
attention at half, backward = 2x forward, optimizer not counted).
"""
import gc
import json
import os
import sys
import time

if os.environ.get("BENCH_FORCE_CPU"):
    # before jax is imported (same as tests/conftest.py)
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def peak_flops(device) -> float:
    """bf16 peak per chip (shared with the telemetry layer's MFU gauge)."""
    from paddle_tpu.observability.step_timer import peak_flops as pf
    return pf(device)


def emit_metrics(payload: dict, path: str):
    """Write ``payload``'s numeric leaves through the observability
    metrics registry as labeled ``bench_result`` gauges and dump the
    registry's JSON exposition to ``path`` — so BENCH_*.json rounds,
    ad-hoc runs, and live training scrapes all share one schema. The
    DEFAULT registry's families ride along too (comm_* incl. the
    exposure counters, serving_*, ckpt_* — whatever the benched code
    recorded), so one file holds both the headline numbers and the
    telemetry behind them."""
    from paddle_tpu.observability.metrics import (MetricsRegistry,
                                                  get_registry)

    reg = MetricsRegistry()
    g = reg.gauge("bench_result", "benchmark scalar results by key path")

    def walk(prefix, obj):
        if isinstance(obj, dict):
            for k, v in obj.items():
                walk(f"{prefix}.{k}" if prefix else str(k), v)
        elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
            g.set(float(obj), key=prefix)

    walk("", payload)
    doc = get_registry().to_json()
    doc.update(reg.to_json())  # bench_result wins on (impossible) clash
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"metrics written to {path}", file=sys.stderr)


def _metrics_out_path():
    """--emit-metrics PATH (or BENCH_EMIT_METRICS env)."""
    if "--emit-metrics" in sys.argv:
        i = sys.argv.index("--emit-metrics")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--emit-metrics requires an output path")
        return sys.argv[i + 1]
    return os.environ.get("BENCH_EMIT_METRICS")


def _time_steps(fn, steps, warmup, ready, reps=3):
    """Per-step seconds by SLOPE: time a short and a long dispatch window
    and divide the difference by the extra steps. A plain total/steps
    folds one constant host<->device round-trip into the window,
    inflating short steps by RTT/steps. The slope
    cancels every per-window constant; per-CALL dispatch overhead stays
    in, as it should (a real training loop pays it too). Returns the
    minimum of ``reps`` slopes (least-interference estimate).
    """
    mean, _ = _time_steps_stats(fn, steps, warmup, ready, reps=reps,
                                reduce="min")
    return mean


def _time_steps_stats(fn, steps, warmup, ready, reps=3, reduce="min"):
    """(per_step_seconds, spread_seconds) over ``reps`` slope measurements
    (spread = max-min). ``reduce``: "min" (noise floor) or "mean"."""
    for _ in range(warmup):
        out = fn()
    ready(out)

    def window(n):
        t0 = time.perf_counter()
        o = None
        for _ in range(n):
            o = fn()
        ready(o)
        return time.perf_counter() - t0

    n1, n2 = steps, 3 * steps
    vals = []
    for _ in range(reps):
        t1 = window(n1)
        t2 = window(n2)
        vals.append((t2 - t1) / (n2 - n1))
    agg = min(vals) if reduce == "min" else sum(vals) / len(vals)
    return agg, (max(vals) - min(vals))


def bench_full_model(on_tpu):
    """Complete TrainStep on a Llama-recipe model; returns
    (flops_per_sec, extras)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            tie_word_embeddings=True)
        # B=4 fits (and beats B=2 by ~6 MFU points) since the fused
        # chunked CE removed the [T, V] logits from HBM; B=8 measured
        # slightly worse (59.8%)
        B, S = 4, 2048
        steps, warmup = 10, 2
    else:  # smoke config so the bench is runnable anywhere
        cfg = LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=448,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=512,
            tie_word_embeddings=True)
        B, S = 2, 256
        steps, warmup = 3, 1

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))

    def loss_fn(m, x):
        return m(x, labels=x)[1]

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    x = pt.to_tensor(rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int64))

    first_loss = float(step(x).numpy())
    # read the COMPILED program, not a call counter: a kernel call that
    # raised (or was never lowered) leaves no Mosaic custom call behind,
    # so the "72% MFU but naive attention" failure mode of round 2 cannot
    # recur. Per layer: flash forward, dq, dk/dv.
    n_flash = step.compiled_hlo(x).count(
        'custom_call_target="tpu_custom_call"')
    if on_tpu and n_flash != 3 * cfg.num_hidden_layers:
        raise RuntimeError(
            f"compiled step holds {n_flash} Mosaic custom calls, expected "
            f"{3 * cfg.num_hidden_layers} (flash forward + two backward "
            "kernels per layer) — the bench must exercise the Pallas hot "
            "path")

    # 5 independent slope measurements: mean is the headline, spread is
    # published beside it (one canonical number +- variance)
    dt, dt_spread = _time_steps_stats(lambda: step(x), steps, warmup,
                                      lambda loss: loss.numpy(), reps=5,
                                      reduce="mean")

    d, ffn, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                    cfg.num_hidden_layers)
    d_kv = cfg.num_key_value_heads * (d // cfg.num_attention_heads)
    T = B * S
    per_tok = L * (4 * d * d + 4 * d * d_kv + 6 * d * ffn) + 2 * d * V
    attn = L * 2 * B * S * S * d  # QK^T + AV at causal half
    fwd = T * per_tok + attn
    train_flops = 3 * fwd
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    extras = {
        "loss_first_step": round(first_loss, 3),
        "flash_mosaic_calls": n_flash,
        "params_millions": round(n_params / 1e6, 1),
        "tokens_per_sec": round(T / dt, 1),
        "step_ms": round(dt * 1e3, 2),
        "step_ms_spread": round(dt_spread * 1e3, 2),
        "spread_pct_of_mean": round(dt_spread / dt * 100, 2),
        "achieved_tflops": round(train_flops / dt / 1e12, 2),
        "config": {"d": d, "ffn": ffn, "vocab": V, "layers": L,
                   "heads": cfg.num_attention_heads,
                   "kv_heads": cfg.num_key_value_heads, "batch": B,
                   "seq": S},
    }
    return train_flops / dt, extras


def bench_layer(on_tpu):
    """Single Llama block fwd+bwd (the round-2 metric, kept as the
    layer-vs-model breakdown) — now routed through the flash kernel via the
    tagged causal mask."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.functional import functional_state, swap_state

    if on_tpu:
        D, H, DFF, S, B = 4096, 32, 14336, 2048, 8
        steps, warmup = 20, 3
    else:
        D, H, DFF, S, B = 256, 4, 896, 256, 4
        steps, warmup = 5, 2

    pt.seed(0)

    class Block(nn.Layer):
        """One pre-norm Llama block: RMSNorm -> attn -> RMSNorm -> SwiGLU."""

        def __init__(self):
            super().__init__()
            self.norm1 = nn.RMSNorm(D)
            self.attn = nn.MultiHeadAttention(D, H)
            self.norm2 = nn.RMSNorm(D)
            self.gate = nn.Linear(D, DFF, bias_attr=False)
            self.up = nn.Linear(D, DFF, bias_attr=False)
            self.down = nn.Linear(DFF, D, bias_attr=False)

        def forward(self, x, mask):
            h = x + self.attn(self.norm1(x), attn_mask=mask)
            z = self.norm2(h)
            return h + self.down(
                nn.functional.silu(self.gate(z)) * self.up(z))

    model = Block()
    model.eval()
    model.bfloat16()

    train, frozen, buffers = functional_state(model)
    state = {**train, **frozen, **buffers}
    # the tagged causal mask routes MultiHeadAttention onto the flash
    # kernel's block-skip path (round 2 fed a raw additive mask here and
    # silently benched naive attention)
    mask = nn.Transformer.generate_square_subsequent_mask(S)

    def fwd(params, x):
        # no_grad: jax owns the differentiation here, as in TrainStep. With
        # the framework's tape on, every op runs its own jax.vjp inside
        # jax.value_and_grad, which then has to differentiate the flash
        # kernel's forward rule — a Pallas JVP jax cannot take. (Until
        # PR 21 that error was swallowed and this bench timed the S×S
        # composite.)
        with no_grad(), swap_state(model, params, collect_buffers=False):
            out = model(pt.Tensor(x), mask)
        return jnp.sum(out.data.astype(jnp.float32))

    grad_fn = jax.jit(jax.value_and_grad(fwd))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, D), dtype=jnp.bfloat16)
    n_flash = grad_fn.lower(state, x).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')
    if on_tpu and n_flash != 3:
        raise RuntimeError(
            f"compiled layer holds {n_flash} Mosaic custom calls, expected "
            "3 (flash forward + two backward kernels)")

    # sync by transferring the scalar loss, a value that depends on the
    # whole step
    dt = _time_steps(lambda: grad_fn(state, x), steps, warmup,
                     lambda out: np.asarray(out[0]))

    tokens = B * S
    # projections 8*D^2/token (QKVO) + SwiGLU 6*D*DFF/token + causal
    # attention 2*S*D/token (QK^T + AV at half)
    fwd_flops = tokens * (8 * D * D + 6 * D * DFF) + 2 * B * S * S * D
    train_flops = 3 * fwd_flops
    return train_flops / dt, {"layer_step_ms": round(dt * 1e3, 2),
                              "layer_tokens_per_sec": round(tokens / dt, 1)}


def bench_decode():
    """Serving numbers for the zoo Llama (headline 0.7B config, bf16):
    prefill tokens/sec and decode tokens/sec at B=1 and B=8, via the
    whole-loop compiled generator. Separation by budget slope: one full
    generate call costs prefill + mnt * per_token (+ window RTT, cancelled
    by the call-count slope inside _time_steps); timing two budgets
    isolates the decode slope, and the intercept is the prefill."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=7168,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=4, max_position_embeddings=4096,
        tie_word_embeddings=True)
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    model.bfloat16()
    S1, S2 = 512, 1024
    m1, m2 = 8, 72
    rng = np.random.RandomState(0)
    out = {}
    for B in (1, 8):
        def t_of(S, mnt):
            ids = pt.to_tensor(rng.randint(0, cfg.vocab_size,
                                           (B, S)).astype(np.int32))
            call = lambda: model.generate_compiled(  # noqa: E731
                ids, max_new_tokens=mnt, temperature=0.0)
            return _time_steps(call, 2, 1, lambda r: r.numpy())

        # decode rate: budget slope at fixed prompt; prefill rate: prompt
        # slope at the MINIMUM budget (mnt=1) so the longer prompt's extra
        # decode-attention cost contaminates the slope by at most one step
        # (an intercept estimate drowns in call noise at B=1 where the
        # whole prefill is a few ms)
        t1, t2 = t_of(S1, m1), t_of(S1, m2)
        per_tok = (t2 - t1) / (m2 - m1)
        prefill_per_tok = max(
            (t_of(S2, 1) - t_of(S1, 1)) / (S2 - S1), 1e-9)
        out[f"B{B}"] = {
            "prefill_tok_per_s": round(B / prefill_per_tok, 1),
            "prefill_ms_at_512": round(prefill_per_tok * S1 * 1e3, 2),
            "decode_ms_per_tok": round(per_tok * 1e3, 3),
            "decode_tok_per_s": round(B / per_tok, 1),
        }
        print(json.dumps({f"B{B}": out[f"B{B}"]}), file=sys.stderr,
              flush=True)
        gc.collect()
    # ragged batch: 8 unequal prompts (256..512) LEFT-padded to 512 —
    # the standard serving shape, one compiled program, mask as input
    B = 8
    lens = np.linspace(256, S1, B).astype(int)
    ids = np.zeros((B, S1), np.int32)
    mask = np.zeros((B, S1), np.int32)
    for b, n in enumerate(lens):
        ids[b, S1 - n:] = rng.randint(0, cfg.vocab_size, n)
        mask[b, S1 - n:] = 1
    ids_t, mask_t = pt.to_tensor(ids), pt.to_tensor(mask)

    def t_ragged(mnt):
        call = lambda: model.generate_compiled(  # noqa: E731
            ids_t, max_new_tokens=mnt, temperature=0.0,
            attention_mask=mask_t)
        return _time_steps(call, 2, 1, lambda r: r.numpy())

    t1, t2 = t_ragged(m1), t_ragged(m2)
    per_tok = (t2 - t1) / (m2 - m1)
    out["B8_ragged"] = {
        "prompt_lens": f"{lens[0]}..{lens[-1]}",
        "decode_ms_per_tok": round(per_tok * 1e3, 3),
        "decode_tok_per_s": round(B / per_tok, 1),
    }
    print(json.dumps({"B8_ragged": out["B8_ragged"]}), file=sys.stderr,
          flush=True)
    out["config"] = {"prompt": S1, "d": cfg.hidden_size,
                     "layers": cfg.num_hidden_layers,
                     "vocab": cfg.vocab_size, "dtype": "bf16"}
    return out


def bench_serve():
    """Continuous-batching serving bench (--serve): drive the
    ``serving.ServingEngine`` with a synthetic Poisson arrival trace and
    report p50/p99 TTFT and aggregate generated tokens/sec. Runs the
    trace under BOTH paged-attention read paths on TPU — ``rpa`` (the
    Ragged-Paged-Attention Pallas kernel, the engine's TPU default) and
    ``gather`` (the XLA fallback it replaced) — so the kernel's win is
    measured in-tree; off-TPU only the gather path runs (interpret-mode
    kernels don't produce meaningful timings). The primary impl's p99
    TTFT and decode tokens/sec are emitted as report-gate headlines
    (``serving_p99_ttft_seconds`` LOWER_BETTER /
    ``serving_decode_tokens_per_sec`` HIGHER_BETTER, ``_cpu_smoke``
    suffix off-TPU), so ``--report`` holds the RPA win against
    regression. A second, shared-prefix Poisson trace (every request =
    one long common prefix + a short unique tail) runs cache-off then
    cache-on and emits the prefix-cache headlines
    (``serving_prefix_cache_hit_rate`` / ``serving_shared_prefix_speedup``
    HIGHER_BETTER, ``serving_cached_p99_ttft_seconds`` /
    ``serving_cold_p99_ttft_seconds`` LOWER_BETTER), gating the 2x
    effective-throughput claim. On TPU the model is the headline 0.7B
    bf16 Llama config;
    elsewhere a smoke config keeps the bench runnable anywhere. Results
    ride the ``--emit-metrics`` JSON schema.
    """
    import time as _time

    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            tie_word_embeddings=True)
        n_req, mean_gap = 32, 0.05
        p_lo, p_hi, g_lo, g_hi = 64, 512, 16, 96
        eng_kw = dict(max_batch=8, max_blocks=512, block_size=16,
                      prefill_chunk=128)
        impls = ("rpa", "gather")
    else:
        cfg = LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=256,
            tie_word_embeddings=True)
        n_req, mean_gap = 12, 0.02
        p_lo, p_hi, g_lo, g_hi = 8, 32, 8, 24
        eng_kw = dict(max_batch=4, max_blocks=64, block_size=8,
                      prefill_chunk=16)
        impls = ("gather",)

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if on_tpu:
        model.bfloat16()

    def run_trace(impl, ledger=True, **extra_kw):
        # ledger=False builds a disarmed engine (the hot path pays only
        # attribute reads on None) — the pair prices the request ledger
        # for the serving_request_ledger_overhead_frac headline;
        # extra_kw rides through to the engine (quantize=, kv_dtype=)
        env_prev = os.environ.get("PADDLE_TPU_REQUEST_LEDGER")
        if not ledger:
            os.environ["PADDLE_TPU_REQUEST_LEDGER"] = "0"
        try:
            engine = ServingEngine(model, attn_impl=impl, **eng_kw,
                                   **extra_kw)
        finally:
            if not ledger:
                if env_prev is None:
                    os.environ.pop("PADDLE_TPU_REQUEST_LEDGER", None)
                else:
                    os.environ["PADDLE_TPU_REQUEST_LEDGER"] = env_prev
        engine.start()
        rng = np.random.RandomState(0)
        # warmup request compiles the unified step outside the timed
        # trace (and proves chunked prefill re-uses it: step_compiles
        # stays 1 through the whole trace)
        engine.submit(rng.randint(1, cfg.vocab_size, 8),
                      max_new_tokens=4).result(timeout=600)

        gaps = rng.exponential(mean_gap, n_req)  # Poisson arrivals
        plens = rng.randint(p_lo, p_hi + 1, n_req)
        gens = rng.randint(g_lo, g_hi + 1, n_req)
        handles = []
        t0 = _time.perf_counter()
        for gap, pl, gn in zip(gaps, plens, gens):
            _time.sleep(gap)
            handles.append(engine.submit(
                rng.randint(1, cfg.vocab_size, pl),
                max_new_tokens=int(gn)))
        engine.drain(timeout=600)
        elapsed = _time.perf_counter() - t0
        engine.shutdown()

        results = [h.result(timeout=1) for h in handles]
        ttfts = np.array([r["ttft_s"] for r in results])
        lats = np.array([r["latency_s"] for r in results])
        gen_tokens = int(sum(r["num_generated"] for r in results))
        stats = engine.stats()
        return {
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
            "ttft_p99_ms": round(float(np.percentile(ttfts, 99)) * 1e3, 2),
            "latency_p50_ms": round(
                float(np.percentile(lats, 50)) * 1e3, 2),
            "latency_p99_ms": round(
                float(np.percentile(lats, 99)) * 1e3, 2),
            "generated_tokens": gen_tokens,
            "tokens_per_sec": round(gen_tokens / elapsed, 1),
            "elapsed_s": round(elapsed, 2),
            "preemptions": stats["preemptions"],
            "step_compiles": stats["step_compiles"],
        }

    def run_shared_prefix(prefix_cache):
        """Shared-prefix Poisson trace (ISSUE 15): every request opens
        with the same long system prefix and diverges in a short unique
        tail — the traffic shape the block-granular prefix cache exists
        for. Same workload cache-on vs cache-off, so the effective-
        throughput ratio (generated tokens over wall-clock INCLUDING
        queue/prefill time) is the cache's end-to-end win."""
        if on_tpu:
            pfx_len, tail_lo, tail_hi, gen_n, n, gap = 256, 8, 24, 24, 24, 0.02
        else:
            pfx_len, tail_lo, tail_hi, gen_n, n, gap = 96, 2, 6, 2, 10, 0.002
        engine = ServingEngine(model, attn_impl=impls[0],
                               prefix_cache=prefix_cache, **eng_kw)
        engine.start()
        rng = np.random.RandomState(1)
        prefix = list(rng.randint(1, cfg.vocab_size, pfx_len))
        # warmup: compiles the step and (cache-on) registers the prefix
        engine.submit(prefix, max_new_tokens=2).result(timeout=600)
        gaps = rng.exponential(gap, n)
        tails = [list(rng.randint(1, cfg.vocab_size,
                                  rng.randint(tail_lo, tail_hi + 1)))
                 for _ in range(n)]
        handles = []
        t0 = _time.perf_counter()
        for g, tail in zip(gaps, tails):
            _time.sleep(g)
            handles.append(engine.submit(prefix + tail,
                                         max_new_tokens=gen_n))
        engine.drain(timeout=600)
        elapsed = _time.perf_counter() - t0
        results = [h.result(timeout=1) for h in handles]
        stats = engine.stats()
        engine.shutdown()
        ttfts = np.array([r["ttft_s"] for r in results])
        gen_tokens = int(sum(r["num_generated"] for r in results))
        pc = stats.get("prefix_cache") or {}
        return {
            "prefix_cache": bool(prefix_cache),
            "prefix_len": pfx_len,
            "requests": n,
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
            "ttft_p99_ms": round(float(np.percentile(ttfts, 99)) * 1e3, 2),
            "effective_tokens_per_sec": round(gen_tokens / elapsed, 1),
            "elapsed_s": round(elapsed, 3),
            "hit_rate": pc.get("hit_rate", 0.0),
            "hit_tokens": pc.get("hit_tokens", 0),
            "evictions": pc.get("evictions", 0),
        }

    out = {}
    for impl in impls:
        out[impl] = run_trace(impl)
        print(json.dumps({impl: out[impl]}), file=sys.stderr, flush=True)
        gc.collect()
    # per-request cost summary (ISSUE 16): the exemplar ring after the
    # primary trace — errors/preempted/slow-tail always kept, the rest
    # sampled (PADDLE_TPU_REQUEST_LOG_SAMPLE)
    from paddle_tpu.observability import requests as obs_requests
    led = obs_requests.active()
    if led is not None:
        ex = led.exemplars()
        if ex:
            cols = ("req_id", "kept", "queue_wait_s", "ttft_s",
                    "latency_s", "itl_p99_s", "prefilled_tokens",
                    "cached_tokens", "decode_tokens", "preemptions",
                    "kv_block_seconds")
            print("request cost exemplars (kept=%d of %d completed):"
                  % (len(ex), led.completed_total), file=sys.stderr)
            print(" | ".join(cols), file=sys.stderr)
            for r in ex:
                print(" | ".join(str(r.get(c)) for c in cols),
                      file=sys.stderr)
            sys.stderr.flush()
    # disarmed twin of the primary trace prices the ledger: the headline
    # is the throughput it costs (≤1% gate — LOWER_BETTER in --report)
    ledger_off = run_trace(impls[0], ledger=False)
    out["ledger_off"] = ledger_off
    tps_on = out[impls[0]]["tokens_per_sec"]
    tps_off = ledger_off["tokens_per_sec"]
    ledger_overhead = round(1.0 - tps_on / max(tps_off, 1e-9), 4)
    out["ledger_overhead_frac"] = ledger_overhead
    print(json.dumps({"ledger_off": ledger_off,
                      "ledger_overhead_frac": ledger_overhead}),
          file=sys.stderr, flush=True)
    gc.collect()
    shared = {"cold": run_shared_prefix(False),
              "cached": run_shared_prefix(True)}
    shared["speedup"] = round(
        shared["cached"]["effective_tokens_per_sec"]
        / max(shared["cold"]["effective_tokens_per_sec"], 1e-9), 2)
    out["shared_prefix"] = shared
    print(json.dumps({"shared_prefix": shared}), file=sys.stderr,
          flush=True)
    gc.collect()

    # quantized + multi-tenant serving (ISSUE 20): the int8 weight-only
    # twin of the primary trace prices quantization in tokens/sec, a
    # greedy-parity probe prices it in quality, the doubled-batch int8
    # KV engine must fit the full-precision engine's pool bytes, and an
    # 8-slot LoRA engine serves one request per tenant from ONE
    # compiled step.
    int8_trace = run_trace(impls[0], quantize="int8_wo")
    out["int8_wo"] = int8_trace
    gc.collect()

    def greedy_probe(**kw):
        engine = ServingEngine(model, attn_impl=impls[0], **eng_kw, **kw)
        engine.start()
        prng = np.random.RandomState(3)
        prompts = [list(prng.randint(1, cfg.vocab_size, 12))
                   for _ in range(4)]
        hs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        engine.drain(timeout=600)
        outs = [tuple(h.result(timeout=5)["token_ids"]) for h in hs]
        engine.shutdown()
        return outs

    base_greedy = greedy_probe()
    int8_greedy = greedy_probe(quantize="int8_wo")
    int8_match = float(np.mean([a == b for a, b
                                in zip(base_greedy, int8_greedy)]))
    out["int8_wo"]["greedy_match_frac"] = int8_match
    gc.collect()

    def pool_bytes(engine):
        c = engine.cache
        leaves = (list(c.k_pools) + list(c.v_pools)
                  + list(c.k_scales) + list(c.v_scales))
        return int(sum(x.nbytes for x in leaves))

    ref_engine = ServingEngine(model, attn_impl=impls[0], **eng_kw)
    ref_bytes = pool_bytes(ref_engine)
    del ref_engine
    kv_kw = dict(eng_kw)
    kv_kw["max_batch"] = eng_kw["max_batch"] * 2
    kv_kw["max_blocks"] = eng_kw["max_blocks"] * 2
    kv_engine = ServingEngine(model, attn_impl=impls[0],
                              kv_dtype="int8", **kv_kw)
    kv_bytes = pool_bytes(kv_engine)
    kv_engine.start()
    prng = np.random.RandomState(4)
    hs = [kv_engine.submit(list(prng.randint(1, cfg.vocab_size, 8)),
                           max_new_tokens=4)
          for _ in range(kv_kw["max_batch"])]
    kv_engine.drain(timeout=600)
    kv_served = int(sum(h.result(timeout=5)["num_generated"] > 0
                        for h in hs))
    kv_engine.shutdown()
    kv_quant_max_batch = kv_kw["max_batch"] if kv_bytes <= ref_bytes \
        else eng_kw["max_batch"]
    out["kv_int8"] = {
        "max_batch": kv_quant_max_batch, "served": kv_served,
        "pool_bytes": kv_bytes, "full_precision_pool_bytes": ref_bytes}
    print(json.dumps({"kv_int8": out["kv_int8"]}), file=sys.stderr,
          flush=True)
    gc.collect()

    from paddle_tpu import tuning
    lora_model = LlamaForCausalLM(cfg)
    lora_model.eval()
    if on_tpu:
        lora_model.bfloat16()
    tuning.apply_lora(lora_model, tuning.LoRAConfig(rank=4), n_slots=8)
    lora_engine = ServingEngine(lora_model, attn_impl=impls[0],
                                quantize="int8_wo", **eng_kw)
    prng = np.random.RandomState(5)
    for s in range(1, 9):
        state = {k: (prng.randn(*v.shape[1:]) * 0.01).astype(np.float32)
                 for k, v in lora_engine._st.items()
                 if k.rsplit(".", 1)[-1].startswith("lora_")}
        lora_engine.load_adapter(s, state, name=f"tenant-{s}")
    lora_engine.start()
    hs = [lora_engine.submit(list(prng.randint(1, cfg.vocab_size, 8)),
                             max_new_tokens=4, adapter_id=s)
          for s in range(1, 9)]
    lora_engine.drain(timeout=600)
    adapters_served = int(sum(h.result(timeout=5)["num_generated"] > 0
                              for h in hs))
    lora_stats = lora_engine.stats()
    lora_engine.shutdown()
    out["lora"] = {"slots": lora_stats["adapters"]["slots"],
                   "loaded": lora_stats["adapters"]["loaded"],
                   "served": adapters_served,
                   "step_compiles": lora_stats["step_compiles"]}
    print(json.dumps({"int8_wo": out["int8_wo"], "lora": out["lora"]}),
          file=sys.stderr, flush=True)
    gc.collect()

    primary = out[impls[0]]
    # flatten the primary impl's numbers at the top level (the committed
    # BENCH_r0*.json "parsed" shape earlier rounds gated on)
    out.update(primary)
    out["impl"] = impls[0]
    out["requests"] = n_req
    out["mean_arrival_gap_s"] = mean_gap
    out["config"] = {"d": cfg.hidden_size, "layers": cfg.num_hidden_layers,
                     "vocab": cfg.vocab_size, **eng_kw}
    # report-gate headlines (stdout JSON lines — the round's tail parser
    # picks {"metric", "value"} up; see _report_metrics_of)
    sfx = "" if on_tpu else "_cpu_smoke"
    print(json.dumps({"metric": f"serving_p99_ttft_seconds{sfx}",
                      "value": round(primary["ttft_p99_ms"] / 1e3, 4),
                      "unit": "seconds"}))
    print(json.dumps({"metric": f"serving_decode_tokens_per_sec{sfx}",
                      "value": primary["tokens_per_sec"],
                      "unit": "tokens/sec"}))
    print(json.dumps({"metric": f"serving_prefix_cache_hit_rate{sfx}",
                      "value": shared["cached"]["hit_rate"],
                      "unit": "fraction"}))
    print(json.dumps({"metric": f"serving_cached_p99_ttft_seconds{sfx}",
                      "value": round(shared["cached"]["ttft_p99_ms"] / 1e3,
                                     4),
                      "unit": "seconds"}))
    print(json.dumps({"metric": f"serving_cold_p99_ttft_seconds{sfx}",
                      "value": round(shared["cold"]["ttft_p99_ms"] / 1e3, 4),
                      "unit": "seconds"}))
    print(json.dumps({"metric": f"serving_shared_prefix_speedup{sfx}",
                      "value": shared["speedup"],
                      "unit": "x"}))
    print(json.dumps({"metric":
                      f"serving_request_ledger_overhead_frac{sfx}",
                      "value": out["ledger_overhead_frac"],
                      "unit": "fraction"}))
    print(json.dumps({"metric": f"serving_int8_tokens_per_sec{sfx}",
                      "value": int8_trace["tokens_per_sec"],
                      "unit": "tokens/sec"}))
    print(json.dumps({"metric": f"serving_kv_quant_max_batch{sfx}",
                      "value": kv_quant_max_batch,
                      "unit": "sequences"}))
    print(json.dumps({"metric": f"serving_adapters_served{sfx}",
                      "value": adapters_served,
                      "unit": "adapters"}))
    return out


def bench_fleet(n_replicas=None):
    """Multi-replica fleet bench (--serve --replicas N): drive N engine
    replicas behind the cache-aware :class:`FleetRouter` with an
    open-loop Poisson trace of shared-prefix request groups and compare
    against a single replica under the SAME per-replica offered load —
    the throughput ratio over N single-replica throughputs is the
    fleet's scaling efficiency. A second, mixed long-prompt/chat trace
    runs disaggregation ON (prefill/decode-tagged replicas, long
    prompts prefilled off the decode path) vs OFF (all mixed) and
    reports the chat traffic's p99 inter-token latency both ways — the
    long-prompt-isolation number. Headlines:
    ``serving_fleet_tokens_per_sec`` / ``serving_fleet_scaling_efficiency``
    / ``serving_router_affinity_hit_rate`` (all HIGHER_BETTER,
    ``_cpu_smoke`` suffix off-TPU)."""
    import time as _time

    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import FleetRouter, Replica, ServingEngine

    if n_replicas is None:
        n_replicas = int(os.environ.get("PADDLE_TPU_FLEET_REPLICAS", "4"))
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            tie_word_embeddings=True)
        eng_kw = dict(max_batch=8, max_blocks=512, block_size=16,
                      prefill_chunk=128)
        n_base, mean_gap, pfx_len, tail_lo, tail_hi, gen_n = \
            16, 0.05, 64, 8, 24, 32
        long_lo, long_hi, chat_gen, long_gen, disagg_thresh = \
            512, 1024, 32, 8, 256
    else:
        cfg = LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=256,
            tie_word_embeddings=True)
        eng_kw = dict(max_batch=4, max_blocks=64, block_size=8,
                      prefill_chunk=16)
        # per-replica offered load sized well under one replica's
        # capacity: the efficiency headline isolates router/contention
        # overhead, not CPU-smoke GIL saturation
        n_base, mean_gap, pfx_len, tail_lo, tail_hi, gen_n = \
            8, 0.1, 16, 4, 8, 8
        long_lo, long_hi, chat_gen, long_gen, disagg_thresh = \
            64, 96, 12, 4, 48

    def model_fn():
        pt.seed(0)
        m = LlamaForCausalLM(cfg)
        m.eval()
        if on_tpu:
            m.bfloat16()
        return m

    def spin_up(n, roles=None, **router_kw):
        roles = list(roles or [])
        roles += ["mixed"] * (n - len(roles))
        reps = [Replica(ServingEngine(model_fn(), **eng_kw), f"r{i}",
                        role=roles[i]) for i in range(n)]
        router = FleetRouter(reps, **router_kw)
        router.start()
        # warmup: compile each replica's unified step outside the
        # timed window (prefill-role replicas too — the disagg path
        # runs through them)
        rng = np.random.RandomState(99)
        for rep in reps:
            rep.engine.submit(rng.randint(1, cfg.vocab_size, 8),
                              max_new_tokens=2).result(timeout=600)
        return router, reps

    def run_trace(router, reqs, itl_sink=None):
        """Open-loop Poisson drive: (gap, prompt, gen, tag) tuples.
        ``itl_sink[tag]`` collects client-observed inter-token gaps."""
        handles = []
        t0 = _time.perf_counter()
        for gap, prompt, gen, tag in reqs:
            _time.sleep(gap)
            on_token = None
            if itl_sink is not None:
                stamps = itl_sink.setdefault(tag, [])
                marker = []

                def on_token(h, tok, _s=stamps, _m=marker):
                    now = _time.perf_counter()
                    if _m:
                        _s.append(now - _m[0])
                    _m[:] = [now]
            handles.append(router.submit(prompt, max_new_tokens=gen,
                                         on_token=on_token))
        results = [h.result(timeout=600) for h in handles]
        elapsed = _time.perf_counter() - t0
        tokens = sum(r["num_generated"] for r in results)
        return tokens / elapsed, elapsed, results

    def shared_prefix_trace(rng, n_req, gap_mean):
        """Shared-prefix request groups (4 system prompts): the traffic
        shape cache-aware placement exists for — after each group's
        first request registers its blocks somewhere, affinity should
        pin the rest of the group to that replica."""
        prefixes = [list(rng.randint(1, cfg.vocab_size, pfx_len))
                    for _ in range(4)]
        gaps = rng.exponential(gap_mean, n_req)
        out = []
        for i in range(n_req):
            p = prefixes[rng.randint(len(prefixes))]
            tail = list(rng.randint(1, cfg.vocab_size,
                                    rng.randint(tail_lo, tail_hi + 1)))
            out.append((gaps[i], p + tail, gen_n, "chat"))
        return out

    out = {"replicas": n_replicas}

    # -- scaling: same per-replica offered load, 1 vs N replicas -----------
    router1, _ = spin_up(1)
    tps1, el1, _ = run_trace(router1,
                             shared_prefix_trace(np.random.RandomState(2),
                                                 n_base, mean_gap))
    router1.shutdown(drain=True)
    gc.collect()

    routerN, _ = spin_up(n_replicas)
    tpsN, elN, _ = run_trace(
        routerN, shared_prefix_trace(np.random.RandomState(2),
                                     n_base * n_replicas,
                                     mean_gap / n_replicas))
    statsN = routerN.stats()
    routerN.shutdown(drain=True)
    gc.collect()

    efficiency = round(tpsN / max(n_replicas * tps1, 1e-9), 4)
    out["single_replica_tokens_per_sec"] = round(tps1, 1)
    out["fleet_tokens_per_sec"] = round(tpsN, 1)
    out["scaling_efficiency"] = efficiency
    out["affinity_hit_rate"] = statsN.get("affinity_hit_rate") or 0.0
    out["routing"] = statsN.get("routing")
    print(json.dumps({"fleet_scaling": {
        "tps_1": out["single_replica_tokens_per_sec"],
        "tps_n": out["fleet_tokens_per_sec"],
        "efficiency": efficiency, "routing": out["routing"]}}),
        file=sys.stderr, flush=True)

    # -- disaggregation: long-prompt/chat mix, disagg on vs off ------------
    def mixed_trace(rng):
        gaps = rng.exponential(mean_gap, n_base * 2)
        reqs = []
        for i in range(n_base * 2):
            if i % 4 == 0:  # every 4th request drags a long prompt in
                plen = rng.randint(long_lo, long_hi + 1)
                reqs.append((gaps[i],
                             list(rng.randint(1, cfg.vocab_size, plen)),
                             long_gen, "long"))
            else:
                plen = rng.randint(tail_lo + 4, tail_lo + 12)
                reqs.append((gaps[i],
                             list(rng.randint(1, cfg.vocab_size, plen)),
                             chat_gen, "chat"))
        return reqs

    def chat_p99_itl(disagg):
        roles = (["prefill"] + ["decode"] * (n_replicas - 1)) if disagg \
            else None
        router, _ = spin_up(max(n_replicas, 2), roles=roles,
                            disagg=disagg,
                            prefill_threshold=disagg_thresh)
        sink = {}
        _, _, _ = run_trace(router, mixed_trace(np.random.RandomState(5)),
                            itl_sink=sink)
        stats = router.stats()
        router.shutdown(drain=True)
        gc.collect()
        itls = sink.get("chat") or [0.0]
        return (round(float(np.percentile(itls, 99)) * 1e3, 3),
                stats.get("routing"))

    disagg_itl, disagg_routing = chat_p99_itl(True)
    mixed_itl, _ = chat_p99_itl(False)
    out["disagg"] = {
        "chat_p99_itl_ms_disagg_on": disagg_itl,
        "chat_p99_itl_ms_disagg_off": mixed_itl,
        "isolation_ratio": round(mixed_itl / max(disagg_itl, 1e-9), 3),
        "routing": disagg_routing,
    }
    print(json.dumps({"fleet_disagg": out["disagg"]}), file=sys.stderr,
          flush=True)

    # report-gate headlines ({"metric","value"} stdout JSON lines)
    sfx = "" if on_tpu else "_cpu_smoke"
    print(json.dumps({"metric": f"serving_fleet_tokens_per_sec{sfx}",
                      "value": out["fleet_tokens_per_sec"],
                      "unit": "tokens/sec"}))
    print(json.dumps({"metric": f"serving_fleet_scaling_efficiency{sfx}",
                      "value": efficiency, "unit": "fraction"}))
    print(json.dumps({"metric": f"serving_router_affinity_hit_rate{sfx}",
                      "value": out["affinity_hit_rate"],
                      "unit": "fraction"}))
    return out


def bench_ckpt():
    """Checkpoint subsystem bench (--ckpt): save/restore GB/s through the
    ``CheckpointManager`` and the step-loop STALL each save mode injects
    (sync = snapshot + shard write + fsync + commit on the caller;
    async = snapshot only, writing overlaps the next steps) — the number
    the async writer exists to shrink. A fake train loop of fixed-work
    steps measures the stall end to end; ``ckpt_blocking_seconds``
    reports the same quantity from the metrics side. Results ride the
    ``--emit-metrics`` JSON schema."""
    import shutil
    import tempfile
    import time as _time

    import paddle_tpu as pt
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.checkpoint.writer import ckpt_metrics

    mb = float(os.environ.get("BENCH_CKPT_MB", "256"))
    n_tensors = 16
    per = max(int(mb * 1e6 / 4 / n_tensors), 1)
    rng = np.random.RandomState(0)
    state = {f"layers.{i}.weight":
             pt.to_tensor(rng.randn(per // 256 + 1, 256).astype(np.float32))
             for i in range(n_tensors)}
    nbytes = sum(int(np.prod(t.shape)) * 4 for t in state.values())

    root = tempfile.mkdtemp(prefix="pt_ckpt_bench_")
    out = {"state_mb": round(nbytes / 1e6, 1)}
    try:
        mgr = CheckpointManager(root, keep_last_k=2)

        # -- raw save / restore bandwidth (sync, timed to commit) ---------
        t0 = _time.perf_counter()
        mgr.save(0, state, async_=False)
        save_s = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        mgr.restore(0)
        restore_s = _time.perf_counter() - t0
        out["save_gbps"] = round(nbytes / save_s / 1e9, 3)
        out["restore_gbps"] = round(nbytes / restore_s / 1e9, 3)

        # -- step-loop stall: fixed-work steps, one save injected ---------
        step_work_s = 0.01

        def loop(step_offset, async_):
            times = []
            for i in range(8):
                t0 = _time.perf_counter()
                _time.sleep(step_work_s)  # the "train step"
                if i == 2:
                    fut = mgr.save(step_offset, state, async_=async_)
                times.append(_time.perf_counter() - t0)
            fut.wait(600)
            return max(times) - step_work_s

        sync_stall = loop(1, async_=False)
        async_stall = loop(2, async_=True)
        out["sync_stall_ms"] = round(sync_stall * 1e3, 2)
        out["async_stall_ms"] = round(async_stall * 1e3, 2)
        out["stall_ratio"] = round(sync_stall / max(async_stall, 1e-9), 1)
        blocked = ckpt_metrics()["blocking_seconds"]
        out["blocking_ms_sync_mean"] = round(
            blocked.stats(mode="sync")["mean"] * 1e3, 2)
        out["blocking_ms_async_mean"] = round(
            blocked.stats(mode="async")["mean"] * 1e3, 2)
        mgr.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_data():
    """Input-pipeline bench (--data): the two numbers the
    ``paddle_tpu.data`` subsystem exists to move (docs/DATA.md).

    1. **packed vs padded tokens/sec** — same variable-length corpus,
       same model, same compiled TrainStep geometry: the padded loader
       places one document per row (padding the tail, the classic
       fine-tuning shape); the packed pipeline first-fit-packs documents
       into the same [B, seq] with segment-id masking. Throughput is
       counted in REAL (non-pad) tokens — the tokens that actually
       train — so the ratio is the utilization the packer recovers.
       Packing efficiency (real-token fraction per batch) is reported
       from the ``data_packing_efficiency`` histogram.
    2. **prefetch on/off step-time delta** — a deliberately slow
       (IO-bound, GIL-releasing) dataset feeds the same fit-shaped loop
       with and without the async device prefetcher; the delta is the
       per-step data wait the prefetcher hides (the
       ``train_step_data_seconds`` component StepTelemetry reports).

    Results ride the ``--emit-metrics`` JSON schema."""
    import time as _time

    import jax
    import paddle_tpu as pt
    from paddle_tpu.data import DataPipeline
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            tie_word_embeddings=True)
        B, S, n_docs, steps = 4, 2048, 512, 8
        d_lo, d_hi = 128, 1024
    else:
        cfg = LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=448,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=512,
            tie_word_embeddings=True)
        B, S, n_docs, steps = 2, 256, 256, 6
        d_lo, d_hi = 24, 128

    class Corpus:
        """Deterministic variable-length documents."""

        def __getitem__(self, i):
            rng = np.random.RandomState(7000 + i)
            return rng.randint(1, cfg.vocab_size,
                               rng.randint(d_lo, d_hi + 1)).astype(np.int32)

        def __len__(self):
            return n_docs

    def build_step():
        pt.seed(0)
        model = LlamaForCausalLM(cfg)
        if on_tpu:
            model.bfloat16()
        opt = pt.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)

        def loss_fn(m, **batch):
            out = m(**batch)
            return out[1] if isinstance(out, tuple) else out
        return TrainStep(model, loss_fn, opt)

    def run(batches, step):
        """(elapsed_s, real_tokens) over pre-built batches (data cost
        excluded — this measures the step-time value of density)."""
        real = 0
        loss = None
        for b in batches:  # warmup/compile on the first call
            loss = step(**{k: pt.to_tensor(v) for k, v in b.items()})
            break
        loss.numpy()
        t0 = _time.perf_counter()
        for b in batches:
            real += int((np.asarray(b["attention_mask"]) > 0).sum())
            loss = step(**{k: pt.to_tensor(v) for k, v in b.items()})
        loss.numpy()
        return _time.perf_counter() - t0, real

    corpus = Corpus()
    out = {"config": {"batch": B, "seq": S, "docs": n_docs,
                      "doc_len": f"{d_lo}..{d_hi}"}}

    # -- packed: first-fit pipeline batches ------------------------------
    pipe = DataPipeline(corpus, batch_size=B, seq_len=S, pack=True,
                        base_seed=3, shuffle=True, drop_last=True)
    packed = []
    for b in pipe:
        packed.append(b)
        if len(packed) >= steps:
            break
    # -- padded: one doc per row, padded to S (same label/mask form) -----
    padded = []
    di = 0
    while len(padded) < len(packed):
        ids = np.zeros((B, S), np.int32)
        seg = np.zeros((B, S), np.int32)
        pos = np.zeros((B, S), np.int32)
        lab = np.full((B, S), -100, np.int32)
        for r in range(B):
            d = corpus[di % n_docs][:S]
            di += 1
            ids[r, :len(d)] = d
            seg[r, :len(d)] = 1
            pos[r, :len(d)] = np.arange(len(d))
            lab[r, 1:len(d)] = d[1:]
        padded.append({"input_ids": ids, "attention_mask": seg,
                       "position_ids": pos, "labels": lab})

    step_fn = build_step()
    t_packed, tok_packed = run(packed, step_fn)
    del step_fn
    gc.collect()
    step_fn = build_step()  # fresh params: identical compile state
    t_padded, tok_padded = run(padded, step_fn)
    del step_fn
    gc.collect()

    eff = pipe.packer.efficiency_stats()
    out["packing_efficiency"] = round(eff["mean"], 4)
    out["packed_tokens_per_sec"] = round(tok_packed / t_packed, 1)
    out["padded_tokens_per_sec"] = round(tok_padded / t_padded, 1)
    out["packed_over_padded"] = round(
        (tok_packed / t_packed) / max(tok_padded / t_padded, 1e-9), 2)
    out["packed_step_ms"] = round(t_packed / len(packed) * 1e3, 2)
    out["padded_step_ms"] = round(t_padded / len(padded) * 1e3, 2)

    # -- prefetch on/off: hide a slow host fetch -------------------------
    fetch_s = 0.015

    class SlowDocs:
        """IO-bound corpus: sleep stands in for object-store reads and
        releases the GIL exactly like real IO would."""

        def __getitem__(self, i):
            _time.sleep(fetch_s)
            return corpus[i]

        def __len__(self):
            return n_docs

    def timed_loop(loader, n):
        """Mean per-step wall time of a fit-shaped loop: fetch (the
        measured wait) + a fixed compute phase."""
        it = iter(loader)
        next(it)  # exclude iterator spin-up
        t0 = _time.perf_counter()
        got = 0
        for b in it:
            _time.sleep(0.01)  # the "train step" the chip would run
            got += 1
            if got >= n:
                break
        return (_time.perf_counter() - t0) / max(got, 1)

    def fresh_pipe(prefetch):
        return DataPipeline(SlowDocs(), batch_size=B, seq_len=S,
                            pack=True, base_seed=3, shuffle=True,
                            drop_last=True, device_prefetch=prefetch)

    n_timed = max(len(packed) - 2, 3)
    sync_step = timed_loop(fresh_pipe(0), n_timed)
    pre_step = timed_loop(fresh_pipe(2), n_timed)
    out["sync_step_ms"] = round(sync_step * 1e3, 2)
    out["prefetch_step_ms"] = round(pre_step * 1e3, 2)
    out["prefetch_data_wait_saved_ms"] = round(
        (sync_step - pre_step) * 1e3, 2)
    return out


def _chaos_worker():
    """Trainer side of ``--chaos`` (launched under the elastic launcher):
    a tiny resilient fit — FitResilience checkpointing every step and
    resuming from ``latest_step`` on relaunch — that appends one JSON
    line per completed step, so the parent can reconstruct the kill /
    recovery timeline from the file alone."""
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    from paddle_tpu.resilience import FitResilience

    run_dir = os.environ["BENCH_CHAOS_DIR"]
    target = int(os.environ.get("BENCH_CHAOS_STEPS", "12"))
    steps_path = os.path.join(run_dir, "steps.jsonl")

    model = pt.hapi.Model(nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                        nn.Linear(16, 1)))
    model.prepare(pt.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters()),
                  nn.MSELoss())
    fr = FitResilience(checkpoint_dir=os.path.join(run_dir, "ckpt"),
                       save_every_steps=1, preemption=True)
    resumed = fr.restore(model)

    class Progress(pt.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            with open(steps_path, "a") as f:
                f.write(json.dumps({"gs": fr.global_step,
                                    "pid": os.getpid(),
                                    "t": time.time()}) + "\n")

    remaining = target - (resumed or 0)
    if remaining > 0:
        rng = np.random.RandomState(0)
        data = [(rng.randn(4, 8).astype(np.float32),
                 rng.randn(4, 1).astype(np.float32)) for _ in range(4)]
        # StepTelemetry drives the StepTimer → the goodput ledger, whose
        # per-step snapshots (PADDLE_TPU_GOODPUT_DIR) the parent folds
        # into the job_goodput_fraction headline
        model.fit(data, epochs=(remaining + len(data) - 1) // len(data),
                  num_iters=remaining, verbose=0,
                  callbacks=[fr, pt.callbacks.StepTelemetry(), Progress()])
    fr.exit_if_preempted()


def bench_chaos():
    """Chaos/MTTR bench (--chaos): run the resilient worker under the
    elastic launcher, SIGKILL it mid-run through the chaos harness
    (``PADDLE_TPU_CHAOS_KILL_AT_STEP``), and measure recovery end to
    end: mean time to recovery (gap between the last step before the
    kill and the first step after the relaunch — dominated by process
    start + jax import + restore), steps lost to the async-save window,
    and whether the run still reached its target step count. Results
    ride the ``--emit-metrics`` JSON schema."""
    import shutil
    import subprocess
    import tempfile

    kill_step = int(os.environ.get("BENCH_CHAOS_KILL_STEP", "5"))
    target = int(os.environ.get("BENCH_CHAOS_STEPS", "12"))
    run_dir = tempfile.mkdtemp(prefix="pt_chaos_bench_")
    env = dict(os.environ)
    env.update({
        "BENCH_CHAOS_DIR": run_dir,
        "BENCH_CHAOS_STEPS": str(target),
        "PADDLE_TPU_CHAOS_KILL_AT_STEP": str(kill_step),
        "PADDLE_TPU_CHAOS_MARK_DIR": run_dir,  # kill fires once per job
        # per-step goodput ledger snapshots (one file per incarnation;
        # the launcher stamps PADDLE_TPU_GOODPUT_DOWN_AT on relaunch, so
        # the second file's ledger carries the kill→resume gap as
        # restart badput)
        "PADDLE_TPU_GOODPUT_DIR": run_dir,
    })
    try:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--max_restarts", "2", os.path.abspath(__file__),
             "--chaos-worker"],
            env=env, timeout=600)
        elapsed = time.perf_counter() - t0
        steps = []
        with open(os.path.join(run_dir, "steps.jsonl")) as f:
            steps = [json.loads(line) for line in f if line.strip()]
        pids = list(dict.fromkeys(s["pid"] for s in steps))
        out = {"target_steps": target, "kill_step": kill_step,
               "elapsed_s": round(elapsed, 2),
               "launcher_rc": proc.returncode,
               "restarts": len(pids) - 1,
               "completed": bool(steps) and steps[-1]["gs"] >= target}
        if len(pids) >= 2:
            boundary = next(i for i, s in enumerate(steps)
                            if s["pid"] == pids[1])
            last_before, first_after = steps[boundary - 1], steps[boundary]
            out["mttr_s"] = round(first_after["t"] - last_before["t"], 2)
            # steps re-run because the kill outran the async commit
            out["steps_lost"] = last_before["gs"] + 1 - first_after["gs"]
        out.update(_chaos_goodput(run_dir))
        # the elastic counterpart: same class of event (2 of 8 hosts
        # lost), handled as an in-place resize instead of the
        # kill→checkpoint→relaunch above — MTTRs land side by side
        out["resize_drill"] = bench_resize_drill(out.get("mttr_s"))
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    if "job_goodput_fraction" in out:
        # report-gate headline (stdout JSON line; see _report_metrics_of)
        import jax
        sfx = "" if jax.default_backend() == "tpu" else "_cpu_smoke"
        print(json.dumps({"metric": f"job_goodput_fraction{sfx}",
                          "value": out["job_goodput_fraction"],
                          "unit": "fraction"}))
    return out


def _chaos_goodput(run_dir: str) -> dict:
    """Fold the chaos run's per-incarnation goodput ledger snapshots
    (``goodput_rank0_<pid>.json``, written per step under
    ``PADDLE_TPU_GOODPUT_DIR``) into the job-level accounting: summed
    bins, the SIGKILL relaunch gap as restart badput, and the headline
    ``job_goodput_fraction``. ``wall_coverage`` is the invariant the
    docs promise — the bins sum to measured wall-clock (first ledger
    birth → last classified step) within a few percent; only the
    last-step→SIGKILL slice and the launcher's reap latency escape."""
    import glob as _glob
    snaps = []
    for p in _glob.glob(os.path.join(run_dir, "goodput_rank*.json")):
        try:
            with open(p) as f:
                snaps.append(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
    if not snaps:
        return {}
    snaps.sort(key=lambda s: s.get("start_unix", 0.0))
    bins = {}
    for s in snaps:
        for b, v in s.get("bins", {}).items():
            bins[b] = bins.get(b, 0.0) + v
    binned = sum(bins.values())
    last = snaps[-1]
    end_unix = last["start_unix"] + last["wall_s"] - \
        last.get("bins", {}).get("restart", 0.0)
    measured = end_unix - snaps[0]["start_unix"]
    out = {"goodput_bins": {b: round(v, 3) for b, v in bins.items()},
           "goodput_restart_s": round(bins.get("restart", 0.0), 3),
           "goodput_incarnations": len(snaps)}
    if binned > 0:
        out["job_goodput_fraction"] = round(
            bins.get("productive", 0.0) / binned, 4)
    if measured > 0:
        out["goodput_wall_coverage"] = round(binned / measured, 4)
    return out


def bench_resize_drill(relaunch_mttr_s=None):
    """Elastic resize drill (rides ``--chaos``): 8 simulated hosts in ONE
    process lose 2 mid-epoch and continue on 6 — the live-resharding
    path (resilience.elastic) end to end, with the acceptance checks
    inline: the consensus boundary lands on the same step for every
    lane, the in-memory shard exchange reassembles model+opt
    bit-identically (same offset math as the checkpoint-file reshard),
    the remapped data order stays exactly-once (token-multiset digest
    over pre+post batches equals one full epoch), zero filesystem writes
    happen on the resize path, and the in-place MTTR comes in far under
    the kill→checkpoint→relaunch MTTR measured by the main chaos run
    (passed in as ``relaunch_mttr_s``). Badput lands in the ``reshard``
    goodput bin — ``restart`` stays at 0."""
    import builtins
    from collections import Counter

    from paddle_tpu.checkpoint.layout import flatten_state
    from paddle_tpu.data.pipeline import DataPipeline
    from paddle_tpu.distributed.tcp_store import TCPStore
    from paddle_tpu.observability.goodput import GoodputLedger
    from paddle_tpu.resilience import elastic
    from paddle_tpu.resilience.elastic import ElasticResizeListener

    OLD, NEW = 8, 6
    rng = np.random.RandomState(7)
    # 240 docs = lcm(8, 6) * 10: both worlds cover every doc exactly once
    docs = [rng.randint(1, 1000, size=rng.randint(5, 48)).astype(np.int32)
            for _ in range(240)]

    class Docs:
        def __len__(self):
            return len(docs)

        def __getitem__(self, i):
            return docs[i]

    def pipes(n):
        return [DataPipeline(Docs(), batch_size=2, seq_len=32, pack=True,
                             base_seed=11, shuffle=True, shard_index=k,
                             num_shards=n, drop_last=False)
                for k in range(n)]

    def toks(batch):
        ids, m = batch["input_ids"], batch["attention_mask"]
        return ids[m > 0].tolist()

    want = Counter()
    for d in docs:
        want.update(d.tolist())

    # the replicated model+opt every host holds after allreduce; the
    # deterministic "train step" makes post-resize state divergence
    # detectable through the weights themselves
    state = {"model": {"w": rng.randn(64, 64).astype(np.float32),
                       "b": rng.randn(64).astype(np.float32)},
             "opt": {"m": np.zeros((64, 64), np.float32),
                     "step": np.int64(0)}}

    def train_step(st, n_tok):
        st["model"]["w"] *= np.float32(1.0 - 1e-4)
        st["opt"]["m"] += np.float32(n_tok)
        st["opt"]["step"] = st["opt"]["step"] + 1

    ledger = GoodputLedger()
    store = TCPStore(is_master=True, world_size=1)
    listeners = [ElasticResizeListener(store=store) for _ in range(OLD)]
    have = Counter()
    old = pipes(OLD)
    iters = [iter(p) for p in old]
    kill_at, gs, boundary, t_kill = 3, 0, None, None
    while boundary is None:
        t0 = time.perf_counter()
        batches = [next(it) for it in iters]
        for b in batches:
            have.update(toks(b))
        train_step(state, sum(int(b["attention_mask"].sum())
                              for b in batches))
        gs += 1
        ledger.record("productive", time.perf_counter() - t0)
        if gs == kill_at:
            # 2 of 8 hosts are going away: the doomed host's preemption
            # notice arrives through the elastic seam on ONE lane; the
            # consensus protocol spreads it to all
            t_kill = time.perf_counter()
            listeners[6].request(NEW, "preempt_2_hosts")
        decided = [ln.should_resize(step=gs) for ln in listeners]
        if all(decided):
            boundary = gs
        else:
            assert not any(decided), "consensus boundary diverged"
    agreed = {ln.target_world for ln in listeners}
    assert agreed == {NEW}, f"target world diverged: {agreed}"

    # --- the resize itself: all 8 publish, 6 assemble — NO filesystem ---
    writes = []
    _open = builtins.open

    def spy(f, mode="r", *a, **k):
        if any(c in str(mode) for c in "wxa+"):
            writes.append(str(f))
        return _open(f, mode, *a, **k)

    import threading
    clients = [TCPStore(host="127.0.0.1", port=store.port,
                        is_master=False, world_size=1)
               for _ in range(OLD)]
    results = [None] * OLD

    def one_rank(r):
        results[r] = elastic.perform_resize(
            clients[r], state=state, data_state=old[r].state_dict(),
            world=OLD, rank=r, new_world=NEW, generation=0,
            boundary_step=boundary, timeout=120)

    t0 = time.perf_counter()
    builtins.open = spy
    try:
        # one thread per simulated host — the same concurrent publish →
        # barrier → assemble dance real ranks run
        ths = [threading.Thread(target=one_rank, args=(r,), daemon=True)
               for r in range(OLD)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=180)
    finally:
        builtins.open = _open
    assert all(s is None and d is None for s, d in results[NEW:]), \
        "departing ranks must not assemble"
    new_states = [s for s, _ in results[:NEW]]
    new_datas = [d for _, d in results[:NEW]]

    _, f0 = flatten_state(state)
    bit_identical = True
    for ns in new_states:
        _, f1 = flatten_state(ns)
        bit_identical &= f0.keys() == f1.keys() and all(
            f0[k][0].tobytes() == f1[k][0].tobytes() for k in f0)

    new = pipes(NEW)
    for j, p in enumerate(new):
        p.load_state_dict(new_datas[j])
    t_ready = time.perf_counter()
    resize_s = t_ready - t0
    # MTTR: preemption notice → consensus boundary → in-place reshard →
    # ready to train on the new world
    mttr_s = t_ready - t_kill
    ledger.record("reshard", resize_s)

    # --- continue on 6: drive the epoch to completion on the survivors
    post_steps = 0
    iters = [iter(p) for p in new]
    live = list(range(NEW))
    while live:
        t0 = time.perf_counter()
        done = []
        for j in live:
            try:
                b = next(iters[j])
            except StopIteration:
                done.append(j)
                continue
            have.update(toks(b))
        if len(done) < len(live):
            train_step(new_states[0], 1)
            post_steps += 1
            ledger.record("productive", time.perf_counter() - t0)
        live = [j for j in live if j not in done]
    snap = ledger.snapshot()
    b = snap["bins"]
    binned = b["productive"] + b["reshard"] + b["restart"]
    out = {"old_world": OLD, "new_world": NEW,
           "boundary_step": boundary, "post_steps": post_steps,
           "resize_s": round(resize_s, 4),
           "resize_mttr_s": round(mttr_s, 4),
           "state_bit_identical": bool(bit_identical),
           "exactly_once": have == want,
           "filesystem_writes_on_resize_path": len(writes),
           "goodput_restart_s": b["restart"],
           "goodput_reshard_s": b["reshard"],
           # productive share of (train + downtime) — the apples-to-
           # apples counterpart of the relaunch run's fraction, where
           # the same membership change bins seconds of restart badput
           "job_goodput_fraction": round(
               b["productive"] / binned, 4) if binned > 0 else None}
    if relaunch_mttr_s:
        out["relaunch_mttr_s"] = relaunch_mttr_s
        if mttr_s > 0:
            out["resize_vs_relaunch_speedup"] = round(
                float(relaunch_mttr_s) / mttr_s, 1)
    return out


def bench_eager():
    """Eager-dispatch overhead — SURVEY §7's #1 risk ('per-op eager
    dispatch is untenable'), finally measured (reference ships the
    equivalent microbench: eager/tests/performance_tests/
    benchmark_eager_cuda.cc). Two numbers: µs per small eager op (tape
    node + XLA dispatch, slope-timed so the sync constant cancels), and
    the eager-vs-TrainStep step-time ratio at the headline config — the
    factor a user pays for skipping compilation on the hot loop."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    # --- 1) µs/op on a chain of small adds (dependent: no fusion escape)
    a = pt.to_tensor(np.ones((8, 8), np.float32))
    b = pt.to_tensor(np.ones((8, 8), np.float32))

    def chain(n):
        c = a
        t0 = time.perf_counter()
        for _ in range(n):
            c = pt.ops.add(c, b)
        float(np.asarray(c.numpy()).sum())
        return time.perf_counter() - t0

    chain(20)  # warm
    n1, n2 = 100, 500
    us_per_op = min((chain(n2) - chain(n1)) / (n2 - n1)
                    for _ in range(3)) * 1e6

    # --- 2) eager vs TrainStep, headline model (scaled to keep the eager
    # run tractable: same recipe, 4 layers, B=2)
    on_tpu = jax.default_backend() == "tpu"
    cfg = LlamaConfig(
        vocab_size=128256 if on_tpu else 512,
        hidden_size=2048 if on_tpu else 128,
        intermediate_size=7168 if on_tpu else 448,
        num_hidden_layers=4 if on_tpu else 2,
        num_attention_heads=16 if on_tpu else 4,
        num_key_value_heads=4 if on_tpu else 2,
        max_position_embeddings=4096 if on_tpu else 512,
        tie_word_embeddings=True)
    B, S = (2, 2048) if on_tpu else (2, 128)
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    rng = np.random.RandomState(0)
    x = pt.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                     .astype(np.int64))

    def eager_step():
        _, loss = model(x, labels=x)
        loss.backward()
        opt.step()
        opt.clear_grad(set_to_zero=False)
        return loss

    eager_dt = _time_steps(eager_step, 1, 1, lambda l: l.numpy(), reps=2)

    pt.seed(0)
    model2 = LlamaForCausalLM(cfg)
    model2.bfloat16()
    opt2 = pt.optimizer.AdamW(learning_rate=1e-4,
                              parameters=model2.parameters(),
                              multi_precision=True)
    step = TrainStep(model2, lambda m, t: m(t, labels=t)[1], opt2)
    comp_dt = _time_steps(lambda: step(x), 3, 1, lambda l: l.numpy())

    return {
        "eager_us_per_small_op": round(us_per_op, 1),
        "eager_step_ms": round(eager_dt * 1e3, 1),
        "trainstep_step_ms": round(comp_dt * 1e3, 1),
        "eager_over_trainstep": round(eager_dt / comp_dt, 1),
        "config": {"layers": cfg.num_hidden_layers, "d": cfg.hidden_size,
                   "batch": B, "seq": S},
    }


# ===================== regression gate (--report) ===========================
# BENCH_r0*.json / MULTICHIP_r0*.json files in --baseline-dir are the perf
# trajectory; --report compares a current run against the newest usable
# round and exits nonzero past a configurable tolerance. These helpers
# import neither
# jax nor paddle_tpu — doctored-trajectory tests run them in-process.

#: per-metric comparison direction; metrics not listed are reported
#: informationally but never gate
REPORT_HIGHER_BETTER = {
    "llama_full_train_step_mfu_bf16", "llama3_8b_layer_mfu_bf16",
    "tokens_per_sec", "layer_tokens_per_sec", "achieved_tflops",
    "layer_mfu_pct",
    # serving throughput under the RPA kernel (ISSUE 8): bench.py
    # --serve Poisson-trace aggregate decode rate
    "serving_decode_tokens_per_sec",
    # productive share of chaos-run wall-clock (ISSUE 13): bench.py
    # --chaos goodput ledger headline — restart/rollback badput must
    # not silently grow
    "job_goodput_fraction",
    # multi-replica fleet serving (ISSUE 17): bench.py --serve
    # --replicas N — aggregate fleet decode rate, its ratio over N
    # single-replica runs at the same per-replica offered load, and
    # the cache-aware router's sketch-match placement rate on
    # shared-prefix traffic
    "serving_fleet_tokens_per_sec",
    "serving_fleet_scaling_efficiency",
    "serving_router_affinity_hit_rate",
    # block-granular prefix cache on shared-prefix traffic (ISSUE 15):
    # fraction of admissions that reused cached KV blocks, and the
    # cache-on/cache-off effective-throughput ratio on the same trace
    "serving_prefix_cache_hit_rate",
    "serving_shared_prefix_speedup",
    # quantized + multi-tenant serving (ISSUE 20): int8 weight-only
    # decode rate on the primary Poisson trace, the batch the int8 KV
    # cache sustains inside the full-precision engine's pool bytes,
    # and the tenants served concurrently from one compiled step
    "serving_int8_tokens_per_sec",
    "serving_kv_quant_max_batch",
    "serving_adapters_served",
}
REPORT_LOWER_BETTER = {"step_ms", "layer_step_ms",
                       # step-glue fusion/overlap trajectory (ISSUE 7):
                       # fused multi-tensor optimizer phase and exposed
                       # (non-overlapped) collective share of the step
                       "optimizer_phase_seconds",
                       "train_step_exposed_collective_seconds",
                       # serving tail latency under the RPA kernel
                       # (ISSUE 8): bench.py --serve p99 TTFT
                       "serving_p99_ttft_seconds",
                       # shared-prefix trace tail latency with the
                       # prefix cache on and off (ISSUE 15) — the
                       # cached path must hold its TTFT win and the
                       # cold oracle must not quietly degrade either
                       "serving_cached_p99_ttft_seconds",
                       "serving_cold_p99_ttft_seconds",
                       # throughput cost of the per-request ledger
                       # (ISSUE 16): armed-vs-disarmed decode rate on
                       # the same Poisson trace — must stay ≤ 1%
                       "serving_request_ledger_overhead_frac",
                       # static program-audit headlines (ISSUE 9,
                       # bench.py --audit / paddle_tpu.analysis): dp
                       # collective census, bytes the step keeps
                       # double-buffered (undonated), and the largest
                       # intermediate (the fused-CE before/after metric)
                       "train_step_allreduce_count",
                       "train_step_undonated_bytes",
                       "train_step_largest_intermediate_bytes",
                       # runtime-truth peak HBM of the compiled train
                       # step (ISSUE 11, observability.memory): XLA
                       # buffer-assignment total for the audited step
                       "train_step_peak_hbm_bytes",
                       # instrumented-vs-plain step cost of the numerics
                       # observatory's sampled twin (ISSUE 14, bench.py
                       # --numerics) — the tap seam must stay cheap
                       "numerics_step_overhead_frac"}
#: open-ended LOWER_BETTER families — the static comm budget is one
#: metric per mesh axis (ISSUE 12, bench.py --audit /
#: paddle_tpu.analysis commplan), so membership is by prefix; the
#: ``_cpu_smoke`` suffix rides after the axis name
REPORT_LOWER_BETTER_PREFIXES = ("train_step_comm_bytes_",)
#: absolute ceilings: current must stay under max(baseline, bound) —
#: step-time spread is a stability gate, not a race
REPORT_BOUNDED = {"spread_pct_of_mean": 1.5}


def _lower_better(name: str) -> bool:
    return name in REPORT_LOWER_BETTER or \
        name.startswith(REPORT_LOWER_BETTER_PREFIXES)


def _report_metrics_of(doc: dict) -> dict:
    """Flat {metric: value} from one round document — either a committed
    BENCH_r0*.json ({"tail", "parsed", ...}) or a bare result dict. The
    headline {"metric": name, "value": v} line (stdout JSON) becomes a
    metric under its own name."""
    out = {}
    parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) \
        else None
    flat = parsed if parsed is not None else doc
    for k, v in flat.items():
        # rc/unix_time are round bookkeeping, not perf metrics — counting
        # them would let a metric-less round pass for a usable baseline
        if k in ("rc", "unix_time"):
            continue
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = float(v)
    tail = doc.get("tail", "")
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and "metric" in obj and "value" in obj:
            try:
                out[str(obj["metric"])] = float(obj["value"])
            except (TypeError, ValueError):
                continue  # null / non-numeric headline: not comparable
    if "metric" in doc and "value" in doc:
        try:
            out[str(doc["metric"])] = float(doc["value"])
        except (TypeError, ValueError):
            pass
    return out


def _round_key(path: str) -> int:
    """Numeric round id from BENCH_r12.json — lexicographic sort would
    pin the gate to r09 forever once r10 lands."""
    import re
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else -1


def report_baseline(baseline_dir: str, pattern: str = "BENCH_r*.json"):
    """(round_name, metrics) from the newest trajectory round that has
    comparable numbers (rc==0 and at least one numeric metric)."""
    import glob as _glob
    paths = sorted(_glob.glob(os.path.join(baseline_dir, pattern)),
                   key=_round_key)
    for path in reversed(paths):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if doc.get("rc", 0) != 0:
            continue
        metrics = _report_metrics_of(doc)
        if metrics:
            return os.path.basename(path), metrics
    return None, {}


def report_compare(baseline: dict, current: dict,
                   tolerance_pct: float) -> dict:
    """Row-per-metric comparison. A metric regresses when it moves past
    ``tolerance_pct`` in its bad direction (or past its absolute bound);
    baseline metrics missing from the current run are listed as
    ``skipped`` — visible, but only ``--strict`` turns them into a
    failure."""
    tol = tolerance_pct / 100.0
    rows, failures, skipped = [], [], []
    for name in sorted(baseline):
        base = baseline[name]
        if name not in current:
            if name in REPORT_HIGHER_BETTER or _lower_better(name) \
                    or name in REPORT_BOUNDED:
                skipped.append(name)
            continue
        cur = current[name]
        delta_pct = ((cur - base) / abs(base) * 100) if base else 0.0
        status = "info"
        if name in REPORT_HIGHER_BETTER:
            status = "fail" if cur < base * (1 - tol) else "ok"
        elif _lower_better(name):
            status = "fail" if cur > base * (1 + tol) else "ok"
        elif name in REPORT_BOUNDED:
            limit = max(base, REPORT_BOUNDED[name])
            status = "fail" if cur > limit * (1 + tol) else "ok"
        row = {"metric": name, "baseline": base, "current": cur,
               "delta_pct": round(delta_pct, 2), "status": status}
        rows.append(row)
        if status == "fail":
            failures.append(name)
    return {"rows": rows, "failures": failures, "skipped": skipped,
            "compared": sum(1 for r in rows if r["status"] in
                            ("ok", "fail"))}


def _multichip_segments(doc: dict):
    """Dryrun segment labels out of a MULTICHIP_r0*.json tail — the
    coverage set a current run must not shrink."""
    import re
    tail = doc.get("tail", "")
    segs = set()
    for line in tail.splitlines():
        if "dryrun_multichip" not in line:
            continue
        body = line.split(":", 1)[-1]
        # parity fragments like "|5.55671-5.55671|<tol" also split on
        # "|": only letter-led tokens are segment labels
        for part in body.split("|"):
            m = re.match(r"\s*([A-Za-z][A-Za-z0-9_\[\]x-]*)", part)
            if m:
                segs.add(m.group(1))
    return segs


def report_multichip(baseline_path_dir: str, current_doc: dict) -> dict:
    """Gate the multichip dryrun: the current run must be ok (rc 0) and
    cover every segment the newest committed round covered."""
    import glob as _glob
    paths = sorted(_glob.glob(os.path.join(baseline_path_dir,
                                           "MULTICHIP_r*.json")),
                   key=_round_key)
    base_doc = None
    for path in reversed(paths):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if doc.get("rc", 1) == 0 and doc.get("ok"):
            base_doc = doc
            break
    if base_doc is None:
        return {"status": "no-baseline"}
    missing = sorted(_multichip_segments(base_doc) -
                     _multichip_segments(current_doc))
    ok = bool(current_doc.get("ok")) and current_doc.get("rc", 1) == 0 \
        and not missing
    return {"status": "ok" if ok else "fail",
            "current_ok": bool(current_doc.get("ok")),
            "missing_segments": missing}


def _report_argv_value(argv, flag, default=None):
    if flag in argv:
        i = argv.index(flag)
        if i + 1 >= len(argv):
            raise SystemExit(f"{flag} requires a value")
        return argv[i + 1]
    return default


def bench_report(argv=None) -> int:
    """``bench.py --report`` entry point; returns the exit code.

    Flags: ``--current FILE`` (a prior run's JSON: committed-round shape
    or a flat result dict; default: run the bench now), ``--baseline-dir
    DIR`` (default: this file's directory), ``--tolerance PCT`` (default
    3), ``--multichip FILE`` (also gate dryrun coverage), ``--strict``
    (baseline metrics missing from the current run fail the gate).
    """
    argv = sys.argv if argv is None else argv
    baseline_dir = _report_argv_value(
        argv, "--baseline-dir", os.path.dirname(os.path.abspath(__file__)))
    tolerance = float(_report_argv_value(argv, "--tolerance", "3"))
    strict = "--strict" in argv
    current_path = _report_argv_value(argv, "--current")

    round_name, baseline = report_baseline(baseline_dir)
    if not baseline:
        print(json.dumps({"report": {"status": "no-baseline",
                                     "baseline_dir": baseline_dir}}))
        return 2 if strict else 0

    if current_path:
        with open(current_path) as f:
            cur_doc = json.load(f)
        if cur_doc.get("rc", 0) != 0:
            # a crashed bench's partial numbers must not pass the gate —
            # the same rc discipline report_baseline applies to baselines
            print(json.dumps({"report": {
                "status": "current-run-failed",
                "rc": cur_doc.get("rc")}}))
            return 1
        current = _report_metrics_of(cur_doc)
    else:
        import jax
        on_tpu = jax.default_backend() == "tpu"
        dev = jax.devices()[0]
        peak = peak_flops(dev)
        flops_per_s, extras = bench_full_model(on_tpu)
        gc.collect()
        layer_flops_per_s, layer_extras = bench_layer(on_tpu)
        current = _report_metrics_of({**extras, **layer_extras})
        if on_tpu and peak:
            current["llama_full_train_step_mfu_bf16"] = \
                round(flops_per_s / peak * 100, 2)
            current["layer_mfu_pct"] = \
                round(layer_flops_per_s / peak * 100, 2)
        elif not on_tpu:
            # a CPU smoke run must not race the committed TPU round
            # under identical metric names — suffix everything so the
            # gate lists the baseline's metrics as skipped (soft) rather
            # than failing on hardware, not regression
            current = {f"{k}_cpu_smoke": v for k, v in current.items()}

    cmp = report_compare(baseline, current, tolerance)
    report = {"baseline_round": round_name, "tolerance_pct": tolerance,
              **cmp}

    mc_path = _report_argv_value(argv, "--multichip")
    if mc_path:
        with open(mc_path) as f:
            report["multichip"] = report_multichip(baseline_dir,
                                                   json.load(f))
        if report["multichip"].get("status") == "fail":
            report.setdefault("failures", []).append("multichip")

    failed = bool(report["failures"]) or (strict and report["skipped"])
    report["status"] = "fail" if failed else (
        "ok" if report["compared"] else "no-comparable-metrics")
    for r in report["rows"]:
        print(f"  {r['status']:<5} {r['metric']:<40} "
              f"{r['baseline']:>12.3f} -> {r['current']:>12.3f} "
              f"({r['delta_pct']:+.2f}%)", file=sys.stderr)
    if report["skipped"]:
        print(f"  skipped (absent from current run): "
              f"{', '.join(report['skipped'])}", file=sys.stderr)
    if not report["compared"]:
        print("  no comparable metrics — baseline is a TPU round and the "
              "current run carries none of its gated metrics (CPU smoke?)",
              file=sys.stderr)
    print(json.dumps({"report": report}))
    return 1 if failed else 0


def bench_attribution():
    """Phase-level step attribution (--attribution) on the committed
    bench geometry: where the 287.88ms step goes — embedding+layers vs
    loss-head vs optimizer vs exposed collective — with per-phase MFU
    from XLA cost analysis (docs/OBSERVABILITY.md). The table the
    fusion/overlap work must move."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability.attribution import attribute_train_step

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            tie_word_embeddings=True)
        B, S = 4, 2048
        steps, warmup, reps = 8, 2, 3
    else:
        cfg = LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=448,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=512,
            tie_word_embeddings=True)
        B, S = 2, 256
        # the optimizer phase is a ~1ms difference of ~60ms measurements
        # on the 1-CPU smoke box: more reps keep the min-over-windows
        # stable enough for the fused-vs-looped comparison row
        steps, warmup, reps = 4, 1, 4

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
    rng = np.random.RandomState(0)
    x = pt.to_tensor(rng.randint(0, cfg.vocab_size, (B, S))
                     .astype(np.int64))
    config = {"d": cfg.hidden_size, "layers": cfg.num_hidden_layers,
              "vocab": cfg.vocab_size, "batch": B, "seq": S}
    # fused (the shipped default, whose table/gauges this run reports)
    # measured FIRST on the freshest process state, looped second for the
    # before/after comparison row — the phase is a ~1ms difference of
    # ~60ms programs on CPU smoke and allocator growth between attribute
    # calls would otherwise bias whichever run goes last
    report = attribute_train_step(
        model, opt, x, steps=steps, warmup=warmup, reps=reps,
        config=config, fused=True)
    gc.collect()
    looped = attribute_train_step(
        model, opt, x, steps=steps, warmup=warmup, reps=reps,
        config=config, fused=False)

    def _opt_row(r):
        p = r.phases["optimizer"]
        share = p["seconds"] / r.step_time_s * 100 if r.step_time_s else 0.0
        return p["seconds"], share
    looped_s, looped_share = _opt_row(looped)
    fused_s, fused_share = _opt_row(report)
    print(report.table(), file=sys.stderr)
    print(f"optimizer phase: looped {looped_s * 1e3:.3f}ms "
          f"({looped_share:.2f}%) -> fused {fused_s * 1e3:.3f}ms "
          f"({fused_share:.2f}%)", file=sys.stderr)
    out = report.to_json()
    out["sums_within_5pct"] = report.check(0.05)
    out["optimizer_phase_ms_fused"] = round(fused_s * 1e3, 3)
    out["optimizer_phase_ms_looped"] = round(looped_s * 1e3, 3)
    # regression-gate headlines; CPU smoke keeps the suffix so it can't
    # race a TPU round
    suffix = "" if on_tpu else "_cpu_smoke"
    print(json.dumps({"metric": f"optimizer_phase_seconds{suffix}",
                      "value": round(fused_s, 6)}))
    print(json.dumps({
        "metric": f"train_step_exposed_collective_seconds{suffix}",
        "value": round(report.phases["exposed_collective"]["seconds"], 6)}))
    return out


def bench_audit():
    """Static program audit (--audit): compiled-HLO invariants on the
    committed geometry, as report-gate headlines (docs/ANALYSIS.md).

    Three LOWER_BETTER numbers: ``train_step_allreduce_count`` (the
    dp collective census — buckets+1 when the bucketed path holds, a
    storm when it regresses), ``train_step_undonated_bytes`` (buffers
    the step keeps two copies of), and
    ``train_step_largest_intermediate_bytes`` (the giant-intermediate
    watermark; the ROADMAP fused-CE item must move it). Off-TPU the
    metrics ride the ``_cpu_smoke`` suffix like every other bench mode.
    Nothing executes — programs are lowered and compiled only, so this
    runs in seconds even on the full chip geometry."""
    # the dp census needs a multi-device mesh: arm the 8-virtual-device
    # CPU platform BEFORE the backend initializes (no-op on TPU)
    from paddle_tpu.analysis.driver import ensure_cpu_mesh, \
        run_default_audit
    ensure_cpu_mesh()
    import jax
    on_tpu = jax.default_backend() == "tpu"

    if on_tpu:
        # the committed bench geometry (bench_full_model's shape), bf16
        # with f32 masters — the donation/upcast/intermediate subject
        from paddle_tpu.models.llama import LlamaConfig
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            tie_word_embeddings=True)
        result = run_default_audit(include_serving=False, bf16=True,
                                   batch=(4, 2048), llama_cfg=cfg)
    else:
        result = run_default_audit(include_serving=True)

    findings = result.pop("findings", [])
    result["findings"] = [f.to_json() for f in findings]
    for rep in result["reports"]:
        print(f"  {rep['label']:<14} all_reduce={rep['all_reduce_count']} "
              f"donation_coverage={rep['donation_coverage']} "
              f"undonated={rep['undonated_bytes']}B "
              f"largest={rep['largest_intermediate_bytes']}B "
              f"upcasts={rep['upcast_count']}", file=sys.stderr)
    suffix = "" if on_tpu else "_cpu_smoke"
    for name in ("train_step_allreduce_count",
                 "train_step_undonated_bytes",
                 "train_step_largest_intermediate_bytes",
                 "train_step_peak_hbm_bytes"):
        print(json.dumps({"metric": f"{name}{suffix}",
                          "value": result.get(name)}))

    # per-axis static comm budget (ISSUE 12): the bucketed-dp step's
    # comm-plan ledger as LOWER_BETTER headlines — the before/after
    # instrument the overlap/fusion work pairs with the runtime
    # train_step_exposed_collective_seconds counter
    from paddle_tpu.analysis.driver import run_commplan
    plan = run_commplan(only=("dp8",))
    for axis, slot in sorted(plan["ledgers"].get("dp8", {}).items()):
        result[f"train_step_comm_bytes_{axis}"] = slot["bytes"]
        print(json.dumps({"metric": f"train_step_comm_bytes_{axis}{suffix}",
                          "value": slot["bytes"]}))
    return result


def bench_profile():
    """On-demand device profiler smoke (--profile): compile the tiny
    llama step, open a bounded ``observability.profile`` capture around
    a few steps, and report how many trace files landed under
    ``PADDLE_TPU_TRACE_DIR`` (docs/OBSERVABILITY.md#device-profiler).
    Arming the profiler must not retrace — the step's executable cache
    is asserted unchanged across the captured window."""
    from paddle_tpu.analysis.driver import ensure_cpu_mesh, \
        tiny_llama_step
    ensure_cpu_mesh()
    import jax

    from paddle_tpu.observability import profile
    on_tpu = jax.default_backend() == "tpu"

    step, batch = tiny_llama_step()
    jax.block_until_ready(step(*batch))  # compile outside the window
    traces0 = len(step._cache)
    out_dir = profile.start_capture(label="bench")
    try:
        for _ in range(3):
            jax.block_until_ready(step(*batch))
    finally:
        profile.stop_capture()
    assert len(step._cache) == traces0, \
        "profiler capture must not retrace the train step"
    n_files = sum(len(files) for _, _, files in os.walk(out_dir))
    print(f"  profile capture -> {out_dir} ({n_files} files)",
          file=sys.stderr)
    suffix = "" if on_tpu else "_cpu_smoke"
    print(json.dumps({"metric": f"profile_trace_files{suffix}",
                      "value": n_files}))
    return {"trace_dir": out_dir, "trace_files": n_files}


def bench_numerics():
    """Numerics observatory overhead smoke (--numerics): compile the
    tiny llama step twice — plain and with the instrumented numerics
    twin forced on every step — and report the relative step-time cost
    of the in-graph tap/grad-stat telemetry as the
    ``numerics_step_overhead_frac`` LOWER_BETTER report-gate headline
    (``_cpu_smoke`` suffix off-TPU; docs/OBSERVABILITY.md#numerics).
    The sampled production cost is this number divided by
    ``PADDLE_TPU_NUMERICS_EVERY``."""
    from paddle_tpu.analysis.driver import ensure_cpu_mesh, \
        tiny_llama_step
    ensure_cpu_mesh()
    import jax

    from paddle_tpu.observability import numerics
    on_tpu = jax.default_backend() == "tpu"
    steps, warmup = (20, 3) if on_tpu else (8, 2)

    prev = {k: os.environ.get(k)
            for k in ("PADDLE_TPU_NUMERICS", "PADDLE_TPU_NUMERICS_EVERY")}
    try:
        os.environ["PADDLE_TPU_NUMERICS"] = "0"
        step, batch = tiny_llama_step()

        def time_steps():
            for _ in range(warmup):
                jax.block_until_ready(step(*batch))
            t0 = time.perf_counter()
            for _ in range(steps):
                jax.block_until_ready(step(*batch))
            return (time.perf_counter() - t0) / steps

        t_plain = time_steps()
        compiles0 = len(step._cache)
        os.environ["PADDLE_TPU_NUMERICS"] = "1"
        os.environ["PADDLE_TPU_NUMERICS_EVERY"] = "1"
        t_inst = time_steps()
        assert len(step._cache) == compiles0 + 1, \
            "arming numerics must compile exactly ONE instrumented twin"
        sample = step.last_numerics
        assert sample and sample["taps"], "instrumented steps must sample"
    finally:
        for k, v in prev.items():
            os.environ.pop(k, None) if v is None \
                else os.environ.__setitem__(k, v)

    overhead = (t_inst - t_plain) / t_plain if t_plain > 0 else 0.0
    print(f"  plain={t_plain * 1e3:.2f}ms instrumented={t_inst * 1e3:.2f}ms "
          f"overhead={overhead * 100:.1f}% taps={len(sample['taps'])} "
          f"grad_buckets={len(sample['grads'])}", file=sys.stderr)
    suffix = "" if on_tpu else "_cpu_smoke"
    print(json.dumps({"metric": f"numerics_step_overhead_frac{suffix}",
                      "value": round(overhead, 4)}))
    return {"plain_step_s": t_plain, "instrumented_step_s": t_inst,
            "overhead_frac": overhead, "taps": len(sample["taps"]),
            "grad_buckets": len(sample["grads"])}


def main():
    if "--chaos-worker" in sys.argv:
        _chaos_worker()
        return

    if "--report" in sys.argv:
        raise SystemExit(bench_report())

    import jax

    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    metrics_out = _metrics_out_path()

    if "--suite" in sys.argv or os.environ.get("BENCH_SUITE"):
        suite = bench_suite()
        print(json.dumps({"suite": suite}))
        if metrics_out:
            emit_metrics({"suite": suite}, metrics_out)
        return

    if "--decode" in sys.argv:
        decode = bench_decode()
        print(json.dumps({"decode": decode}))
        if metrics_out:
            emit_metrics({"decode": decode}, metrics_out)
        return

    if "--eager" in sys.argv:
        eager = bench_eager()
        print(json.dumps({"eager": eager}))
        if metrics_out:
            emit_metrics({"eager": eager}, metrics_out)
        return

    if "--attribution" in sys.argv:
        attribution = bench_attribution()
        print(json.dumps({"attribution": attribution}))
        if metrics_out:
            emit_metrics({"attribution": attribution}, metrics_out)
        return

    if "--audit" in sys.argv:
        audit = bench_audit()
        print(json.dumps({"audit": audit}))
        if metrics_out:
            emit_metrics({"audit": audit}, metrics_out)
        return

    if "--profile" in sys.argv:
        prof = bench_profile()
        print(json.dumps({"profile": prof}))
        if metrics_out:
            emit_metrics({"profile": prof}, metrics_out)
        return

    if "--numerics" in sys.argv:
        nums = bench_numerics()
        print(json.dumps({"numerics": nums}))
        if metrics_out:
            emit_metrics({"numerics": nums}, metrics_out)
        return

    if "--serve" in sys.argv:
        if "--replicas" in sys.argv:
            n = int(sys.argv[sys.argv.index("--replicas") + 1])
            fleet = bench_fleet(n)
            print(json.dumps({"fleet": fleet}))
            if metrics_out:
                emit_metrics({"fleet": fleet}, metrics_out)
        else:
            serve = bench_serve()
            print(json.dumps({"serve": serve}))
            if metrics_out:
                emit_metrics({"serve": serve}, metrics_out)
        return

    if "--ckpt" in sys.argv:
        ckpt = bench_ckpt()
        print(json.dumps({"ckpt": ckpt}))
        if metrics_out:
            emit_metrics({"ckpt": ckpt}, metrics_out)
        return

    if "--data" in sys.argv:
        data = bench_data()
        print(json.dumps({"data": data}))
        if metrics_out:
            emit_metrics({"data": data}, metrics_out)
        return

    if "--chaos" in sys.argv:
        chaos = bench_chaos()
        print(json.dumps({"chaos": chaos}))
        if metrics_out:
            emit_metrics({"chaos": chaos}, metrics_out)
        return

    on_tpu = jax.default_backend() == "tpu"
    dev = jax.devices()[0]
    if not on_tpu and not os.environ.get("BENCH_FORCE_CPU"):
        raise SystemExit(
            f"bench.py measures the chip: jax found platform="
            f"{dev.platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). BENCH_FORCE_CPU=1 runs "
            "the smoke-size configuration on the CPU instead.")
    peak = peak_flops(dev)

    model_flops_per_s, extras = bench_full_model(on_tpu)
    gc.collect()  # free the full model's params/optimizer HBM first
    layer_flops_per_s, layer_extras = bench_layer(on_tpu)
    extras.update(layer_extras)
    extras["device"] = getattr(dev, "device_kind", str(dev))

    if on_tpu and peak:
        model_mfu = model_flops_per_s / peak
        layer_mfu = layer_flops_per_s / peak
        extras["layer_mfu_pct"] = round(layer_mfu * 100, 2)
        result = {"metric": "llama_full_train_step_mfu_bf16",
                  "value": round(model_mfu * 100, 2),
                  "unit": "percent_mfu",
                  "vs_baseline": round(model_mfu / 0.40, 3)}
    else:
        result = {"metric": "llama_full_train_step_tokens_per_sec_cpu_smoke",
                  "value": extras["tokens_per_sec"], "unit": "tokens/sec",
                  "vs_baseline": 0.0}
    print(json.dumps(result))
    print(json.dumps(extras), file=sys.stderr)
    if metrics_out:
        emit_metrics({"headline": result, "detail": extras}, metrics_out)




# ===================== BASELINE config suite (--suite) ======================
# Every BASELINE.json family gets a measured number on the real chip:
# ERNIE pretraining, DeepSeekMoE/Qwen2-MoE-style MoE LM (ragged dispatch),
# DiT (SD-3-family diffusion transformer), PP-OCRv4 conv recognizer, and a
# Llama-3-70B-geometry decoder layer (the full 70B cannot fit one chip).
# Shapes are scaled to a single
# v5e's HBM; FLOPs come from XLA's own cost analysis of the compiled
# fwd+bwd program (no hand formulas), so MFU is consistent across
# matmul- and conv-dominated models.

def _measure_pure(build, steps=10, warmup=2):
    import jax
    import jax.numpy as jnp

    fn, state, batch, per_step = build()
    # commit the batch to the device ONCE: numpy args would re-transfer
    # host->device on every timed call
    batch = tuple(jnp.asarray(b) for b in batch)
    # AOT-compile once; the same executable serves cost analysis AND the
    # timing loop (jit would re-trace/re-compile a second copy)
    compiled = jax.jit(jax.value_and_grad(fn)).lower(
        state, *batch).compile()
    flops = None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost["flops"])
    except Exception:
        pass
    dt = _time_steps(lambda: compiled(state, *batch), steps, warmup,
                     lambda out: np.asarray(out[0]))
    return {"step_ms": round(dt * 1e3, 2),
            "throughput": round(per_step / dt, 1),
            "measured_gflops_per_step": (round(flops / 1e9, 1)
                                         if flops else None),
            "achieved_tflops": (round(flops / dt / 1e12, 2)
                                if flops else None),
            "_flops_per_sec": (flops / dt) if flops else None}


def _functional(model, loss):
    """(pure_fn, state) for a Layer: loss(model_out...) as a jax scalar."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.functional import functional_state, swap_state

    model.bfloat16()
    train, frozen, buffers = functional_state(model)
    state = {**train, **frozen, **buffers}

    def fn(st, *batch):
        wrapped = [pt.Tensor(b.astype(jnp.bfloat16)
                             if jnp.issubdtype(b.dtype, jnp.floating)
                             else b) for b in batch]
        # no_grad: jax.value_and_grad differentiates (see bench_layer)
        with no_grad(), swap_state(model, st, collect_buffers=False):
            out = loss(*wrapped)
        return out.data.astype(jnp.float32)
    return fn, state


def _suite_ernie():
    import paddle_tpu as pt
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining

    pt.seed(0)
    cfg = ErnieConfig(hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    model = ErnieForPretraining(cfg)
    B, S = 16, 512
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S))
    mlm = rng.randint(0, cfg.vocab_size, (B, S))
    sop = rng.randint(0, 2, (B,))

    def loss(ids_t, mlm_t, sop_t):
        return model(ids_t, masked_lm_labels=mlm_t, sop_labels=sop_t)[-1]

    fn, state = _functional(model, loss)
    return fn, state, (ids, mlm, sop.astype(np.int64)), B * S


def _suite_moe_lm():
    import paddle_tpu as pt
    from paddle_tpu.models.moe import MoeConfig, MoeForCausalLM

    pt.seed(0)
    cfg = MoeConfig(vocab_size=32000, hidden_size=1024,
                    intermediate_size=2816, moe_intermediate_size=704,
                    num_hidden_layers=6, num_attention_heads=8,
                    num_key_value_heads=8, num_experts=16,
                    num_experts_per_tok=4)
    model = MoeForCausalLM(cfg)
    B, S = 4, 1024
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (B, S))

    def loss(ids_t):
        out = model(ids_t, labels=ids_t)
        return out[1] if isinstance(out, tuple) else out

    fn, state = _functional(model, loss)
    return fn, state, (ids,), B * S


def _suite_dit():
    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    from paddle_tpu.models.dit import DiT, DiTConfig

    pt.seed(0)
    cfg = DiTConfig(depth=8)  # DiT-XL/2 width (1152/16 heads), depth/3.5
    model = DiT(cfg)
    B = 64
    rng = np.random.RandomState(0)
    x = rng.randn(B, cfg.in_channels, cfg.input_size,
                  cfg.input_size).astype(np.float32)
    t = rng.randint(0, 1000, (B,)).astype(np.int64)
    y = rng.randint(0, cfg.num_classes, (B,)).astype(np.int64)
    target = rng.randn(B, cfg.in_channels * 2, cfg.input_size,
                       cfg.input_size).astype(np.float32)
    mse = nn.MSELoss()

    def loss(x_t, t_t, y_t, tgt):
        return mse(model(x_t, t_t, y_t), tgt)

    fn, state = _functional(model, loss)
    return fn, state, (x, t, y, target), B


def _suite_ppocr():
    import paddle_tpu as pt
    from paddle_tpu.models.ppocr import PPOCRRecConfig, PPOCRRecModel

    pt.seed(0)
    cfg = PPOCRRecConfig()
    model = PPOCRRecModel(cfg)
    B, W = 64, 320
    rng = np.random.RandomState(0)
    imgs = rng.randn(B, 3, cfg.img_height, W).astype(np.float32)
    labels = rng.randint(1, cfg.num_classes, (B, 16)).astype(np.int64)
    lens = np.full((B,), 16, np.int64)

    def loss(im, lab, ln):
        return model.loss(model(im), lab, ln)

    fn, state = _functional(model, loss)
    return fn, state, (imgs, labels, lens), B


def _suite_llama70b_layer():
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    # one decoder layer at exact 70B geometry (full model: 140GB of bf16
    # weights alone — cannot fit a 16GB chip)
    cfg = LlamaConfig(vocab_size=512, hidden_size=8192,
                      intermediate_size=28672, num_hidden_layers=1,
                      num_attention_heads=64, num_key_value_heads=8,
                      max_position_embeddings=4096,
                      tie_word_embeddings=True)
    model = LlamaForCausalLM(cfg)
    B, S = 1, 2048
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (B, S))

    def loss(ids_t):
        out = model(ids_t, labels=ids_t)
        return out[1] if isinstance(out, tuple) else out

    fn, state = _functional(model, loss)
    return fn, state, (ids,), B * S


_SUITE = {
    "ernie_base_pretrain": (_suite_ernie, "tokens/sec"),
    "moe_lm_deepseek_style": (_suite_moe_lm, "tokens/sec"),
    "dit_xl_width_d8": (_suite_dit, "images/sec"),
    "ppocr_v4_rec_conv": (_suite_ppocr, "images/sec"),
    "llama3_70b_geometry_layer": (_suite_llama70b_layer, "tokens/sec"),
}


def bench_suite():
    import jax

    dev = jax.devices()[0]
    peak = peak_flops(dev)
    results = {}
    for name, (builder, unit) in _SUITE.items():
        r = _measure_pure(lambda b=builder: b())
        fps = r.pop("_flops_per_sec")
        r["throughput_unit"] = unit
        r["mfu_pct"] = round(fps / peak * 100, 2) if fps and peak else None
        results[name] = r
        print(json.dumps({name: r}), file=sys.stderr, flush=True)
        gc.collect()
    return results

if __name__ == "__main__":
    main()
