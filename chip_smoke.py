#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives both hot loops once through the entry points a user
calls, at the full width of the repo's one-chip Llama configuration (hidden
2048, 16 query / 4 KV heads of 128, FFN 7168, vocab 128,256, tied head,
bf16; depth 8), with random weights made from a seed:

1. trainer leg  — ``jit.TrainStep`` + ``AdamW(multi_precision=True)`` +
   global-norm clip as ``bench.py`` builds them, batch 4 x 2048, five
   steps on one fixed batch;
2. kernel check — the Ragged-Paged-Attention Pallas kernel against the
   XLA gather reader on the same bf16 pools, at the server leg's geometry;
3. server leg   — ``ServingEngine`` behind ``serving.Server`` on 127.0.0.1,
   six concurrent ``POST /generate`` requests (64..1,500 prompt tokens,
   half streamed, two sharing a 256-token prefix);
4. drafted leg  — a small drafting model (``models/exaone_moe.py`` at head
   and page widths the kernels take on the chip) served with
   ``draft_tokens=1`` and without: the token streams must be equal;
5. four chips   — when jax reports >= 4 devices: the trainer on a
   dp2 x mp2 mesh and the server on an mp4 mesh (otherwise stated as not
   run).

It refuses to run unless the backend is ``tpu``, fails on the first failed
check (no leg is wrapped so that the next one runs), and prints as the last
line of stdout ``{"ok": true, "device": {...}}``. Times printed here are
smoke observations, not benchmark numbers. ``tests/test_chip_smoke.py``
runs the leg functions at ``LlamaConfig.tiny`` size on the CPU.
"""
from __future__ import annotations

import gc
import json
import os
import re
import sys
import threading
import time
import urllib.request

import numpy as np

#: the one-chip Llama configuration (bench.py's, the only one with chip
#: history), full width, depth 8
MODEL = dict(vocab_size=128256, hidden_size=2048, intermediate_size=7168,
             num_hidden_layers=8, num_attention_heads=16,
             num_key_value_heads=4, max_position_embeddings=4096,
             tie_word_embeddings=True)
BATCH, SEQ = 4, 2048
ENGINE = dict(max_batch=8, max_blocks=512, block_size=16, prefill_chunk=128)
#: 64..1,500 prompt tokens: several prefill chunks, many pages, prefill
#: and decode rows in one step; the two marked True share PREFIX_LEN tokens
PROMPTS = [(64, False), (200, False), (512, True), (700, True),
           (1100, False), (1500, False)]
PREFIX_LEN = 256
NEW_TOKENS = 32
#: RPA-vs-gather output tolerance on bf16 pools. Both readers accumulate in
#: f32 and round twice to bf16 (the probabilities, then the output): 2^-8
#: relative each. On N(0,1) values the outputs reach |o| ~ 4, where one
#: bf16 ulp is 2^-6 = 0.0156; two ulps is what two independent roundings
#: can differ by.
KERNEL_ATOL = 2 ** -5
#: first-step loss, four chips vs one: the step's activations are bf16 and
#: mp=2 splits every row-parallel contraction in two partial sums; one
#: bf16 ulp at the loss's magnitude (8..16) is 2^-4
LOSS_ATOL = 2 ** -4


class SmokeFailure(RuntimeError):
    """A leg's check did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def describe_backend():
    """Print the installation and the device line, refuse anything but a
    TPU, then place the compile cache. Returns the device dict."""
    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu_version}")
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"device_count={dev['count']}")
    if jax.default_backend() != "tpu":
        raise SmokeFailure(
            f"chip_smoke needs a TPU backend, jax found "
            f"platform={dev['platform']!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    from paddle_tpu.device import applied_xla_tuning, use_compile_cache
    print(f"compile_cache_dir={use_compile_cache()}")
    print(f"libtpu tuning flags applied: {len(applied_xla_tuning())}")
    return dev


def _memory(prefix):
    """Print device 0's allocator counters (peak is cumulative for the
    process); returns bytes_in_use per device."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    in_use = [int(s.get("bytes_in_use", 0)) for s in stats]
    print(f"  {prefix}: bytes_in_use={in_use[0]:,} "
          f"peak_bytes_in_use={int(stats[0].get('peak_bytes_in_use', 0)):,}")
    return in_use


def _mosaic_calls(hlo: str):
    return [ln for ln in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


# ------------------------------------------------------------------ trainer --
def trainer_leg(model_kw, batch, seq, steps=5, mesh=None, dtype="bfloat16"):
    """``TrainStep`` on one fixed seeded batch. Under ``mesh`` (dp x mp)
    the model is built tensor-parallel and the one-chip batch is repeated
    once per dp replica, so the first loss is the one-chip leg's.
    Returns the losses, the Mosaic lines of the compiled HLO and the
    bytes in use per device."""
    import jax
    import paddle_tpu as pt
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(**{**model_kw, "tensor_parallel": mesh is not None})
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    if dtype == "bfloat16":
        model.bfloat16()
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))

    def loss_fn(m, x):
        return m(x, labels=x)[1]

    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    if mesh is None:
        step = TrainStep(model, loss_fn, opt)
    else:
        ids = np.concatenate([ids] * mesh.shape["dp"])
        step = TrainStep(model, loss_fn, opt, mesh=mesh, input_spec=P("dp"))
    x = pt.to_tensor(ids)

    t0 = time.perf_counter()
    losses = [float(step(x).numpy())]
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        losses.append(float(step(x).numpy()))   # .numpy() waits for the step
    step_s = (time.perf_counter() - t0) / max(steps - 1, 1)
    print(f"  losses: {' '.join(f'{v:.4f}' for v in losses)}")
    print(f"  compile+first step {compile_s:.1f}s, steady "
          f"{step_s * 1e3:.1f} ms/step over {steps - 1} steps "
          f"(smoke, not benchmark)")

    check(all(np.isfinite(losses)), "every loss finite")
    check(losses[-1] < losses[0],
          f"loss fell: step {steps} {losses[-1]:.4f} < step 1 "
          f"{losses[0]:.4f}")
    executables = list(step._cache.values())
    check(len(executables) == 1 and executables[0]._cache_size() == 1,
          "one compilation in total")
    mosaic = _mosaic_calls(step.compiled_hlo(x))
    print(f"  Mosaic custom calls in the compiled step: {len(mosaic)}")
    if jax.default_backend() == "tpu":
        # per layer: flash forward, dq, dk/dv
        check(len(mosaic) == 3 * cfg.num_hidden_layers,
              f"flash forward + two backward kernels in all "
              f"{cfg.num_hidden_layers} layers")
    return {"losses": losses, "mosaic": mosaic,
            "bytes_in_use": _memory("after trainer leg")}


# ------------------------------------------------------------- kernel check --
def kernel_check(n_heads, n_kv, head_dim, seqs, *, max_batch, max_blocks,
                 block_size, prefill_chunk, max_blocks_per_seq,
                 dtype="bfloat16", window=None):
    """``ragged_paged_attention`` against ``ragged_gather_attention`` on
    the same pools, shaped as ``ServingEngine`` shapes its step. ``seqs``
    is ``[(new_tokens, context_tokens), ...]``. Outputs, not sampled
    tokens, are the oracle. Under a ``window`` (a window layer's group)
    the pages wholly behind a sequence's window are released: null in its
    table, and never walked. Returns the max abs difference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import ragged_gather_attention
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        build_step_maps, default_tile_q, ragged_paged_attention,
        rpa_max_items, rpa_run_pages)

    rng = np.random.RandomState(1)
    tile = default_tile_q(n_heads // n_kv, dtype)
    T = -(-(max_batch + prefill_chunk) // tile) * tile
    run = rpa_run_pages(block_size, head_dim, head_dim,
                        jnp.dtype(dtype).itemsize, window=window)
    max_items = rpa_max_items(T // tile, max_batch, max_blocks_per_seq, run,
                              window=window, tile_q=tile,
                              block_size=block_size)
    bt = np.zeros((max_batch + 1, max_blocks_per_seq), np.int32)
    cu = np.zeros(max_batch + 2, np.int32)
    ctx = np.zeros(max_batch + 1, np.int32)
    sid = np.full(T, max_batch, np.int32)        # sentinel = padding token
    pos = np.zeros(T, np.int32)
    next_block, off, kv_lens = 1, 0, []
    for s, (n, c) in enumerate(seqs):
        pages = -(-(n + c) // block_size)
        if pages > max_blocks_per_seq or next_block + pages - 1 > max_blocks \
                or off + n > T:
            raise ValueError(
                f"kernel-check sequence {s} exceeds the block table, the "
                f"pool or the token budget")
        bt[s, :pages] = np.arange(next_block, next_block + pages)
        next_block += pages
        if window is not None:       # a released-page sequence
            bt[s, :max(0, c - window + 1) // block_size] = 0
        ctx[s] = c
        sid[off:off + n] = s
        pos[off:off + n] = c + np.arange(n)
        off += n
        cu[s + 1] = off
        kv_lens.append(n + c)
    cu[len(seqs) + 1:] = off
    shape = (max_blocks + 1, n_kv, block_size, head_dim)
    k_pool = jnp.asarray(rng.randn(*shape), dtype)
    v_pool = jnp.asarray(rng.randn(*shape), dtype)
    q = jnp.asarray(rng.randn(T, n_heads, head_dim), dtype)
    maps = build_step_maps(cu[:len(seqs) + 1], kv_lens, total_tokens=T,
                           tile_q=tile, block_size=block_size,
                           max_items=max_items, max_seqs=max_batch,
                           run_pages=run, window=window)
    t0 = time.perf_counter()
    rpa = jax.jit(ragged_paged_attention, static_argnames="window")(
        q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(cu),
        jnp.asarray(ctx), jnp.asarray(maps.step_seq),
        jnp.asarray(maps.step_blk), jnp.asarray(maps.step_tile),
        window=window)
    rpa = np.asarray(rpa.astype(jnp.float32))
    print(f"  rpa kernel compile+run {time.perf_counter() - t0:.1f}s "
          f"(tile_q={tile}, tokens={T}, flat work list: {maps.walked} "
          f"grid steps a kv head = {maps.live} live (tile, sequence, "
          f"run of {run} pages) items naming {maps.pages} pages + "
          f"{maps.walked - maps.live} tiles without work, "
          f"in arrays of {max_items}; pages "
          f"{[-(-kv // block_size) for kv in kv_lens]}"
          + (f"; window {window}: {maps.pages} of {maps.pages_causal} "
             f"pages" if window else "") + ")")
    gather = jax.jit(ragged_gather_attention,
                     static_argnames=("scale", "window"))(
        q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(sid),
        jnp.asarray(pos), scale=1.0 / float(np.sqrt(head_dim)),
        window=window)
    gather = np.asarray(gather.astype(jnp.float32))
    live = sid < max_batch
    err = float(np.max(np.abs(rpa[live] - gather[live])))
    check(np.isfinite(rpa).all(), "kernel output finite")
    check(err <= KERNEL_ATOL,
          f"rpa vs gather max |diff| {err:.4f} <= {KERNEL_ATOL:.4f}")
    check(bool(np.all(rpa[~live] == 0.0)), "padding tokens exactly 0")
    return err


# ------------------------------------------------------------------- server --
def _post(url, body, out, i):
    """One ``POST /generate``; stores ``(summary, streamed tokens)``."""
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=900) as resp:
            if body.get("stream"):
                lines = [json.loads(ln) for ln in resp if ln.strip()]
                out[i] = (lines[-1], [ln["token"] for ln in lines[:-1]])
            else:
                out[i] = (json.loads(resp.read()), None)
    except Exception as e:  # noqa: BLE001 — reported by the caller's check
        out[i] = ({"error": repr(e)}, None)


def server_leg(model_kw, engine_kw, prompts, prefix_len, new_tokens,
               mesh=None, dtype="bfloat16"):
    """``ServingEngine`` behind ``serving.Server``: a warm-up request (it
    pays the compile and leaves the shared prefix in the cache), then
    every prompt of ``prompts`` concurrently. The engine picks its own
    attention reader. Returns the engine's final stats."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Server, ServingEngine

    cfg = LlamaConfig(**{**model_kw, "tensor_parallel": mesh is not None})
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if dtype == "bfloat16":
        model.bfloat16()
    engine = ServingEngine(model, mesh=mesh, **engine_kw)
    rng = np.random.RandomState(2)
    prefix = [int(t) for t in rng.randint(1, cfg.vocab_size, prefix_len)]
    bodies = []
    for i, (n, shared) in enumerate(prompts):
        ids = [int(t) for t in rng.randint(1, cfg.vocab_size, n)]
        if shared:
            ids[:prefix_len] = prefix
        bodies.append({"prompt_ids": ids, "max_new_tokens": new_tokens,
                       "stream": i % 2 == 1})
    results = [None] * len(bodies)
    with Server(engine, request_timeout=900.0) as server:
        warm = [None]
        t0 = time.perf_counter()
        _post(server.url, {"prompt_ids": prefix + [1, 2, 3],
                           "max_new_tokens": 4}, warm, 0)
        compile_s = time.perf_counter() - t0
        check(warm[0][0].get("finish_reason") == "length",
              f"warm-up request finished: {warm[0][0]}")
        threads = [threading.Thread(target=_post,
                                    args=(server.url, b, results, i))
                   for i, b in enumerate(bodies)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        wall = time.perf_counter() - t0
        check(not any(t.is_alive() for t in threads),
              "every client thread returned")
        with urllib.request.urlopen(server.url + "/healthz",
                                    timeout=30) as resp:
            health = json.loads(resp.read())
        check(engine._thread is not None and engine._thread.is_alive(),
              "engine thread alive after the requests")
        hlo = engine.compiled_hlo()
    print(f"  warm-up request (compile + {prefix_len + 3}-token prefill + 4 "
          f"tokens) {compile_s:.1f}s")
    print(f"  {len(bodies)} concurrent requests in {wall:.2f}s wall, "
          f"{wall / len(bodies):.2f} s/request (smoke, not benchmark)")
    for (n, shared), body, (res, streamed) in zip(prompts, bodies, results):
        print(f"    prompt {n:5d}{' +prefix' if shared else '        '} "
              f"stream={body['stream']!s:5} -> "
              f"{res.get('finish_reason', res.get('error'))} "
              f"{res.get('num_generated')} tokens, ttft "
              f"{res.get('ttft_ms')} ms, latency {res.get('latency_ms')} ms")
    check(all(res.get("finish_reason") in ("length", "eos")
              and res.get("num_generated") == new_tokens
              for res, _ in results),
          f"all {len(bodies)} requests finished with {new_tokens} tokens")
    check(all(streamed is None or streamed == res["token_ids"]
              for res, streamed in results),
          "streamed tokens equal the final token_ids")
    check(health["attn_impl"] == "rpa" and engine.attn_impl == "rpa",
          "engine chose attn_impl == 'rpa'")
    check(health["step_compiles"] == 1, "step_compiles == 1")
    check(health["kv_blocks_in_use"] == 0, "kv_blocks_in_use == 0")
    hits = health["prefix_cache"]["hits"]
    check(hits > 0, f"prefix-cache hits {hits} > 0")
    mosaic = _mosaic_calls(hlo)
    print(f"  Mosaic custom calls in the compiled serving step: "
          f"{len(mosaic)}")
    if jax.default_backend() == "tpu":
        check(len(mosaic) == cfg.num_hidden_layers,
              "the RPA kernel once per layer")
    _memory("after server leg")
    return engine, health


# --------------------------------------------------------------- four chips --
#: the drafted leg's model: a dense layer and one ``L L G L`` run, 8 query
#: heads a KV head of 128, a window of one 128-token page, 4 of 8 experts
DRAFTED_MODEL = dict(vocab_size=512, hidden_size=512, intermediate_size=1024,
                     num_hidden_layers=5, num_attention_heads=8,
                     num_key_value_heads=1, head_dim=128,
                     moe_intermediate_size=256, num_experts=8,
                     num_experts_per_tok=2, held_experts=(0, 2, 5, 7),
                     max_position_embeddings=2048)
DRAFTED_ENGINE = dict(max_batch=8, max_blocks={"window": 48, "full": 96},
                      block_size=128, prefill_chunk=256)


def drafted_leg(model_kw, engine_kw, prompt_lens, new_tokens, dtype="bfloat16"):
    """Serve the same prompts through an engine that verifies a draft a
    sequence and step (the run loop one step ahead) and through one that
    does not: pass iff the token streams are equal, the step compiled once
    and no page leaked. Returns the drafting engine's ``stats()["drafts"]``."""
    import paddle_tpu as pt
    from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                              ExaoneMoeForCausalLM)
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    kw = dict(model_kw)
    kw.setdefault("sliding_windows",
                  tuple(0 if i % 4 == 3 else engine_kw["block_size"]
                        for i in range(8)))
    model = ExaoneMoeForCausalLM(ExaoneMoeConfig(**kw))
    if dtype == "bfloat16":
        model.bfloat16()
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, kw["vocab_size"], n).tolist()
               for n in prompt_lens]
    streams, drafts = {}, None
    for d in (0, 1):
        engine = ServingEngine(model, draft_tokens=d, **engine_kw)
        engine.start()
        handles = [engine.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        for h in handles:
            check(h.wait(600), f"drafted leg: request {h.req_id} finished")
        engine.shutdown()
        engine.cache.assert_no_leaks()
        check(engine.step_traces == 1,
              f"drafted leg: the step compiled once (draft_tokens={d}: "
              f"{engine.step_traces})")
        streams[d] = [h.token_ids for h in handles]
        drafts = engine.stats().get("drafts", drafts)
        del engine
    check(streams[0] == streams[1],
          "drafted leg: the drafted engine's tokens are the undrafted "
          "engine's")
    check(drafts["drafted"] > 0, "drafted leg: drafts were verified")
    print(f"drafted leg: {len(prompts)} requests x {new_tokens} tokens "
          f"equal with and without drafts; {drafts}")
    return drafts


def multichip_leg(one_chip_first_loss):
    """dp2 x mp2 training and mp4 serving on the first four devices, in
    device order as ``init_mesh`` lays them out."""
    import jax
    from paddle_tpu.distributed.mesh import init_mesh

    devices = jax.devices()[:4]
    print("[four chips: trainer dp2 x mp2]")
    mesh = init_mesh({"dp": 2, "mp": 2}, devices=devices)
    out = trainer_leg(MODEL, BATCH, SEQ, steps=3, mesh=mesh)
    diff = abs(out["losses"][0] - one_chip_first_loss)
    check(diff <= LOSS_ATOL,
          f"first-step loss {out['losses'][0]:.4f} equals the one-chip "
          f"leg's {one_chip_first_loss:.4f} within {LOSS_ATOL}")
    in_use = out["bytes_in_use"][:4]
    print(f"  per-device bytes_in_use: {in_use}")
    check(min(in_use) >= 0.75 * max(in_use),
          "every chip's bytes_in_use within 25% of the others")
    # flash operands [batch * heads, seq, head_dim]: the per-shard KV
    # operand must be there and the global query operand must not
    heads, kv = MODEL["num_attention_heads"], MODEL["num_key_value_heads"]
    hd = MODEL["hidden_size"] // heads
    global_q = f"[{2 * BATCH * heads},{SEQ},{hd}]"
    shard_kv = f"[{BATCH * kv // 2},{SEQ},{hd}]"
    shapes = [set(re.findall(r"\[[\d,]+\]", ln)) for ln in out["mosaic"]]
    check(all(shard_kv in s and global_q not in s for s in shapes),
          f"flash custom calls take per-shard operands ({shard_kv} present, "
          f"global {global_q} absent)")
    del out
    gc.collect()

    print("[four chips: server mp4]")
    mesh = init_mesh({"mp": 4}, devices=devices)
    engine, health = server_leg(MODEL, ENGINE, PROMPTS, PREFIX_LEN,
                                NEW_TOKENS, mesh=mesh)
    check(health["tensor_parallel"] == 4, "tensor_parallel == 4")
    pool = engine.cache.k_pools[0]
    weight = engine._st["model.layers.0.self_attn.q_proj.weight"]
    check(len(pool.sharding.device_set) == 4
          and not pool.sharding.is_fully_replicated,
          "KV pools sharded over all four chips")
    check(len(weight.sharding.device_set) == 4
          and not weight.sharding.is_fully_replicated,
          "projection weights sharded over all four chips")


def run():
    t_start = time.perf_counter()
    dev = describe_backend()

    print("[trainer leg]")
    trained = trainer_leg(MODEL, BATCH, SEQ)
    first_loss = trained["losses"][0]
    del trained
    gc.collect()
    _memory("after release")

    print("[kernel check]")
    hd = MODEL["hidden_size"] // MODEL["num_attention_heads"]
    table = min(ENGINE["max_blocks"],
                MODEL["max_position_embeddings"] // ENGINE["block_size"])
    # one multi-page prefill chunk plus decode rows with 1..200 pages
    kernel_check(MODEL["num_attention_heads"], MODEL["num_key_value_heads"],
                 hd, [(100, 1100), (1, 15), (1, 16), (1, 700), (1, 3199),
                      (1, 1)],
                 max_blocks_per_seq=table, **ENGINE)
    # the MHA geometry: group == 1 takes a taller q tile in bf16
    kernel_check(MODEL["num_attention_heads"], MODEL["num_attention_heads"],
                 hd, [(40, 300), (1, 15), (1, 2000)],
                 max_blocks_per_seq=table, **ENGINE)
    # 7 query heads a KV head under a window of 512: a chunk that straddles
    # the window's edge and decode rows whose early pages were released
    kernel_check(28, 4, 128, [(80, 1100), (1, 15), (1, 600), (1, 3199),
                              (40, 480)],
                 max_blocks_per_seq=table, window=512, **ENGINE)

    print("[server leg]")
    engine, _ = server_leg(MODEL, ENGINE, PROMPTS, PREFIX_LEN, NEW_TOKENS)
    del engine
    gc.collect()
    _memory("after release")

    print("[drafted leg]")
    drafted_leg(DRAFTED_MODEL, DRAFTED_ENGINE, (40, 300, 700, 130), 48)
    gc.collect()
    _memory("after release")

    import jax
    if len(jax.devices()) >= 4:
        multichip_leg(first_loss)
    else:
        print(f"multichip: not run ({len(jax.devices())} devices)")

    print(f"chip_smoke passed in {time.perf_counter() - t_start:.0f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


def main() -> int:
    """Exit code: 0 only when every leg passed. A failed check is reported
    in one line; any other exception keeps its traceback."""
    try:
        run()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
