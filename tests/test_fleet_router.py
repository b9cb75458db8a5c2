"""Serving fleet: cache-aware multi-replica router (ISSUE 17).

Pins the four fleet contracts end to end on CPU:

- **Placement** — sketch-affinity routing lands shared-prefix traffic
  on the replica already holding the blocks; least-loaded is the
  fallback and the ``PADDLE_TPU_ROUTER_*`` knobs gate both.
- **Chaos/failover** — a replica stub-killed mid-stream fails over to
  a survivor with the greedy stream token-identical to the eager
  oracle, no streamed token duplicated, and the re-admission's
  tail-only recompute pinned via the request ledger's
  ``cached_tokens`` / ``prefilled_tokens`` fields.
- **Disaggregation** — long prompts prefill on a ``prefill``-role
  replica, the KV blocks host-stage into a ``decode`` replica, and the
  decoded stream still matches eager greedy exactly.
- **Front-end** — ``RouterServer``'s /generate traceparent echo,
  /fleetz, /statusz fleet section, and the fleet-saturated 503 shed
  path (Retry-After + traceparent echo +
  ``serving_rejections_total{reason="fleet_saturated"}``), plus one
  ``trace merge --requests`` chain spanning router, prefill replica,
  and decode replica.
"""
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import requests as obs_requests
from paddle_tpu.observability import trace
from paddle_tpu.serving import (FleetRouter, Replica, RouterServer,
                                ServingEngine)
from paddle_tpu.serving.engine import serving_metrics
from paddle_tpu.serving.fleet import build_fleet, router_metrics

ENG_KW = dict(max_batch=4, max_blocks=32, block_size=4, prefill_chunk=8)


def _tiny(seed=0):
    pt.seed(seed)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=True))
    m.eval()
    return m


def _eager(model, prompt, n, eos=None):
    out = model.generate(pt.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=n, temperature=0.0,
                         eos_token_id=eos).numpy()[0]
    return [int(t) for t in out[len(prompt):]]


def _mk_replica(name, role="mixed", seed=0):
    return Replica(ServingEngine(_tiny(seed), **ENG_KW), name, role=role)


@pytest.fixture(scope="module")
def oracle():
    return _tiny(0)


@pytest.fixture(scope="module")
def fleet2(oracle):
    """Two mixed replicas behind one router (shared by the
    non-destructive placement tests)."""
    reps = [_mk_replica(f"r{i}") for i in range(2)]
    router = FleetRouter(reps, prefill_threshold=64)
    router.start()
    yield router, reps
    router.shutdown(drain=True)
    for r in reps:
        if r.alive:
            r.engine.cache.assert_no_leaks()


class TestPlacement:
    def test_basic_parity_and_stats(self, fleet2, oracle):
        router, reps = fleet2
        rng = np.random.RandomState(0)
        prompt = [int(t) for t in rng.randint(1, 128, 9)]
        res = router.submit(prompt, max_new_tokens=6).result(timeout=120)
        assert res["token_ids"] == _eager(oracle, prompt, 6)
        assert res["failovers"] == 0
        s = router.stats()
        assert s["replicas"] == 2 and s["replicas_live"] == 2
        assert s["routing"]["least_loaded"] + s["routing"]["affinity"] >= 1
        fz = router.fleetz()
        assert [p["name"] for p in fz["per_replica"]] == ["r0", "r1"]

    def test_affinity_routes_to_warmed_replica(self, fleet2, oracle):
        router, reps = fleet2
        rng = np.random.RandomState(1)
        shared = [int(t) for t in rng.randint(1, 128, 12)]
        # warm r1's prefix cache out-of-band, then route a request that
        # extends the same prefix: the sketch match must pin it to r1
        reps[1].engine.submit(shared, max_new_tokens=2).result(timeout=120)
        reps[1].engine.drain(timeout=120)
        before = router.decisions["affinity"]
        h = router.submit(shared + [5, 6], max_new_tokens=4)
        res = h.result(timeout=120)
        assert res["token_ids"] == _eager(oracle, shared + [5, 6], 4)
        assert router.decisions["affinity"] == before + 1
        assert h._attempt_replica.name == "r1"

    def test_affinity_off_falls_back_least_loaded(self):
        reps = [_mk_replica("a0"), _mk_replica("a1")]
        router = FleetRouter(reps, affinity=False, disagg=False)
        router.start()
        try:
            rng = np.random.RandomState(2)
            prompt = [int(t) for t in rng.randint(1, 128, 8)]
            router.submit(prompt, max_new_tokens=3).result(timeout=120)
            assert router.decisions["affinity"] == 0
            assert router.decisions["least_loaded"] == 1
        finally:
            router.shutdown(drain=True)

    def test_env_knobs_gate_policies(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ROUTER_AFFINITY", "0")
        monkeypatch.setenv("PADDLE_TPU_ROUTER_DISAGG", "0")
        monkeypatch.setenv("PADDLE_TPU_ROUTER_PREFILL_THRESHOLD", "32")
        router = FleetRouter([_mk_replica("k0")])
        assert router.affinity is False
        assert router.disagg is False
        assert router.prefill_threshold == 32

    def test_build_fleet_env_replica_count(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLEET_REPLICAS", "3")
        reps = build_fleet(_tiny, roles=["prefill"], **ENG_KW)
        assert [r.role for r in reps] == ["prefill", "mixed", "mixed"]
        assert len({r.name for r in reps}) == 3
        for r in reps:
            r.kill()


class TestChaosFailover:
    def test_mid_stream_kill_failover_greedy_identical(self, oracle):
        """Stub-kill the replica serving a stream after >=3 tokens: the
        survivor must complete it token-identically (no duplicates),
        recomputing only the tail of the re-admitted prompt."""
        led = obs_requests.maybe_arm()
        assert led is not None
        old_rate = led.sample_rate
        led.sample_rate = 1.0  # keep every record: the pin reads the ring
        reps = [_mk_replica("c0"), _mk_replica("c1")]
        router = FleetRouter(reps, prefill_threshold=64)
        router.start()
        try:
            rng = np.random.RandomState(7)
            # compile both replicas' steps up front so the kill window
            # below is not racing a cold jit compile
            for r in reps:
                r.engine.submit([int(t) for t in rng.randint(1, 128, 5)],
                                max_new_tokens=2).result(timeout=120)
                r.engine.drain(timeout=120)
            prompt = [int(t) for t in rng.randint(1, 128, 7)]
            got, seen3, killed = [], threading.Event(), threading.Event()

            def on_tok(_h, t):
                got.append(t)
                if len(got) >= 3:
                    seen3.set()
                    if not killed.is_set():
                        # stall the victim's decode loop (the callback
                        # runs inside it) so the stream cannot finish
                        # before the plug is pulled
                        killed.wait(0.15)

            tid = "f1ee7000" * 4
            h = router.submit(prompt, max_new_tokens=16, on_token=on_tok,
                              trace_id=tid)
            assert seen3.wait(60)
            victim = h._attempt_replica
            survivor = reps[1] if victim is reps[0] else reps[0]
            # warm the survivor with the original prompt so the
            # re-admission is a prefix-cache hit, then pull the plug
            survivor.engine.submit(
                prompt, max_new_tokens=2).result(timeout=120)
            survivor.engine.drain(timeout=120)
            victim.kill()
            killed.set()
            res = h.result(timeout=120)
            exp = _eager(oracle, prompt, 16)
            assert res["token_ids"] == exp
            assert got == exp  # streamed exactly once, in order
            assert res["failovers"] == 1
            assert router.decisions["failover"] == 1
            assert router.stats()["replicas_dead"] == 1
            # tail-only recompute: the survivor attempt's ledger record
            # reused the prompt's full blocks and cold-prefilled only
            # the tail of (prompt + already-streamed tokens)
            recs = [d for d in led.exemplars()
                    if d["trace_id"] == tid and d["error"] is None]
            assert recs, "survivor attempt record not kept"
            rec = recs[-1]
            assert rec["cached_tokens"] >= ENG_KW["block_size"]
            assert rec["prefilled_tokens"] < rec["prompt_len"]
            assert rec["cached_tokens"] + rec["prefilled_tokens"] \
                == rec["prompt_len"]
        finally:
            led.sample_rate = old_rate
            router.shutdown(drain=True)


class TestDisaggregation:
    def test_prefill_decode_handoff_parity(self, oracle):
        pre = _mk_replica("pre0", role="prefill")
        dec = _mk_replica("dec0", role="decode")
        router = FleetRouter([pre, dec], prefill_threshold=12)
        router.start()
        try:
            m = router_metrics()
            blocks_before = m["kv_handoff_blocks"].value()
            rng = np.random.RandomState(3)
            prompt = [int(t) for t in rng.randint(1, 128, 17)]
            res = router.submit(prompt, max_new_tokens=6).result(
                timeout=120)
            assert res["token_ids"] == _eager(oracle, prompt, 6)
            assert router.decisions["disagg_prefill"] == 1
            # the decode replica admitted the imported blocks as a
            # prefix-cache hit: 17 tokens / block 4 -> 4 staged blocks
            ds = dec.engine.stats()["prefix_cache"]
            assert ds["hits"] >= 1 and ds["entries"] >= 4
            assert m["kv_handoff_blocks"].value() - blocks_before >= 4
            # short prompts skip the prefill hop entirely
            router.submit([int(t) for t in rng.randint(1, 128, 6)],
                          max_new_tokens=3).result(timeout=120)
            assert router.decisions["disagg_prefill"] == 1
        finally:
            router.shutdown(drain=True)
        pre.engine.cache.assert_no_leaks()
        dec.engine.cache.assert_no_leaks()


class TestRouterServer:
    def test_endpoints_shed_and_trace_chain(self, oracle, tmp_path):
        trace.enable(str(tmp_path))
        pre = _mk_replica("pre0", role="prefill")
        dec = _mk_replica("dec0", role="decode")
        mix = _mk_replica("mix0", role="mixed")
        router = FleetRouter([pre, dec, mix], prefill_threshold=12)
        srv = RouterServer(router, max_queue_depth=4).start()
        tid = "ab" * 16
        try:
            rng = np.random.RandomState(5)
            prompt = [int(t) for t in rng.randint(1, 128, 17)]
            body = json.dumps({"prompt_ids": prompt,
                               "max_new_tokens": 5}).encode()
            req = urllib.request.Request(
                f"{srv.url}/generate", data=body,
                headers={"Content-Type": "application/json",
                         "traceparent":
                         f"00-{tid}-b7ad6b7169203331-01"})
            r = urllib.request.urlopen(req, timeout=120)
            res = json.loads(r.read())
            assert res["token_ids"] == _eager(oracle, prompt, 5)
            assert res["trace_id"] == tid
            assert tid in r.headers.get("traceparent", "")

            fz = json.loads(urllib.request.urlopen(
                f"{srv.url}/fleetz", timeout=30).read())
            assert fz["replicas"] == 3 and len(fz["per_replica"]) == 3
            assert fz["routing"]["disagg_prefill"] >= 1

            sz = json.loads(urllib.request.urlopen(
                f"{srv.url}/statusz?format=json", timeout=30).read())
            assert "fleet" in sz
            html = urllib.request.urlopen(
                f"{srv.url}/statusz", timeout=30).read().lower()
            assert b"<table" in html or b"<html" in html

            # fleet-saturated shed: depth 0 saturates every replica
            srv.max_queue_depth = 0
            rej = serving_metrics()["rejections"]
            before = rej.value(reason="fleet_saturated")
            req503 = urllib.request.Request(
                f"{srv.url}/generate", data=body,
                headers={"Content-Type": "application/json",
                         "traceparent":
                         f"00-{'cd' * 16}-b7ad6b7169203331-01"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req503, timeout=30)
            e = ei.value
            assert e.code == 503
            assert e.headers.get("Retry-After")
            assert "cd" * 16 in e.headers.get("traceparent", "")
            assert "fleet" in json.loads(e.read())["error"]
            assert rej.value(reason="fleet_saturated") == before + 1
            srv.max_queue_depth = 4
        finally:
            srv.close(drain=True)
            trace.disable()

        # one merge --requests chain spans router + prefill replica +
        # decode replica: router_route/router_handoff plus the
        # replicas' own serving spans, all on the request's trace id
        summary = trace.merge(str(tmp_path), requests=True)
        rollup = summary.get("requests_rollup") or summary.get("requests")
        chain = rollup["requests"].get(tid)
        assert chain is not None and chain["spans"] >= 4
        import os
        with open(os.path.join(str(tmp_path), "merged_trace.json")) as f:
            ev = json.load(f)
        names = {e.get("name") for e in ev.get("traceEvents", ev)
                 if isinstance(e, dict)
                 and (e.get("args") or {}).get("trace") == tid}
        assert "router_route" in names and "router_handoff" in names
