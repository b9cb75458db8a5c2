"""End-to-end continuous-batching serving demo.

Starts ``serving.Server`` (HTTP front-end + background engine loop) on a
tiny Llama, fires a handful of CONCURRENT ``/generate`` requests with
mixed prompt/output lengths, and prints each request's TTFT and total
latency plus the engine's final stats — note ``step_compiles: 1``:
every request, prefill chunks and decode alike, rode ONE compiled
unified step (the Ragged-Paged-Attention layout, docs/SERVING.md). Run:

    python examples/serve_llama.py
"""
import json
import threading
import urllib.request

import numpy as np

from _common import build_tiny_llama
from paddle_tpu.serving import Server, ServingEngine


def main():
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    model = build_tiny_llama(seed=0, num_hidden_layers=1)
    engine = ServingEngine(model, max_batch=4, max_blocks=32,
                           block_size=4, prefill_chunk=8)
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(1, 256, n)]
               for n in (6, 14, 9)]
    budgets = [6, 8, 4]
    results = [None] * len(prompts)

    with Server(engine) as server:
        print(f"serving on {server.url}")

        def client(i):
            req = urllib.request.Request(
                server.url + "/generate",
                data=json.dumps({"prompt_ids": prompts[i],
                                 "max_new_tokens": budgets[i]}).encode(),
                headers={"Content-Type": "application/json"})
            results[i] = json.loads(
                urllib.request.urlopen(req, timeout=300).read())

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # check completeness BEFORE formatting, so a failed client
        # surfaces as the real error instead of a NoneType print crash
        assert all(r is not None for r in results), results
        for i, res in enumerate(results):
            print(f"req {i}: prompt {len(prompts[i]):>2} tok -> "
                  f"{res['num_generated']:>2} tok | "
                  f"ttft {res['ttft_ms']:8.1f} ms | "
                  f"latency {res['latency_ms']:8.1f} ms")
        health = json.loads(urllib.request.urlopen(
            server.url + "/healthz", timeout=10).read())
        print("engine stats:", {k: health[k] for k in
                                ("step_compiles", "attn_impl", "kv_headroom",
                                 "preemptions", "kv_blocks_in_use")})


if __name__ == "__main__":
    main()
