"""Tiny presets for the benchmark's tests: the same files, code and run
flow as a cell, at sizes a CPU test holds. They reach the harness through
Python (``harness.Context``), never through a switch of the command."""
import copy
import time

from benchmark import harness

CFG = dict(model="mistral", hidden_size=64, intermediate_size=128,
           num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           vocab_size=256, rope_theta=1e6, rms_norm_eps=1e-5,
           max_position_embeddings=512, dtype="float32",
           engine=dict(max_batch=4, max_blocks=64, block_size=8,
                       prefill_chunk=12, max_blocks_per_seq=32))
PEAKS = harness.load_json(harness.HERE, "peaks.json")["devices"]["TPU v5 lite"]


def train_mix():
    mix = harness.load_json(harness.HERE, "traffic", "train-4k.json")
    mix.update(batch=2, seq_len=64, batch_pool=4, trace_start_s=0.1,
               trace_seconds=0.5)
    return mix


def chat_mix():
    mix = harness.load_json(harness.HERE, "traffic", "chat.json")
    mix.update(
        sessions_per_s=6.0, drain_s=60, warmup_prompt=20, check_pad_to=512,
        check_positions=160, trace_start_s=0.2, trace_seconds=0.5,
        prompt_total={"dist": "lognormal", "median": 40, "sigma": 0.8,
                      "min": 8, "max": 150},
        prefix={"pool": 4, "share": 0.3,
                "tokens": {"dist": "fixed", "value": 16}},
        suffix={"dist": "fixed", "value": 4, "min": 4},
        answer={"dist": "lognormal", "median": 8, "sigma": 0.7, "min": 2,
                "max": 24})
    return mix


def flood_mix():
    mix = harness.load_json(harness.HERE, "traffic", "flood.json")
    mix.update(
        sessions_per_s=3.0, warmup_prompt=20, check_pad_to=512,
        check_positions=64, trace_start_s=0.2, trace_seconds=0.5,
        ask_gap_s={"dist": "uniform", "min": 0.1, "max": 0.3},
        prefix={"pool": 0, "share": 1.0,
                "tokens": {"dist": "lognormal", "median": 60, "sigma": 0.5,
                           "min": 20, "max": 140}},
        suffix={"dist": "uniform", "min": 4, "max": 12},
        answer={"dist": "uniform", "min": 2, "max": 8})
    return mix


def context(cell, mix, seed=2 ** 31 + 5, seconds=1.5, cfg=None, **kw):
    return harness.Context(
        cell=cell, cfg=copy.deepcopy(cfg or CFG), mix=mix, seed=seed,
        seconds=seconds, traced=False, peaks=PEAKS,
        t_process_start=time.perf_counter(), trace_dir="", **kw)


def result(cell, outcome, limits, traced=False):
    return harness.result_line(
        harness.load_manifest(), cell, outcome,
        {"platform": "cpu", "kind": "cpu", "count": 1}, limits, traced)
