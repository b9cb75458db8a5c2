"""The room for another model is real: a copy of the benchmark's tree takes
a second model as new files and new entries only (a model module, a
published file, a configuration at other widths whose vocabulary is a chip's
share, a cell on the ``chat`` mix with its limits), passes the manifest's
rules, and runs that cell through ``harness.run_cell`` with no hook set,
compared with the second module's own reference.

The second module wraps the Llama builder and the Mistral-shaped reference
under another name, at other widths and *with other weights* (it shifts the
seed on both sides): what is proved is the finding, not an architecture. A
harness that took either side from ``models/mistral.py`` would compare an
engine with a reference of other weights, and the cell would read false.

The copy also takes a further configuration of the model the benchmark has
(another depth, with a cell of its own), and the copy's own ``test_benchmark_manifest.py`` then runs inside it: a test there
that pins what the benchmark holds today (the list of models, the number of
configurations) fails here, and not first in the PR that adds a model and
may not edit it.
"""
import copy
import filecmp
import json
import os
import subprocess
import sys

import manifest_rules as rules
from benchmark import harness

ROOT = harness.ROOT
CELL = "second-chat"

MODEL_PY = '''"""Model ``second``: the test's stand-in for another model."""
from benchmark.kernels.model import (  # noqa: F401
    forward_flops_per_token, matmul_params)
from benchmark.reference import mistral as _reference
from benchmark import sut as _sut

SHIFT = 7            # other weights than ``mistral`` makes from the same seed
CALLS = []


def build_engine(cfg, seed, overrides=None):
    CALLS.append("build_engine")
    return _sut.build_engine(cfg, seed + SHIFT, overrides)


def serve_logits(seed, cfg, tokens, rows, cols, mode="exact"):
    CALLS.append("serve_logits")
    return _reference.serve_logits(seed + SHIFT, cfg, tokens, rows, cols,
                                   mode=mode)
'''

SOURCE = "https://example.org/second/config.json"
PUBLISHED = {"source": SOURCE, "assumed": ["a test's model: nobody publishes it"],
             "config": dict(hidden_size=96, intermediate_size=160,
                            num_hidden_layers=6, num_attention_heads=6,
                            num_key_value_heads=2, head_dim=16,
                            vocab_size=1024, max_position_embeddings=512,
                            rope_theta=10000.0, rms_norm_eps=1e-6,
                            tie_word_embeddings=False)}
CONFIG = dict(PUBLISHED["config"], source=SOURCE, model="second",
              published_file="benchmark/published/second.json",
              num_hidden_layers=2, vocab_size=256, dtype="float32",
              published={"num_hidden_layers": 6, "vocab_size": 1024},
              deployment={"chips_per_layer": 4,
                          "how": "vocabulary 4-way, attention replicated"},
              engine=dict(max_batch=4, max_blocks=64, block_size=8,
                          prefill_chunk=12, max_blocks_per_seq=32))
#: set as a cell's are, from this tiny cell's own readings on the CPU: sound
#: runs read 0 to 0.001 (float32 on both sides), the first model's reference
#: against the second's engine 1.2 and 0.59 (the child reads both)
LIMITS = {"served_gap_max": 0.004, "served_gap_mean": 0.001}

#: a further configuration of the first model, with a cell of its own, under
#: names that no PR will want (one chip: the copy has to stay inside the
#: four-chip share whatever the benchmark holds by then; it adds two cells to
#: whatever there are, so it needs two of the 24 free)
DEEPER, DEEPER_CELL = "mistral-7b-v0.3-copytest-l4", "copytest-train-l4"

CHILD = '''
import json, sys
import benchmark_tiny as tiny
from benchmark import harness
from benchmark.models import mistral, second

assert harness.ROOT == sys.argv[1], (harness.ROOT, sys.argv[1])
manifest = harness.load_manifest()
wl, cfg, mix, limits = harness.load_cell(manifest, "second-chat")
assert harness.model_of(cfg) is second and mix["kind"] == "open_loop"


def run(**hooks):
    ctx = tiny.context("second-chat", tiny.chat_mix(), cfg=cfg, hooks=hooks)
    return tiny.result("second-chat", harness.run_cell(ctx), limits)


sound = run()                         # no hook set: everything is found
calls = list(second.CALLS)
crossed = run(serve_logits=mistral.serve_logits)
print(json.dumps({"sound": sound, "calls": calls, "crossed": crossed}))
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "x") as f:             # "x": a new file, never an edit
        f.write(text)


def _grow(root):
    """Add the second model to the tree at ``root``; returns the manifest."""
    bench = os.path.join(root, "benchmark")
    _write(os.path.join(bench, "models", "second.py"), MODEL_PY)
    _write(os.path.join(bench, "published", "second.json"),
           json.dumps(PUBLISHED))
    _write(os.path.join(bench, "configs", "second-l2.json"),
           json.dumps(CONFIG))
    _write(os.path.join(bench, "limits", CELL + ".json"), json.dumps(LIMITS))
    deeper = harness.load_json(bench, "configs",
                               "mistral-7b-v0.3-train-l2.json")
    deeper["num_hidden_layers"] = 4
    _write(os.path.join(bench, "configs", DEEPER + ".json"),
           json.dumps(deeper))
    _write(os.path.join(bench, "limits", DEEPER_CELL + ".json"), json.dumps(
        harness.load_json(bench, "limits", "train-4k.json")))
    manifest = harness.load_json(root, "BENCHMARK.json")
    manifest["configs"].append({
        "name": "second-l2", "source": SOURCE,
        "file": "benchmark/configs/second-l2.json",
        "reduced": ["num_hidden_layers", "vocab_size"],
        "why": "2 of 6 layers and a quarter of the vocabulary: one of 4 chips"})
    manifest["workloads"].append({
        "name": CELL, "config": "second-l2", "traffic": "chat", "chips": 1,
        "why": "the chat mix on a second model"})
    manifest["configs"].append({
        "name": DEEPER, "source": deeper["source"],
        "file": f"benchmark/configs/{DEEPER}.json",
        "reduced": ["num_hidden_layers"], "why": "4 of 32 layers"})
    manifest["workloads"].append({
        "name": DEEPER_CELL, "config": DEEPER, "traffic": "train-4k",
        "chips": 1, "why": "the training job on a further configuration"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for old, new in (("serve-chat", CELL), ("train-4k", DEEPER_CELL)):
            if old in m.get("workloads", []):
                m["workloads"].append(new)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return manifest


def _only_added_to(old, new):
    """``new`` is ``old`` with entries appended and cells added to lists."""
    if isinstance(old, dict):
        return set(old) == set(new) and all(
            _only_added_to(old[k], new[k]) for k in old)
    if isinstance(old, list):
        return len(new) >= len(old) and all(
            _only_added_to(a, b) for a, b in zip(old, new))
    return old == new


def _copy_of_the_tree(tmp_path):
    return rules.copy_of_the_tree(harness.load_manifest(),
                                  tmp_path / "checkout")


def _changed_files(a, b):
    """Files of ``a`` that ``b`` lacks or holds with other bytes."""
    out = []
    for base, dirs, files in os.walk(a):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".pyc"):
                continue
            mine = os.path.join(base, name)
            theirs = os.path.join(b, os.path.relpath(mine, a))
            if not (os.path.exists(theirs)
                    and filecmp.cmp(mine, theirs, shallow=False)):
                out.append(os.path.relpath(mine, a))
    return out


def _child_env(root):
    """The copy's ``benchmark`` (and its tiny presets) come before the
    checkout, which only lends ``paddle_tpu``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [root, os.path.join(root, "tests", "benchmark"), ROOT]))
    for key in ("XLA_FLAGS", "PYTEST_XDIST_WORKER", "PYTEST_XDIST_WORKER_COUNT",
                "PYTEST_ADDOPTS", "PYTEST_CURRENT_TEST"):
        env.pop(key, None)
    return env


def test_a_second_model_is_new_files_and_entries_only(tmp_path):
    root = _copy_of_the_tree(tmp_path)
    before = harness.load_json(root, "BENCHMARK.json")
    had = harness.model_names(os.path.join(root, "benchmark"))
    manifest = _grow(root)

    # nothing that was there was edited: every file of the checkout's paths
    # is in the copy byte for byte, and the manifest only gained entries
    for d in before["paths"]:
        assert _changed_files(os.path.join(ROOT, d),
                              os.path.join(root, d)) == []
    assert _only_added_to(before, manifest) and manifest != before
    rules.check_all(manifest, root)
    assert {CELL, DEEPER_CELL} <= set(rules.cells(manifest))
    assert sorted(had + ["second"]) == harness.model_names(
        os.path.join(root, "benchmark"))
    _, cfg, mix, limits = harness.load_cell(manifest, CELL, root)
    assert cfg["model"] == "second" and limits == LIMITS

    # the flow, in a child of its own
    p = subprocess.run([sys.executable, "-c", CHILD, root], cwd=root,
                       env=_child_env(root), capture_output=True, text=True,
                       timeout=420)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["calls"] == ["build_engine", "serve_logits"]
    sound, crossed = got["sound"], got["crossed"]
    assert sound["correct"] is True, sound["compared"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert set(sound["compared"]) == set(LIMITS)
    # the second engine against the first model's reference: not correct
    assert crossed["correct"] is False
    assert crossed["compared"]["served_gap_max"]["value"] > \
        10 * LIMITS["served_gap_max"]


def test_the_grown_copy_passes_its_own_manifest_test(tmp_path):
    """No test of the manifest pins what the benchmark holds today: with a
    model, two configurations and two cells more, the copy's own file
    passes, its new parametrised cases with it."""
    root = _copy_of_the_tree(tmp_path)
    _grow(root)
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         os.path.join("tests", "benchmark", "test_benchmark_manifest.py")],
        cwd=root, env=_child_env(root), capture_output=True, text=True,
        timeout=420)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    # and it was the copy's manifest that it read: the new entries' cases ran
    for case in (f"test_config_files[{DEEPER}] PASSED",
                 "test_config_files[second-l2] PASSED",
                 f"test_cell_files_exist_and_report[{DEEPER_CELL}] PASSED",
                 f"test_cell_files_exist_and_report[{CELL}] PASSED"):
        assert case in p.stdout, case


def test_the_copy_checks_see_an_edit_and_a_removed_entry(tmp_path):
    root = _copy_of_the_tree(tmp_path)
    with open(os.path.join(root, "benchmark", "peaks.json"), "a") as f:
        f.write("\n")
    assert _changed_files(os.path.join(ROOT, "benchmark"),
                          os.path.join(root, "benchmark")) == ["peaks.json"]
    m = harness.load_manifest()
    less = copy.deepcopy(m)
    less["end_to_end"][0]["bound"] = 0.02
    assert not _only_added_to(m, less)
    less = copy.deepcopy(m)
    less["per_layer"].pop()
    assert not _only_added_to(m, less)
