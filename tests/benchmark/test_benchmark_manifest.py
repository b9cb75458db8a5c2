"""``BENCHMARK.json`` against the contract's form, and the command where
there is no chip."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import harness

ROOT = harness.ROOT
M = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = M["end_to_end"] + M["per_layer"]
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert M["command"][:2] == ["python3", "benchmark/run.py"]
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry and "unit" not in entry or key == "layer" and key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]


@pytest.mark.parametrize("conf", M["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"].startswith("benchmark/configs/")
    cfg = harness.load_json(ROOT, conf["file"])
    assert cfg["source"] == conf["source"]
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "vocab_size")
    assert not set(conf["reduced"]) & set(widths)
    # the published widths of Mistral-7B-v0.3
    assert [cfg[k] for k in widths] == [4096, 14336, 128, 32, 8, 32768]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert any(w["config"] == conf["name"] for w in M["workloads"])


@pytest.mark.parametrize("wl", M["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist_and_report(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert wl["chips"] in (1, 4)
    _, cfg, mix, limits = harness.load_cell(M, wl["name"])
    assert os.path.exists(os.path.join(harness.HERE, "kinds",
                                       mix["kind"] + ".py"))
    assert limits, "every cell has its limits file"
    e2e = [m["name"] for m in harness.metrics_of(M, "end_to_end", wl["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(M, "per_layer", wl["name"])


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_and_moves(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert os.path.exists(os.path.join(harness.HERE, "layer_metrics",
                                       m["name"] + ".py"))
    moved = next(e for e in M["end_to_end"] if e["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"], \
            f"{cell} does not report {m['moves']}"
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("m", M["end_to_end"], ids=lambda m: m["name"])
def test_bounds(m):
    assert set(m) <= {"name", "unit", "better", "source", "bound",
                      "workloads"}
    assert 0.01 <= m["bound"] <= 0.1
    assert m["source"] in ("host_clock", "device_trace")


def test_four_chip_share():
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_peaks_name_their_source():
    table = harness.load_json(harness.HERE, "peaks.json")
    assert "cloud.google.com" in table["source"]
    assert table["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_without_a_chip_fails_and_prints_no_metric():
    p = _run(ROOT)
    assert p.returncode not in (0, None)
    assert "metrics" not in p.stdout and "needs a TPU" in p.stderr


def test_command_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in M["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and "metrics" not in p.stdout
