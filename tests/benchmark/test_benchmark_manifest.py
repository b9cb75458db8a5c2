"""``BENCHMARK.json`` against the contract's form, each configuration
against the published values it names, and the command where there is no
chip. The rules themselves are ``manifest_rules.py``'s, functions of
``(manifest, root)``."""
import copy
import os
import re
import subprocess
import sys

import pytest

import manifest_rules as rules
from benchmark import harness

ROOT = harness.ROOT
M = harness.load_manifest()
METRICS = M["end_to_end"] + M["per_layer"]
CELLS = rules.cells(M)


def test_top_level_keys_and_sizes():
    rules.check_top_level(M, ROOT)


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_units_and_keys(entry):
    rules.check_entry(entry)


@pytest.mark.parametrize("conf", M["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    """Any configuration: every value its source publishes is held as
    published, or is in ``reduced`` as the depth or a chip's share."""
    rules.check_config(M, conf, ROOT)


def test_mistral_keeps_its_published_widths():
    """Nothing is loosened for the model the benchmark has: the literal
    widths and depth of Mistral-7B-v0.3 stand on its published file, every
    configuration that names that file holds them, and the two the benchmark
    has cut the depth alone. (A later configuration of this source, or of
    another, adds to what is looked at here and fails nothing.)"""
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "vocab_size")
    file = "benchmark/published/mistral-7b-v0.3.json"
    pub = rules.published_values(harness.load_json(ROOT, file))
    assert [pub[k] for k in widths] == [4096, 14336, 128, 32, 8, 32768]
    assert pub["num_hidden_layers"] == 32
    held = {}
    for conf in M["configs"]:
        cfg = harness.load_json(ROOT, conf["file"])
        if cfg["published_file"] == file:
            held[conf["name"]] = conf, cfg
            assert cfg["model"] == "mistral"
            assert [cfg[k] for k in widths] == [4096, 14336, 128, 32, 8, 32768]
            assert set(pub) <= set(cfg)            # held to every key of it
    for name in ("mistral-7b-v0.3-train-l2", "mistral-7b-v0.3-serve-l16"):
        conf, cfg = held[name]
        assert conf["reduced"] == ["num_hidden_layers"]
        assert cfg["published"] == {"num_hidden_layers": 32}


# One planted fault each, on a configuration whose vocabulary and experts are
# a chip's share (README's worked example, smaller): the rule has to refuse it.
_PUBLISHED = {"source": "https://example.org/wide-moe/config.json", "config": {
    "hidden_size": 512, "moe_intermediate_size": 128, "kv_lora_rank": 64,
    "num_hidden_layers": 12, "first_k_dense_replace": 3,
    "num_attention_heads": 16, "vocab_size": 65536,
    "n_routed_experts": 64, "num_experts_per_tok": 4},
    "derived": {"head_dim": 32}}
_CONF = {"name": "wide-moe-l5", "source": _PUBLISHED["source"],
         "file": "benchmark/configs/wide-moe-l5.json", "why": "a share",
         "reduced": ["num_hidden_layers", "first_k_dense_replace",
                     "vocab_size", "n_routed_experts"]}
_CFG = dict(_PUBLISHED["config"], source=_PUBLISHED["source"], model="mistral",
            head_dim=32, num_hidden_layers=5, first_k_dense_replace=1,
            vocab_size=8192, n_routed_experts=8,
            published={"num_hidden_layers": 12, "first_k_dense_replace": 3,
                       "vocab_size": 65536, "n_routed_experts": 64},
            deployment={"chips_per_layer": 8,
                        "how": "experts and vocabulary 8-way"})


def _plant(reduced=None, drop=(), **cfg):
    conf, held = copy.deepcopy(_CONF), copy.deepcopy(_CFG)
    conf["reduced"] = [k for k in conf["reduced"] if k not in drop] \
        + (reduced or [])
    for key, value in cfg.items():
        if value is None:
            del held[key]
        else:
            held[key] = value
    for key in reduced or []:
        held["published"][key] = _PUBLISHED["config"][key]
    for key in [k for k in held["published"] if k not in conf["reduced"]]:
        del held["published"][key]
    return conf, held


def _readme_example():
    """``benchmark/README.md``'s worked example, at its own numbers."""
    pub = {"source": _PUBLISHED["source"], "config": {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "num_experts_per_tok": 8,
        "vocab_size": 153600, "moe_intermediate_size": 2048}}
    held = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
            "n_routed_experts": 16, "vocab_size": 19200}
    cfg = dict(pub["config"], **held, source=pub["source"], model="mistral",
               published={k: pub["config"][k] for k in held},
               deployment={"chips_per_layer": 16, "how": "experts 16-way, "
                           "vocabulary 8-way, attention replicated"})
    return dict(_CONF, reduced=list(held)), cfg, pub


@pytest.mark.parametrize("conf,cfg,published", [
    (_CONF, _CFG, _PUBLISHED), _readme_example()], ids=["small", "readme"])
def test_a_share_of_a_deployment_passes(conf, cfg, published):
    rules.check_config_values(conf, cfg, published, ["mistral"])


@pytest.mark.parametrize("planted,says", [
    (_plant(["kv_lora_rank"], kv_lora_rank=32), "is a width"),
    (_plant(["num_experts_per_tok"], num_experts_per_tok=2), "is a width"),
    (_plant(hidden_size=256), "not reduced"),
    (_plant(head_dim=64), "not reduced"),
    (_plant(head_dim=None), "left out"),
    (_plant(vocab_size=8191), "under an eighth"),
    (_plant(n_routed_experts=7), "floor is 8"),
    (_plant(n_routed_experts=12), "no whole share"),
    (_plant(["num_attention_heads"], num_attention_heads=6),
     "no whole share"),
    (_plant(num_hidden_layers=4), "floor is 4 after"),
    (_plant(first_k_dense_replace=2), "floor is 4 after"),
    (_plant(first_k_dense_replace=1, drop=["first_k_dense_replace"]),
     "not reduced"),
    (_plant(deployment=None), "needs its"),
    (_plant(model="no-such-model"), "benchmark/models/ has"),
    (_plant(model=None), "benchmark/models/ has"),
], ids=["latent_rank_reduced", "experts_a_token_reduced", "width_changed",
        "derived_head_size_changed", "derived_head_size_left_out",
        "vocabulary_under_an_eighth", "seven_experts_held",
        "twelve_experts_of_64", "six_heads_of_16", "three_layers_after_dense",
        "two_dense_of_five", "dense_layers_cut_unlisted",
        "share_without_deployment", "model_without_a_module",
        "no_model_named"])
def test_a_planted_configuration_fault_is_refused(planted, says):
    conf, cfg = planted
    with pytest.raises(AssertionError, match=says):
        rules.check_config_values(conf, cfg, _PUBLISHED, ["mistral"])


def test_a_model_is_found_by_the_configurations_name():
    from benchmark.models import mistral
    assert harness.model_of({"model": "mistral"}) is mistral
    assert "mistral" in harness.model_names()
    for cfg in ({"model": "no-such-model"}, {}):
        with pytest.raises(LookupError,
                           match=r"benchmark/models/ has \[.*'mistral'"):
            harness.model_of(cfg)
    for name in ("build_engine", "Trainer", "serve_logits", "train_steps",
                 "forward_flops_per_token", "train_flops_per_token",
                 "matmul_params"):
        assert callable(getattr(mistral, name))


def test_nothing_outside_the_model_module_names_the_model():
    """The kinds, ``control.py`` and ``harness.py`` take builder, reference
    and counts from ``harness.model_of``: none names them itself."""
    named = re.compile(
        r"reference\.mistral|reference import mistral|kernels\.model"
        r"|kernels import model|sut\.build_engine|sut\.Trainer|build_model"
        r"|LlamaForCausalLM")
    here = harness.HERE
    files = [os.path.join(here, "control.py"), os.path.join(here, "harness.py"),
             os.path.join(here, "run.py")]
    files += [os.path.join(here, "kinds", f)
              for f in os.listdir(os.path.join(here, "kinds"))
              if f.endswith(".py")]
    assert len(files) >= 6
    for path in files:
        with open(path) as f:
            hits = named.findall(f.read())
        assert not hits, (path, hits)


def test_a_model_that_serves_only_is_told_so_when_asked_to_train(monkeypatch):
    import types

    import benchmark_tiny as tiny
    serves_only = types.SimpleNamespace(train_steps=None)
    monkeypatch.setattr(harness, "model_of", lambda cfg: serves_only)
    with pytest.raises(ValueError, match="supplies no Trainer"):
        harness.run_cell(tiny.context("train-4k", tiny.train_mix()))


@pytest.mark.parametrize("wl", M["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist_and_report(wl):
    rules.check_cell(M, wl, ROOT)
    _, cfg, mix, limits = harness.load_cell(M, wl["name"])
    assert cfg["model"] in harness.model_names() and mix["kind"] and limits


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_and_moves(m):
    rules.check_per_layer(M, m, ROOT)


@pytest.mark.parametrize("m", M["end_to_end"], ids=lambda m: m["name"])
def test_bounds(m):
    rules.check_bound(m)


def test_four_chip_share():
    rules.check_four_chip_share(M)


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
    p = _run(rules.copy_of_the_tree(M, tmp_path))
    assert p.returncode != 0 and "metrics" not in p.stdout
