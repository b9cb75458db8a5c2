"""The manifest's rules as functions of ``(manifest, root)``: what
``test_benchmark_manifest.py`` holds this checkout to, and what
``test_second_model.py`` holds a copy of the tree to after it has added a
model, a configuration and a cell as new files and entries only.

A rule fails with an ``AssertionError``. Nothing here names a model: a
configuration is held to the published file that it names itself.
"""
import os
import re
import shutil

from benchmark.harness import ROOT, load_json, metrics_of, model_names

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_LEVEL = {"command", "paths", "run_seconds", "configs", "workloads",
             "end_to_end", "per_layer"}

#: what ``reduced`` may hold, by the key's name: the depth (and how many of
#: the leading dense layers it keeps), and a chip's share of a stated
#: deployment (model-configs guide, section 4)
SHARES = {
    "depth": re.compile(r"^(num_hidden_layers|num_layers|n_layers?)$"),
    "leading_dense": re.compile(r"^first_k_dense_replace$"),
    "experts": re.compile(
        r"^(n_routed_experts|num_experts|num_local_experts|moe_num_experts)$"),
    "vocabulary": re.compile(r"^vocab_size$"),
    "heads": re.compile(r"^(num|n)_([a-z]+_)*heads$"),
}
#: never in ``reduced``: hidden, feed-forward and expert widths, head sizes,
#: latent ranks, experts a token, expansion factors, window and state sizes
WIDTH = re.compile(
    r"_size$|_dim$|_rank$|_width$|head_dim|expand|expansion|per_tok|top_?k"
    r"|window|_channels$|(^|_)d_[a-z]+$")
MIN_EXPERTS_HELD = 8
MIN_VOCABULARY_SHARE = 8          # at least an eighth of the published rows
MIN_LAYERS_AFTER_DENSE = 4        # where the source has leading dense layers
#: cuts in depth: the layers left out lie on further chips, as the stages of
#: a pipeline, so they are no share of a layer and need no ``deployment``
DEPTH = {"depth", "leading_dense"}


def bench_dir(root):
    return os.path.join(root, "benchmark")


def cells(manifest):
    return [w["name"] for w in manifest["workloads"]]


def copy_of_the_tree(manifest, dst):
    """``BENCHMARK.json`` and the directories under ``paths``, and nothing
    else of the checkout, copied to ``dst``."""
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for d in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, d), os.path.join(dst, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return str(dst)


# ----------------------------------------------------------- the manifest --
def check_top_level(manifest, root):
    assert set(manifest) == TOP_LEVEL
    assert isinstance(manifest["run_seconds"], int) \
        and 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert manifest["command"][:2] == ["python3", "benchmark/run.py"]
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for key, most in (("configs", 24), ("workloads", 24), ("end_to_end", 16),
                      ("per_layer", 128)):
        names = [e["name"] for e in manifest[key]]
        assert 1 <= len(names) <= most and len(set(names)) == len(names)


def check_entry(entry):
    """Names, units and one-line texts of any entry."""
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    # a metric's ``source`` is one of four words; elsewhere it is a text
    for key in ("layer",) if "unit" in entry else ("why", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]


# ------------------------------------------------------- a configuration --
def share_kind(key):
    """Which of the guide's cuts a key of ``reduced`` is (a key of
    ``SHARES``), or None: a width or anything else may not be reduced."""
    for kind, pattern in SHARES.items():
        if pattern.match(key):
            return kind
    return None


def _number(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def published_values(published):
    """What a configuration is held to: the source's own ``config`` values,
    and those the source leaves to a default or a quotient of them, which
    the published file lists apart under ``derived``."""
    assert not set(published["config"]) & set(published.get("derived", {}))
    return {**published["config"], **published.get("derived", {})}


def check_config_values(conf, cfg, published, models):
    """One configuration (its manifest entry ``conf`` and its file ``cfg``)
    against the published file it names and the model modules there are."""
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert cfg["source"] == conf["source"] == published["source"]
    assert cfg.get("model") in models, \
        f"\"model\": {cfg.get('model')!r} and benchmark/models/ has {models}"
    pub, reduced = published_values(published), conf["reduced"]
    assert len(reduced) <= 16 and len(set(reduced)) == len(reduced)
    assert all(NAME.match(k) for k in reduced)
    # every value the source states is held as published, or is in reduced
    for key, value in pub.items():
        if key in reduced:
            continue
        if _number(value):
            assert key in cfg, f"{key} of the source is left out"
        if key in cfg:
            assert cfg[key] == value, \
                f"{key}: {cfg[key]!r} held, {value!r} published, not reduced"
    # reduced: the depth or a chip's share, never a width, within the floors
    kinds = set()
    for key in reduced:
        kind = share_kind(key)
        assert not (WIDTH.search(key) and kind != "vocabulary"), \
            f"{key} is a width: no width is ever cut"
        assert kind, f"{key} is none of {sorted(SHARES)}"
        assert _number(pub.get(key)) and _number(cfg.get(key))
        assert cfg["published"][key] == pub[key]
        assert 1 <= cfg[key] < pub[key], \
            f"{key}: holds {cfg[key]}, the source states {pub[key]}"
        if kind == "vocabulary":
            assert cfg[key] * MIN_VOCABULARY_SHARE >= pub[key], \
                f"{key}: {cfg[key]} rows are under an eighth of {pub[key]}"
        if kind == "experts":
            assert cfg[key] >= MIN_EXPERTS_HELD, \
                f"{key}: {cfg[key]} experts held, the floor is 8"
        if kind in ("experts", "heads"):
            assert pub[key] % cfg[key] == 0, \
                f"{key}: {cfg[key]} held is no whole share of {pub[key]}"
        kinds.add(kind)
    assert set(cfg.get("published", {})) == set(reduced)
    # where the source has leading dense layers, a cut in depth keeps one of
    # them (``1 <=`` above) and at least four of the layers that follow them
    for dense in (k for k in pub if share_kind(k) == "leading_dense"):
        for depth in (k for k in reduced if share_kind(k) == "depth"):
            assert pub[dense] < 1 \
                or cfg[depth] - cfg[dense] >= MIN_LAYERS_AFTER_DENSE, \
                f"{cfg[depth]} layers, {cfg[dense]} of them leading dense " \
                f"ones: the floor is {MIN_LAYERS_AFTER_DENSE} after those"
    if kinds - DEPTH:
        dep = cfg.get("deployment")
        assert isinstance(dep, dict), \
            f"a share ({sorted(kinds - DEPTH)}) needs its \"deployment\""
        assert int(dep["chips_per_layer"]) >= 2 and dep["how"].strip()


def check_config(manifest, conf, root):
    assert conf["file"].startswith("benchmark/configs/")
    cfg = load_json(root, conf["file"])
    assert cfg["published_file"].startswith("benchmark/published/")
    published = load_json(root, cfg["published_file"])
    check_config_values(conf, cfg, published,
                        model_names(bench_dir(root)))
    files = [c["file"] for c in manifest["configs"]]
    assert files.count(conf["file"]) == 1
    assert any(w["config"] == conf["name"] for w in manifest["workloads"])


# ---------------------------------------------------- cells and metrics --
def check_cell(manifest, wl, root):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert wl["chips"] in (1, 4)
    assert any(c["name"] == wl["config"] for c in manifest["configs"])
    here = bench_dir(root)
    mix = load_json(here, "traffic", wl["traffic"] + ".json")
    assert os.path.exists(os.path.join(here, "kinds", mix["kind"] + ".py"))
    limits = load_json(here, "limits", wl["name"] + ".json")
    assert limits, "every cell has its limits file"
    e2e = [m["name"] for m in metrics_of(manifest, "end_to_end", wl["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert metrics_of(manifest, "per_layer", wl["name"])


def check_per_layer(manifest, m, root):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert os.path.exists(os.path.join(bench_dir(root), "layer_metrics",
                                       m["name"] + ".py"))
    moved = next(e for e in manifest["end_to_end"] if e["name"] == m["moves"])
    for cell in m.get("workloads", cells(manifest)):
        assert cell in cells(manifest)
        assert "workloads" not in moved or cell in moved["workloads"], \
            f"{cell} does not report {m['moves']}"
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def check_bound(m):
    assert set(m) <= {"name", "unit", "better", "source", "bound",
                      "workloads"}
    assert 0.01 <= m["bound"] <= 0.1
    assert m["source"] in ("host_clock", "device_trace")


def check_four_chip_share(manifest):
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def check_all(manifest, root):
    """Every rule, on every entry."""
    check_top_level(manifest, root)
    for entry in manifest["configs"] + manifest["workloads"] \
            + manifest["end_to_end"] + manifest["per_layer"]:
        check_entry(entry)
    for conf in manifest["configs"]:
        check_config(manifest, conf, root)
    for wl in manifest["workloads"]:
        check_cell(manifest, wl, root)
    for m in manifest["per_layer"]:
        check_per_layer(manifest, m, root)
    for m in manifest["end_to_end"]:
        check_bound(m)
    check_four_chip_share(manifest)
