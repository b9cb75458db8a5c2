"""SPMD communication-plan auditor (ISSUE 12): collective parser on
doctored HLO fragments (five kinds, async -start/-done, nested-brace and
iota replica_groups, use_global_device_ids), replica-group -> named-axis
mapping, the ring-cost ledger, implicit/redundant-reshard defect passes,
the comm-bytes budget gate, and the ``python -m paddle_tpu.analysis
commplan`` CLI over the real parallelism matrix (docs/ANALYSIS.md)."""
import itertools
import json
import os

import pytest

from paddle_tpu.analysis import commplan as CP
from paddle_tpu.analysis.findings import (BaselineError, load_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(names, sizes, procs=None):
    """Hand-built MeshInfo (row-major coords, identity device ids)."""
    coords = [tuple(c) for c in
              itertools.product(*[range(s) for s in sizes])]
    n = len(coords)
    return CP.MeshInfo(tuple(names), tuple(sizes), coords,
                       procs or [0] * n, {i: i for i in range(n)})


def _coll(kind, payload, groups=None, pairs=None, **kw):
    return CP.Collective(kind=kind, name=f"%{kind}.1",
                         computation="main", entry=True,
                         payload_bytes=payload, groups=groups,
                         pairs=pairs, **kw)


# ---------------- parser: doctored fragments --------------------------------

FIVE_KINDS = """\
HloModule jit_step, entry_computation_layout={(f32[4]{0})->(f32[4]{0})}

ENTRY %main.9_spmd (param.1: f32[4]) -> (f32[4]) {
  %param.1 = f32[4]{0} parameter(0)
  %all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %param.1), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, to_apply=%add
  %all-gather.2 = f32[32]{0} all-gather(f32[4]{0} %param.1), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %reduce-scatter.3 = f32[4]{0} reduce-scatter(f32[32]{0} %all-gather.2), channel_id=3, replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add
  %all-to-all.4 = (f32[4]{0}, f32[4]{0}) all-to-all(f32[4]{0} %param.1, f32[4]{0} %param.1), channel_id=4, replica_groups={{0,1},{2,3},{4,5},{6,7}}
  %collective-permute.5 = f32[4]{0} collective-permute(f32[4]{0} %param.1), channel_id=5, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}, metadata={op_name="ring" source_file="ring.py" source_line=7}
  ROOT %tuple = (f32[4]{0}) tuple(f32[4]{0} %param.1)
}
"""


def test_parser_five_kinds():
    cs = CP.parse_collectives(FIVE_KINDS)
    by_kind = {c.kind: c for c in cs}
    assert set(by_kind) == {"all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute"}
    assert all(c.entry and c.computation == "main.9_spmd" for c in cs)
    ar = by_kind["all-reduce"]
    assert ar.channel_id == 1 and ar.use_global_ids
    assert ar.groups == [list(range(8))]
    assert ar.payload_bytes == 16
    ag = by_kind["all-gather"]
    assert not ag.use_global_ids and ag.groups == [list(range(8))]
    assert ag.payload_bytes == 128          # f32[32] result
    # plain all-to-all tuple result moves every element
    assert by_kind["all-to-all"].payload_bytes == 32
    assert by_kind["all-to-all"].groups == [[0, 1], [2, 3], [4, 5], [6, 7]]
    cp = by_kind["collective-permute"]
    assert cp.pairs == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert cp.source == "ring.py:7"


ASYNC_PAIR = """\
ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %all-gather-start.1 = (f32[8]{0}, f32[64]{0}) all-gather-start(f32[8]{0} %p), channel_id=7, replica_groups=[1,8]<=[8], dimensions={0}
  ROOT %all-gather-done.1 = f32[64]{0} all-gather-done((f32[8]{0}, f32[64]{0}) %all-gather-start.1)
}
"""


def test_async_start_counted_once_done_excluded():
    cs = CP.parse_collectives(ASYNC_PAIR)
    assert len(cs) == 1
    c = cs[0]
    assert c.kind == "all-gather" and c.name == "%all-gather-start.1"
    # -start tuple payload = the destination (largest element), not sum
    assert c.payload_bytes == 256


def test_combined_all_reduce_counts_every_buffer():
    """XLA's combiner packs independent all-reduces into one variadic
    instruction (a tuple result, with ``/*index=5*/`` comments once it is
    long): the ledger and the census count every buffer it reduces and
    sum their bytes, so a pin holds whether or not the combiner ran."""
    shapes = ["f32[]", "f32[4]{0}", "f32[2,3]{1,0}", "f32[4]{0}",
              "f32[4]{0}", "/*index=5*/f32[4]{0}"]
    line = (f"  %all-reduce.44 = ({', '.join(shapes)}) all-reduce(%a, %b, "
            "%c, %d, %e, /*index=5*/%f), channel_id=3, "
            "replica_groups=[1,8]<=[8], use_global_device_ids=true, "
            "to_apply=%add")
    text = "ENTRY %e (x: f32[4]) -> f32[4] {\n" + line + "\n}\n"
    (c,) = CP.parse_collectives(text)
    assert c.kind == "all-reduce" and c.payloads == 6
    assert c.payload_bytes == 4 + 4 * 16 + 24
    (slot,) = CP.comm_ledger([c], None).values()
    assert slot["ops"] == 6 and slot["kinds"] == {"all-reduce": 6}
    from paddle_tpu.analysis.hlo import collective_census
    assert collective_census(text)["all-reduce"] == 6


def test_iota_transpose_decode():
    # [4,2]<=[2,4]T(1,0): arange(8).reshape(2,4).T.reshape(4,2)
    line = ("  %all-reduce.2 = f32[4]{0} all-reduce(f32[4]{0} %x), "
            "replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add")
    cs = CP.parse_collectives("ENTRY %e (x: f32[4]) -> f32[4] {\n"
                              + line + "\n}\n")
    assert cs[0].groups == [[0, 4], [1, 5], [2, 6], [3, 7]]


def test_nested_brace_groups_tail_fields_ignored():
    line = ("  %reduce-scatter.8 = f32[2]{0} reduce-scatter(f32[8]{0} %x), "
            "replica_groups={{0,2},{1,3}}, dimensions={0}, to_apply=%add, "
            'metadata={op_name="scatter{nested}"}')
    cs = CP.parse_collectives("ENTRY %e (x: f32[8]) -> f32[2] {\n"
                              + line + "\n}\n")
    assert cs[0].groups == [[0, 2], [1, 3]]


ENTRY_COMMENTS = r"""HloModule jit_train, entry_computation_layout={(f32[4]{0}, f32[8,4]{1,0})->(f32[], /*index=1*/f32[4]{0})}

%fused_computation.15 (param_0.3: f32[4]) -> f32[4] {
  %param_0.3 = f32[4]{0} parameter(0)
  ROOT %all-reduce.7 = f32[4]{0} all-reduce(f32[4]{0} %param_0.3), replica_groups=[1,8]<=[8], to_apply=%add
}

ENTRY %main.185_spmd (param.2: f32[4], param.1: f32[8,4]) -> (f32[], /*index=1*/f32[4]) {
  %param.2 = f32[4]{0} parameter(0), sharding={devices=[8]<=[8]}, metadata={op_name="train[\'0.bias\']"}
  %param.1 = f32[8,4]{1,0} parameter(1), metadata={op_name="flat_batch[0]"}
  %all-gather.3 = f32[32]{0} all-gather(f32[4]{0} %param.2), channel_id=1, replica_groups=[1,8]<=[8], dimensions={0}, use_global_device_ids=true, metadata={op_name="g" source_file="m.py" source_line=9}
  ROOT %fusion.2 = f32[4]{0} fusion(f32[4]{0} %param.2), kind=kLoop, calls=%fused_computation.15
}
"""


def test_entry_attribution_survives_index_comments():
    """The ENTRY header's /*index=N*/ result comments (they contain `=`)
    must not break computation tracking — the regression that silenced
    the implicit-reshard pass."""
    cs = CP.parse_collectives(ENTRY_COMMENTS)
    by_comp = {c.computation: c for c in cs}
    assert by_comp["main.185_spmd"].entry
    assert not by_comp["fused_computation.15"].entry


def test_entry_param_labels_from_metadata():
    _, entry_params, labels = CP._def_maps(ENTRY_COMMENTS)
    assert entry_params == {"%param.2": 0, "%param.1": 1}
    assert labels == {0: "train['0.bias']", 1: "flat_batch[0]"}


# ---------------- axis mapping and cost model -------------------------------

def test_map_axes_single_and_combined():
    mesh = _mesh(("dp", "mp"), (4, 2))
    dp_groups = [[0, 2, 4, 6], [1, 3, 5, 7]]
    axes, exact, crosses = CP.map_axes(
        _coll("all-reduce", 16, groups=dp_groups), mesh)
    assert axes == ("dp",) and exact and not crosses
    axes, exact, _ = CP.map_axes(
        _coll("all-reduce", 16, groups=[[0, 1], [2, 3], [4, 5], [6, 7]]),
        mesh)
    assert axes == ("mp",) and exact
    axes, exact, _ = CP.map_axes(
        _coll("all-reduce", 16, groups=[list(range(8))]), mesh)
    assert axes == ("dp", "mp") and exact


def test_map_axes_partial_group_is_inexact():
    mesh = _mesh(("dp", "mp"), (4, 2))
    axes, exact, _ = CP.map_axes(
        _coll("all-gather", 16, groups=[[0, 2]]), mesh)
    assert axes == ("dp",) and not exact


def test_map_axes_dcn_when_group_spans_processes():
    mesh = _mesh(("dp",), (4,), procs=[0, 0, 1, 1])
    axes, _, crosses = CP.map_axes(
        _coll("all-reduce", 16, groups=[[0, 1, 2, 3]]), mesh)
    assert axes == ("dp",) and crosses
    ledger = CP.comm_ledger(
        [_coll("all-reduce", 16, groups=[[0, 1, 2, 3]])], mesh)
    assert ledger["dp"]["hops"] == "dcn"


def test_permute_pairs_map_to_ring_axis():
    mesh = _mesh(("pp",), (4,))
    c = _coll("collective-permute", 64,
              pairs=[(0, 1), (1, 2), (2, 3), (3, 0)])
    axes, exact, _ = CP.map_axes(c, mesh)
    assert axes == ("pp",) and exact
    assert CP.wire_bytes(c) == 64


def test_wire_bytes_cost_model():
    g4 = [[0, 1, 2, 3]]
    assert CP.wire_bytes(_coll("all-reduce", 100, groups=g4)) == 150
    assert CP.wire_bytes(_coll("all-gather", 100, groups=g4)) == 75
    assert CP.wire_bytes(_coll("reduce-scatter", 100, groups=g4)) == 300
    assert CP.wire_bytes(_coll("all-to-all", 100, groups=g4)) == 75
    # degenerate single-member group moves nothing
    assert CP.wire_bytes(_coll("all-reduce", 100, groups=[[3]])) == 0


def test_comm_ledger_aggregates_per_axis():
    mesh = _mesh(("dp", "mp"), (4, 2))
    cs = [_coll("all-reduce", 100, groups=[[0, 2, 4, 6], [1, 3, 5, 7]]),
          _coll("all-reduce", 40, groups=[[0, 2, 4, 6], [1, 3, 5, 7]]),
          _coll("all-gather", 80, groups=[[0, 1], [2, 3], [4, 5], [6, 7]])]
    ledger = CP.comm_ledger(cs, mesh)
    assert ledger["dp"]["ops"] == 2
    assert ledger["dp"]["bytes"] == 150 + 60
    assert ledger["dp"]["kinds"] == {"all-reduce": 2}
    assert ledger["mp"] == {"ops": 1, "bytes": 40,
                            "kinds": {"all-gather": 1}, "hops": "ici",
                            "inexact_groups": 0}


# ---------------- defect passes on doctored programs ------------------------

def test_implicit_reshard_flags_state_leaf_gather():
    mesh = _mesh(("dp",), (8,))
    rep = CP.audit_comm(ENTRY_COMMENTS, "doctored", mesh=mesh)
    p0 = [f for f in rep.findings if f.rule == "implicit-reshard"]
    assert len(p0) == 1
    assert p0[0].severity == "P0"
    assert p0[0].data["leaf"] == "train['0.bias']"
    assert p0[0].data["axes"] == "dp"
    assert "m.py:9" in p0[0].message


def test_implicit_reshard_quiet_when_gather_ok():
    mesh = _mesh(("dp",), (8,))
    rep = CP.audit_comm(ENTRY_COMMENTS, "doctored", mesh=mesh,
                       gather_ok=True)
    assert not [f for f in rep.findings if f.rule == "implicit-reshard"]


def test_implicit_reshard_ignores_batch_leaves():
    hlo = ENTRY_COMMENTS.replace("%param.2)", "%param.1)").replace(
        "all-gather(f32[4]{0}", "all-gather(f32[8,4]{1,0}")
    mesh = _mesh(("dp",), (8,))
    rep = CP.audit_comm(hlo, "doctored", mesh=mesh)
    assert not [f for f in rep.findings if f.rule == "implicit-reshard"]


def test_redundant_reshard_pair():
    hlo = """\
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %all-gather.1 = f32[32]{0} all-gather(f32[4]{0} %p), replica_groups=[1,8]<=[8], dimensions={0}
  %convert.2 = f32[32]{0} convert(f32[32]{0} %all-gather.1)
  ROOT %reduce-scatter.3 = f32[4]{0} reduce-scatter(f32[32]{0} %convert.2), replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add
}
"""
    mesh = _mesh(("dp",), (8,))
    rep = CP.audit_comm(hlo, "doctored", mesh=mesh, gather_ok=True)
    p1 = [f for f in rep.findings if f.rule == "redundant-reshard"]
    assert len(p1) == 1 and p1[0].data["gathered"] == 128


# ---------------- budget gate ------------------------------------------------

def test_budget_findings_new_axis_kind_and_drift(monkeypatch):
    pinned = {"dp": {"ops": 2, "bytes": 1000,
                     "kinds": {"all-reduce": 2}}}
    clean = {"dp": {"ops": 2, "bytes": 1010, "kinds": {"all-reduce": 2},
                    "hops": "ici", "inexact_groups": 0}}
    assert CP.budget_findings("g", clean, pinned) == []
    drift = {"dp": {**clean["dp"], "bytes": 1200}}
    fs = CP.budget_findings("g", drift, pinned)
    assert [f.rule for f in fs] == ["comm-budget-drift"]
    # tolerance knob widens the budget
    monkeypatch.setenv("PADDLE_TPU_ANALYSIS_COMM_TOL", "0.5")
    assert CP.budget_findings("g", drift, pinned) == []
    monkeypatch.delenv("PADDLE_TPU_ANALYSIS_COMM_TOL")
    newkind = {"dp": {**clean["dp"],
                      "kinds": {"all-reduce": 2, "all-gather": 1}}}
    assert [f.rule for f in CP.budget_findings("g", newkind, pinned)] \
        == ["comm-new-collective"]
    newaxis = {**clean, "mp": {"ops": 1, "bytes": 5, "kinds": {},
                               "hops": "ici", "inexact_groups": 0}}
    assert [f.rule for f in CP.budget_findings("g", newaxis, pinned)] \
        == ["comm-new-axis"]
    # shrink is silent (re-pin to claim it)
    shrink = {"dp": {**clean["dp"], "bytes": 10, "ops": 1}}
    assert CP.budget_findings("g", shrink, pinned) == []


def test_corrupt_baseline_raises_baseline_error(tmp_path):
    p = tmp_path / "broken.json"
    p.write_text('{"findings": {')
    with pytest.raises(BaselineError) as ei:
        load_baseline(str(p))
    assert "--write-baseline" in str(ei.value)


# ---------------- real parallelism matrix (integration) ---------------------

@pytest.fixture(scope="module")
def commplan_run():
    from paddle_tpu.analysis.driver import ensure_cpu_mesh, run_commplan
    ensure_cpu_mesh()
    return run_commplan()


def test_matrix_covers_segments_and_maps_every_collective(commplan_run):
    run = commplan_run
    covered = set(run["reports"]) | set(run["skipped"])
    assert {"dp8", "dpxmp", "pp", "dpxpp", "zero", "sp", "ep",
            "serving"} <= covered
    # dp x mp, ZeRO, sp and ep must actually lower on this jax
    assert {"dp8", "dpxmp", "pp", "zero", "sp", "ep"} <= \
        set(run["reports"])
    for label, ledger in run["ledgers"].items():
        assert "unmapped" not in ledger and "none" not in ledger, \
            f"{label}: unattributed collectives {ledger}"
        for slot in ledger.values():
            assert slot["inexact_groups"] == 0
    # real geometries are CLEAN — defects only come from seeded typos
    assert run["findings"] == []


def test_ledgers_match_pinned_baseline(commplan_run):
    """The pins are what this installation's XLA emits (PR 29): counted
    per reduced buffer, the dp8 and dpxmp/dp pins held as they had been
    pinned; the other geometries were pinned again."""
    pinned = load_baseline().commplan
    assert pinned, "commplan section missing from committed baseline"
    for label, ledger in commplan_run["ledgers"].items():
        assert label in pinned, f"geometry {label} never pinned"
        for axis, slot in ledger.items():
            pin = pinned[label][axis]
            assert slot["ops"] == pin["ops"], (label, axis)
            assert slot["bytes"] == pin["bytes"], (label, axis)
            assert slot["kinds"] == pin["kinds"], (label, axis)
        assert CP.budget_findings(label, ledger, pinned.get(label)) == []


def test_cli_clean_exit0_and_seeded_typo_exit1(capsys):
    from paddle_tpu.analysis.__main__ import main
    assert main(["commplan", "--only", "dp8", "--quiet"]) == 0
    capsys.readouterr()
    assert main(["commplan", "--only", "dp8", "--seed-typo"]) == 1
    out = capsys.readouterr().out
    assert "implicit-reshard" in out and "[P0]" in out
    assert "train['0.bias']" in out


def test_cli_missing_and_corrupt_baseline_exit2(tmp_path, capsys):
    from paddle_tpu.analysis.__main__ import main
    missing = tmp_path / "nope.json"
    assert main(["commplan", "--only", "serving",
                 "--baseline", str(missing)]) == 2
    err = capsys.readouterr().err
    assert "--write-baseline" in err
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("{not json")
    assert main(["commplan", "--only", "serving",
                 "--baseline", str(corrupt)]) == 2
    assert "corrupt JSON" in capsys.readouterr().err


def test_cli_write_baseline_pins_ledgers(tmp_path):
    from paddle_tpu.analysis.__main__ import main
    path = tmp_path / "pins.json"
    assert main(["commplan", "--only", "dp8", "--quiet",
                 "--baseline", str(path), "--write-baseline"]) == 0
    doc = json.loads(path.read_text())
    assert doc["commplan"]["dp8"]["dp"]["kinds"] == {"all-reduce": 2}
    # and the freshly pinned file gates clean
    assert main(["commplan", "--only", "dp8", "--quiet",
                 "--baseline", str(path)]) == 0
