"""SPMD communication-plan auditor: the collective schedule as data.

Prong 3 of the analysis subsystem (docs/ANALYSIS.md). GSPMD decides the
collective schedule — which axes all-reduce, what gets gathered, how
many bytes cross links per step — and that decision is only visible in
the compiled HLO. This module lifts it into a checkable artifact:

- :func:`parse_collectives`: every collective instruction (the five
  stems, async ``-start`` counted once / ``-done`` excluded) with its
  decoded ``replica_groups`` (explicit nested-brace and iota
  ``[G,S]<=[dims]T(perm)`` forms), ``channel_id``,
  ``use_global_device_ids``, ``source_target_pairs`` and operands.
- :func:`map_axes` / :class:`MeshInfo`: replica-group member ids mapped
  back to **named mesh axes** (the axes whose coordinates vary inside a
  group), with an ICI-vs-DCN classification (a group spanning processes
  pays DCN hops; a within-process group stays on ICI).
- :func:`comm_ledger`: the per-axis static ledger — op count, wire
  bytes per step (ring cost model, per participant), collective kinds.
- Defect passes over the plan: **implicit reshard** (an all-gather whose
  operand chains back to a parameter/state leaf that the geometry says
  must never be gathered — the accidental-all-gather P0 class a
  sharding-spec typo produces), **redundant reshard** (an all-gather
  re-scattered on the same axes), and **budget drift** (per-axis bytes
  pinned in ``analysis/baseline.json``; NEW collectives or growth past
  ``PADDLE_TPU_ANALYSIS_COMM_TOL`` fail CI).

Everything below :func:`audit_comm` is pure text+arithmetic — no jax
import — so the parser unit-tests run on doctored fragments and the
same code audits a real TPU dump.

Wire-bytes cost model (per participating device, per step; ``g`` =
replica-group size, ``payload`` = full result bytes):

====================  =============================================
all-reduce            ``2 * (g-1)/g * payload`` (reduce-scatter +
                      all-gather phases of a ring)
all-gather            ``(g-1)/g * payload`` (each device ships its
                      shard around the ring)
reduce-scatter        ``(g-1) * payload`` (payload is the scattered
                      shard; ``g-1`` chunks of it transit)
all-to-all            ``(g-1)/g * payload`` (every device keeps its
                      own slice)
collective-permute    ``payload`` (each source sends one full buffer)
====================  =============================================
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .findings import Finding, P0, P1
from .hlo import (COLLECTIVE_STEMS, COMMENT_RE, _balanced_braces,
                  shape_bytes)

__all__ = ["Collective", "MeshInfo", "parse_collectives", "map_axes",
           "wire_bytes", "comm_ledger", "CommReport", "audit_comm",
           "budget_findings", "comm_tolerance"]

#: drift tolerance on per-axis bytes (fraction); growth past it is a
#: finding. Shrink never fails — re-pin with --write-baseline to claim
#: the win.
_DEFAULT_COMM_TOL = 0.05

#: leaf-name prefixes that name persistent state (model parameters and
#: optimizer state) in a TrainStep entry — the buffers an implicit
#: reshard must never gather unless the geometry says so (ZeRO does).
STATE_LEAF_PREFIXES = ("train", "frozen", "states", "buffers")


def comm_tolerance() -> float:
    raw = os.environ.get("PADDLE_TPU_ANALYSIS_COMM_TOL", "")
    try:
        return float(raw) if raw else _DEFAULT_COMM_TOL
    except ValueError:
        return _DEFAULT_COMM_TOL


# -- parsing ----------------------------------------------------------------

#: `%name = <result> <stem>[-start|-done](` — result is a shape or a
#: tuple of shapes; the leading %/ROOT guard keeps computation headers
#: and operand mentions out.
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*"
    r"(\([^=]*?\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(" + "|".join(COLLECTIVE_STEMS) + r")(-start|-done)?\(")
_SHAPE_TOK_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_CHANNEL_RE = re.compile(r"\bchannel_id=(\d+)")
_GLOBAL_IDS_RE = re.compile(r"\buse_global_device_ids=(true|false)")
_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
    r"(?:T\(([0-9,]+)\))?")
_SOURCE_RE = re.compile(r'source_file="([^"]*)"(?:\s+source_line=(\d+))?')
#: computation header: `%name (args) -> result {` / `ENTRY %name (...) {`.
#: The `(` must follow the name directly (instructions carry ` = ` there)
#: and the line must end with the open brace; the signature itself can
#: contain `=` inside /*index=N*/ comments, so no char-class shortcuts.
_COMPUTATION_RE = re.compile(
    r"^\s*(ENTRY\s+)?(%[\w.\-]+)\s*\(.*\{\s*$")
_PARAM_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*[^=]*\bparameter\((\d+)\)")
#: the entry-parameter leaf label jax stamps into metadata
#: (op_name="train[\'0.bias\']") — shard-shape-proof, unlike aligning
#: on (dtype, dims) which breaks when SPMD rewrites params to shard
#: shapes
_PARAM_LABEL_RE = re.compile(r'metadata=\{op_name="([^"]*)"')
_OPERAND_RE = re.compile(r"%[\w.\-]+")


@dataclass
class Collective:
    """One parsed collective instruction."""
    kind: str                       # one of COLLECTIVE_STEMS
    name: str                       # %all-gather.3
    computation: str                # enclosing computation (% stripped)
    entry: bool                     # lives in the ENTRY computation
    payload_bytes: int              # see module doc (tuple handling)
    payloads: int = 1               # buffers moved: >1 in a variadic
    #                                 all-reduce (XLA's combiner)
    groups: Optional[List[List[int]]] = None   # decoded replica groups
    pairs: Optional[List[Tuple[int, int]]] = None  # source_target_pairs
    channel_id: Optional[int] = None
    use_global_ids: bool = False
    operands: Tuple[str, ...] = ()
    source: str = ""                # "file:line" metadata when present
    line: str = ""

    @property
    def group_size(self) -> int:
        if self.groups:
            return max(len(g) for g in self.groups)
        if self.pairs:
            # a permute "group" is the cycle the pairs trace; for the
            # cost model only "more than one participant" matters
            return 2 if self.pairs else 1
        return 1


def _decode_iota(num_groups: int, group_size: int, dims: Sequence[int],
                 perm: Optional[Sequence[int]]) -> List[List[int]]:
    """Decode the iota replica-group form ``[G,S]<=[dims]T(perm)``:
    ``arange(prod(dims)).reshape(dims)``, optionally transposed by
    ``perm``, reshaped to ``[G, S]`` (pure python — no numpy needed for
    the group sizes involved)."""
    n = 1
    for d in dims:
        n *= d
    flat = list(range(n))

    def strides(shape):
        out, acc = [], 1
        for d in reversed(shape):
            out.append(acc)
            acc *= d
        return list(reversed(out))

    if perm:
        src_strides = strides(list(dims))
        tshape = [dims[p] for p in perm]
        tstrides = strides(tshape)
        out = [0] * n
        for j in range(n):
            rem, coords = j, []
            for st in tstrides:
                coords.append(rem // st)
                rem %= st
            src = sum(c * src_strides[p]
                      for c, p in zip(coords, perm))
            out[j] = flat[src]
        flat = out
    return [flat[i * group_size:(i + 1) * group_size]
            for i in range(num_groups)]


def _parse_groups(line: str) -> Optional[List[List[int]]]:
    m = _IOTA_RE.search(line)
    if m:
        dims = [int(d) for d in m.group(3).split(",")]
        perm = [int(p) for p in m.group(4).split(",")] if m.group(4) \
            else None
        return _decode_iota(int(m.group(1)), int(m.group(2)), dims, perm)
    key = "replica_groups="
    i = line.find(key)
    if i < 0 or not line[i + len(key):].startswith("{"):
        return None
    body = _balanced_braces(line, i + len(key))
    groups = []
    for gm in re.finditer(r"\{([0-9,\s]*)\}", body):
        groups.append([int(t) for t in gm.group(1).split(",") if t.strip()])
    if not groups and body.strip():
        # single flat group: replica_groups={0,1,2}
        groups = [[int(t) for t in body.split(",") if t.strip()]]
    return groups


def _parse_pairs(line: str) -> Optional[List[Tuple[int, int]]]:
    key = "source_target_pairs="
    i = line.find(key)
    if i < 0:
        return None
    body = _balanced_braces(line, i + len(key))
    return [(int(pm.group(1)), int(pm.group(2)))
            for pm in re.finditer(r"\{(\d+)\s*,\s*(\d+)\}", body)]


def _result_bytes(result: str, kind: str, is_start: bool) -> int:
    """Payload bytes from the result type. A plain tuple all-to-all and
    a variadic all-reduce (sync or ``-start``: its result has its
    operands' shapes) move every element (sum); any other ``-start``
    tuple is (operand, dest, context...) — the destination (largest
    element) is the payload."""
    shapes = [(d, c) for d, c in _SHAPE_TOK_RE.findall(result)]
    if not shapes:
        return 0
    if not result.startswith("("):
        d, c = shapes[0]
        return shape_bytes(d, c)
    sizes = [shape_bytes(d, c) for d, c in shapes]
    if kind == "all-reduce" or (kind == "all-to-all" and not is_start):
        return sum(sizes)
    return max(sizes)


def parse_collectives(hlo_text: str) -> List[Collective]:
    """Every collective instruction in the module, with async ``-start``
    counted once and ``-done`` excluded (it carries no second payload)."""
    out: List[Collective] = []
    computation, entry = "", False
    for raw in hlo_text.splitlines():
        cm = _COMPUTATION_RE.match(raw)
        if cm:
            computation = cm.group(2).lstrip("%")
            entry = bool(cm.group(1))
            continue
        raw = COMMENT_RE.sub("", raw)
        m = _COLL_RE.match(raw)
        if not m:
            continue
        name, result, kind, suffix = m.groups()
        if suffix == "-done":
            continue
        src = ""
        sm = _SOURCE_RE.search(raw)
        if sm:
            src = sm.group(1) + (f":{sm.group(2)}" if sm.group(2) else "")
        ch = _CHANNEL_RE.search(raw)
        gl = _GLOBAL_IDS_RE.search(raw)
        operands = tuple(
            t for t in _OPERAND_RE.findall(raw[m.end():]) if t != name)
        out.append(Collective(
            kind=kind, name=name, computation=computation, entry=entry,
            payload_bytes=_result_bytes(result, kind, suffix == "-start"),
            payloads=len(_SHAPE_TOK_RE.findall(result))
            if kind == "all-reduce" else 1,
            groups=_parse_groups(raw), pairs=_parse_pairs(raw),
            channel_id=int(ch.group(1)) if ch else None,
            use_global_ids=bool(gl and gl.group(1) == "true"),
            operands=operands, source=src, line=raw.strip()))
    return out


# -- mesh mapping -----------------------------------------------------------

@dataclass
class MeshInfo:
    """The mesh facts axis mapping needs, detached from jax: axis names
    and sizes (in mesh order), device coordinates per flat position, and
    the process index per flat position (DCN detection)."""
    axis_names: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]
    #: flat position (row-major over the device array) -> coords
    coords: List[Tuple[int, ...]]
    #: flat position -> process index
    process: List[int]
    #: global device id -> flat position (use_global_device_ids=true)
    by_device_id: Dict[int, int] = field(default_factory=dict)

    @classmethod
    def from_mesh(cls, mesh) -> "MeshInfo":
        """From a ``jax.sharding.Mesh`` (what ``init_mesh`` returns)."""
        devs = mesh.devices
        names = tuple(mesh.axis_names)
        sizes = tuple(devs.shape)
        coords, process, by_id = [], [], {}
        flat = list(devs.flatten())
        for pos, d in enumerate(flat):
            rem, c = pos, []
            for s in _strides(sizes):
                c.append(rem // s)
                rem %= s
            coords.append(tuple(c))
            process.append(int(getattr(d, "process_index", 0)))
            by_id[int(getattr(d, "id", pos))] = pos
        return cls(names, sizes, coords, process, by_id)

    def position(self, member: int, use_global_ids: bool) -> Optional[int]:
        if use_global_ids and member in self.by_device_id:
            return self.by_device_id[member]
        return member if member < len(self.coords) else None


def _strides(sizes: Sequence[int]) -> List[int]:
    out, acc = [], 1
    for s in reversed(sizes):
        out.append(acc)
        acc *= s
    return list(reversed(out))


def map_axes(c: Collective, mesh: Optional[MeshInfo]) \
        -> Tuple[Tuple[str, ...], bool, bool]:
    """``(axes, exact, crosses_process)`` for one collective: the mesh
    axes whose coordinates vary inside its replica groups (or across its
    permute pairs). ``exact`` when every group's size equals the product
    of the varying axis sizes — i.e. the groups ARE that axis subgrid;
    a False means a partial/irregular group (reported as inexact, still
    attributed to the varying axes)."""
    if mesh is None:
        return ("unmapped",), False, False
    groups = c.groups
    if groups is None and c.pairs:
        groups = [[s, t] for s, t in c.pairs]
    if not groups:
        return (), True, False
    varying: set = set()
    crosses, sizes_ok = False, True
    for g in groups:
        pos = [mesh.position(m, c.use_global_ids) for m in g]
        if any(p is None for p in pos):
            return ("unmapped",), False, False
        ref = mesh.coords[pos[0]]
        gaxes = set()
        for p in pos[1:]:
            for ax, (a, b) in enumerate(zip(ref, mesh.coords[p])):
                if a != b:
                    gaxes.add(ax)
        varying |= gaxes
        procs = {mesh.process[p] for p in pos}
        crosses = crosses or len(procs) > 1
        want = 1
        for ax in gaxes:
            want *= mesh.axis_sizes[ax]
        if len(g) != want:
            sizes_ok = False
    if not varying:
        return (), True, crosses
    axes = tuple(mesh.axis_names[ax] for ax in sorted(varying))
    # permute pairs never cover the full axis subgrid pairwise; a ring
    # along one axis is exact by construction
    exact = sizes_ok or (c.pairs is not None and len(axes) == 1)
    return axes, exact, crosses


def wire_bytes(c: Collective) -> int:
    """Per-participant wire bytes per step (module-doc cost model)."""
    g = c.group_size
    p = c.payload_bytes
    if c.kind == "collective-permute":
        return p if c.pairs or c.groups else 0
    if g <= 1:
        return 0
    if c.kind == "all-reduce":
        return int(2 * (g - 1) * p / g)
    if c.kind == "all-gather":
        return int((g - 1) * p / g)
    if c.kind == "reduce-scatter":
        return (g - 1) * p
    if c.kind == "all-to-all":
        return int((g - 1) * p / g)
    return p


def comm_ledger(collectives: List[Collective],
                mesh: Optional[MeshInfo]) -> Dict[str, dict]:
    """Aggregate per mesh-axis key (``"dp"``, ``"dp+mp"`` for a group
    varying on both, ``"none"`` for degenerate single-member groups):
    op count, wire bytes/step, per-kind counts, hop class. A variadic
    all-reduce counts once per buffer it reduces, as in
    ``hlo.collective_census``: the pins hold whether or not XLA's
    combiner packed the reductions into one instruction."""
    out: Dict[str, dict] = {}
    for c in collectives:
        axes, exact, crosses = map_axes(c, mesh)
        key = "+".join(axes) if axes else "none"
        slot = out.setdefault(key, {
            "ops": 0, "bytes": 0, "kinds": {}, "hops": "ici",
            "inexact_groups": 0})
        slot["ops"] += c.payloads
        slot["bytes"] += wire_bytes(c)
        slot["kinds"][c.kind] = slot["kinds"].get(c.kind, 0) + c.payloads
        if crosses:
            slot["hops"] = "dcn"
        if not exact:
            slot["inexact_groups"] += 1
    return out


# -- def-use chase (implicit / redundant reshard) ---------------------------

def _def_maps(hlo_text: str):
    """``(defs, entry_params, param_labels)``: per-computation
    ``name -> (opcode, operand names)``, the entry computation's
    ``param name -> parameter number``, and ``parameter number -> leaf
    label`` from the op_name metadata jax stamps on entry parameters
    (``train[\\'0.bias\\']``)."""
    defs: Dict[str, Dict[str, Tuple[str, Tuple[str, ...]]]] = {}
    entry_params: Dict[str, int] = {}
    param_labels: Dict[int, str] = {}
    comp, entry = "", False
    op_re = re.compile(
        r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*"
        r"(?:\([^=]*?\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s*"
        r"([a-z][a-z0-9\-]*)\(")
    for raw in hlo_text.splitlines():
        cm = _COMPUTATION_RE.match(raw)
        if cm:
            comp = cm.group(2).lstrip("%")
            entry = bool(cm.group(1))
            continue
        pm = _PARAM_RE.match(raw)
        if pm and entry:
            num = int(pm.group(2))
            entry_params[pm.group(1)] = num
            lm = _PARAM_LABEL_RE.search(raw)
            if lm:
                param_labels[num] = lm.group(1).replace("\\'", "'")
        m = op_re.match(raw)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        operands = tuple(t for t in _OPERAND_RE.findall(raw[m.end():])
                         if t != name)
        defs.setdefault(comp, {})[name] = (opcode, operands)
    return defs, entry_params, param_labels


#: opcodes a param chase may walk through — data-preserving moves only.
#: Anything arithmetic (dot, add, fusion, ...) stops the chase: a gather
#: of a *computed* tensor legitimately has parameters among its distant
#: ancestors, and flagging those would drown the real signal (the MoE
#: routing intermediates chase back to gate.weight through top_k and
#: einsums, and that is not a parameter re-materialization).
_TRANSPARENT_OPS = frozenset({
    "copy", "bitcast", "bitcast-convert", "convert", "reshape",
    "transpose", "broadcast", "get-tuple-element", "tuple",
    "optimization-barrier", "copy-start", "copy-done"})


def _chase_to_params(start_operands, local_defs, entry_params,
                     depth: int = 12) -> List[int]:
    """BFS from instruction operands back to entry parameter numbers,
    within one computation (HLO parameters are computation-local, so a
    chase never crosses a call boundary), walking only through
    :data:`_TRANSPARENT_OPS` so a hit means the gathered bytes ARE the
    parameter's bytes, not merely derived from them."""
    seen, hits = set(), []
    frontier = list(start_operands)
    for _ in range(depth):
        if not frontier:
            break
        nxt = []
        for name in frontier:
            if name in seen:
                continue
            seen.add(name)
            if name in entry_params:
                hits.append(entry_params[name])
                continue
            d = local_defs.get(name)
            if d is not None and d[0] in _TRANSPARENT_OPS:
                nxt.extend(d[1])
        frontier = nxt
    return hits


# -- report -----------------------------------------------------------------

@dataclass
class CommReport:
    """The comm-plan audit result for one compiled program."""
    label: str
    collectives: List[Collective] = field(default_factory=list)
    ledger: Dict[str, dict] = field(default_factory=dict)
    findings: List[Finding] = field(default_factory=list)

    @property
    def census(self) -> Dict[str, int]:
        out = {}
        for c in self.collectives:
            out[c.kind] = out.get(c.kind, 0) + 1
        return out

    def summary(self) -> dict:
        return {
            "label": self.label,
            "census": self.census,
            "ledger": self.ledger,
            "findings": [f.to_json() for f in self.findings],
        }


def audit_comm(hlo_text: str, label: str, mesh=None,
               leaf_names: Optional[List[str]] = None,
               gather_ok: bool = False,
               state_prefixes: Tuple[str, ...] = STATE_LEAF_PREFIXES,
               chase_depth: int = 12) -> CommReport:
    """Parse, map and defect-check one compiled program's comm plan.

    ``mesh``: a ``jax.sharding.Mesh`` or prebuilt :class:`MeshInfo`
    (None = single-program, everything lands in the ``unmapped``
    bucket). ``leaf_names``: entry-parameter leaf names aligned to
    parameter numbers (what ``audit._align_params`` produces) — enables
    the implicit-reshard pass. ``gather_ok``: the geometry legitimately
    gathers its state leaves (ZeRO re-materializes params every step),
    so the implicit-reshard pass stays quiet.
    """
    info = None
    if mesh is not None:
        info = mesh if isinstance(mesh, MeshInfo) else \
            MeshInfo.from_mesh(mesh)
    r = CommReport(label=label)
    r.collectives = parse_collectives(hlo_text)
    r.ledger = comm_ledger(r.collectives, info)

    defs, entry_params, param_labels = _def_maps(hlo_text)
    by_name: Dict[str, Collective] = {c.name: c for c in r.collectives}

    def leaf_label(pnum: int) -> str:
        # metadata label first (shard-shape-proof), caller-supplied
        # alignment as fallback, positional last
        if pnum in param_labels:
            return param_labels[pnum]
        if leaf_names and pnum < len(leaf_names):
            return leaf_names[pnum]
        return f"param{pnum}"

    # implicit reshard: an entry all-gather fed (transitively) by a
    # state leaf that this geometry must never gather
    if not gather_ok:
        for c in r.collectives:
            if c.kind != "all-gather" or not c.entry:
                continue
            local = defs.get(c.computation, {})
            for pnum in _chase_to_params(c.operands, local, entry_params,
                                         chase_depth):
                name = leaf_label(pnum)
                if not name.split("[")[0].split("'")[0].startswith(
                        state_prefixes):
                    continue
                axes, _, _ = map_axes(c, info)
                axkey = "+".join(axes) or "none"
                r.findings.append(Finding(
                    "implicit-reshard", P0, label, "commplan",
                    anchor=f"{name}@{axkey}",
                    message=(f"{c.kind} on axis '{axkey}' gathers state "
                             f"leaf {name} ({c.payload_bytes}B result) — "
                             f"its declared sharding should never need "
                             f"gathering; a sharding-spec typo or GSPMD "
                             f"propagation change re-materializes it "
                             f"every step"
                             + (f" ({c.source})" if c.source else "")),
                    data={"bytes": c.payload_bytes, "leaf": name,
                          "axes": axkey, "source": c.source}))
                break  # one finding per collective

    # redundant reshard: reduce-scatter directly downstream of an
    # all-gather on the same axes (gather immediately undone)
    for c in r.collectives:
        if c.kind != "reduce-scatter":
            continue
        local = defs.get(c.computation, {})
        seen, frontier = set(), list(c.operands)
        for _ in range(3):
            nxt = []
            for name in frontier:
                if name in seen:
                    continue
                seen.add(name)
                up = by_name.get(name)
                if up is not None and up.kind == "all-gather" \
                        and up.computation == c.computation:
                    ag_axes, _, _ = map_axes(up, info)
                    rs_axes, _, _ = map_axes(c, info)
                    if ag_axes == rs_axes:
                        r.findings.append(Finding(
                            "redundant-reshard", P1, label, "commplan",
                            anchor=f"{'+'.join(rs_axes) or 'none'}:"
                                   f"{c.payload_bytes}",
                            message=(f"all-gather immediately re-scattered "
                                     f"on axis "
                                     f"'{'+'.join(rs_axes) or 'none'}' "
                                     f"({up.payload_bytes}B gathered, "
                                     f"{c.payload_bytes}B shard) — the "
                                     f"round trip is pure waste"),
                            data={"gathered": up.payload_bytes,
                                  "shard": c.payload_bytes}))
                    continue
                d = local.get(name)
                if d is not None:
                    nxt.extend(d[1])
            frontier = nxt
    return r


def budget_findings(label: str, ledger: Dict[str, dict],
                    pinned: Optional[Dict[str, dict]],
                    tol: Optional[float] = None) -> List[Finding]:
    """Budget-drift pass: compare one geometry's ledger against its
    pinned baseline section. NEW axes, NEW collective kinds on a known
    axis, and bytes growth past ``tol`` are findings (P1); shrinkage is
    silent (re-pin to claim it). ``pinned`` None means the geometry has
    never been pinned — every axis reports as new."""
    if tol is None:
        tol = comm_tolerance()
    out: List[Finding] = []
    pinned = pinned or {}
    for axis, slot in sorted(ledger.items()):
        pin = pinned.get(axis)
        if pin is None:
            out.append(Finding(
                "comm-new-axis", P1, label, "commplan", anchor=axis,
                message=(f"collectives on unpinned axis '{axis}' "
                         f"({slot['ops']} op(s), {slot['bytes']}B/step) — "
                         f"new communication the budget never saw; "
                         f"re-pin with --write-baseline if intended"),
                data={"ops": slot["ops"], "bytes": slot["bytes"]}))
            continue
        for kind, n in sorted(slot["kinds"].items()):
            if kind not in pin.get("kinds", {}):
                out.append(Finding(
                    "comm-new-collective", P1, label, "commplan",
                    anchor=f"{axis}/{kind}",
                    message=(f"NEW collective kind {kind} (x{n}) on axis "
                             f"'{axis}' — the plan changed shape, not "
                             f"just size"),
                    data={"axis": axis, "kind": kind, "count": n}))
        if slot["bytes"] > pin.get("bytes", 0) * (1 + tol):
            out.append(Finding(
                "comm-budget-drift", P1, label, "commplan",
                anchor=axis,
                message=(f"axis '{axis}' moves {slot['bytes']}B/step, "
                         f"pinned {pin.get('bytes', 0)}B "
                         f"(+{tol:.0%} tolerance) — comm bytes grew past "
                         f"budget"),
                data={"bytes": slot["bytes"],
                      "pinned": pin.get("bytes", 0), "tol": tol}))
    return out
