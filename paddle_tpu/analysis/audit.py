"""Compiled-program audit: machine-checked invariants over real programs.

Prong 1 of the analysis subsystem (docs/ANALYSIS.md). Each audit takes a
*compiled* step — ``TrainStep`` or ``ServingEngine`` through their
``compiled_hlo()`` inspection seams — and runs the :mod:`.hlo` text
passes plus the host-side contract checks that need the step object:

- **collective census + bucketed-dp contract**: the bucketed path's HLO
  must carry exactly ``len(buckets) + 1`` all-reduces (one per bucket,
  one scalar-loss pmean — docs/PERFORMANCE.md). More means the
  per-param all-reduce storm is back (the GSPMD regression PR 7 counted
  by hand); fewer means a bucket got silently dropped.
- **donation coverage**: every train-param and optimizer-state leaf must
  alias an output buffer. An undonated hot buffer is the 2x-memory
  class — XLA keeps both the old and new copy live across the step.
- **upcasts + giant intermediates**: f32 ``convert``s reachable from
  bf16 inputs, and the largest instruction results (the ``[B, seq,
  vocab]`` logits tensor is the ROADMAP fused-CE target; its byte size
  is that item's before/after metric).
- **recompile diff** (:func:`diff_compile_keys`): name the exact
  aval/leaf two compile keys disagree on, instead of staring at two
  opaque cache keys.

Findings fingerprint against ``analysis/baseline.json`` like lint
findings; the numeric summary feeds ``bench.py --audit``'s report-gate
headlines.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from . import hlo as H
from .findings import Finding, P0, P1, P2

__all__ = ["ProgramAudit", "audit_program", "audit_train_step",
           "audit_serving_engine", "diff_compile_keys",
           "recompile_report", "train_step_arg_names"]

#: an undonated/upcast buffer below this size is noise, not a finding
#: (the tiny CPU-smoke geometries still produce meaningful reports
#: because the thresholds scale with the audited program via kwargs)
DEFAULT_LARGE_BYTES = 1 << 20

#: positional arg names of the compiled TrainStep ``pure`` function —
#: used to give HLO entry parameters human names (train['w'] etc.)
TRAIN_STEP_ARGS = ("train", "frozen", "buffers", "states", "group_lrs",
                   "rng", "batch")
SERVING_STEP_ARGS = ("state", "tokens", "k_pools", "v_pools",
                     "k_scales", "v_scales", "block_tables",
                     "cu_seqlens", "context_lens", "seq_ids", "positions",
                     "step_seq", "step_blk", "step_tile", "last_idx",
                     "adapter_ids", "prev_tokens", "src_rows", "sampling",
                     "key_base", "key_counts")


@dataclass
class ProgramAudit:
    """The audit result for one compiled program."""
    label: str
    collectives: Dict[str, int] = field(default_factory=dict)
    #: [(name, dtype, dims, nbytes, donated)] per entry parameter
    params: List[tuple] = field(default_factory=list)
    donated_bytes: int = 0
    undonated_bytes: int = 0
    #: requested-donation leaves that did NOT alias an output
    donation_misses: List[tuple] = field(default_factory=list)
    upcasts: List[H.HloOp] = field(default_factory=list)
    largest: List[H.HloOp] = field(default_factory=list)
    findings: List[Finding] = field(default_factory=list)

    @property
    def all_reduce_count(self) -> int:
        return self.collectives.get("all-reduce", 0)

    @property
    def largest_intermediate_bytes(self) -> int:
        return self.largest[0].nbytes if self.largest else 0

    @property
    def donation_coverage(self) -> float:
        """Donated fraction of the bytes that *should* be donated
        (donated + missed); 1.0 when nothing was expected."""
        missed = sum(nb for _, nb in self.donation_misses)
        want = self.donated_bytes + missed
        return self.donated_bytes / want if want else 1.0

    def summary(self) -> dict:
        return {
            "label": self.label,
            "all_reduce_count": self.all_reduce_count,
            "collectives": {k: v for k, v in self.collectives.items() if v},
            "donated_bytes": self.donated_bytes,
            "undonated_bytes": self.undonated_bytes,
            "donation_coverage": round(self.donation_coverage, 4),
            "donation_misses": [n for n, _ in self.donation_misses],
            "upcast_count": len(self.upcasts),
            "largest_intermediate_bytes": self.largest_intermediate_bytes,
            "largest_intermediates": [
                {"shape": o.shape, "op": o.opcode, "bytes": o.nbytes,
                 "source": o.source} for o in self.largest],
            "findings": [f.to_json() for f in self.findings],
        }


def _align_params(entry_params, leaves_with_names):
    """Match HLO entry parameters (kept args, in order) to flattened
    argument leaves (all args, in order): jit drops unused leaves at
    lowering, so alignment is a sequential merge on (dtype, dims)."""
    out = []
    li = 0
    for dtype, dims, nbytes in entry_params:
        name, donated = f"param{len(out)}", False
        scan = li
        while scan < len(leaves_with_names):
            lname, ldtype, ldims, ldonated = leaves_with_names[scan]
            scan += 1
            if ldtype == dtype and tuple(ldims) == tuple(dims):
                name, donated = lname, ldonated
                li = scan  # consume only up to the match
                break
        out.append((name, dtype, dims, nbytes, donated))
    return out


def _leaf_names(args_info, arg_names):
    """Flatten a ``Lowered.args_info`` pytree into
    ``[(name, dtype, dims, donation_requested)]`` in flatten order."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(args_info)
    out = []
    for path, info in leaves:
        label = jax.tree_util.keystr(path)
        # paths look like [0][0]['w'] — replace the leading positional
        # index with the human arg name
        if label.startswith("[0]["):
            rest = label[3:]
            idx_end = rest.index("]")
            try:
                pos = int(rest[1:idx_end])
                label = arg_names[pos] + rest[idx_end + 1:] \
                    if pos < len(arg_names) else label
            except ValueError:
                pass
        aval = info.aval if hasattr(info, "aval") else info._aval
        out.append((label, _hlo_dtype(aval.dtype), tuple(aval.shape),
                    bool(getattr(info, "donated", False))))
    return out


def _hlo_dtype(np_dtype) -> str:
    """numpy/jax dtype → HLO dtype token (float32 → f32)."""
    s = str(np_dtype)
    table = {"float32": "f32", "float64": "f64", "float16": "f16",
             "bfloat16": "bf16", "int8": "s8", "int16": "s16",
             "int32": "s32", "int64": "s64", "uint8": "u8",
             "uint16": "u16", "uint32": "u32", "uint64": "u64",
             "bool": "pred", "complex64": "c64", "complex128": "c128"}
    return table.get(s, s)


def audit_program(hlo_text: str, label: str, args_info=None,
                  arg_names: Tuple[str, ...] = (),
                  expected_donated_prefixes: Tuple[str, ...] = (),
                  large_bytes: int = DEFAULT_LARGE_BYTES,
                  expected_all_reduce: Optional[int] = None,
                  top: int = 5) -> ProgramAudit:
    """Run every HLO pass over one compiled program.

    ``expected_donated_prefixes``: leaf-name prefixes (e.g. ``train``,
    ``states``) whose buffers the program contract says must be donated;
    a leaf under them that doesn't alias an output is a finding even
    when donation was never *requested* (the ``donate=False`` class).
    ``expected_all_reduce``: the bucketed-dp contract count
    (``buckets + 1``); ``None`` skips the contract check.
    """
    a = ProgramAudit(label=label)
    a.collectives = H.collective_census(hlo_text)
    entry = H.parse_entry_params(hlo_text)
    donated_idx = H.donated_params(hlo_text)

    if args_info is not None:
        leaves = _leaf_names(args_info, arg_names)
        aligned = _align_params(entry, leaves)
    else:
        aligned = [(f"param{i}", d, dims, nb, False)
                   for i, (d, dims, nb) in enumerate(entry)]

    for i, (name, dtype, dims, nbytes, requested) in enumerate(aligned):
        donated = i in donated_idx
        a.params.append((name, dtype, dims, nbytes, donated))
        if donated:
            a.donated_bytes += nbytes
        else:
            a.undonated_bytes += nbytes
            expected = requested or any(
                name == p or name.startswith(p + "[")
                for p in expected_donated_prefixes)
            if expected:
                a.donation_misses.append((name, nbytes))
                if nbytes >= large_bytes:
                    a.findings.append(Finding(
                        "undonated-buffer", P0, label, "donation", anchor=name,
                        message=(f"{name} ({dtype}{list(dims)}, {nbytes} "
                                 f"bytes) should be donated but does not "
                                 f"alias any output — the step keeps two "
                                 f"copies live (the 2x-memory class)"),
                        data={"bytes": nbytes}))

    ops = H.iter_ops(hlo_text)  # ONE parse shared by the text passes
    a.upcasts = H.upcast_ops(hlo_text, min_bytes=large_bytes, ops=ops)
    for op in a.upcasts:
        a.findings.append(Finding(
            "f32-upcast", P1, label, "dtype", anchor=op.shape,
            message=(f"{op.nbytes}-byte f32 intermediate {op.shape} "
                     f"converted from a narrower float"
                     + (f" at {op.source}" if op.source else "")),
            data={"bytes": op.nbytes, "source": op.source}))

    a.largest = H.largest_ops(hlo_text, top=top, ops=ops)

    if expected_all_reduce is not None \
            and a.all_reduce_count != expected_all_reduce:
        kind = "storm" if a.all_reduce_count > expected_all_reduce \
            else "missing-reduction"
        a.findings.append(Finding(
            "allreduce-contract", P0, label, "collectives",
            anchor=kind,
            message=(f"{a.all_reduce_count} all-reduces, contract says "
                     f"{expected_all_reduce} (buckets + 1) — "
                     + ("per-param collective storm is back"
                        if kind == "storm" else
                        "a bucket reduction disappeared")),
            data={"count": a.all_reduce_count,
                  "expected": expected_all_reduce}))
    return a


def train_step_arg_names() -> Tuple[str, ...]:
    return TRAIN_STEP_ARGS


def audit_train_step(step, *args, large_bytes: int = DEFAULT_LARGE_BYTES,
                     expected_all_reduce: Optional[int] = None,
                     label: str = "train_step",
                     top: int = 5, **kwargs) -> ProgramAudit:
    """Audit one ``jit.TrainStep`` on a concrete batch.

    RNG-neutral like ``TrainStep.compiled_hlo`` (the step never runs;
    the key stream is restored), and contract-aware:

    - all-reduce census vs ``len(step._comm_buckets) + 1`` when the
      bucketed dp path is active, or vs an explicit
      ``expected_all_reduce`` (pass the reference plan's count to catch
      a step that silently fell back to the per-param GSPMD storm);
    - train-param and optimizer-state leaves are ALWAYS expected to be
      donated — a ``donate=False`` step or an XLA-dropped donation is
      exactly the 2x-memory class this pass exists for.
    """
    from paddle_tpu.core import generator as _gen

    rng_state = _gen.get_rng_state()
    try:
        _, compiled, call_args = step._prepare(args, kwargs)
        lowered = compiled.lower(*call_args)
        hlo_text = lowered.compile().as_text()
        args_info = lowered.args_info
    finally:
        _gen.set_rng_state(rng_state)

    expected = expected_all_reduce
    if expected is None and step._comm_buckets is not None:
        expected = len(step._comm_buckets) + 1
    return audit_program(
        hlo_text, label, args_info=args_info,
        arg_names=TRAIN_STEP_ARGS,
        expected_donated_prefixes=("train", "states"),
        large_bytes=large_bytes, expected_all_reduce=expected, top=top)


def audit_serving_engine(engine, large_bytes: int = DEFAULT_LARGE_BYTES,
                         top: int = 5) -> ProgramAudit:
    """Audit the engine's ONE unified serving step (via the
    ``compiled_hlo``/``_lowered_step`` seam — state-neutral, see
    serving/engine.py). ``args_info`` from the lowering names the
    entry parameters (``k_pools[3]``, ``state['...']``, ``tokens``).

    Donation expectations: the KV pools are donated on TPU only (the
    CPU runtime can't honor donation), so pool donation is asserted
    only where the engine requested it — a TPU engine whose pools stop
    aliasing their outputs is the 2x-KV-memory class."""
    import jax

    lowered = engine._lowered_step()
    hlo_text = lowered.compile().as_text()
    prefixes = ("k_pools", "v_pools") \
        if jax.default_backend() == "tpu" else ()
    return audit_program(
        hlo_text, "serving_step", args_info=lowered.args_info,
        arg_names=SERVING_STEP_ARGS, expected_donated_prefixes=prefixes,
        large_bytes=large_bytes, top=top)


# -- recompile diff ---------------------------------------------------------

def _sig_leaf_names(treedef) -> List[str]:
    """Leaf path names for one compile key's batch treedef."""
    import jax

    n = treedef.num_leaves
    tree = jax.tree_util.tree_unflatten(treedef, list(range(n)))
    named = sorted(jax.tree_util.tree_flatten_with_path(tree)[0],
                   key=lambda kv: kv[1])
    return [jax.tree_util.keystr(p) for p, _ in named]


def diff_compile_keys(key_a, key_b) -> List[str]:
    """Human-readable difference between two ``TrainStep`` compile keys
    ``(treedef, sig, training, train_names, instrument)`` — names the
    exact leaf whose structure/shape/dtype changed, the mode flip, the
    trainable-set change, or the numerics-instrumentation flip that
    forced the recompilation."""
    treedef_a, sig_a, training_a, train_a = key_a[:4]
    treedef_b, sig_b, training_b, train_b = key_b[:4]
    # 4-tuple keys predate the instrumentation flag; treat as disarmed
    inst_a = key_a[4] if len(key_a) > 4 else False
    inst_b = key_b[4] if len(key_b) > 4 else False
    out = []
    if training_a != training_b:
        out.append(f"model mode changed: training={training_a} -> "
                   f"{training_b}")
    if inst_a != inst_b:
        # the expected sampled-twin retrace, not a perf smell
        # (docs/OBSERVABILITY.md#numerics)
        out.append(f"numerics instrumentation changed: {inst_a} -> "
                   f"{inst_b}")
    if train_a != train_b:
        frozen = sorted(set(train_a) - set(train_b))
        unfrozen = sorted(set(train_b) - set(train_a))
        if frozen:
            out.append(f"params left the trainable set: {frozen}")
        if unfrozen:
            out.append(f"params entered the trainable set: {unfrozen}")
    if treedef_a != treedef_b:
        out.append(f"batch structure changed: {treedef_a} -> {treedef_b}")
        return out  # leaf-wise sig comparison is meaningless across trees
    if sig_a != sig_b:
        names = _sig_leaf_names(treedef_a)
        for i, (la, lb) in enumerate(zip(sig_a, sig_b)):
            if la == lb:
                continue
            name = names[i] if i < len(names) else f"leaf[{i}]"
            out.append(f"batch leaf {name}: {_fmt_sig(la)} -> "
                       f"{_fmt_sig(lb)}")
    return out or ["keys are identical"]


def _fmt_sig(leaf_sig) -> str:
    if leaf_sig and leaf_sig[0] in ("T", "A") and len(leaf_sig) == 3:
        _, shape, dtype = leaf_sig
        return f"{dtype}{list(shape)}"
    return repr(leaf_sig)


def recompile_report(step) -> List[dict]:
    """Why each retrace after the first happened: consecutive compile-key
    diffs over the step's cache, in insertion order. Empty when the step
    compiled at most once — the healthy steady state."""
    keys = list(step._cache.keys())
    out = []
    for prev, cur in zip(keys, keys[1:]):
        out.append({"causes": diff_compile_keys(prev, cur)})
    return out
