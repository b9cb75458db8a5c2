"""Compiled-HLO text analysis: the lowered program as a readable artifact.

Every pass here is pure text → data over ``Compiled.as_text()`` output
(the string ``TrainStep.compiled_hlo()`` / ``ServingEngine.compiled_hlo()``
return), so the audits run identically on the CPU smoke box and on chip,
need no XLA internals, and can be unit-tested on doctored fragments.

What the text reliably carries (verified on the pinned jax):

- the module header's ``input_output_alias={ {out}: (param, {...}, kind) }``
  map — buffer donation survives into the compiled module even on CPU,
  where the runtime ignores it;
- ``entry_computation_layout={(<param shapes>)->(<result shapes>)}`` —
  one entry per flattened argument leaf, in ``jax.tree_util`` flatten
  order (which is how :mod:`paddle_tpu.analysis.audit` names leaves);
- one instruction per line, ``%name = dtype[dims]{layout} op(...)``,
  with collective ops spelled ``all-reduce`` / ``all-reduce-start`` /
  ``all-gather`` / ``reduce-scatter`` / ``collective-permute`` /
  ``all-to-all`` and ``metadata={... source_file=... source_line=...}``
  attribution where available.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = ["parse_entry_params", "donated_params", "collective_census",
           "iter_ops", "shape_bytes", "upcast_ops", "largest_ops",
           "HloOp"]

#: bytes per element for HLO dtype tokens (tokens not listed — tuples,
#: opaque, token — contribute 0, i.e. are never "large")
DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
#: `%name = dtype[shape]{layout} opname(` — the instruction form; the
#: leading %/ROOT guard keeps computation headers and operands out
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*"
    r"(?:\(.*?\)|([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?)\s*"
    r"([a-z][a-z0-9\-]*)\(")
#: XLA interleaves ``/*index=5*/`` position comments into long tuple
#: types and layouts; their ``=`` ends a result type early for a pattern
COMMENT_RE = re.compile(r"/\*.*?\*/")
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\((\d+)")
_ENTRY_RE = re.compile(r"entry_computation_layout=\{\((.*?)\)->")
_METADATA_RE = re.compile(
    r'source_file="([^"]*)"(?:\s+source_line=(\d+))?')

#: collective instruction stems (async forms counted once via -start;
#: *-done carries no second payload)
COLLECTIVE_STEMS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")


def shape_bytes(dtype: str, dims_csv: str) -> int:
    """Byte size of ``dtype[dims]`` (scalar when dims empty)."""
    unit = DTYPE_BYTES.get(dtype, 0)
    if not dims_csv:
        return unit
    n = 1
    for d in dims_csv.split(","):
        if d:
            n *= int(d)
    return n * unit


@dataclass
class HloOp:
    """One parsed instruction line."""
    opcode: str
    dtype: str
    dims: Tuple[int, ...]
    nbytes: int
    line: str
    source: str = ""  # "file:line" from metadata when present

    @property
    def shape(self) -> str:
        return f"{self.dtype}[{','.join(map(str, self.dims))}]"


def iter_ops(hlo_text: str) -> List[HloOp]:
    """Every instruction with a single (non-tuple) array result."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m or m.group(1) is None:
            continue
        dtype, dims_csv, opcode = m.group(1), m.group(2), m.group(3)
        dims = tuple(int(d) for d in dims_csv.split(",") if d)
        src = ""
        sm = _METADATA_RE.search(line)
        if sm:
            src = sm.group(1) + (f":{sm.group(2)}" if sm.group(2) else "")
        out.append(HloOp(opcode, dtype, dims,
                         shape_bytes(dtype, dims_csv), line.strip(), src))
    return out


def _split_top(s: str) -> List[str]:
    """Split on commas at bracket depth 0 (shapes carry commas inside
    both ``[...]`` and layout ``{...}``)."""
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        parts.append(tail)
    return parts


def parse_entry_params(hlo_text: str) -> List[Tuple[str, Tuple[int, ...],
                                                    int]]:
    """``[(dtype, dims, nbytes)]`` per entry parameter, in parameter
    order — one entry per flattened argument leaf."""
    m = _ENTRY_RE.search(hlo_text)
    if not m:
        return []
    body = COMMENT_RE.sub("", m.group(1))
    out = []
    for tok in _split_top(body):
        sm = _SHAPE_RE.match(tok)
        if not sm:
            out.append(("opaque", (), 0))
            continue
        dtype, dims_csv = sm.group(1), sm.group(2)
        dims = tuple(int(d) for d in dims_csv.split(",") if d)
        out.append((dtype, dims, shape_bytes(dtype, dims_csv)))
    return out


def _balanced_braces(text: str, start: int) -> str:
    """Content of the ``{...}`` group opening at ``text[start]`` (the
    alias map nests braces, so a regex can't delimit it)."""
    assert text[start] == "{"
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
            if depth == 0:
                return text[start + 1:i]
    return text[start + 1:]


def donated_params(hlo_text: str) -> set:
    """Parameter numbers that alias an output (i.e. whose buffer the
    donation actually landed in)."""
    key = "input_output_alias="
    i = hlo_text.find(key)
    if i < 0:
        return set()
    body = _balanced_braces(hlo_text, i + len(key))
    return {int(g) for g in _ALIAS_ENTRY_RE.findall(body)}


def collective_census(hlo_text: str) -> Dict[str, int]:
    """Count per collective stem. Async pairs count once (``-start``
    carries the payload; ``-done`` is just the wait). An all-reduce
    counts once per buffer it reduces: XLA's combiner packs independent
    all-reduces into one variadic instruction with a tuple result, more
    of them on a small program than a large one, and the contracts read
    here (``buckets + 1`` payloads, a per-parameter storm) are about what
    is reduced, not how the compiler grouped it."""
    census = {stem: 0 for stem in COLLECTIVE_STEMS}
    hlo_text = COMMENT_RE.sub("", hlo_text)
    for stem in COLLECTIVE_STEMS:
        for result in re.findall(
                rf"= ([^=]*)\b{stem}(?:-start)?\(", hlo_text):
            census[stem] += len(_SHAPE_RE.findall(result)) \
                if stem == "all-reduce" else 1
    return census


def upcast_ops(hlo_text: str, min_bytes: int = 0,
               ops: Optional[List[HloOp]] = None) -> List[HloOp]:
    """``convert`` instructions producing f32/f64 from a narrower float
    operand — the silent-upcast class (a bf16 model paying f32 memory
    bandwidth for an intermediate it never asked for). ``ops`` reuses
    a prior :func:`iter_ops` parse (the text can be tens of MB on the
    chip geometry)."""
    out, dtype_of = [], {}
    for op in (iter_ops(hlo_text) if ops is None else ops):
        name = re.match(r"(?:ROOT\s+)?(%[\w.\-]+)", op.line)
        if name:
            dtype_of[name.group(1)] = op.dtype
        if op.opcode != "convert" or op.dtype not in ("f32", "f64"):
            continue
        if op.nbytes < min_bytes:
            continue
        # the operand's dtype rides the line (``convert(bf16[...] %x)``)
        # or, where XLA prints operands bare (``convert(%x)``), is that
        # of the operand's own definition earlier in its computation
        m = re.search(r"convert\((?:([a-z][a-z0-9]*)\[\S* )?(%[\w.\-]+)",
                      op.line)
        if not m or (m.group(1) or dtype_of.get(m.group(2))) not in (
                "bf16", "f16", "f8e4m3fn", "f8e5m2"):
            continue
        out.append(op)
    return out


def largest_ops(hlo_text: str, top: int = 5,
                exclude: Tuple[str, ...] = ("parameter",),
                ops: Optional[List[HloOp]] = None) -> List[HloOp]:
    """The ``top`` largest instruction results by bytes — the giant-
    intermediate detector (a ``[B, seq, vocab]`` logits tensor dwarfs
    everything else in a train step)."""
    pool = [o for o in (iter_ops(hlo_text) if ops is None else ops)
            if o.opcode not in exclude]
    pool.sort(key=lambda o: o.nbytes, reverse=True)
    return pool[:top]
