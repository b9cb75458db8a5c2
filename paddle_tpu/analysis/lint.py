"""Trace-safety and concurrency lint: the paid-for bug classes, as AST rules.

Prong 2 of the analysis subsystem (docs/ANALYSIS.md). Every rule here
codifies a bug this repo actually shipped and then fixed the hard way:

- ``gc-eager-jax`` (P0): jax/jnp array ops reachable from ``__del__``
  outside ``jax.core.eval_context()``. A GC-time flush that runs while
  *another* function is being traced stages its ops into that foreign
  trace and leaks tracers into live state (the nastiest bug of PR 7 —
  ``TrainStep.__del__`` → ``_flush_flat`` → jnp split).
- ``signal-unsafe-call`` (P0): lock/Event/Condition acquisition or
  metrics calls inside a signal handler. A handler that takes a lock
  deadlocks when the signal interrupts the main thread *holding* it
  (PR 4: preemption handlers write plain GIL-atomic attributes only).
- ``trace-attr-mutation`` (P0): assignment to ``self.<attr>`` inside a
  function that jax traces. The write happens once at trace time — or
  worse, stores a tracer on the object (the removed ``opt._cur_param``
  side channel).
- ``traced-impurity`` (P1): wall-clock / host-randomness calls inside
  traced functions — the value is baked at trace time, silently frozen
  across every subsequent step.
- ``unjoined-thread`` (P1): a non-daemon thread started but never
  joined anywhere in its module — blocks interpreter exit and leaks
  work past the owner's lifetime.
- ``blocking-call-under-lock`` (P0): ``time.sleep``, timeout-less
  ``.join()``/``.result()``/``.get()``/``.wait()`` inside a
  ``with <lock>`` body (depth-2 callees included) — the serving/
  prefetch stall class PR 4/5 paid for at runtime: whoever else wants
  that lock now waits on an unbounded sleep or join.
- ``stale-suppression`` (P2, advisory unless ``--strict-suppressions``):
  an ``# analysis: allow(<rule>)`` comment that no longer suppresses
  anything — allow-rot; either the flagged code was fixed (delete the
  comment) or the comment drifted away from the finding line.

The linter is deliberately *lexical*: it resolves calls one–two levels
deep within the same class/module and never imports the code it scans,
so it runs in milliseconds over the whole tree and can't be crashed by
import-time side effects. Cross-module reachability is out of scope —
the fixture tests in tests/test_analysis.py document the supported
shapes.

Suppression: a finding whose own line or enclosing ``def`` line carries
``# analysis: allow(<rule>)`` is intentionally accepted in place (use
for the rare case where the flagged pattern is the point, e.g. the
serving engine's trace-time compile counter). Everything else gates
against ``analysis/baseline.json`` fingerprints.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from .findings import Finding, P0, P1, iter_py_files

__all__ = ["lint_file", "lint_tree", "RULES"]

RULES = ("gc-eager-jax", "signal-unsafe-call", "trace-attr-mutation",
         "traced-impurity", "unjoined-thread", "blocking-call-under-lock",
         "stale-suppression")

#: dotted-name suffixes whose first argument is traced by jax
_TRACE_WRAPPERS = ("jax.jit", "jit", "jax.value_and_grad",
                   "value_and_grad", "jax.grad", "shard_map",
                   "pallas_call", "jax.vmap", "vmap",
                   "jax.checkpoint", "jax.remat")
#: wall-clock / host-randomness dotted names (exact or prefix.)
_IMPURE_EXACT = {"time.time", "time.time_ns", "time.perf_counter",
                 "time.perf_counter_ns", "time.monotonic",
                 "time.monotonic_ns", "datetime.now", "datetime.utcnow",
                 "datetime.datetime.now", "datetime.datetime.utcnow"}
_IMPURE_RANDOM_FNS = {"random", "randint", "randn", "rand", "choice",
                      "uniform", "normal", "shuffle", "sample", "seed",
                      "permutation"}
#: method names whose invocation inside a signal handler can deadlock
#: (lock/CV traffic) or take the metrics-registry lock
_SIGNAL_UNSAFE_METHODS = {"acquire": "lock acquisition",
                          "wait": "condition/event wait",
                          "notify": "condition notify",
                          "notify_all": "condition notify",
                          "join": "thread join",
                          "inc": "metrics-registry lock",
                          "observe": "metrics-registry lock"}
_THREADING_PRIMITIVES = {"Lock", "RLock", "Condition", "Event",
                         "Semaphore", "BoundedSemaphore", "Barrier"}


def _dotted(node) -> Optional[str]:
    """``a.b.c`` for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> Optional[str]:
    return _dotted(call.func)


class _Module:
    """Parsed module with the cheap indexes every rule shares."""

    def __init__(self, path: str, rel: str, text: str):
        self.path, self.rel = path, rel
        self.lines = text.splitlines()
        self.tree = ast.parse(text)
        # qualname per function node + name -> nodes index
        self.funcs: List[Tuple[ast.AST, str, Optional[str]]] = []
        self.by_name: Dict[str, List[ast.AST]] = {}
        self.methods: Dict[str, Dict[str, ast.AST]] = {}  # class -> name
        self.qual: Dict[ast.AST, str] = {}
        self.jnp_roots: Set[str] = set()
        self.np_aliases: Set[str] = set()
        #: ways signal.signal is callable here: "<alias>.signal"
        #: attribute forms and bare names from `from signal import ...`
        self.signal_attr_roots: Set[str] = {"signal"}
        self.signal_bare_names: Set[str] = set()
        #: (lineno, rule) allow-comments that suppressed something
        self.used_allows: Set[Tuple[int, str]] = set()
        self._index()

    def _index(self):
        def walk(node, prefix, cls):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    q = f"{prefix}{child.name}"
                    self.funcs.append((child, q, cls))
                    self.qual[child] = q
                    self.by_name.setdefault(child.name, []).append(child)
                    if cls is not None and "." not in q[len(cls) + 1:]:
                        self.methods.setdefault(cls, {})[child.name] = child
                    walk(child, q + ".", cls)
                elif isinstance(child, ast.ClassDef):
                    self.methods.setdefault(child.name, {})
                    walk(child, child.name + ".", child.name)
                else:
                    walk(child, prefix, cls)
        walk(self.tree, "", None)

        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    alias = a.asname or a.name.split(".")[0]
                    if a.name in ("jax.numpy",):
                        self.jnp_roots.add(a.asname or "jax.numpy")
                    elif a.name == "numpy":
                        self.np_aliases.add(alias)
                    elif a.name == "signal":
                        self.signal_attr_roots.add(alias)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "jax":
                    for a in node.names:
                        if a.name == "numpy":
                            self.jnp_roots.add(a.asname or "numpy")
                elif node.module == "signal":
                    for a in node.names:
                        if a.name == "signal":
                            self.signal_bare_names.add(
                                a.asname or "signal")
        # `import jax` makes jax.numpy/jax.lax reachable by full path
        self.jnp_roots.update({"jnp", "jax.numpy"})

    def suppressed(self, rule: str, *linenos: int) -> bool:
        hit = False
        for ln in linenos:
            if 0 < ln <= len(self.lines) \
                    and f"analysis: allow({rule})" in self.lines[ln - 1]:
                # record every match so the stale-suppression pass knows
                # which allow comments actually earn their keep
                self.used_allows.add((ln, rule))
                hit = True
        return hit

    def resolve(self, name: str) -> List[ast.AST]:
        return self.by_name.get(name, [])

    def resolve_method(self, cls: Optional[str], name: str) \
            -> Optional[ast.AST]:
        if cls and name in self.methods.get(cls, {}):
            return self.methods[cls][name]
        return None


# -- traced-function rules --------------------------------------------------

def _traced_functions(mod: _Module) -> List[Tuple[ast.AST, str]]:
    """Functions (and lambdas) whose body jax traces: first args of the
    wrapper calls + decorated defs, plus their lexically nested defs."""
    roots: List[ast.AST] = []

    def wrapped_arg(call: ast.Call):
        name = _call_name(call)
        if name is None:
            return None
        if not any(name == w or name.endswith("." + w)
                   for w in _TRACE_WRAPPERS):
            return None
        if not call.args:
            return None
        arg = call.args[0]
        # functools.partial(kernel, ...) -> kernel
        if isinstance(arg, ast.Call) and (_call_name(arg) or "").endswith(
                "partial") and arg.args:
            arg = arg.args[0]
        return arg

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            arg = wrapped_arg(node)
            if isinstance(arg, ast.Name):
                roots.extend(mod.resolve(arg.id))
            elif isinstance(arg, ast.Lambda):
                roots.append(arg)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                name = _dotted(d) or ""
                if any(name == w or name.endswith("." + w)
                       for w in _TRACE_WRAPPERS):
                    roots.append(node)

    out, seen = [], set()
    for root in roots:
        for node in ast.walk(root):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and id(node) not in seen:
                seen.add(id(node))
                q = mod.qual.get(node, getattr(node, "name", "<lambda>"))
                out.append((node, q))
    return out


def _own_nodes(fn):
    """Nodes of ``fn``'s body excluding nested function/lambda subtrees
    (those are scanned under their own qualname — no double reports)."""
    out = []
    stack = list(fn.body) if isinstance(fn.body, list) else [fn.body]
    while stack:
        node = stack.pop()
        out.append(node)
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                stack.append(child)
    return out


def _check_traced(mod: _Module, findings: List[Finding]):
    for fn, qual in _traced_functions(mod):
        def_line = getattr(fn, "lineno", 0)
        for node in _own_nodes(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if isinstance(t, ast.Attribute) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        if mod.suppressed("trace-attr-mutation",
                                          node.lineno, def_line):
                            continue
                        findings.append(Finding(
                            "trace-attr-mutation", P0, mod.rel, qual,
                            anchor=t.attr, line=node.lineno,
                            message=(f"self.{t.attr} assigned inside a "
                                     f"jax-traced function — runs once at "
                                     f"trace time and can leak tracers "
                                     f"into live state (the _cur_param "
                                     f"class)")))
            elif isinstance(node, ast.Call):
                name = _call_name(node) or ""
                impure = name in _IMPURE_EXACT
                if not impure and "." in name:
                    root, leaf = name.rsplit(".", 1)
                    if leaf in _IMPURE_RANDOM_FNS and (
                            root == "random"
                            or root.endswith(".random")
                            or root in {f"{a}.random"
                                        for a in mod.np_aliases}):
                        impure = True
                if impure:
                    if mod.suppressed("traced-impurity", node.lineno,
                                      def_line):
                        continue
                    findings.append(Finding(
                        "traced-impurity", P1, mod.rel, qual,
                        anchor=name, line=node.lineno,
                        message=(f"{name}() inside a jax-traced function "
                                 f"— evaluated once at trace time, frozen "
                                 f"into the compiled program")))


# -- __del__ reachability ---------------------------------------------------

def _check_gc_paths(mod: _Module, findings: List[Finding]):
    for cls, methods in mod.methods.items():
        dtor = methods.get("__del__")
        if dtor is None:
            continue
        # BFS self.<m>() within the class plus module-level Name calls
        seen: Set[int] = set()
        frontier = [(dtor, mod.qual.get(dtor, f"{cls}.__del__"))]
        depth = 0
        while frontier and depth <= 3:
            nxt = []
            for fn, qual in frontier:
                if id(fn) in seen:
                    continue
                seen.add(id(fn))
                _scan_eager_jax(mod, fn, qual, cls, findings, nxt)
            frontier, depth = nxt, depth + 1


def _scan_eager_jax(mod: _Module, fn, qual, cls, findings, frontier):
    """Flag jnp/jax.lax/jax.random calls in ``fn`` not under
    ``eval_context``; queue same-class/module callees. The guard flag
    follows arbitrary nesting (an ``eval_context`` with-block under an
    ``if``/``try`` still guards its body)."""
    def visit(node, guarded):
        if isinstance(node, ast.With):
            g = guarded
            for item in node.items:
                nm = _call_name(item.context_expr) \
                    if isinstance(item.context_expr, ast.Call) \
                    else _dotted(item.context_expr)
                if nm and "eval_context" in nm:
                    g = True
                visit(item.context_expr, guarded)
            for child in node.body:
                visit(child, g)
            return
        if isinstance(node, ast.Call):
            name = _call_name(node) or ""
            root = name.rsplit(".", 1)[0] if "." in name else ""
            if not guarded and (root in mod.jnp_roots
                                or root in ("jax.lax", "jax.random",
                                            "lax")
                                or name.startswith("jax.numpy.")):
                if not mod.suppressed("gc-eager-jax", node.lineno,
                                      getattr(fn, "lineno", 0)):
                    findings.append(Finding(
                        "gc-eager-jax", P0, mod.rel, qual,
                        anchor=name, line=node.lineno,
                        message=(f"{name}() reachable from __del__ "
                                 f"outside jax.core.eval_context() — "
                                 f"a GC-time run during another "
                                 f"function's trace stages ops into "
                                 f"that trace (the PR 7 flush leak)")))
            # queue callees (self.m() / module fn) for the BFS
            if isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "self":
                callee = mod.resolve_method(cls, node.func.attr)
                if callee is not None:
                    frontier.append(
                        (callee, mod.qual.get(callee, node.func.attr)))
            elif isinstance(node.func, ast.Name):
                for callee in mod.resolve(node.func.id):
                    frontier.append(
                        (callee, mod.qual.get(callee, node.func.id)))
        for child in ast.iter_child_nodes(node):
            visit(child, guarded)

    for stmt in (fn.body if isinstance(fn.body, list) else [fn.body]):
        visit(stmt, False)


# -- signal handlers --------------------------------------------------------

def _handler_nodes(mod: _Module):
    """(handler_fn_node, qualname, class) for every function installed
    via ``signal.signal(signum, handler)``."""
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node) or ""
        registers = (
            name in mod.signal_bare_names                # from signal import signal
            or any(name == f"{root}.signal"
                   for root in mod.signal_attr_roots)    # signal/sig.signal
            or name.split(".")[-2:] == ["signal", "signal"])
        if not registers:
            continue
        if len(node.args) < 2:
            continue
        h = node.args[1]
        if isinstance(h, ast.Attribute) and isinstance(h.value, ast.Name) \
                and h.value.id == "self":
            # enclosing class: find the method whose body contains node
            for cls, methods in mod.methods.items():
                m = methods.get(h.attr)
                if m is not None:
                    out.append((m, mod.qual.get(m, h.attr), cls))
        elif isinstance(h, ast.Name):
            for fn in mod.resolve(h.id):
                out.append((fn, mod.qual.get(fn, h.id), None))
        elif isinstance(h, ast.Lambda):
            out.append((h, "<lambda handler>", None))
    return out


def _check_signal_handlers(mod: _Module, findings: List[Finding]):
    for handler, qual, cls in _handler_nodes(mod):
        seen: Set[int] = set()
        frontier = [(handler, qual)]
        depth = 0
        while frontier and depth <= 2:
            nxt = []
            for fn, q in frontier:
                if id(fn) in seen:
                    continue
                seen.add(id(fn))
                _scan_signal_unsafe(mod, fn, q, cls, findings, nxt)
            frontier, depth = nxt, depth + 1


def _scan_signal_unsafe(mod: _Module, fn, qual, cls, findings, frontier):
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    def_line = getattr(fn, "lineno", 0)
    for node in [n for stmt in body for n in ast.walk(stmt)]:
        if isinstance(node, ast.With):
            for item in node.items:
                nm = (_call_name(item.context_expr)
                      if isinstance(item.context_expr, ast.Call)
                      else _dotted(item.context_expr)) or ""
                leaf = nm.split(".")[-1].lower()
                if "lock" in leaf or leaf in ("_cv", "cv", "cond",
                                              "condition"):
                    if not mod.suppressed("signal-unsafe-call",
                                          node.lineno, def_line):
                        findings.append(Finding(
                            "signal-unsafe-call", P0, mod.rel, qual,
                            anchor=f"with:{nm}", line=node.lineno,
                            message=(f"`with {nm}` in signal-handler "
                                     f"context — deadlocks when the "
                                     f"signal interrupts the holder")))
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node) or ""
        leaf = name.split(".")[-1]
        reason = None
        if isinstance(node.func, ast.Attribute) \
                and leaf in _SIGNAL_UNSAFE_METHODS:
            reason = _SIGNAL_UNSAFE_METHODS[leaf]
        elif leaf in _THREADING_PRIMITIVES and (
                name == leaf or name.startswith("threading.")):
            reason = "threading-primitive construction"
        if reason is not None:
            if not mod.suppressed("signal-unsafe-call", node.lineno,
                                  def_line):
                findings.append(Finding(
                    "signal-unsafe-call", P0, mod.rel, qual,
                    anchor=name, line=node.lineno,
                    message=(f"{name}() in signal-handler context "
                             f"({reason}) — only plain GIL-atomic "
                             f"attribute writes are safe; defer the "
                             f"rest to the next poll")))
        # follow self.m() / module-fn callees
        if isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "self":
            callee = mod.resolve_method(cls, node.func.attr)
            if callee is not None:
                frontier.append((callee,
                                 mod.qual.get(callee, node.func.attr)))
        elif isinstance(node.func, ast.Name):
            for callee in mod.resolve(node.func.id):
                frontier.append((callee,
                                 mod.qual.get(callee, node.func.id)))


# -- blocking calls under a lock --------------------------------------------

def _lock_item_names(node: ast.With) -> List[str]:
    """Dotted names of with-items that look like lock/CV acquisitions
    (same heuristic the signal pass uses — ONE definition of 'lock')."""
    names = []
    for item in node.items:
        nm = (_call_name(item.context_expr)
              if isinstance(item.context_expr, ast.Call)
              else _dotted(item.context_expr)) or ""
        leaf = nm.split(".")[-1].lower()
        if "lock" in leaf or leaf in ("_cv", "cv", "cond", "condition"):
            names.append(nm)
    return names


def _blocking_reason(node: ast.Call, lock_names) -> Optional[str]:
    """Why this call must not run while holding a lock, or None."""
    name = _call_name(node) or ""
    if name in ("time.sleep", "sleep"):
        return "sleeps while holding the lock"
    if not isinstance(node.func, ast.Attribute):
        return None
    leaf = name.split(".")[-1]
    has_timeout = bool(node.args) or any(
        kw.arg == "timeout" for kw in node.keywords)
    if leaf == "join" and not has_timeout:
        return "timeout-less .join() blocks until the thread exits"
    if leaf == "result" and not has_timeout:
        return "timeout-less Future.result() blocks on the executor"
    if leaf == "get" and not node.args and not node.keywords:
        return "timeout-less Queue.get() blocks until a producer runs"
    if leaf == "wait" and not has_timeout:
        # cv.wait() on the with-item itself RELEASES that lock while
        # waiting — the canonical condition-variable pattern, not a hold
        if (_dotted(node.func.value) or "") in lock_names:
            return None
        return "timeout-less .wait() holds the lock across the wait"
    return None


def _scan_blocking(mod: _Module, nodes, qual, cls, lock_names, lock_name,
                   sup_lines, findings, frontier):
    for node in nodes:
        if not isinstance(node, ast.Call):
            continue
        reason = _blocking_reason(node, lock_names)
        if reason is not None:
            name = _call_name(node) or "<call>"
            if not mod.suppressed("blocking-call-under-lock",
                                  node.lineno, *sup_lines):
                findings.append(Finding(
                    "blocking-call-under-lock", P0, mod.rel, qual,
                    anchor=f"{lock_name}:{name}", line=node.lineno,
                    message=(f"{name}() inside `with {lock_name}` — "
                             f"{reason}; every other taker of the lock "
                             f"stalls behind it (the serving/prefetch "
                             f"deadlock class)")))
        # queue self.m() / module-fn callees: they run under the lock too
        if isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "self":
            callee = mod.resolve_method(cls, node.func.attr)
            if callee is not None:
                frontier.append((callee,
                                 mod.qual.get(callee, node.func.attr)))
        elif isinstance(node.func, ast.Name):
            for callee in mod.resolve(node.func.id):
                frontier.append((callee,
                                 mod.qual.get(callee, node.func.id)))


def _check_blocking_under_lock(mod: _Module, findings: List[Finding]):
    for fn, qual, cls in mod.funcs:
        def_line = getattr(fn, "lineno", 0)
        for node in _own_nodes(fn):
            if not isinstance(node, ast.With):
                continue
            locks = _lock_item_names(node)
            if not locks:
                continue
            lock_name = locks[0]
            body_nodes = []
            stack = list(node.body)
            while stack:
                n = stack.pop()
                body_nodes.append(n)
                for child in ast.iter_child_nodes(n):
                    # a def/lambda created under the lock runs later,
                    # not here
                    if not isinstance(child, (ast.FunctionDef,
                                              ast.AsyncFunctionDef,
                                              ast.Lambda)):
                        stack.append(child)
            seen: Set[int] = set()
            frontier: List[Tuple[ast.AST, str]] = []
            _scan_blocking(mod, body_nodes, qual, cls, locks, lock_name,
                           (node.lineno, def_line), findings, frontier)
            depth = 1
            while frontier and depth <= 2:
                nxt: List[Tuple[ast.AST, str]] = []
                for callee, cq in frontier:
                    if id(callee) in seen:
                        continue
                    seen.add(id(callee))
                    # inside a callee the cv-receiver exception can't be
                    # tracked — pass no lock_names, flag every wait()
                    _scan_blocking(
                        mod, _own_nodes(callee), cq, cls, (), lock_name,
                        (getattr(callee, "lineno", 0), node.lineno,
                         def_line), findings, nxt)
                frontier, depth = nxt, depth + 1


# -- stale suppressions ------------------------------------------------------

_ALLOW_RE = None  # compiled lazily; ast is imported, re is not yet


def _check_stale_suppressions(mod: _Module, findings: List[Finding]):
    """Every ``# analysis: allow(<rule>)`` comment that no check
    consulted is allow-rot: either the finding it silenced was fixed
    (delete the comment) or it drifted off the line the checks look at.
    Runs LAST — it reads ``mod.used_allows`` filled by the other rules."""
    global _ALLOW_RE
    if _ALLOW_RE is None:
        import re
        _ALLOW_RE = re.compile(r"#\s*analysis:\s*allow\(([\w\-]+)\)")
    from .findings import P2
    for ln, line in enumerate(mod.lines, start=1):
        for m in _ALLOW_RE.finditer(line):
            rule = m.group(1)
            if (ln, rule) in mod.used_allows:
                continue
            code = line[:m.start()].split("#")[0].strip()
            findings.append(Finding(
                "stale-suppression", P2, mod.rel, "<module>",
                anchor=f"{rule}@{code[:60]}", line=ln,
                message=(f"allow({rule}) suppresses nothing "
                         f"{'(unknown rule) ' if rule not in RULES else ''}"
                         f"— the finding was fixed or the comment "
                         f"drifted; delete it")))


# -- threads ----------------------------------------------------------------

def _check_threads(mod: _Module, findings: List[Finding]):
    joined: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "join":
            tgt = _dotted(node.func.value)
            if tgt:
                joined.add(tgt)

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node) or ""
        if name not in ("threading.Thread", "Thread"):
            continue
        daemon = None
        for kw in node.keywords:
            if kw.arg == "daemon" and isinstance(kw.value, ast.Constant):
                daemon = bool(kw.value.value)
        if daemon:
            continue  # dies with the process; join is optional
        # the target this Thread lands in (t = ... / self._t = ...)
        target = None
        parent = getattr(node, "_pt_parent", None)
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
            target = _dotted(parent.targets[0])
        if target and target in joined:
            continue
        if mod.suppressed("unjoined-thread", node.lineno):
            continue
        findings.append(Finding(
            "unjoined-thread", P1, mod.rel,
            target or "<unassigned>", anchor=target or f"L{node.lineno}",
            line=node.lineno,
            message=("non-daemon Thread started with no .join() in this "
                     "module — blocks interpreter exit / leaks work past "
                     "its owner" if target else
                     "non-daemon Thread constructed inline (no handle to "
                     "join) — set daemon=True or keep a joinable handle")))


def _annotate_parents(tree):
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._pt_parent = node


# -- entry points -----------------------------------------------------------

def lint_file(path: str, rel: Optional[str] = None) -> List[Finding]:
    with open(path) as f:
        text = f.read()
    try:
        mod = _Module(path, rel or path, text)
    except SyntaxError as e:
        return [Finding("parse-error", P1, rel or path, "<module>",
                        anchor=str(e.lineno), line=e.lineno or 0,
                        message=f"file does not parse: {e.msg}")]
    _annotate_parents(mod.tree)
    findings: List[Finding] = []
    _check_traced(mod, findings)
    _check_gc_paths(mod, findings)
    _check_signal_handlers(mod, findings)
    _check_threads(mod, findings)
    _check_blocking_under_lock(mod, findings)
    # must run after every suppressible check has queried mod.suppressed
    _check_stale_suppressions(mod, findings)
    return findings


def lint_tree(root: Optional[str] = None,
              extra_files: Tuple[str, ...] = ()) -> List[Finding]:
    """Lint every ``.py`` file under ``root`` (default: the installed
    ``paddle_tpu`` package) plus ``extra_files``; repo-relative paths in
    the findings keep fingerprints machine-independent."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = os.path.dirname(os.path.abspath(root))
    findings: List[Finding] = []
    targets = iter_py_files(root) + list(extra_files)
    for path in targets:
        rel = os.path.relpath(path, base)
        findings.extend(lint_file(path, rel))
    return findings
