"""Program auditor + trace-safety linter (ISSUE 9): compiled-HLO audit
passes (collective census vs the bucketed-dp contract, donation
coverage, f32 upcasts, giant intermediates, compile-key diff), the AST
lint rules reproducing three paid-for bug classes, the env-knob
registry drift gate, and the bench.py --audit report-gate headlines
(docs/ANALYSIS.md)."""
import importlib.util
import json
import os
import textwrap

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.analysis import audit as A
from paddle_tpu.analysis import hlo as H
from paddle_tpu.analysis import knobs as K
from paddle_tpu.analysis.driver import (dp8_bucketed_step,
                                        tiny_llama_step,
                                        tiny_serving_engine)
from paddle_tpu.analysis.findings import Baseline, Finding, load_baseline
from paddle_tpu.analysis.lint import lint_file, lint_tree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_for_tests", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mlp_step(donate=True, seed=3):
    pt.seed(seed)
    m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    o = pt.optimizer.AdamW(learning_rate=0.01, parameters=m.parameters())
    step = pt.jit.TrainStep(
        m, lambda mm, x, y: ((mm(x) - y) ** 2).mean(), o, donate=donate)
    rng = np.random.RandomState(0)
    X = rng.randn(8, 16).astype(np.float32)
    Y = X @ rng.randn(16, 4).astype(np.float32)
    return step, (pt.to_tensor(X), pt.to_tensor(Y))


# ---------------- HLO text passes (pure fragments) ---------------------------

HEADER = ("HloModule jit_f, is_scheduled=true, input_output_alias={ "
          "{0}: (0, {}, may-alias), {2}: (3, {}, must-alias) }, "
          "entry_computation_layout={(bf16[8,16]{1,0}, f32[]{:T(256)}, "
          "/*index=2*/s32[2,64]{1,0}, f32[128]{0})->(bf16[8,16]{1,0})}\n")

BODY = textwrap.dedent("""\
    %fused (p: bf16[8,16]) -> f32[] {
      %p = bf16[8,16]{1,0} parameter(0)
      %convert.3 = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %p), metadata={op_name="jit(f)/mul" source_file="m.py" source_line=4}
      %big = f32[128,512]{1,0} broadcast(f32[] %c), dimensions={}
      ROOT %reduce.0 = f32[] reduce(f32[8,16]{1,0} %convert.3, f32[] %c)
    }
    ENTRY %main () -> f32[] {
      %ar0 = f32[100]{0} all-reduce(f32[100]{0} %x), to_apply=%add
      %ars = f32[50]{0} all-reduce-start(f32[50]{0} %y), to_apply=%add
      %ard = f32[50]{0} all-reduce-done(f32[50]{0} %ars)
      %ag = f32[64]{0} all-gather(f32[8]{0} %z), dimensions={0}
      %cp = f32[8]{0} collective-permute(f32[8]{0} %w)
    }
""")


class TestHloPasses:
    def test_shape_bytes(self):
        assert H.shape_bytes("f32", "128,512") == 128 * 512 * 4
        assert H.shape_bytes("bf16", "8,16") == 256
        assert H.shape_bytes("f32", "") == 4
        assert H.shape_bytes("opaque", "7") == 0

    def test_entry_params_skip_index_comments(self):
        params = H.parse_entry_params(HEADER)
        assert [(d, dims) for d, dims, _ in params] == [
            ("bf16", (8, 16)), ("f32", ()), ("s32", (2, 64)),
            ("f32", (128,))]
        assert params[2][2] == 2 * 64 * 4

    def test_donated_params_nested_braces(self):
        assert H.donated_params(HEADER) == {0, 3}
        assert H.donated_params("HloModule x\n") == set()

    def test_collective_census_counts_start_once(self):
        c = H.collective_census(BODY)
        assert c["all-reduce"] == 2          # plain + start, done excluded
        assert c["all-gather"] == 1
        assert c["collective-permute"] == 1
        assert c["all-to-all"] == 0

    def test_upcast_ops(self):
        ups = H.upcast_ops(BODY)
        assert len(ups) == 1 and ups[0].shape == "f32[8,16]"
        assert ups[0].source == "m.py:4"
        assert H.upcast_ops(BODY, min_bytes=10 ** 6) == []

    def test_largest_ops(self):
        top = H.largest_ops(BODY, top=1)
        assert top[0].shape == "f32[128,512]"
        assert top[0].nbytes == 128 * 512 * 4


# ---------------- compiled-program audits ------------------------------------

BASE = load_baseline()


class TestTrainStepAudit:
    @pytest.fixture(scope="class")
    def dp8(self):
        step, batch = dp8_bucketed_step(8)
        rep = A.audit_train_step(step, *batch)
        return step, rep

    @pytest.fixture(scope="class")
    def llama(self):
        step, batch = tiny_llama_step()
        rep = A.audit_train_step(step, *batch)
        return step, rep

    def test_dp8_allreduce_contract_pinned(self, dp8):
        """The PR 7 contract as a machine-checked regression: one
        all-reduce per bucket + one for the loss, exactly."""
        step, rep = dp8
        assert step._comm_buckets is not None
        assert rep.all_reduce_count == len(step._comm_buckets) + 1
        assert rep.all_reduce_count == \
            BASE.audit["train_step_allreduce_count"]
        assert not [f for f in rep.findings
                    if f.rule == "allreduce-contract"]

    def test_dp8_donation_clean(self, dp8):
        _, rep = dp8
        assert rep.donation_coverage == 1.0
        assert rep.donation_misses == []

    def test_unbucketed_storm_flagged(self, dp8):
        """Seeded defect: the same model with the bucketed path doctored
        off carries a per-param all-reduce storm — flagged P0 against
        the reference contract. Counted per reduced buffer: on a program
        this small XLA's combiner packs the storm into one variadic
        all-reduce, and it is five payloads all the same."""
        step, _ = dp8
        contract = len(step._comm_buckets) + 1
        import paddle_tpu.distributed as dist
        mesh = dist.init_mesh({"dp": 8})
        pt.seed(3)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                            nn.Linear(32, 4))
        m = dist.DataParallel(net, mesh=mesh)
        o = pt.optimizer.AdamW(learning_rate=0.01,
                               parameters=m.parameters())
        doctored = pt.jit.TrainStep(
            m, lambda mm, x, y: ((mm(x) - y) ** 2).mean(), o,
            bucketed=False)
        rng = np.random.RandomState(0)
        X = rng.randn(64, 16).astype(np.float32)
        Y = X @ rng.randn(16, 4).astype(np.float32)
        rep = A.audit_train_step(doctored, pt.to_tensor(X),
                                 pt.to_tensor(Y),
                                 expected_all_reduce=contract)
        assert rep.all_reduce_count > contract
        storms = [f for f in rep.findings if f.rule == "allreduce-contract"]
        assert len(storms) == 1 and storms[0].severity == "P0"
        assert storms[0].anchor == "storm"

    def test_llama_donation_coverage_pinned(self, llama):
        """Committed geometry: every train-param and optimizer-state
        leaf aliases an output; the only undonated bytes are the token
        batch + the lr scalar (pinned)."""
        _, rep = llama
        assert rep.donation_coverage == 1.0
        assert rep.donation_misses == []
        assert rep.undonated_bytes == \
            BASE.audit["train_step_undonated_bytes"]
        assert rep.donation_coverage == \
            BASE.audit["train_step_donation_coverage"]

    def test_llama_param_names_aligned(self, llama):
        _, rep = llama
        names = [p[0] for p in rep.params]
        assert any(n.startswith("train['model.embed_tokens") for n in names)
        # the token batch leaf is undonated, by name
        und = [n for n, _, _, _, don in rep.params if not don]
        assert any(n.startswith("batch") or n.startswith("param")
                   for n in und)

    def test_llama_largest_intermediate_pinned(self, llama):
        _, rep = llama
        assert rep.largest_intermediate_bytes == \
            BASE.audit["train_step_largest_intermediate_bytes"]
        # at least logits-sized ([B=2, S=64, V=512] f32)
        assert rep.largest_intermediate_bytes >= 2 * 64 * 512 * 4

    def test_llama_no_upcasts_clean(self, llama):
        _, rep = llama
        assert rep.upcasts == []
        assert not rep.findings

    def test_donation_miss_flagged(self):
        """Seeded defect: donate=False is exactly the 2x-memory class —
        every train/state leaf is reported missed, large ones as P0."""
        step, batch = _mlp_step(donate=False)
        rep = A.audit_train_step(step, *batch, large_bytes=64)
        assert rep.donation_coverage == 0.0
        assert len(rep.donation_misses) > 0
        misses = [f for f in rep.findings if f.rule == "undonated-buffer"]
        assert misses and all(f.severity == "P0" for f in misses)
        assert any("train['0.weight']" == f.anchor for f in misses)

    def test_injected_upcast_flagged(self):
        """Seeded defect: a bf16 program with an injected f32 upcast of
        a large intermediate is flagged with source attribution."""
        import jax
        import jax.numpy as jnp

        def f(x):
            big = x.astype(jnp.float32) * 2.0   # the injected upcast
            return big.sum()

        x = jnp.ones((256, 512), jnp.bfloat16)
        hlo = jax.jit(f).lower(x).compile().as_text()
        rep = A.audit_program(hlo, "doctored", large_bytes=256 * 512 * 4)
        ups = [f for f in rep.findings if f.rule == "f32-upcast"]
        assert len(ups) == 1
        assert ups[0].anchor == "f32[256,512]"

    def test_audit_is_rng_neutral(self):
        """Auditing mid-training must not shift the key stream (same
        contract as TrainStep.compiled_hlo)."""
        def run(with_audit):
            step, batch = _mlp_step(seed=11)
            out = [float(step(*batch).numpy())]
            if with_audit:
                A.audit_train_step(step, *batch)
            out += [float(step(*batch).numpy()) for _ in range(2)]
            return out

        np.testing.assert_array_equal(run(True), run(False))


class TestServingAudit:
    def test_engine_audit_and_state_neutral_inspection(self):
        """ServingEngine.compiled_hlo: audit sees the unified step (no
        collectives on one mesh), and inspection shares the jit cache
        with real calls — the compile-once counter reads exactly 1
        after inspect + run, same as an uninspected engine after its
        first step."""
        engine = tiny_serving_engine()
        rep = A.audit_serving_engine(engine)
        assert rep.all_reduce_count == 0
        # the ONE unified-step trace happened during inspection
        assert engine.step_traces == 1
        # args_info naming: per-layer pools + metadata leaves by name,
        # so the TPU pool-donation contract has real names to match
        names = [p[0] for p in rep.params]
        assert any(n.startswith("k_pools[") for n in names), names[:6]
        assert any(n.startswith("state['") for n in names)
        # the donation check CAN fire: expecting pool donation on this
        # CPU engine (which never requests it) must produce misses
        doctored = A.audit_program(
            engine.compiled_hlo(), "serving_step",
            args_info=engine._lowered_step().args_info,
            arg_names=A.SERVING_STEP_ARGS,
            expected_donated_prefixes=("k_pools", "v_pools"),
            large_bytes=1024)
        assert doctored.donation_misses
        assert any(f.rule == "undonated-buffer"
                   and f.anchor.startswith("k_pools[")
                   for f in doctored.findings)
        h = engine.compiled_hlo()       # second inspection: cached
        assert "HloModule" in h
        assert engine.step_traces == 1
        # a real request after inspection: no re-trace, tokens out
        handle = engine.submit([3, 5, 7], max_new_tokens=4)
        engine.run_until_idle()
        res = handle.result(timeout=30)
        assert res["num_generated"] == 4
        assert engine.step_traces == 1
        assert engine.stats()["step_compiles"] == 1


# ---------------- recompile diff ---------------------------------------------

class TestRecompileDiff:
    def _key(self, args, kwargs=None, training=False,
             train=("w", "b")):
        from paddle_tpu.jit.api import _sig_of
        treedef, sig = _sig_of((args, kwargs or {}))
        return (treedef, sig, training, tuple(train))

    def test_shape_change_names_leaf(self):
        a = self._key((np.zeros((4, 8), np.float32),))
        b = self._key((np.zeros((4, 16), np.float32),))
        (cause,) = A.diff_compile_keys(a, b)
        assert "f32" not in cause or True
        assert "[4, 8]" in cause and "[4, 16]" in cause

    def test_dtype_change_names_leaf(self):
        a = self._key((np.zeros((4,), np.float32),))
        b = self._key((np.zeros((4,), np.int32),))
        (cause,) = A.diff_compile_keys(a, b)
        assert "float32" in cause and "int32" in cause

    def test_structure_change(self):
        a = self._key((np.zeros((4,), np.float32),))
        b = self._key((np.zeros((4,), np.float32),
                       np.zeros((4,), np.float32)))
        causes = A.diff_compile_keys(a, b)
        assert any("structure" in c for c in causes)

    def test_mode_and_trainable_set(self):
        x = (np.zeros((4,), np.float32),)
        a = self._key(x, training=True, train=("w", "b"))
        b = self._key(x, training=False, train=("w",))
        causes = " | ".join(A.diff_compile_keys(a, b))
        assert "training=True -> False" in causes
        assert "'b'" in causes and "left the trainable set" in causes

    def test_identical_keys(self):
        a = self._key((np.zeros((4,), np.float32),))
        assert A.diff_compile_keys(a, a) == ["keys are identical"]

    def test_recompile_report_on_real_step(self):
        step, (X, Y) = _mlp_step(seed=5)
        step(X, Y)
        rng = np.random.RandomState(1)
        X2 = pt.to_tensor(rng.randn(16, 16).astype(np.float32))
        Y2 = pt.to_tensor(rng.randn(16, 4).astype(np.float32))
        step(X2, Y2)
        report = A.recompile_report(step)
        assert len(report) == 1
        causes = " | ".join(report[0]["causes"])
        assert "[8, 16]" in causes and "[16, 16]" in causes


# ---------------- linter -----------------------------------------------------

GC_LEAK = textwrap.dedent("""\
    import jax
    import jax.numpy as jnp

    class LeakyFlusher:
        def _flush(self):
            self._state = jnp.split(self._flat, self._sizes)

        def __del__(self):
            try:
                self._flush()
            except Exception:
                pass

    class GuardedFlusher:
        def _flush(self):
            with jax.core.eval_context():
                self._state = jnp.split(self._flat, self._sizes)

        def __del__(self):
            self._flush()
""")

SIGNAL_LOCK = textwrap.dedent("""\
    import signal
    import threading

    class Listener:
        def install(self):
            signal.signal(signal.SIGTERM, self._handler)

        def _handler(self, sn, frame):
            with self._lock:
                self._flagged = True
            self._metric.inc(reason="preempt")
            self._note()

        def _note(self):
            self._ev = threading.Event()

    class SafeListener:
        def install(self):
            signal.signal(signal.SIGTERM, self._handler)

        def _handler(self, sn, frame):
            self._flagged = True
            self.reason = "sig"
""")

TRACE_MUT = textwrap.dedent("""\
    import time
    import jax
    import numpy as np

    class Stepper:
        def build(self):
            def step(x):
                self._cur_param = x
                t = time.perf_counter()
                r = np.random.randn(3)
                return x * t + r.sum()
            return jax.jit(step)

        def build_allowed(self):
            def step(x):
                self.traces += 1  # analysis: allow(trace-attr-mutation)
                return x * 2
            return jax.jit(step)

        def eager_ok(self, x):
            self._cur_param = x      # not traced: no finding
            return x
""")

THREADS = textwrap.dedent("""\
    import threading

    def leak():
        t = threading.Thread(target=print)
        t.start()

    def joined():
        u = threading.Thread(target=print)
        u.start()
        u.join()

    def daemonized():
        v = threading.Thread(target=print, daemon=True)
        v.start()
""")


def _lint_src(tmp_path, src, name="fixture.py"):
    p = tmp_path / name
    p.write_text(src)
    return lint_file(str(p), name)


class TestLinter:
    def test_eval_context_guard_nested_in_if(self, tmp_path):
        """An eval_context guard under an ``if``/``try`` still guards
        its body — the natural shape of the PR 7 flush must not raise
        a false P0."""
        src = textwrap.dedent("""\
            import jax
            import jax.numpy as jnp

            class F:
                def _flush(self):
                    if self._flat is not None:
                        try:
                            with jax.core.eval_context():
                                self._state = jnp.split(self._flat, 3)
                        except Exception:
                            pass
                    else:
                        jnp.zeros(())

                def __del__(self):
                    self._flush()
        """)
        fs = _lint_src(tmp_path, src)
        gc = [f for f in fs if f.rule == "gc-eager-jax"]
        # only the UNguarded else-branch call is flagged
        assert len(gc) == 1 and gc[0].anchor == "jnp.zeros"

    def test_gc_trace_leak_caught(self, tmp_path):
        """Historical class 1: the PR 7 GC-time flush that staged jnp
        ops into a foreign trace."""
        fs = _lint_src(tmp_path, GC_LEAK)
        rules = [(f.rule, f.where) for f in fs]
        assert ("gc-eager-jax", "LeakyFlusher._flush") in rules
        # the eval_context-guarded twin is clean
        assert not [f for f in fs if "Guarded" in f.where]
        f = [f for f in fs if f.rule == "gc-eager-jax"][0]
        assert f.severity == "P0" and f.anchor == "jnp.split"

    def test_signal_handler_lock_caught(self, tmp_path):
        """Historical class 2: lock/Event/metrics traffic in signal
        context (PR 4: handlers write plain attributes only)."""
        fs = _lint_src(tmp_path, SIGNAL_LOCK)
        sig = [f for f in fs if f.rule == "signal-unsafe-call"]
        anchors = {f.anchor for f in sig}
        assert "with:self._lock" in anchors       # the with-lock
        assert "self._metric.inc" in anchors      # metrics in handler
        assert "threading.Event" in anchors       # depth-1 callee
        assert all(f.severity == "P0" for f in sig)
        assert not [f for f in sig if "SafeListener" in f.where]

    def test_signal_registration_aliases(self, tmp_path):
        """Aliased registration forms must not dodge the rule:
        `from signal import signal` and `import signal as sig`."""
        src = textwrap.dedent("""\
            import signal as sig
            from signal import signal as reg

            class A:
                def install(self):
                    sig.signal(sig.SIGTERM, self._h)
                    reg(sig.SIGUSR1, self._g)

                def _h(self, sn, frame):
                    self._lock.acquire()

                def _g(self, sn, frame):
                    self._m.observe(1.0)
        """)
        fs = _lint_src(tmp_path, src)
        anchors = {f.anchor for f in fs if f.rule == "signal-unsafe-call"}
        assert "self._lock.acquire" in anchors
        assert "self._m.observe" in anchors

    def test_trace_attr_mutation_caught(self, tmp_path):
        """Historical class 3: the _cur_param trace-time side channel."""
        fs = _lint_src(tmp_path, TRACE_MUT)
        mut = [f for f in fs if f.rule == "trace-attr-mutation"]
        assert len(mut) == 1 and mut[0].anchor == "_cur_param"
        assert mut[0].severity == "P0"
        # eager method and allow()-annotated counter are clean
        assert not [f for f in fs if "eager_ok" in f.where]
        assert not [f for f in fs if f.anchor == "traces"]

    def test_traced_impurity_caught(self, tmp_path):
        fs = _lint_src(tmp_path, TRACE_MUT)
        imp = {f.anchor for f in fs if f.rule == "traced-impurity"}
        assert imp == {"time.perf_counter", "np.random.randn"}

    def test_unjoined_thread(self, tmp_path):
        fs = _lint_src(tmp_path, THREADS)
        th = [f for f in fs if f.rule == "unjoined-thread"]
        assert len(th) == 1 and th[0].anchor == "t"

    def test_fingerprints_stable_under_line_shift(self, tmp_path):
        a = _lint_src(tmp_path, GC_LEAK, "a_fixture.py")
        shifted = "# pad\n" * 7 + GC_LEAK
        b = _lint_src(tmp_path, shifted.replace("a_fixture", "x"),
                      "a_fixture.py")
        assert {f.fingerprint for f in a} == {f.fingerprint for f in b}
        assert a[0].line != b[0].line

    def test_repo_tree_lint_clean_vs_baseline(self):
        """The whole package (+bench.py) lints clean against the
        committed baseline — the CI gate every future PR runs."""
        findings = lint_tree(os.path.join(REPO, "paddle_tpu"),
                             extra_files=(os.path.join(REPO, "bench.py"),))
        new, known, stale = BASE.split(findings)
        assert not new, "new lint findings:\n" + "\n".join(
            f.format() for f in new)
        assert not stale, f"fixed findings still in baseline: {stale}"

    def test_baseline_split_semantics(self):
        f1 = Finding("r", "P0", "a.py", "X.y", "m", anchor="z")
        f2 = Finding("r", "P0", "a.py", "X.q", "m", anchor="w")
        base = Baseline({"findings": {f1.fingerprint: {"rule": "r"},
                                      "deadbeef00000000": {"rule": "r"}}})
        new, known, stale = base.split([f1, f2])
        assert [f.where for f in new] == ["X.q"]
        assert [f.where for f in known] == ["X.y"]
        assert set(stale) == {"deadbeef00000000"}


# ---------------- env-knob registry ------------------------------------------

class TestKnobRegistry:
    def test_collects_real_knobs_with_sites(self):
        code = K.collect_code_knobs(
            os.path.join(REPO, "paddle_tpu"),
            extra_files=(os.path.join(REPO, "bench.py"),))
        assert "PADDLE_TPU_COMM_BUCKET_MB" in code
        files = [f for f, _ in code["PADDLE_TPU_COMM_BUCKET_MB"]]
        assert any(f.endswith("jit/bucketing.py") for f in files)
        # prefix family collected from the startswith scan
        assert "PADDLE_TPU_CHAOS_" in code
        # docstring-only mentions don't create registry entries
        assert all(not f.endswith("serving/engine.py")
                   for f, _ in code["PADDLE_TPU_NUMERICS"])

    def test_no_drift_on_committed_tree(self):
        """Tier-1 contract (modeled on TestDocsMetricDrift): every knob
        read in code is documented in docs/*.md or README.md, and every
        documented knob still has a read site."""
        d = K.drift(os.path.join(REPO, "paddle_tpu"),
                    extra_files=(os.path.join(REPO, "bench.py"),))
        assert not d["undocumented"], (
            f"knobs read in code but absent from docs/*.md: "
            f"{d['undocumented']} — document them (docs/ANALYSIS.md has "
            f"the knob table workflow)")
        assert not d["ghosts"], (
            f"knobs documented but never read: {d['ghosts']} — fix the "
            f"doc or restore the read site")

    def test_drift_detects_both_directions(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'import os\nX = os.environ.get("PADDLE_TPU_NEW_KNOB")\n')
        docs_base = tmp_path / "repo"
        (docs_base / "docs").mkdir(parents=True)
        (docs_base / "docs" / "X.md").write_text(
            "`PADDLE_TPU_GHOST_KNOB` does nothing anymore\n")
        d = K.drift(str(pkg), docs_root=str(docs_base))
        assert d["undocumented"] == ["PADDLE_TPU_NEW_KNOB"]
        assert d["ghosts"] == ["PADDLE_TPU_GHOST_KNOB"]

    def test_prefix_family_covers_members(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'import os\n'
            'ks = [k for k in os.environ if '
            'k.startswith("PADDLE_TPU_FAM_")]\n')
        docs_base = tmp_path / "repo"
        (docs_base / "docs").mkdir(parents=True)
        (docs_base / "docs" / "X.md").write_text(
            "set any `PADDLE_TPU_FAM_WHATEVER` member\n")
        d = K.drift(str(pkg), docs_root=str(docs_base))
        assert d["undocumented"] == [] and d["ghosts"] == []


# ---------------- CLI + bench gate -------------------------------------------

class TestCliAndGate:
    def test_lint_cli(self, tmp_path, capsys):
        """CLI smoke on a tiny tree (the full-tree gate is
        test_repo_tree_lint_clean_vs_baseline): clean file exits 0, a
        seeded defect exits 1 and prints NEW."""
        from paddle_tpu.analysis.__main__ import main
        (tmp_path / "ok.py").write_text("x = 1\n")
        assert main(["lint", "--root", str(tmp_path)]) == 0
        (tmp_path / "bad.py").write_text(GC_LEAK)
        assert main(["lint", "--root", str(tmp_path)]) == 1
        assert "gc-eager-jax" in capsys.readouterr().out

    def test_knobs_cli_clean(self, capsys):
        from paddle_tpu.analysis.__main__ import main
        assert main(["knobs", "--json"]) == 0

    def test_report_gate_learns_audit_directions(self):
        bench = _bench()
        for name in ("train_step_allreduce_count",
                     "train_step_undonated_bytes",
                     "train_step_largest_intermediate_bytes"):
            assert name in bench.REPORT_LOWER_BETTER
        cmp = bench.report_compare(
            {"train_step_allreduce_count": 2.0,
             "train_step_undonated_bytes": 516.0},
            {"train_step_allreduce_count": 5.0,     # storm: regression
             "train_step_undonated_bytes": 500.0},  # improvement: ok
            tolerance_pct=3)
        by = {r["metric"]: r["status"] for r in cmp["rows"]}
        assert by["train_step_allreduce_count"] == "fail"
        assert by["train_step_undonated_bytes"] == "ok"
        assert cmp["failures"] == ["train_step_allreduce_count"]

    @pytest.mark.slow
    def test_bench_audit_emits_headlines(self):
        """Full bench.py --audit subprocess: the three LOWER_BETTER
        headline JSON lines are on stdout with the _cpu_smoke suffix."""
        import subprocess
        import sys as _sys
        env = dict(os.environ, BENCH_FORCE_CPU="1")
        out = subprocess.run(
            [_sys.executable, os.path.join(REPO, "bench.py"), "--audit"],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=REPO)
        assert out.returncode == 0, out.stderr[-2000:]
        metrics = {}
        for line in out.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                obj = json.loads(line)
                if "metric" in obj:
                    metrics[obj["metric"]] = obj["value"]
        for name in ("train_step_allreduce_count",
                     "train_step_undonated_bytes",
                     "train_step_largest_intermediate_bytes"):
            assert f"{name}_cpu_smoke" in metrics
        assert metrics["train_step_allreduce_count_cpu_smoke"] == \
            BASE.audit["train_step_allreduce_count"]


# ---------------- ISSUE 12 lint satellites -----------------------------------

LOCKS = textwrap.dedent("""\
    import time
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition()

        def bad(self):
            with self._lock:
                time.sleep(1.0)
                self._t.join()
                self._q.get()
                self._fut.result()

        def bounded_ok(self):
            with self._lock:
                self._t.join(timeout=2)
                self._fut.result(timeout=1)
                self._ev.wait(0.5)

        def cv_ok(self):
            with self._cv:
                self._cv.wait()

        def via_callee(self):
            with self._lock:
                self._drain()

        def _drain(self):
            self._t2.join()

        def no_lock(self):
            time.sleep(1.0)
""")


class TestBlockingUnderLock:
    def test_blocking_calls_flagged(self, tmp_path):
        fs = _lint_src(tmp_path, LOCKS)
        hits = [f for f in fs if f.rule == "blocking-call-under-lock"]
        assert all(f.severity == "P0" for f in hits)
        anchors = {f.anchor for f in hits}
        assert anchors == {"self._lock:time.sleep",
                           "self._lock:self._t.join",
                           "self._lock:self._q.get",
                           "self._lock:self._fut.result",
                           "self._lock:self._t2.join"}
        # depth-1 callee hit is attributed to the callee's qualname
        callee = [f for f in hits if f.anchor.endswith("_t2.join")]
        assert callee[0].where == "Worker._drain"

    def test_timeouts_and_cv_wait_exempt(self, tmp_path):
        fs = _lint_src(tmp_path, LOCKS)
        lines = {f.line for f in fs
                 if f.rule == "blocking-call-under-lock"}
        src_lines = LOCKS.splitlines()
        for needle in ("join(timeout=2)", "result(timeout=1)",
                       "wait(0.5)", "self._cv.wait()"):
            ln = next(i for i, s in enumerate(src_lines, 1) if needle in s)
            assert ln not in lines, f"{needle} wrongly flagged"

    def test_suppression_honored(self, tmp_path):
        allowed = LOCKS.replace(
            "time.sleep(1.0)",
            "time.sleep(1.0)  # analysis: allow(blocking-call-under-lock)")
        fs = _lint_src(tmp_path, allowed)
        anchors = {f.anchor for f in fs
                   if f.rule == "blocking-call-under-lock"}
        assert "self._lock:time.sleep" not in anchors
        assert "self._lock:self._t.join" in anchors


class TestStaleSuppressions:
    def test_live_allow_not_reported(self, tmp_path):
        fs = _lint_src(tmp_path, TRACE_MUT)
        assert not [f for f in fs if f.rule == "stale-suppression"]

    def test_dead_allow_reported_p2(self, tmp_path):
        src = ("def f():\n"
               "    return 1  # analysis: allow(gc-eager-jax)\n")
        fs = _lint_src(tmp_path, src)
        stale = [f for f in fs if f.rule == "stale-suppression"]
        assert len(stale) == 1 and stale[0].severity == "P2"
        assert "gc-eager-jax" in stale[0].anchor

    def test_strict_suppressions_cli_flag(self, tmp_path, capsys):
        from paddle_tpu.analysis.__main__ import main
        (tmp_path / "mod.py").write_text(
            "x = 1  # analysis: allow(unjoined-thread)\n")
        bl = str(tmp_path / "bl.json")
        assert main(["lint", "--root", str(tmp_path),
                     "--baseline", bl]) == 0
        assert "stale-suppression" in capsys.readouterr().err
        assert main(["lint", "--root", str(tmp_path), "--baseline", bl,
                     "--strict-suppressions"]) == 1
        assert "stale-suppression" in capsys.readouterr().out


class TestCommBytesReportFamily:
    def test_prefix_membership_and_gate_direction(self):
        bench = _bench()
        assert bench._lower_better("train_step_comm_bytes_dp_cpu_smoke")
        assert bench._lower_better("train_step_comm_bytes_mp")
        assert not bench._lower_better("train_step_comm_count")
        cmp = bench.report_compare(
            {"train_step_comm_bytes_dp_cpu_smoke": 4739.0},
            {"train_step_comm_bytes_dp_cpu_smoke": 6000.0},
            tolerance_pct=5)
        assert cmp["failures"] == ["train_step_comm_bytes_dp_cpu_smoke"]
        cmp = bench.report_compare(
            {"train_step_comm_bytes_dp_cpu_smoke": 4739.0}, {},
            tolerance_pct=5)
        assert cmp["skipped"] == ["train_step_comm_bytes_dp_cpu_smoke"]
