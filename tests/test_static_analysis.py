"""dl4j-analyze: the analyzer analyzed.

Tier-1 wiring for the static suite (the shipped tree must be clean vs
tools/analyze_baseline.json), true-positive fixtures per rule,
false-positive guards, baseline round-trip, pragma suppression, the
zero-jax CLI contract, and the runtime LockOrderSanitizer drills —
including a real A->B / B->A cycle across two threads.
"""

import json
import os
import runpy
import subprocess
import sys
import textwrap
import threading
import time
from pathlib import Path

import pytest

from deeplearning4j_tpu.analysis import (
    RULES,
    Baseline,
    LockOrderSanitizer,
    analyze,
)
from deeplearning4j_tpu.analysis import sanitizers
from deeplearning4j_tpu.analysis.concurrency_lint import (
    run_with_catalog,
)
from deeplearning4j_tpu.analysis.source import load_sources

pytestmark = pytest.mark.analysis

ROOT = Path(__file__).resolve().parent.parent
PKG = ROOT / "deeplearning4j_tpu"
TESTS = ROOT / "tests"
BASELINE = ROOT / "tools" / "analyze_baseline.json"
BAD = TESTS / "fixtures" / "analysis_cases" / "bad"
CLEAN = TESTS / "fixtures" / "analysis_cases" / "clean"


# ==================================================== rule catalog
def test_rule_catalog_covers_four_passes():
    by_pass = {}
    for r in RULES.values():
        by_pass.setdefault(r.pass_name, []).append(r.id)
        assert r.description
    static_rules = sum(len(v) for k, v in by_pass.items()
                       if k not in ("runtime", "program"))
    assert static_rules >= 8, by_pass
    assert set(by_pass) == {"jit", "concurrency", "conformance",
                            "program", "runtime"}
    # the runtime sanitizer rules ride the same catalog
    assert "san-lock-order-cycle" in RULES
    assert "san-long-held-lock" in RULES
    # the program-pass catalog IS the pinned registry (and vice versa:
    # conformance re-checks this equality from the AST, so the pin
    # holds even for a build that never imports program_lint)
    from deeplearning4j_tpu.analysis.program_lint import (
        REGISTERED_PROGRAM_RULES,
    )

    assert set(by_pass["program"]) == set(REGISTERED_PROGRAM_RULES)


# ============================================== tier-1: tree is clean
def test_shipped_tree_clean_vs_baseline():
    """THE tier-1 gate: a new violation anywhere in the package fails
    this test with the same file:line report the CLI prints."""
    baseline = Baseline.load(BASELINE)
    res = analyze(PKG, root=ROOT, tests_dir=TESTS, baseline=baseline)
    assert res.clean, "new dl4j-analyze findings:\n" + "\n".join(
        f.render() for f in res.new)
    # the baseline may only shrink through an explicit edit: a stale
    # entry means a violation was fixed but left suppressed
    assert not res.stale, (
        "stale baseline entries (fixed — remove from "
        "tools/analyze_baseline.json): "
        + ", ".join(f"{e['rule']}@{e['file']}" for e in res.stale))
    assert res.files_scanned > 100


# ==================================================== true positives
EXPECTED_BAD = {
    "jit-host-sync": "bad_jit.py",
    "jit-missing-donate": "bad_jit.py",
    "jit-traced-python-scalar": "bad_jit.py",
    "jit-use-after-donation": "bad_jit.py",
    "thr-unnamed-thread": "bad_threads.py",
    "thr-non-daemon-thread": "bad_threads.py",
    "thr-orphan-thread": "bad_threads.py",
    "thr-blocking-under-lock": "bad_threads.py",
    "reg-unregistered-fault-point": "bad_registry.py",
    "reg-unfired-fault-point": "faults.py",
    "reg-unregistered-metric": "bad_registry.py",
    "reg-unemitted-metric": "metrics.py",
    "reg-swallowed-exception": "bad_registry.py",
    "reg-unregistered-program-rule": "program_rules.py",
    "reg-unimplemented-program-rule": "program_rules.py",
}


def _bad_findings():
    return analyze(BAD, root=ROOT, tests_dir=None).findings


@pytest.mark.parametrize("rule,expect_file",
                         sorted(EXPECTED_BAD.items()))
def test_bad_fixture_true_positive(rule, expect_file):
    hits = [f for f in _bad_findings() if f.rule == rule]
    assert hits, f"rule {rule} found nothing in the bad fixtures"
    assert any(f.file.endswith(expect_file) for f in hits), \
        [f.render() for f in hits]
    for f in hits:
        assert f.line > 0 and f.message


def test_bad_fixture_exact_shape():
    """Pin the full bad-fixture report: every finding accounted for,
    no rule fires anywhere unexpected (over-match guard)."""
    finds = _bad_findings()
    got = {(f.rule, f.file.rsplit("/", 1)[-1]) for f in finds}
    assert got == {(r, f) for r, f in EXPECTED_BAD.items()}, got
    # the two traced-scalar shapes (x.shape[i], len()) both fire
    assert sum(1 for f in finds
               if f.rule == "jit-traced-python-scalar") == 2
    # the module-level `jit = functools.partial(jax.jit)` alias call
    # site is a recognized jit site: the step-shaped fn it wraps
    # without donation fires jit-missing-donate (satellite)
    assert any(f.rule == "jit-missing-donate"
               and f.symbol == "fused_update_fn" for f in finds), \
        [f.render() for f in finds if f.rule == "jit-missing-donate"]
    # the reachability guard: cold_helper's .item() is NOT flagged
    assert not any(f.rule == "jit-host-sync"
                   and f.symbol == "cold_helper" for f in finds)
    # the annotated swallow is NOT flagged
    assert not any(f.rule == "reg-swallowed-exception"
                   and f.symbol == "swallow_annotated" for f in finds)


def test_clean_fixture_no_findings():
    res = analyze(CLEAN, root=ROOT, tests_dir=None)
    assert res.findings == [], [f.render() for f in res.findings]


# ============================================= baseline round-trip
def test_baseline_round_trip(tmp_path):
    finds = _bad_findings()
    bl_path = tmp_path / "bl.json"
    Baseline.from_findings(finds).save(bl_path)
    bl = Baseline.load(bl_path)
    res = analyze(BAD, root=ROOT, tests_dir=None, baseline=bl)
    assert res.clean
    assert len(res.suppressed) == len(finds)
    assert not res.stale
    # fingerprints are line-free: the same violation after an edit
    # that shifts lines still matches
    data = json.loads(bl_path.read_text())
    assert all("fingerprint" in e for e in data["suppressions"])


def test_baseline_reports_stale_entries():
    finds = _bad_findings()
    bl = Baseline.from_findings(finds)
    bl.entries.append({"rule": "thr-unnamed-thread",
                       "file": "deeplearning4j_tpu/ghost.py",
                       "line": 1, "symbol": "gone",
                       "message": "fixed long ago",
                       "fingerprint": "0000000000000000"})
    res = analyze(BAD, root=ROOT, tests_dir=None, baseline=bl)
    assert res.clean
    assert len(res.stale) == 1
    assert res.stale[0]["fingerprint"] == "0000000000000000"


def test_baseline_multiplicity(tmp_path):
    """Two identical findings (same fingerprint — same rule, file,
    symbol, message) need two baseline entries: baselining one copy
    must not hide the second."""
    pkg = tmp_path / "minipkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent("""\
        import threading

        def start_two():
            threading.Thread(target=print, daemon=True).start()
            threading.Thread(target=print, daemon=True).start()
    """))
    finds = analyze(pkg, root=tmp_path, tests_dir=None).findings
    unnamed = [f for f in finds if f.rule == "thr-unnamed-thread"]
    assert len(unnamed) == 2
    assert unnamed[0].fingerprint() == unnamed[1].fingerprint()
    bl = Baseline.from_findings([unnamed[0]])
    res = analyze(pkg, root=tmp_path, tests_dir=None, baseline=bl)
    assert any(f.rule == "thr-unnamed-thread" for f in res.new), \
        "second identical violation hidden by a single baseline entry"


# ================================================ pragma suppression
def test_pragma_suppresses_rule(tmp_path):
    pkg = tmp_path / "minipkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent("""\
        import threading

        def start():
            # analyze: allow=thr-unnamed-thread,thr-orphan-thread — drill
            t = threading.Thread(target=print, daemon=True)
            t.start()
    """))
    res = analyze(pkg, root=tmp_path, tests_dir=None)
    assert not any(f.rule in ("thr-unnamed-thread", "thr-orphan-thread")
                   for f in res.findings), \
        [f.render() for f in res.findings]


# ======================================================== CLI contract
def test_cli_clean_and_jax_free():
    """`python tools/analyze.py` exits 0 on the shipped tree WITHOUT
    importing jax (the no-jax AST-only tier-1 contract)."""
    code = (
        "import runpy, sys\n"
        "sys.argv = ['analyze.py']\n"
        "rc = 0\n"
        "try:\n"
        "    runpy.run_path(r'%s', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    rc = e.code or 0\n"
        "assert 'jax' not in sys.modules, 'CLI imported jax'\n"
        "sys.exit(rc)\n" % (ROOT / "tools" / "analyze.py"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "0 new finding(s)" in p.stdout


def test_cli_rules_and_diff_mode():
    p = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "analyze.py"), "--rules"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0
    for rule in RULES:
        assert rule in p.stdout
    # --diff: either no changed files (clean exit) or a changed-file
    # subset that is clean vs the baseline
    p = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "analyze.py"), "--diff"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr


# ================================================= thread/lock catalog
def test_concurrency_catalog():
    sources = load_sources(BAD, ROOT)
    _, catalog = run_with_catalog(sources)
    assert len(catalog.threads) == 2
    named = [t for t in catalog.threads if t.named]
    assert named and named[0].name_literal == "bad-fire-and-forget"
    kinds = {lk.kind for lk in catalog.locks}
    assert kinds == {"Lock", "Condition"}


# ========================================== runtime: LockOrderSanitizer
@pytest.fixture()
def _no_session_sanitizer():
    """The drills install/uninstall their own sanitizer; under a
    DL4J_TPU_SANITIZE=locks sweep a session-level one is already
    patched in and must not be clobbered."""
    if sanitizers.active_sanitizer() is not None:
        pytest.skip("session lock sanitizer active "
                    "(DL4J_TPU_SANITIZE=locks sweep)")
    yield


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_lock_order_cycle_detected_across_two_threads():
    """The drill the acceptance criteria names: thread 1 takes A then
    B, thread 2 takes B then A — real threads, real (proxied) locks,
    sequential execution so the test can never deadlock — and the
    sanitizer must report the A<->B cycle with both creation sites."""
    san = LockOrderSanitizer(long_hold_s=30.0).install()
    try:
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def a_then_b():
            with lock_a:
                with lock_b:
                    pass

        def b_then_a():
            with lock_b:
                with lock_a:
                    pass

        for fn, name in ((a_then_b, "drill-ab"), (b_then_a, "drill-ba")):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            t.join(timeout=10.0)
            assert not t.is_alive()

        cycles = san.cycles()
        assert cycles, f"no cycle found; edges={san.edges()}"
        sites = {s for c in cycles for s in c}
        assert all("test_static_analysis.py" in s for s in sites), sites
        assert len(sites) == 2          # the two lock creation lines
        vio = san.violations()
        assert any(v["rule"] == "san-lock-order-cycle" for v in vio)
        # both drill threads contributed edges
        threads = {e.thread for e in san.edges()}
        assert {"drill-ab", "drill-ba"} <= threads
    finally:
        san.uninstall()


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_lock_order_no_false_cycle_on_consistent_order():
    san = LockOrderSanitizer().install()
    try:
        lock_a = threading.Lock()
        lock_b = threading.Lock()
        for _ in range(3):
            with lock_a:
                with lock_b:
                    pass
        assert san.cycles() == []
        assert len(san.edges()) == 1
    finally:
        san.uninstall()


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_rlock_reentry_is_not_a_self_edge():
    san = LockOrderSanitizer().install()
    try:
        r = threading.RLock()
        with r:
            with r:                      # re-entry, no edge
                pass
        assert san.edges() == []
        # and Condition round-trips through the proxied RLock
        cond = threading.Condition()
        with cond:
            cond.notify_all()
    finally:
        san.uninstall()


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_long_held_lock_flagged():
    san = LockOrderSanitizer(long_hold_s=0.05).install()
    try:
        lk = threading.Lock()
        with lk:
            time.sleep(0.12)
        holds = san.long_holds()
        assert holds and holds[0].duration_s >= 0.05
        assert any(v["rule"] == "san-long-held-lock"
                   for v in san.violations())
    finally:
        san.uninstall()


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_queue_handoff_cycle_detected():
    """Satellite (queue.Queue ordering in the cross-thread graph): the
    classic coupled-queue deadlock — producer holds L blocking-put on
    a BOUNDED queue, the consumer that drains it takes L to process
    the item — surfaces as the cycle L -> Q -> L even on a run whose
    interleaving never wedged (the drill runs the threads
    sequentially, so the test itself can never deadlock)."""
    import queue

    san = LockOrderSanitizer(long_hold_s=30.0).install()
    try:
        q = queue.Queue(maxsize=4)
        lock = threading.Lock()

        def producer():
            with lock:
                q.put("item")        # bounded blocking put under L

        def consumer():
            q.get()                  # handoff window opens
            with lock:               # processing the item needs L
                pass

        for fn, name in ((producer, "q-prod"), (consumer, "q-cons")):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            t.join(timeout=10.0)
            assert not t.is_alive()
        cycles = san.cycles()
        assert cycles, f"no cycle; edges={san.edges()}"
        sites = {s for c in cycles for s in c}
        assert any(s.startswith("q:") for s in sites), sites
        assert any(v["rule"] == "san-lock-order-cycle"
                   for v in san.violations())
    finally:
        san.uninstall()


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_queue_nonblocking_and_unbounded_ops_make_no_producer_edge():
    """False-positive guards: an UNBOUNDED blocking put cannot wedge
    (no producer edge, so the same handoff pattern is not a cycle),
    and put_nowait/get_nowait never participate at all."""
    import queue

    san = LockOrderSanitizer().install()
    try:
        lock = threading.Lock()
        q_unbounded = queue.Queue()

        def producer():
            with lock:
                q_unbounded.put("x")

        def consumer():
            q_unbounded.get()
            with lock:
                pass

        for fn in (producer, consumer):
            t = threading.Thread(target=fn, name="q-fp", daemon=True)
            t.start()
            t.join(timeout=10.0)
        assert san.cycles() == []

        san.reset()
        q_bounded = queue.Queue(maxsize=2)
        with lock:
            q_bounded.put_nowait(1)      # non-blocking: no edge
        q_bounded.get_nowait()
        assert all(not e.src.startswith("q:")
                   and not e.dst.startswith("q:")
                   for e in san.edges())
    finally:
        san.uninstall()


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_uninstall_restores_real_locks():
    import queue

    before = threading.Lock
    san = LockOrderSanitizer().install()
    assert threading.Lock is not before
    san.uninstall()
    assert threading.Lock is sanitizers._REAL_LOCK
    assert threading.RLock is sanitizers._REAL_RLOCK
    assert sanitizers.active_sanitizer() is None
    # queue.Queue methods restored too (no tracking attribute)
    assert queue.Queue.put is sanitizers._REAL_Q_PUT
    assert queue.Queue.get is sanitizers._REAL_Q_GET
    q = queue.Queue(maxsize=1)
    q.put(1)
    assert q.get() == 1 and not hasattr(q, "_san_site")


@pytest.mark.usefixtures("_no_session_sanitizer")
def test_install_from_env_gating(monkeypatch):
    monkeypatch.delenv(sanitizers.ENV_VAR, raising=False)
    assert sanitizers.install_from_env() is None
    monkeypatch.setenv(sanitizers.ENV_VAR, "locks")
    san = sanitizers.install_from_env()
    try:
        assert san is not None
        assert sanitizers.active_sanitizer() is san
        # idempotent: a second call returns the same instance
        assert sanitizers.install_from_env() is san
    finally:
        san.uninstall()


# ================================= call-graph reachability (PR 9)
def _reach(src: str, tmp_path):
    """build_reachable over a one-file synthetic package."""
    from deeplearning4j_tpu.analysis.jit_lint import build_reachable

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(src))
    return build_reachable(load_sources(pkg, tmp_path))


def test_reachability_resolves_self_calls_through_hierarchy(tmp_path):
    """`self.m()` follows REAL class-hierarchy edges: the override in a
    subclass is reachable (virtual dispatch), while a same-named method
    on an UNRELATED class no longer rides the name-match."""
    seen = _reach(
        """
        class Base:
            def fit(self):
                self.step()
            def step(self):
                pass
        class Child(Base):
            def step(self):          # override: virtually dispatched
                pass
        class Unrelated:
            def step(self):          # same name, different hierarchy
                pass
        """, tmp_path)
    assert "pkg/mod.py::Base.fit" in seen
    assert "pkg/mod.py::Base.step" in seen
    assert "pkg/mod.py::Child.step" in seen
    assert "pkg/mod.py::Unrelated.step" not in seen


def test_reachability_falls_back_to_names_when_unresolvable(tmp_path):
    """A call that is NOT a self-call keeps the conservative name-based
    edge — false reachability costs a pragma, a missed hot function
    costs an untraced recompile."""
    seen = _reach(
        """
        def fit(runner):
            runner.launch()
        class Elsewhere:
            def launch(self):
                pass
        """, tmp_path)
    assert "pkg/mod.py::Elsewhere.launch" in seen


# ============================== pass 4: compiled-program lint (jaxpr/HLO)
PROGRAMS_FIX = TESTS / "fixtures" / "analysis_cases" / "programs"

# one bad fixture record per pinned program rule — this dict also
# keeps every REGISTERED_PROGRAM_RULES id named by a test (the
# reg-untested-registry-name discipline):
#   prog-fp32-matmul-under-policy, prog-unhonored-donation,
#   prog-transpose-churn, prog-hidden-host-transfer,
#   prog-dead-output, prog-excess-padding,
#   prog-unsharded-optimizer-state
EXPECTED_BAD_PROGRAMS = {
    "prog-fp32-matmul-under-policy": "bad_fp32_matmul",
    "prog-unhonored-donation": "bad_unhonored_donation",
    "prog-transpose-churn": "bad_transpose_churn",
    "prog-hidden-host-transfer": "bad_host_transfer",
    "prog-dead-output": "bad_dead_output",
    "prog-excess-padding": "bad_excess_padding",
    "prog-unsharded-optimizer-state": "bad_unsharded_optimizer",
}


def _load_by_path(name, path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _program_fixture_records(name):
    return _load_by_path(f"analysis_programs_{name}",
                         PROGRAMS_FIX / f"{name}.py").build_records()


def _program_findings(name):
    from deeplearning4j_tpu.analysis import program_lint

    return program_lint.run(_program_fixture_records(name))


@pytest.mark.parametrize("rule,program",
                         sorted(EXPECTED_BAD_PROGRAMS.items()))
def test_bad_program_fixture_true_positive(rule, program):
    finds = _program_findings("bad_programs")
    hits = [f for f in finds if f.rule == rule]
    assert hits, f"{rule} found nothing in the bad program fixtures"
    assert any(f.symbol == program for f in hits), \
        [f.render() for f in hits]
    for f in hits:
        assert f.message and "line" not in f.message


def test_bad_program_fixture_exact_shape():
    """Every finding accounted for; no rule fires on the wrong
    program (over-match guard), and fingerprints are stable."""
    finds = _program_findings("bad_programs")
    got = {(f.rule, f.symbol) for f in finds}
    assert got == set(EXPECTED_BAD_PROGRAMS.items()), got
    assert all(f.fingerprint() for f in finds)


def test_clean_program_fixture_no_findings():
    finds = _program_findings("clean_programs")
    assert finds == [], [f.render() for f in finds]


def test_program_findings_ride_the_baseline_machinery():
    """prog-* findings fingerprint/baseline exactly like AST findings:
    a baselined program violation suppresses, a fixed one goes stale."""
    finds = _program_findings("bad_programs")
    bl = Baseline.from_findings(finds)
    new, suppressed, stale = bl.apply(finds)
    assert not new and len(suppressed) == len(finds) and not stale
    new2, _, stale2 = bl.apply(finds[1:])
    assert not new2 and len(stale2) == 1


PIN_RULES = ("prog-unhonored-donation", "prog-fp32-matmul-under-policy")


@pytest.fixture(scope="module")
def resnet50_records():
    from deeplearning4j_tpu.analysis import programs

    return {r.name: r for r in programs._resnet50_records()}


@pytest.mark.parametrize("name", ["engine_resnet50",
                                  "engine_resnet50_group_k2"])
def test_flagship_program_clean_pin(resnet50_records, name):
    """THE acceptance pin: the program the training cell times (zoo
    ResNet50 as the benchmark's configuration builds it, through
    StepProgram: the single step and the k-step group) carries no
    prog-unhonored-donation and no prog-fp32-matmul-under-policy
    finding under the declared bf16 policy."""
    from deeplearning4j_tpu.analysis import program_lint

    record = resnet50_records[name]
    assert record.precision_policy == "bf16"
    assert record.source.startswith("deeplearning4j_tpu/")
    bad = [f for f in program_lint.run([record]) if f.rule in PIN_RULES]
    assert bad == [], [f.render() for f in bad]


def test_graft_entry_forward_clean_pin():
    """The published `__graft_entry__` forward, pinned to the bf16
    policy it declares. The record is built here: a test may load a
    script from the root, the package may not."""
    from deeplearning4j_tpu.analysis import program_lint
    from deeplearning4j_tpu.analysis.program_lint import ProgramRecord

    fwd, args = _load_by_path(
        "graft_entry", ROOT / "__graft_entry__.py").entry(
            hw=32, n_classes=8)
    record = ProgramRecord(
        name="graft_entry_forward", fn=fwd, example_args=args,
        precision_policy="bf16", source="__graft_entry__.py")
    bad = [f for f in program_lint.run([record]) if f.rule in PIN_RULES]
    assert bad == [], [f.render() for f in bad]


def test_engine_and_serving_records_declare_policy():
    """StepProgram and the serving front-end register the explicit
    precision_policy fact the lint checks against — on a bf16 net the
    records say bf16, and the net's JitCache carries the policy for
    every registered program key."""
    import jax.numpy as jnp

    from deeplearning4j_tpu import (
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.engine import StepProgram
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.Builder().seed(1).updater("sgd")
            .learning_rate(0.1).activation("relu")
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    net = MultiLayerNetwork(conf, compute_dtype="bfloat16").init()
    prog = StepProgram(net)
    assert prog.precision_policy == "bf16"
    recs = prog.lint_records(jnp.zeros((4, 6), jnp.float32),
                             jnp.zeros((4, 4), jnp.float32), k=2)
    assert [r.name for r in recs] == ["engine_single",
                                     "engine_single_group_k2"]
    assert all(r.precision_policy == "bf16" for r in recs)
    policies = net._jit_cache.policies()
    assert policies and all(v == "bf16" for v in policies.values())
    # f32 default stays declared too — never a guess
    net2 = MultiLayerNetwork(conf).init()
    assert StepProgram(net2).precision_policy == "f32"


def test_cli_programs_mode_clean_under_60s():
    """`dl4j-analyze --programs` runs the whole representative program
    set on CPU, ends at zero findings with the EMPTY shipped baseline,
    in under 60 seconds (acceptance criterion)."""
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "analyze.py"),
         "--programs"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    elapsed = time.perf_counter() - t0
    assert p.returncode == 0, p.stdout + p.stderr
    assert "0 new finding(s)" in p.stdout
    assert "programs" in p.stdout
    assert elapsed < 60.0, f"--programs took {elapsed:.1f}s"
    # the shipped baseline stays EMPTY: program findings may never be
    # suppressed into it
    data = json.loads(BASELINE.read_text())
    assert data["suppressions"] == []


def test_engine_entry_points_are_reachability_roots():
    """The StepProgram/StepHarness entry points are roots by exact
    qualname: everything the compiled-step path can execute is hot
    even if no `fit`-named function calls it in the scanned set."""
    from deeplearning4j_tpu.analysis.jit_lint import (
        ROOT_QUALNAMES,
        build_reachable,
    )

    sources = load_sources(PKG, ROOT)
    seen = build_reachable(sources)
    for qual in sorted(ROOT_QUALNAMES):
        assert qual in seen, f"engine root {qual} not in reachable set"
    # and the walk actually descends from them: the group builder is
    # only called from run_group
    assert ("deeplearning4j_tpu/engine/step_program.py::"
            "StepProgram._build_group") in seen
