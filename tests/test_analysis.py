"""acclint test suite: every check must prove it detects its bug class
(known-bad fixture flags, known-good fixture passes), the suppression
syntax must round-trip, the whole tree must be clean at HEAD, and the
dynamic lock-order registry must catch a seeded ABBA inversion.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from accl_tpu.analysis import CHECKS, run_checks
from accl_tpu.analysis.base import SourceFile, package_root
from accl_tpu.analysis.lockorder import (
    InstrumentedLock,
    LockOrderRegistry,
    load_snapshot,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(tmp_path, code, checks=None, name="snippet.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(code))
    return run_checks([str(p)], checks)


def _live(findings, check=None):
    return [
        f for f in findings
        if not f.suppressed and (check is None or f.check == check)
    ]


# ---------------------------------------------------------------------------
# unbounded-wait
# ---------------------------------------------------------------------------

BAD_WAITS = [
    ("lock.acquire()", "acquire"),
    ("lock.acquire(True)", "acquire"),
    ("lock.acquire(blocking=True)", "acquire"),
    ("lock.acquire(timeout=None)", "acquire"),
    ("lock.acquire(timeout=-1)", "acquire"),   # -1 blocks forever
    ("lock.acquire(True, -1)", "acquire"),
    ("ev.wait()", "wait"),
    ("cv.wait(None)", "wait"),
    ("cv.wait(timeout=None)", "wait"),
    ("cv.wait_for(lambda: done)", "wait_for"),
    ("t.join()", "join"),
    ("q.get()", "get"),
]

GOOD_WAITS = [
    "lock.acquire(timeout=5)",
    "lock.acquire(False)",
    "lock.acquire(blocking=False)",
    "ev.wait(5.0)",
    "ev.wait(timeout=-1)",  # negative is bounded for wait (returns now)
    "cv.wait(timeout=deadline)",
    "cv.wait_for(lambda: done, timeout=2)",
    "t.join(timeout=2.0)",
    "t.join(5)",
    "q.get(timeout=t)",
    "', '.join(names)",
    "d.get('key')",
    "d.get('key', default)",
    "os.environ.get('X')",
]


@pytest.mark.parametrize("code,what", BAD_WAITS)
def test_unbounded_wait_flags(tmp_path, code, what):
    findings = _live(
        _lint(tmp_path, f"def f(lock, ev, cv, t, q):\n    {code}\n"),
        "unbounded-wait",
    )
    assert len(findings) == 1, (code, findings)
    assert what in findings[0].message


@pytest.mark.parametrize("code", GOOD_WAITS)
def test_bounded_wait_passes(tmp_path, code):
    findings = _live(
        _lint(
            tmp_path,
            f"import os\ndef f(lock, ev, cv, t, q, d, names, deadline, t2):\n"
            f"    {code}\n",
        ),
        "unbounded-wait",
    )
    assert not findings, (code, findings)


# ---------------------------------------------------------------------------
# timer-discipline
# ---------------------------------------------------------------------------


def test_timer_discipline_flags_wall_clock(tmp_path):
    findings = _live(_lint(tmp_path, """
        import time
        def window():
            t0 = time.time()
            return time.time() - t0
    """), "timer-discipline")
    assert len(findings) == 2


def test_timer_discipline_flags_from_import(tmp_path):
    findings = _live(_lint(tmp_path, """
        from time import time
        def f():
            return time()
    """), "timer-discipline")
    assert len(findings) == 2  # the import and the call


def test_timer_discipline_passes_monotonic(tmp_path):
    findings = _live(_lint(tmp_path, """
        import time
        def window():
            t0 = time.perf_counter_ns()
            time.sleep(0.01)
            return time.perf_counter_ns() - t0, time.monotonic()
    """), "timer-discipline")
    assert not findings


# ---------------------------------------------------------------------------
# error-context
# ---------------------------------------------------------------------------


def test_error_context_flags_bare_accl_error(tmp_path):
    findings = _live(_lint(tmp_path, """
        def f():
            raise ACCLError(ErrorCode.INVALID_RANK, "rank 9")
    """), "error-context")
    assert len(findings) == 1


def test_error_context_passes_with_details(tmp_path):
    findings = _live(_lint(tmp_path, """
        def f(rank):
            raise ACCLError(ErrorCode.INVALID_RANK, "rank",
                            details={"rank": rank})
    """), "error-context")
    assert not findings


# ---------------------------------------------------------------------------
# spmd-uniformity
# ---------------------------------------------------------------------------


def test_spmd_uniformity_flags_rank_branch(tmp_path):
    findings = _live(_lint(tmp_path, """
        @spmd_uniform
        def decide(self, comm):
            if comm.local_rank == 0:
                return "fuse"
            return "serial"
    """), "spmd-uniformity")
    assert len(findings) == 1
    assert "local_rank" in findings[0].message


def test_spmd_uniformity_flags_buffer_identity(tmp_path):
    findings = _live(_lint(tmp_path, """
        @spmd_uniform
        def decide(buf, other):
            return "fuse" if id(buf) == id(other) else "serial"
    """), "spmd-uniformity")
    assert len(findings) == 1


def test_spmd_uniformity_flags_health_map(tmp_path):
    findings = _live(_lint(tmp_path, """
        @spmd_uniform
        def decide(self, peer):
            while self._health[peer]["state"] != "ok":
                pass
    """), "spmd-uniformity")
    assert len(findings) == 1


def test_spmd_uniformity_ignores_unmarked_and_uniform(tmp_path):
    findings = _live(_lint(tmp_path, """
        def unmarked(comm):
            if comm.local_rank == 0:   # fine: not marked
                return 1

        @spmd_uniform
        def uniform(count, table):
            if count > 4096:           # fine: uniform operands
                return table["big"]
            return table["small"]
    """), "spmd-uniformity")
    assert not findings


# ---------------------------------------------------------------------------
# jax-free-module / drain-before-config (cross-file, run on the real tree)
# ---------------------------------------------------------------------------


def test_jax_free_modules_clean_at_head():
    assert not _live(run_checks(checks=["jax-free-module"]))


def test_jax_free_module_subset_invocation_matches_full_run():
    """Pointing the analyzer at ONE package file must not fabricate
    'module not found' findings — the import closure is pulled from
    disk so per-file invocations agree with the whole-package verdict."""
    target = os.path.join(package_root(), "plans.py")
    assert not _live(run_checks([target], ["jax-free-module"]))


def test_jax_free_module_traverses_from_import_alias(tmp_path, monkeypatch):
    # 'from . import heavy' names a module via its ALIAS; the closure
    # must follow it (and subpackage __init__s) to the numpy import
    pkg = tmp_path / "accl_tpu"
    (pkg / "sub").mkdir(parents=True)
    (pkg / "overlap.py").write_text("from . import heavy\n")
    (pkg / "heavy.py").write_text("from .sub.leaf import x\n")
    (pkg / "sub" / "__init__.py").write_text("import numpy\n")
    (pkg / "sub" / "leaf.py").write_text("x = 1\n")
    for m in ("constants", "telemetry", "faults", "plans", "contract",
              "monitor", "membership", "arbiter", "wire",
              "errorfeedback", "topology", "hierarchical"):
        (pkg / f"{m}.py").write_text("")
    import accl_tpu.analysis.graph as graph_mod

    monkeypatch.setattr(graph_mod, "package_root", lambda: str(pkg))
    findings = _live(
        run_checks([str(pkg)], ["jax-free-module"]), "jax-free-module"
    )
    assert len(findings) == 1
    assert "numpy" in findings[0].message
    assert findings[0].path.endswith("__init__.py")


def test_jax_free_module_detects_violation(tmp_path, monkeypatch):
    # a copy of the package layout where 'overlap' imports numpy
    pkg = tmp_path / "accl_tpu"
    pkg.mkdir()
    (pkg / "overlap.py").write_text("import numpy\n")
    (pkg / "constants.py").write_text("X = 1\n")
    (pkg / "telemetry.py").write_text("from .constants import X\n")
    (pkg / "faults.py").write_text("")
    (pkg / "plans.py").write_text("")
    (pkg / "contract.py").write_text("")
    (pkg / "monitor.py").write_text("")
    (pkg / "membership.py").write_text("")
    (pkg / "arbiter.py").write_text("")
    (pkg / "wire.py").write_text("")
    (pkg / "errorfeedback.py").write_text("")
    (pkg / "topology.py").write_text("")
    (pkg / "hierarchical.py").write_text("")
    import accl_tpu.analysis.base as base_mod

    monkeypatch.setattr(base_mod, "package_root", lambda: str(pkg))
    import accl_tpu.analysis.graph as graph_mod

    monkeypatch.setattr(graph_mod, "package_root", lambda: str(pkg))
    findings = _live(
        run_checks([str(pkg)], ["jax-free-module"]), "jax-free-module"
    )
    assert len(findings) == 1
    assert "numpy" in findings[0].message


def test_jax_free_module_sees_with_block_imports(tmp_path, monkeypatch):
    """``with contextlib.suppress(ImportError): import numpy`` at module
    scope executes at import time — the closure walk must descend
    module-level with/for/while bodies, not just if/try."""
    pkg = tmp_path / "accl_tpu"
    pkg.mkdir()
    (pkg / "plans.py").write_text(
        "import contextlib\n"
        "with contextlib.suppress(ImportError):\n"
        "    import numpy\n"
    )
    for m in ("constants", "overlap", "telemetry", "faults", "contract",
              "monitor", "membership", "arbiter", "wire",
              "errorfeedback", "topology", "hierarchical"):
        (pkg / f"{m}.py").write_text("")
    import accl_tpu.analysis.base as base_mod
    import accl_tpu.analysis.graph as graph_mod

    monkeypatch.setattr(base_mod, "package_root", lambda: str(pkg))
    monkeypatch.setattr(graph_mod, "package_root", lambda: str(pkg))
    findings = _live(
        run_checks([str(pkg)], ["jax-free-module"]), "jax-free-module"
    )
    assert len(findings) == 1
    assert "numpy" in findings[0].message


def test_jax_free_modules_import_without_heavy_stack():
    """Runtime proof of the static claim: load the six modules in a
    subprocess with jax/numpy/ml_dtypes import-blocked (the package
    __init__ bypassed, exactly as a jax-free rank process loads them)."""
    code = textwrap.dedent("""
        import importlib.util, os, sys, types

        class Blocker:
            BLOCKED = ('jax', 'jaxlib', 'numpy', 'ml_dtypes')
            def find_module(self, name, path=None):
                if name.split('.')[0] in self.BLOCKED:
                    return self
            def load_module(self, name):
                raise ImportError('blocked: ' + name)

        sys.meta_path.insert(0, Blocker())
        root = sys.argv[1]
        pkg = types.ModuleType('accl_tpu')
        pkg.__path__ = [root]
        sys.modules['accl_tpu'] = pkg
        for m in ('constants', 'overlap', 'telemetry', 'faults', 'plans',
                  'contract', 'monitor', 'membership', 'arbiter',
                  'wire', 'errorfeedback', 'topology', 'hierarchical'):
            spec = importlib.util.spec_from_file_location(
                'accl_tpu.' + m, os.path.join(root, m + '.py'))
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod
            spec.loader.exec_module(mod)
        c = sys.modules['accl_tpu.constants']
        assert c.dtype_size(c.DataType.FLOAT32) == 4
        print('OK')
    """)
    out = subprocess.run(
        [sys.executable, "-c", code, package_root()],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_drain_before_config_clean_at_head():
    assert not _live(run_checks(checks=["drain-before-config"]))


def test_drain_before_config_detects_missing_drain(tmp_path):
    findings = _live(_lint(tmp_path, """
        class Engine:
            def soft_reset(self):
                self._slots.clear()   # abandons state, never drains
    """), "drain-before-config")
    assert len(findings) == 1


def test_drain_before_config_follows_call_graph(tmp_path):
    findings = _live(_lint(tmp_path, """
        class Facade:
            def _config(self, fn, value):
                self._sync()
                self.engine.start(CallOptions(op=Operation.CONFIG))

            def _sync(self):
                self.flush()

            def soft_reset(self):
                self._config(0, 1)
    """), "drain-before-config")
    assert not findings


def test_drain_before_config_checks_every_same_named_entry(tmp_path):
    """Two classes in one module can both define soft_reset; EVERY one
    is an entry point — the second must not hide behind the first."""
    findings = _live(_lint(tmp_path, """
        class Good:
            def soft_reset(self):
                self.flush()

        class Bad:
            def soft_reset(self):
                self._slots.clear()   # abandons state, never drains
    """), "drain-before-config")
    assert len(findings) == 1
    assert "soft_reset" in findings[0].message


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppression_same_line_round_trip(tmp_path):
    findings = _lint(tmp_path, """
        def f(ev):
            ev.wait()  # acclint: allow[unbounded-wait] watchdog bounds it
    """)
    assert not _live(findings)
    sup = [f for f in findings if f.suppressed]
    assert len(sup) == 1
    assert sup[0].suppress_reason == "watchdog bounds it"


def test_suppression_own_line_binds_to_next_code_line(tmp_path):
    findings = _lint(tmp_path, """
        def f(ev):
            # acclint: allow[unbounded-wait] reason spans a comment
            # block above the call it audits
            ev.wait()
    """)
    assert not _live(findings)
    assert any(f.suppressed for f in findings)


def test_suppression_without_reason_does_not_apply(tmp_path):
    findings = _lint(tmp_path, """
        def f(ev):
            ev.wait()  # acclint: allow[unbounded-wait]
    """)
    assert _live(findings, "unbounded-wait")
    assert _live(findings, "suppression-syntax")


def test_suppression_is_per_check(tmp_path):
    findings = _lint(tmp_path, """
        import time
        def f(ev):
            ev.wait(time.time())  # acclint: allow[unbounded-wait] nope
    """)
    # the unrelated timer-discipline finding on the same line survives
    assert _live(findings, "timer-discipline")


# ---------------------------------------------------------------------------
# whole-tree gate + CLI
# ---------------------------------------------------------------------------


def test_whole_tree_clean_at_head():
    """THE gate: zero unsuppressed findings over the package."""
    live = _live(run_checks())
    assert not live, "\n".join(f.render() for f in live)


def test_unknown_check_rejected():
    with pytest.raises(ValueError):
        run_checks(checks=["no-such-check"])


def test_cli_check_mode_and_json(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "accl_tpu.analysis", "--check"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr

    bad = tmp_path / "bad.py"
    bad.write_text("def f(ev):\n    ev.wait()\n")
    out = subprocess.run(
        [sys.executable, "-m", "accl_tpu.analysis", "--json", str(bad)],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 1
    data = json.loads(out.stdout)
    assert any(f["check"] == "unbounded-wait" for f in data)

    out = subprocess.run(
        [sys.executable, "-m", "accl_tpu.analysis", "--list"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0
    assert set(out.stdout.split()) == set(CHECKS)


def test_syntax_error_is_a_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = run_checks([str(bad)])
    assert any(f.check == "parse" for f in findings)


# ---------------------------------------------------------------------------
# lock-order registry (the dynamic detector)
# ---------------------------------------------------------------------------


def _locked_pair(reg):
    a = InstrumentedLock(threading.Lock(), "A", "test:A", reg)
    b = InstrumentedLock(threading.Lock(), "B", "test:B", reg)
    return a, b


def test_lockorder_seeded_inversion_detected():
    """The acceptance-criteria proof: an ABBA inversion (A->B on one
    thread, B->A on another) must surface as a cycle."""
    reg = LockOrderRegistry()
    a, b = _locked_pair(reg)

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=ab)
    t2 = threading.Thread(target=ba)
    t1.start(); t1.join(timeout=10)
    t2.start(); t2.join(timeout=10)
    problems = reg.violations()
    assert problems and "cycle" in problems[0]
    assert ("A", "B") in reg.edges and ("B", "A") in reg.edges


def test_lockorder_consistent_order_is_clean():
    reg = LockOrderRegistry()
    a, b = _locked_pair(reg)
    for _ in range(3):
        with a:
            with b:
                pass
    assert reg.violations() == []
    assert reg.family_edges() == {("A", "B")}


def test_lockorder_rlock_reentrancy_not_an_edge():
    reg = LockOrderRegistry()
    r = InstrumentedLock(threading.RLock(), "R", "test:R", reg)
    with r:
        with r:  # re-acquire of a held lock is not an ordering fact
            pass
    assert reg.family_edges() == set()


def test_lockorder_condition_wait_safe():
    """Condition(wrapped Lock) must work through the proxy (the shape
    CommandQueue/InflightWindow use) and record honest edges."""
    reg = LockOrderRegistry()
    inner = InstrumentedLock(threading.Lock(), "CVLock", "test:cv", reg)
    cv = threading.Condition(inner)
    done = []

    def waiter():
        with cv:
            cv.wait(timeout=5)
            done.append(True)

    t = threading.Thread(target=waiter)
    t.start()
    for _ in range(100):
        with cv:
            cv.notify_all()
        if done:
            break
        import time

        time.sleep(0.01)
    t.join(timeout=10)
    assert done
    assert reg.violations() == []


def test_lockorder_snapshot_diff(tmp_path):
    reg = LockOrderRegistry()
    a, b = _locked_pair(reg)
    with a:
        with b:
            pass
    snap = tmp_path / "hier.json"
    reg.write_snapshot(str(snap))
    assert load_snapshot(str(snap)) == {("A", "B")}
    # same edges vs snapshot: clean
    assert reg.violations(load_snapshot(str(snap))) == []
    # a NEW edge not in the snapshot must be reported for review
    reg2 = LockOrderRegistry()
    a2, b2 = _locked_pair(reg2)
    c2 = InstrumentedLock(threading.Lock(), "C", "test:C", reg2)
    with a2:
        with b2:
            pass
        with c2:
            pass
    problems = reg2.violations(load_snapshot(str(snap)))
    assert problems and "not in the reviewed snapshot" in problems[0]
    # an edge CONTRADICTING the snapshot order is an ordering violation
    reg3 = LockOrderRegistry()
    a3, b3 = _locked_pair(reg3)
    with b3:
        with a3:
            pass
    problems = reg3.violations(load_snapshot(str(snap)))
    assert any(
        "ordering violation" in p or "not in the reviewed snapshot" in p
        for p in problems
    )
    merged = reg3.family_edges() | load_snapshot(str(snap))
    assert LockOrderRegistry._find_cycle(merged) is not None


def test_lockorder_install_wraps_only_project_locks(tmp_path):
    """install() must wrap locks created by accl_tpu code and leave
    foreign allocations raw (jax/XLA internals must run untouched)."""
    from accl_tpu.analysis import lockorder

    if lockorder.active_registry() is not None:
        pytest.skip("ACCL_LOCKCHECK session owns the global shim")
    reg = lockorder.install()
    try:
        from accl_tpu.overlap import InflightWindow

        w = InflightWindow(depth=2)
        assert isinstance(w._lock, InstrumentedLock)
        assert w._lock._family == "InflightWindow"
        # a lock created HERE (tests/, outside the package) stays raw
        assert not isinstance(threading.Lock(), InstrumentedLock)
        # and the instrumented window still works end to end
        fired = []
        w.park("k", lambda: None, lambda *a: fired.append(a),
               lambda e: fired.append(e))
        assert w.drain(timeout=10)
        assert len(fired) == 1
        w.stop()
    finally:
        lockorder.uninstall()
    assert reg.acquisitions > 0


def test_lockorder_reinstall_rebinds_surviving_proxies():
    """Long-lived locks created under session A must record into a
    LATER session's registry — a stale proxy bound to a dead registry
    would blind the new session to every edge that lock joins."""
    from accl_tpu.analysis import lockorder

    if lockorder.active_registry() is not None:
        pytest.skip("ACCL_LOCKCHECK session owns the global shim")
    reg1 = lockorder.install()
    try:
        from accl_tpu.overlap import InflightWindow

        w = InflightWindow(depth=2)
        assert isinstance(w._lock, InstrumentedLock)
        assert w._lock._registry is reg1
    finally:
        lockorder.uninstall()
    reg2 = lockorder.install()
    try:
        assert reg2 is not reg1
        assert w._lock._registry is reg2
        before = reg2.acquisitions
        with w._lock:
            pass
        assert reg2.acquisitions == before + 1
    finally:
        w.stop()
        lockorder.uninstall()


def test_committed_lock_hierarchy_snapshot_is_sane():
    """The reviewed artifact must exist, parse, and be cycle-free (a
    committed snapshot containing a cycle would bless a deadlock)."""
    path = os.path.join(REPO, "tests", "lock_hierarchy.json")
    assert os.path.exists(path), "tests/lock_hierarchy.json not committed"
    edges = load_snapshot(path)
    assert edges, "snapshot has no edges — regenerate with ACCL_LOCKCHECK=1"
    assert LockOrderRegistry._find_cycle(edges) is None
    families = {f for e in edges for f in e}
    # the telemetry locks are the one family the completion paths DO
    # nest under (everything else — InflightWindow, CommandQueue,
    # PlanCache — releases before calling out, which is why the
    # committed graph is so small; the detector proves that stays true)
    assert families & {"FlightRecorder", "MetricsRegistry"}


# ---------------------------------------------------------------------------
# thread-naming
# ---------------------------------------------------------------------------


BAD_THREADS = [
    "threading.Thread(target=f)",
    "threading.Thread(target=f, daemon=True)",
    'threading.Thread(target=f, name="worker-1")',
    'Thread(target=f, name="drainer")',
    # import aliases must not bypass the guard
    "th.Thread(target=f)",
    'T(target=f, name="oops")',
]

GOOD_THREADS = [
    'threading.Thread(target=f, name="accl-engine-x", daemon=True)',
    'threading.Thread(target=f, name=f"accl-fabric-{addr}")',
    'Thread(target=f, name="accl-dist-op")',
    "threading.Thread(target=f, name=make_name())",  # non-literal: trusted
    "threading.Timer(1.0, f)",  # Timer is not Thread(); out of scope
]


@pytest.mark.parametrize("code", BAD_THREADS)
def test_thread_naming_flags(tmp_path, code):
    findings = _live(
        _lint(tmp_path, f"""
            import threading
            import threading as th
            from threading import Thread
            from threading import Thread as T
            def g(f, addr, make_name):
                t = {code}
        """),
        "thread-naming",
    )
    assert len(findings) == 1, code


@pytest.mark.parametrize("code", GOOD_THREADS)
def test_thread_naming_passes(tmp_path, code):
    findings = _live(
        _lint(tmp_path, f"""
            import threading
            import threading as th
            from threading import Thread
            from threading import Thread as T
            def g(f, addr, make_name):
                t = {code}
        """),
        "thread-naming",
    )
    assert findings == [], code


def test_thread_naming_suppressible(tmp_path):
    findings = _lint(tmp_path, """
        import threading
        def g(f):
            t = threading.Thread(target=f)  # acclint: allow[thread-naming] short-lived probe
    """, ["thread-naming"])
    assert findings and all(f.suppressed for f in findings)


# ---------------------------------------------------------------------------
# collective-sequence (the static half of the contract plane)
# ---------------------------------------------------------------------------


BAD_SEQUENCES = [
    # op choice branched on rank
    """
    def work(accl, rank, world):
        if rank == 0:
            accl.allreduce(a, b, 64)
        else:
            accl.allgather(a, b, 64)
    """,
    # count derived from rank
    """
    def work(accl, rank, world):
        n = 64 + rank
        accl.allreduce(a, b, n)
    """,
    # root keyword from rank
    """
    def work(accl, rank, world):
        accl.bcast(buf, 64, root=rank % world)
    """,
    # tag from process-local id()
    """
    def work(accl, comm):
        accl.allreduce(a, b, 64, tag=id(comm) & 0xFF)
    """,
    # comm choice from a health map
    """
    def work(accl, comms):
        live = accl.capabilities()["health"]
        accl.barrier(comm=pick(live))
    """,
    # count via a tainted same-module helper (the interprocedural hop)
    """
    def shard(rank, n):
        return n // (rank + 1)
    def work(accl, rank):
        accl.allreduce(a, b, shard(rank, 64))
    """,
    # op guarded by unseeded process RNG
    """
    import random
    def work(accl):
        if random.random() < 0.5:
            accl.barrier()
    """,
    # batch boundary under a rank branch (the contract extends to
    # batches)
    """
    def work(accl, rank):
        if rank == 0:
            accl.begin_batch()
    """,
    # membership plane: a LOCAL health-map read steering a contract
    # field — raw health reads stay taint sources even though the
    # exchanged-verdict accessors (suggest_root/demote_decision) are
    # sanitizers; per-rank health maps differ, so this root diverges
    """
    def work(accl, comm):
        health = accl.capabilities()["health"]
        root = 1 if health[0]["state"] != "ok" else 0
        accl.bcast(buf, 64, root=root)
    """,
    # a collective GUARDED by the local health map (the demote-it-
    # myself anti-pattern the membership plane's exchanged verdicts
    # exist to replace)
    """
    def work(accl, comm):
        health = accl.capabilities()["health"]
        if health[2]["state"] == "ok":
            accl.allreduce(a, b, 64, comm=comm)
    """,
    # elastic expansion: branching a collective on the RAW last_join
    # record (snapshot arrival timing differs per rank around a
    # cutover) instead of the latched join_decision accessor
    """
    def work(accl, comm):
        snap = accl.telemetry_snapshot()
        if snap["membership"]["last_join"]:
            accl.barrier(comm=comm)
    """,
    # the candidate's per-rank self_evicted bit steering a contract
    # field — survivors read False, the healing rank True
    """
    def work(accl, view):
        root = 1 if view.self_evicted else 0
        accl.bcast(buf, 64, root=root)
    """,
]

GOOD_SEQUENCES = [
    # rank-varying OPERANDS are the API working as designed
    """
    def work(accl, rank, world):
        send = accl.create_buffer_from(data) if rank == 0 else None
        accl.scatter(send, recv, 64, root=0)
    """,
    # uniform loop bounds / uniform fields
    """
    def work(accl, rank, world):
        for root in range(world):
            accl.bcast(buf, 256, root=root)
    """,
    # rank flows into DATA, not contract fields
    """
    def work(accl, rank, world):
        chunk = make_data(700 + rank * 13)
        send = accl.create_buffer_from(chunk)
        accl.allreduce(send, recv, 256)
    """,
    # an @spmd_uniform-marked helper sanitizes its result by contract
    """
    from accl_tpu.analysis.markers import spmd_uniform
    @spmd_uniform
    def bucket(n):
        return 1 << n.bit_length()
    def work(accl, rank):
        accl.allreduce(a, b, bucket(64))
    """,
    # create_communicator is the blessed split constructor: per-rank
    # membership in, uniform handle out
    """
    def work(accl, rank, world):
        half = list(range(world // 2)) if rank < world // 2 else \
            list(range(world // 2, world))
        sub = accl.create_communicator(half)
        if sub is not None:
            accl.allreduce(a, b, 64, comm=sub)
    """,
    # bare-name reduce is functools.reduce, not a collective
    """
    from functools import reduce
    def work(rank, xs):
        return reduce(lambda a, b: a + b, xs, rank)
    """,
    # membership plane: suggest_root derives from the EXCHANGED
    # demotion verdict (shared ledger, latched per call index) — a
    # sanitizer by construction, even downstream of a health-tainted
    # handle
    """
    def work(accl, comm):
        health = accl.capabilities()["health"]
        log(health)
        root = accl.suggest_root(comm)
        accl.bcast(buf, 64, root=root)
    """,
    # demote_decision is the latched SPMD-uniform decision surface
    """
    def work(accl, comm, seq):
        d = view.demote_decision(comm.id, 4, seq, [], {})
        accl.bcast(buf, 64, root=d["root"])
    """,
    # join_decision is its admission mirror: majority-confirmed and
    # cutover-applied, every member reads the same record — a
    # sanitizer by construction
    """
    def work(accl, comm):
        d = accl.join_decision()
        accl.bcast(buf, 64, root=min(d["admitted"] or [0]))
    """,
]


@pytest.mark.parametrize("code", BAD_SEQUENCES)
def test_collective_sequence_flags(tmp_path, code):
    findings = _live(
        _lint(tmp_path, code, ["collective-sequence"]),
        "collective-sequence",
    )
    assert findings, code


@pytest.mark.parametrize("code", GOOD_SEQUENCES)
def test_collective_sequence_passes(tmp_path, code):
    findings = _live(
        _lint(tmp_path, code, ["collective-sequence"]),
        "collective-sequence",
    )
    assert findings == [], (code, [f.render() for f in findings])


def test_collective_sequence_suppressible(tmp_path):
    findings = _lint(tmp_path, """
        def work(accl, rank, world):
            # acclint: allow[collective-sequence] ranks rejoin at the barrier below
            accl.bcast(buf, 64, root=rank)
    """, ["collective-sequence"])
    assert findings and all(f.suppressed for f in findings)


def test_collective_sequence_covers_shared_scenarios(tmp_path, monkeypatch):
    """The default (package) run must also analyze the extra-scope
    shared scenario library outside the package — proved by pointing
    extra_scope at a planted bad file and asserting the default run
    flags it (a broken extra-scope wiring would pass a
    file-exists-and-clean assertion vacuously)."""
    scen = os.path.join(REPO, "tests", "shared_scenarios.py")
    assert os.path.isfile(scen)
    assert _live(
        run_checks(checks=["collective-sequence"]), "collective-sequence"
    ) == []
    planted = tmp_path / "scenarios.py"
    planted.write_text(textwrap.dedent("""
        def work(accl, rank, world):
            accl.bcast(buf, 64, root=rank)
    """))
    import accl_tpu.analysis as analysis_mod

    monkeypatch.setattr(
        analysis_mod, "extra_scope", lambda: [str(planted)]
    )
    findings = _live(
        run_checks(checks=["collective-sequence"]), "collective-sequence"
    )
    assert [f for f in findings if f.path == str(planted)], (
        "default run did not analyze the extra-scope file"
    )


def test_collective_sequence_whole_tree_clean():
    assert _live(run_checks(), "collective-sequence") == []


# ---------------------------------------------------------------------------
# SARIF output
# ---------------------------------------------------------------------------


def test_sarif_output_shape(tmp_path):
    from accl_tpu.analysis.__main__ import to_sarif

    findings = _lint(tmp_path, """
        import threading
        def g(f):
            a = threading.Thread(target=f)
            b = threading.Thread(target=f)  # acclint: allow[thread-naming] probe
    """, ["thread-naming"])
    doc = to_sarif(findings)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "acclint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert set(CHECKS) <= rule_ids
    results = run["results"]
    assert len(results) == 2
    by_level = {r["level"] for r in results}
    assert by_level == {"error", "note"}
    supp = next(r for r in results if r["level"] == "note")
    assert supp["suppressions"][0]["justification"] == "probe"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] >= 1
    assert not loc["artifactLocation"]["uri"].startswith("/") or True


def test_sarif_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import threading\nt = threading.Thread()\n")
    out = subprocess.run(
        [sys.executable, "-m", "accl_tpu.analysis", "--sarif", str(bad)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 1
    doc = json.loads(out.stdout)
    assert doc["runs"][0]["results"]
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    out = subprocess.run(
        [sys.executable, "-m", "accl_tpu.analysis", "--sarif", str(good)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0
    assert json.loads(out.stdout)["runs"][0]["results"] == []


def test_collective_sequence_flags_rank_varying_loop_count(tmp_path):
    """A for-loop whose ITERABLE derives from rank governs the trip
    count: collectives inside run a different number of times per rank
    — call-count divergence, flagged like a branch."""
    findings = _live(
        _lint(tmp_path, """
            def work(accl, rank, world):
                for _ in range(rank):
                    accl.barrier()
        """, ["collective-sequence"]),
        "collective-sequence",
    )
    assert findings and "barrier" in findings[0].message
    # uniform loop bounds stay clean
    findings = _live(
        _lint(tmp_path, """
            def work(accl, rank, world):
                for _ in range(world):
                    accl.barrier()
        """, ["collective-sequence"]),
        "collective-sequence",
    )
    assert findings == []


# ---------------------------------------------------------------------------
# metric-naming
# ---------------------------------------------------------------------------

BAD_METRICS = [
    'registry.inc("calls_total")',
    'registry.inc("deadlocks", ("op",))',
    'self.metrics.inc("retries_total")',
    'gauge("device_interactions", 3)',
    'gauge(f"engine_{k}", v)',
    # the causal-trace/postmortem PR's new gauge call sites stay in
    # scope: ring introspection and bundle accounting must carry the
    # prefix like every earlier plane's metrics
    'gauge("cmdring_mailbox_depth", v)',
    'gauge("postmortem_bundles", n)',
    'self.metrics.inc("postmortem_bundles_total")',
]

GOOD_METRICS = [
    'registry.inc("accl_calls_total")',
    'self.metrics.inc("accl_call_errors_total", (op, name))',
    'gauge("accl_device_interactions", n)',
    'gauge(f"accl_engine_{k}", v)',
    'counts.inc("x y z")',      # not a metric-shaped literal
    'registry.inc(name)',       # dynamic: nothing checkable statically
    'd.get("calls_total")',     # not a registry call at all
]


@pytest.mark.parametrize("code", BAD_METRICS)
def test_metric_naming_flags(tmp_path, code):
    findings = _live(
        _lint(tmp_path, f"def f(registry, gauge, k, v, n, op, name, self):\n"
                        f"    {code}\n"),
        "metric-naming",
    )
    assert len(findings) == 1, code
    assert "accl_" in findings[0].message


@pytest.mark.parametrize("code", GOOD_METRICS)
def test_metric_naming_passes(tmp_path, code):
    findings = _live(
        _lint(tmp_path, f"def f(registry, gauge, counts, d, k, v, n, op,"
                        f" name, self):\n    {code}\n"),
        "metric-naming",
    )
    assert not findings, code


def test_metric_naming_suppressible(tmp_path):
    findings = _live(_lint(tmp_path, """
        def f(registry):
            registry.inc("legacy_total")  # acclint: allow[metric-naming] pre-prefix legacy export
    """), "metric-naming")
    assert not findings


def test_metric_naming_clean_at_head():
    assert not _live(run_checks(checks=["metric-naming"]))


# ---------------------------------------------------------------------------
# cmdring-slot-layout (encoder and sequencer agree on ONE table)
# ---------------------------------------------------------------------------

_RING_CONSTS = """
CMDRING_SLOT_WORDS = 4
CMDRING_FIELDS = {"seqn": 0, "opcode": 1, "count": 2, "root": 3}
"""


def _ring_pkg(tmp_path, monkeypatch, consts, encoder):
    pkg = tmp_path / "accl_tpu"
    (pkg / "ops").mkdir(parents=True)
    (pkg / "backends" / "xla").mkdir(parents=True)
    (pkg / "constants.py").write_text(consts)
    (pkg / "ops" / "cmdring.py").write_text(encoder)
    import accl_tpu.analysis.base as base_mod
    import accl_tpu.analysis.graph as graph_mod

    monkeypatch.setattr(base_mod, "package_root", lambda: str(pkg))
    monkeypatch.setattr(graph_mod, "package_root", lambda: str(pkg))
    return _live(
        run_checks([str(pkg)], ["cmdring-slot-layout"]),
        "cmdring-slot-layout",
    )


def test_cmdring_layout_clean_at_head():
    assert not _live(run_checks(checks=["cmdring-slot-layout"]))


def test_cmdring_layout_accepts_table_driven_encoder(
    tmp_path, monkeypatch
):
    findings = _ring_pkg(tmp_path, monkeypatch, _RING_CONSTS, """
from ...constants import CMDRING_FIELDS
_F = CMDRING_FIELDS
def encode(words, seqn):
    words[_F["seqn"]] = seqn
    words[_F["root"]] = 0
""")
    assert not findings


def test_cmdring_layout_flags_unknown_field(tmp_path, monkeypatch):
    findings = _ring_pkg(tmp_path, monkeypatch, _RING_CONSTS, """
from ...constants import CMDRING_FIELDS
_F = CMDRING_FIELDS
def encode(words, seqn):
    words[_F["sequence"]] = seqn
""")
    assert len(findings) == 1
    assert "sequence" in findings[0].message


def test_cmdring_layout_flags_local_redefinition(tmp_path, monkeypatch):
    findings = _ring_pkg(tmp_path, monkeypatch, _RING_CONSTS, """
CMDRING_SLOT_WORDS = 6
def encode(words):
    return words[:CMDRING_SLOT_WORDS]
""")
    assert len(findings) == 1
    assert "redefined" in findings[0].message


def test_cmdring_layout_flags_malformed_table(tmp_path, monkeypatch):
    bad = """
CMDRING_SLOT_WORDS = 2
CMDRING_FIELDS = {"seqn": 0, "opcode": 5}
"""
    findings = _ring_pkg(tmp_path, monkeypatch, bad, """
from ...constants import CMDRING_FIELDS
""")
    assert len(findings) == 1
    assert "dense" in findings[0].message


# the grown-opcode contract: dense enum, full Operation map, and the
# decode module referencing every executable opcode

_RING_CONSTS_OPS = _RING_CONSTS + """
class CmdOpcode:
    NOP = 0
    ALLREDUCE = 1
    HALT = 2
    ALLGATHER = 3

CMDRING_OPCODES = {
    "allreduce": CmdOpcode.ALLREDUCE,
    "allgather": CmdOpcode.ALLGATHER,
}
"""

_RING_DECODER_OPS = """
from ...constants import CMDRING_FIELDS, CmdOpcode
_F = CMDRING_FIELDS
def decode(op, blocks, own):
    if op == CmdOpcode.ALLREDUCE:
        return sum(blocks)
    if op == CmdOpcode.ALLGATHER:
        return blocks
    return own
"""


def test_cmdring_opcode_contract_clean(tmp_path, monkeypatch):
    findings = _ring_pkg(
        tmp_path, monkeypatch, _RING_CONSTS_OPS, _RING_DECODER_OPS
    )
    assert not findings


def test_cmdring_flags_sparse_opcode_values(tmp_path, monkeypatch):
    sparse = _RING_CONSTS_OPS.replace("ALLGATHER = 3", "ALLGATHER = 7")
    findings = _ring_pkg(
        tmp_path, monkeypatch, sparse, _RING_DECODER_OPS
    )
    assert len(findings) == 1
    assert "dense" in findings[0].message and "CmdOpcode" in (
        findings[0].message
    )


def test_cmdring_flags_unmapped_opcode(tmp_path, monkeypatch):
    unmapped = _RING_CONSTS_OPS.replace(
        '    "allgather": CmdOpcode.ALLGATHER,\n', ""
    )
    findings = _ring_pkg(
        tmp_path, monkeypatch, unmapped, _RING_DECODER_OPS
    )
    assert len(findings) == 1
    assert "ALLGATHER" in findings[0].message
    assert "CMDRING_OPCODES" in findings[0].message


def test_cmdring_flags_unimplemented_opcode_in_decoder(
    tmp_path, monkeypatch
):
    decoder = _RING_DECODER_OPS.replace(
        "    if op == CmdOpcode.ALLGATHER:\n        return blocks\n", ""
    )
    findings = _ring_pkg(
        tmp_path, monkeypatch, _RING_CONSTS_OPS, decoder
    )
    assert len(findings) == 1
    assert "ALLGATHER" in findings[0].message
    assert "unimplemented" in findings[0].message


# the fused-opcode contract (kernel-initiated collectives): growing the
# enum with FUSED_* compute slots without wiring the Operation map or
# the decode loop fails the tree — each wiring obligation has a known-bad
# fixture

_RING_CONSTS_FUSED = _RING_CONSTS + """
class CmdOpcode:
    NOP = 0
    ALLREDUCE = 1
    HALT = 2
    FUSED_MATMUL_RS = 3
    FUSED_APPLY = 4

CMDRING_OPCODES = {
    "allreduce": CmdOpcode.ALLREDUCE,
    "fused_matmul_rs": CmdOpcode.FUSED_MATMUL_RS,
    "fused_apply": CmdOpcode.FUSED_APPLY,
}
"""

_RING_DECODER_FUSED = """
from ...constants import CMDRING_FIELDS, CmdOpcode
_F = CMDRING_FIELDS
def decode(op, blocks, own, fp):
    if op == CmdOpcode.ALLREDUCE:
        return sum(blocks)
    if op == CmdOpcode.FUSED_MATMUL_RS:
        return fp * sum(blocks)
    if op == CmdOpcode.FUSED_APPLY:
        return own - fp * sum(blocks)
    return own
"""


def test_cmdring_fused_opcode_contract_clean(tmp_path, monkeypatch):
    findings = _ring_pkg(
        tmp_path, monkeypatch, _RING_CONSTS_FUSED, _RING_DECODER_FUSED
    )
    assert not findings


def test_cmdring_flags_sparse_fused_opcode_values(tmp_path, monkeypatch):
    """A fused opcode added off the dense range (the tempting 0x10
    block) breaks the sequencer's range-check status path."""
    sparse = _RING_CONSTS_FUSED.replace(
        "FUSED_APPLY = 4", "FUSED_APPLY = 16"
    )
    findings = _ring_pkg(
        tmp_path, monkeypatch, sparse, _RING_DECODER_FUSED
    )
    assert len(findings) == 1
    assert "dense" in findings[0].message
    assert "CmdOpcode" in findings[0].message


def test_cmdring_flags_unmapped_fused_opcode(tmp_path, monkeypatch):
    """A fused opcode no Operation maps onto is dead enum growth — the
    engine planner can never encode it."""
    unmapped = _RING_CONSTS_FUSED.replace(
        '    "fused_apply": CmdOpcode.FUSED_APPLY,\n', ""
    )
    findings = _ring_pkg(
        tmp_path, monkeypatch, unmapped, _RING_DECODER_FUSED
    )
    assert len(findings) == 1
    assert "FUSED_APPLY" in findings[0].message
    assert "CMDRING_OPCODES" in findings[0].message


def test_cmdring_flags_fused_opcode_missing_from_the_decode_loop(
    tmp_path, monkeypatch
):
    """The presence check: a fused opcode the decode module (the decode
    loop every window runs) never references is an unimplemented
    epilogue, caught by the tree not a workload."""
    decoder = _RING_DECODER_FUSED.replace(
        "    if op == CmdOpcode.FUSED_APPLY:\n"
        "        return own - fp * sum(blocks)\n", ""
    )
    findings = _ring_pkg(
        tmp_path, monkeypatch, _RING_CONSTS_FUSED, decoder
    )
    assert len(findings) == 1
    assert "FUSED_APPLY" in findings[0].message
    assert "unimplemented" in findings[0].message


# ---------------------------------------------------------------------------
# postmortem-path (causal trace plane PR)
# ---------------------------------------------------------------------------


def _lint_core(tmp_path, code):
    """The postmortem-path rule scopes to the facade module: fixtures
    must live at .../accl_tpu/core.py to be in scope."""
    pkg = tmp_path / "accl_tpu"
    pkg.mkdir(exist_ok=True)
    p = pkg / "core.py"
    p.write_text(textwrap.dedent(code))
    return run_checks([str(p)])


def test_postmortem_path_clean_at_head():
    assert not _live(run_checks(checks=["postmortem-path"]))


def test_postmortem_path_flags_unhooked_covered_raise(tmp_path):
    findings = _live(_lint_core(tmp_path, """
        class ACCL:
            def _gate(self, ctx):
                raise ACCLError(
                    ErrorCode.CONTRACT_VIOLATION, ctx, details={}
                )
    """), "postmortem-path")
    assert len(findings) == 1
    assert "CONTRACT_VIOLATION" in findings[0].message
    assert "BlackBox" in findings[0].message


def test_postmortem_path_follows_call_graph(tmp_path):
    """A raise that reaches the hook through a same-module funnel is
    clean — the drain-before-config depth-bounded walk, reused."""
    findings = _live(_lint_core(tmp_path, """
        class ACCL:
            def _evicted(self, ctx):
                return self._wrap(ACCLError(
                    ErrorCode.RANK_EVICTED, ctx, details={}
                ))

            def _wrap(self, err):
                return self._structured_failure(err)

            def intake(self, ctx):
                raise self._evicted(ctx)
    """), "postmortem-path")
    assert not findings


def test_postmortem_path_ignores_uncovered_codes(tmp_path):
    findings = _live(_lint_core(tmp_path, """
        class ACCL:
            def check_rank(self, rank):
                raise ACCLError(
                    ErrorCode.INVALID_RANK, "rank", details={}
                )
    """), "postmortem-path")
    assert not findings


def test_postmortem_path_out_of_scope_module(tmp_path):
    """Only the facade module is in scope: engines surface the covered
    codes through Request retcodes, which _check_failed funnels."""
    findings = _live(_lint(tmp_path, """
        def f(ctx):
            raise ACCLError(
                ErrorCode.DEADLOCK_SUSPECTED, ctx, details={}
            )
    """), "postmortem-path")
    assert not findings
