"""Test configuration: force an 8-device virtual CPU mesh before jax loads.

Tests never require real TPU hardware; sharding/collective tests run over
XLA's host-platform device emulation (the same way the driver's
dryrun_multichip validates the multi-chip path).
"""

import faulthandler
import os
import threading

# Sanitizer-grade hardening: a wedged drainer/scheduler thread or a
# deadlocked drain point should dump every thread's stack instead of
# dying silently under the suite timeout.
faulthandler.enable()

# Dynamic lock-order registry (acclint's runtime companion): with
# ACCL_LOCKCHECK=1 every threading.Lock/RLock created by accl_tpu code
# is wrapped in a recording proxy BEFORE any engine exists; the
# session-scoped fixture below reports cycles/unreviewed edges at exit.
# Importing the analysis package is safe here — it is stdlib-only and
# must stay so (its own jax-free-module check applies transitively).
LOCKCHECK = os.environ.get("ACCL_LOCKCHECK") == "1"
_lock_registry = None
if LOCKCHECK:
    from accl_tpu.analysis import lockorder as _lockorder

    _lock_registry = _lockorder.install()

# Opt-in REAL-CHIP tier (ref utility.hpp:29-51 --hardware flag): with
# ACCL_TPU_TIER=1 the platform is left alone (jax takes the TPU, or
# whatever JAX_PLATFORMS names — the tier itself can be developed on the
# CPU host) and collection narrows to tests marked `tpu`
# (tests/test_tpu_tier.py: the facade at world=1 on DeviceBuffer and the
# gang backend single-rank) plus the Pallas kernel suite, compiled by
# Mosaic there; its multi-device tests run on as many chips as the host
# has.  Everything else keeps the 8-device virtual CPU mesh, which has
# to be asked for BEFORE jax is imported.
TPU_TIER = os.environ.get("ACCL_TPU_TIER") == "1"

if not TPU_TIER:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"  # tests don't need real hardware

# No persistent compilation cache is configured here: the tier-1 run
# compiles from cold, so what it reaches inside its time limit does not
# depend on what an earlier run left in the checkout.  (Where
# JAX_COMPILATION_CACHE_DIR is set, jax uses it on its own.)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def _make_group(backend: str, n: int):
    """The reference runs one gtest suite against every execution tier
    (emulator / RTL sim / hardware, utility.hpp:29-51); we parameterize the
    shared fixtures over the Python emulator and the native C++ engine the
    same way."""
    if backend == "native":
        from accl_tpu.backends.native import (
            engine_library_available,
            native_group,
        )

        if not engine_library_available():
            pytest.skip("native engine library unavailable")
        return native_group(n)
    from accl_tpu import emulated_group

    return emulated_group(n)


@pytest.fixture(scope="module", params=["emu", "native"])
def group2(request):
    g = _make_group(request.param, 2)
    yield g
    for a in g:
        a.deinit()


@pytest.fixture(scope="module", params=["emu", "native"])
def group4(request):
    g = _make_group(request.param, 4)
    yield g
    for a in g:
        a.deinit()


@pytest.fixture(scope="module")
def gang4():
    """Four rank handles over the single-process XLA gang backend."""
    from accl_tpu.core import xla_group

    g = xla_group(4)
    yield g
    for a in g:
        a.deinit()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# -- sanitizer-grade runtime hardening ---------------------------------------

#: thread-name prefixes of the project's background machinery (overlap
#: drainers, emulator schedulers, the dist executor); an exception
#: escaping one of these dies silently today unless
#: leaked_scheduler_threads() happens to be asserted
_ACCL_THREAD_PREFIX = "accl-"


@pytest.fixture(autouse=True)
def _accl_thread_excepthook_guard():
    """Fail any test during which an exception escaped a drainer or
    scheduler thread.  The engines' completion paths are wrapped in
    defensive handlers; anything that still reaches threading.excepthook
    on an ``accl-*`` thread is a real bug leaking silently."""
    captured = []
    prev = threading.excepthook

    def hook(args):
        name = getattr(args.thread, "name", "") or ""
        if name.startswith(_ACCL_THREAD_PREFIX):
            captured.append(
                f"{name}: {args.exc_type.__name__}: {args.exc_value}"
            )
        prev(args)

    threading.excepthook = hook
    try:
        yield
    finally:
        threading.excepthook = prev
    assert not captured, (
        "exception(s) leaked on accl background threads (would have died "
        "silently): " + "; ".join(captured)
    )


_LOCK_SNAPSHOT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "lock_hierarchy.json"
)


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_verdict():
    """ACCL_LOCKCHECK=1: after the whole session, check the recorded
    lock-acquisition graph for cycles and for edges the committed
    ``tests/lock_hierarchy.json`` snapshot has not reviewed.  With
    ACCL_LOCKCHECK_UPDATE=1 the snapshot is (re)generated instead —
    audit the diff and commit it."""
    yield
    if _lock_registry is None:
        return
    from accl_tpu.analysis import lockorder as _lockorder

    _lockorder.uninstall()
    if os.environ.get("ACCL_LOCKCHECK_UPDATE") == "1":
        _lockorder.merge_snapshot(_LOCK_SNAPSHOT_PATH, _lock_registry)
        return
    snapshot = None
    if os.path.exists(_LOCK_SNAPSHOT_PATH):
        snapshot = _lockorder.load_snapshot(_LOCK_SNAPSHOT_PATH)
    problems = _lock_registry.violations(snapshot)
    assert not problems, (
        "lock-order violations detected "
        f"({_lock_registry.acquisitions} acquisitions recorded):\n"
        + "\n".join(problems)
    )


@pytest.fixture
def fault_plan():
    """Factory for chaos-plane fault plans: rules as dicts (or FaultRule
    instances), an optional ``seed`` kwarg; install the result on a fabric
    with ``engine.fabric.install_fault_plan(plan)``."""
    from accl_tpu.faults import FaultPlan, FaultRule

    def make(*rules, seed=1234):
        return FaultPlan(
            rules=[
                r if isinstance(r, FaultRule) else FaultRule(**r)
                for r in rules
            ],
            seed=seed,
        )

    return make


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "pallas: Pallas kernel tier (runs interpreted off-TPU)",
    )
    config.addinivalue_line(
        "markers",
        "tpu: real-chip tier (opt-in via ACCL_TPU_TIER=1)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection (chaos-plane) tests",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running soaks excluded from the tier-1 fast run",
    )


def pytest_collection_modifyitems(config, items):
    """ACCL_TPU_TIER=1 swaps the suite to the chip-marked tests only (and
    vice versa) — one flag, two tiers, same tree (utility.hpp:29-51)."""
    if TPU_TIER:
        # chip tier = the tpu-marked facade/world-1 tests PLUS the whole
        # Pallas kernel suite, which on a real chip compiles via Mosaic
        # instead of the interpreter (multi-device Pallas tests self-skip
        # on a single chip via their mesh fixture)
        skip = pytest.mark.skip(reason="not part of the real-TPU tier")
        for item in items:
            if "tpu" not in item.keywords and "pallas" not in item.keywords:
                item.add_marker(skip)
    else:
        skip = pytest.mark.skip(reason="needs ACCL_TPU_TIER=1 + a real chip")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip)
