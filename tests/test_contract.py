"""Contract plane: cross-rank runtime sequence verification.

The acceptance matrix of the contract PR: seeded divergence (the
``diverge`` fault action) is detected within ``ACCL_VERIFY_INTERVAL``
calls and fails FAST with the diverging rank named in
``ACCLError.details`` — on the emulator (InProc board), socket (wire
piggyback) and XLA gang (shared-board) tiers — while ``kill_rank``
keeps failing through the dead-peer path (death is not divergence).
"""

import socket as socketlib
import threading
import time

import numpy as np
import pytest

from accl_tpu import (
    ACCLError,
    ErrorCode,
    FaultPlan,
    FaultRule,
    emulated_group,
    socket_group_member,
)
from accl_tpu import contract as contract_mod
from accl_tpu.contract import (
    ContractBoard,
    ContractVerifier,
    call_fingerprint,
    roll_digest,
)

pytestmark = pytest.mark.chaos


def _free_addresses(n):
    socks, addrs = [], []
    for _ in range(n):
        s = socketlib.socket()
        s.setsockopt(socketlib.SOL_SOCKET, socketlib.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        addrs.append(f"127.0.0.1:{s.getsockname()[1]}")
    for s in socks:
        s.close()
    return addrs


def _drive(group, work):
    """One thread per rank handle; returns {rank: ACCLError} for ranks
    that failed.  Joins are BOUNDED — a hang is a test failure, not a
    suite timeout."""
    errs = {}

    def runner(a, rank):
        try:
            work(a, rank)
        except ACCLError as e:
            errs[rank] = e

    threads = [
        threading.Thread(
            target=runner, args=(a, i), name=f"accl-test-rank{i}",
            daemon=True,
        )
        for i, a in enumerate(group)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(not t.is_alive() for t in threads), "rank thread hung"
    return errs, time.monotonic() - t0


# ---------------------------------------------------------------------------
# fingerprint / digest units
# ---------------------------------------------------------------------------


def test_fingerprint_deterministic_and_sensitive():
    base = call_fingerprint("allreduce", 0, 1, "FLOAT32", 64, "0/0", 0, 3)
    assert base == call_fingerprint(
        "allreduce", 0, 1, "FLOAT32", 64, "0/0", 0, 3
    )
    # every contract field moves the fingerprint
    assert base != call_fingerprint("bcast", 0, 1, "FLOAT32", 64, "0/0", 0, 3)
    assert base != call_fingerprint(
        "allreduce", 0, 1, "FLOAT32", 65, "0/0", 0, 3
    )
    assert base != call_fingerprint(
        "allreduce", 0, 1, "FLOAT32", 64, "1/0", 0, 3
    )
    assert base != call_fingerprint(
        "allreduce", 0, 1, "FLOAT32", 64, "0/0", 7, 3
    )
    assert base != call_fingerprint(
        "allreduce", 0, 1, "BFLOAT16", 64, "0/0", 0, 3
    )
    assert base != call_fingerprint(
        "allreduce", 0, 2, "FLOAT32", 64, "0/0", 0, 3
    )


def test_digest_is_order_sensitive():
    a = call_fingerprint("allreduce", 0, 1, "FLOAT32", 64, "0/0", 0, 0)
    b = call_fingerprint("allgather", 0, 1, "FLOAT32", 64, "0/0", 0, 1)
    assert roll_digest(roll_digest(0, a), b) != roll_digest(
        roll_digest(0, b), a
    )


def test_board_majority_convicts_minority():
    board = ContractBoard()
    ring = [{"seqn": 0, "op": "allreduce", "fingerprint": 1}]
    bad_ring = [{"seqn": 0, "op": "bcast", "fingerprint": 2}]
    assert board.post(5, 1, 0, 0, 4, 111, ring) is None
    assert board.post(5, 1, 0, 1, 4, 111, ring) is None
    # two agreeing posts of four are not yet a strict majority vs one
    # dissenter; the third agreeing post is
    assert board.post(5, 1, 0, 3, 4, 222, bad_ring) is None
    verdict = board.post(5, 1, 0, 2, 4, 111, ring)
    assert verdict is not None
    assert verdict["diverging_rank"] == 3
    assert verdict["basis"] == "majority"
    assert verdict["first_mismatch"]["expected"]["op"] == "allreduce"
    assert verdict["first_mismatch"]["got"]["op"] == "bcast"
    # standing: later posts on the comm return the same verdict
    assert board.post(5, 1, 1, 0, 4, 333, ring) is verdict
    assert board.standing(5) is verdict


def test_board_two_rank_split_stays_silent():
    """A 1-1 split cannot name a culprit — the board must NOT convict
    (two-rank groups rely on the wire piggyback's pairwise blame)."""
    board = ContractBoard()
    assert board.post(1, 1, 0, 0, 2, 111, []) is None
    assert board.post(1, 1, 0, 1, 2, 222, []) is None
    assert board.standing(1) is None


def test_verifier_pairwise_claim_matching():
    v = ContractVerifier(rank=0, world=2, interval=2)
    # two identical calls complete window 0
    for _ in range(2):
        assert v.record("allreduce", 0, "FLOAT32", 8, "0/0", 0) is None
    gen, w, digest = v.stamp(0)
    assert (gen, w) == (1, 0)
    # peer claim that MATCHES: no verdict
    assert v.observe_claim(0, 1, gen, 0, digest) is None
    # peer claim that MISMATCHES: pairwise verdict naming the peer
    verdict = v.observe_claim(0, 1, gen, 0, digest ^ 0xDEAD)
    assert verdict is not None and verdict["diverging_rank"] == 1
    assert verdict["basis"] == "pairwise"
    assert v.check(0) is not None


def test_verifier_parks_claims_from_ranks_ahead():
    v = ContractVerifier(rank=0, world=2, interval=2)
    # the peer finished window 0 before we did: the claim parks...
    assert v.observe_claim(0, 1, 1, 0, 12345) is None
    assert v.check(0) is None
    # ...and is compared when OUR window 0 completes (digests differ)
    v.record("allreduce", 0, "FLOAT32", 8, "0/0", 0)
    verdict = v.record("allreduce", 0, "FLOAT32", 8, "0/0", 0)
    assert verdict is not None and verdict["diverging_rank"] == 1


def test_verifier_reset_clears_verdicts_and_bumps_generation():
    v = ContractVerifier(rank=0, world=2, interval=1)
    v.record("allreduce", 0, "FLOAT32", 8, "0/0", 0)
    gen, w, digest = v.stamp(0)
    assert v.observe_claim(0, 1, gen, w, digest ^ 1) is not None
    v.reset()
    assert v.check(0) is None and v.generation == gen + 1
    # stale claims from the old generation are ignored after reset
    assert v.observe_claim(0, 1, gen, 0, 999) is None
    assert v.check(0) is None


# ---------------------------------------------------------------------------
# seeded divergence: emulator (InProc board) tier
# ---------------------------------------------------------------------------


def _allreduce_loop(n_calls=10, count=8):
    def work(a, rank):
        s = a.create_buffer_from(np.full(count, rank + 1.0, np.float32))
        d = a.create_buffer(count, np.float32)
        for _ in range(n_calls):
            a.allreduce(s, d, count)

    return work


def test_emulator_seeded_divergence_fails_fast_naming_rank():
    g = emulated_group(4)
    try:
        g[0].engine.fabric.install_fault_plan(FaultPlan(
            rules=[FaultRule(action="diverge", rank=2)], seed=7
        ))
        for a in g:
            a.set_contract_verify(True, interval=2)
        errs, elapsed = _drive(g, _allreduce_loop())
        # fail-fast: nowhere near the 30 s engine deadline
        assert elapsed < 10
        assert set(errs) == {0, 1, 2, 3}
        for rank, e in errs.items():
            assert e.code == ErrorCode.CONTRACT_VIOLATION
            assert e.details["contract"]["basis"] in (
                "majority", "pairwise"
            )
            assert "flight_recorder" in e.details
            if rank != 2:
                # every CONFORMING rank names rank 2: board majorities
                # directly, wire pairwise because only rank 2's claims
                # can mismatch a conforming digest.  Rank 2 itself may
                # pairwise-blame a peer before the majority lands — the
                # two-party ambiguity the docs call out.
                assert e.details["diverging_rank"] == 2
        # detection within the interval: the verifier saw at most
        # interval calls past the first perturbed one
        snap = g[0].telemetry_snapshot()["contract"]
        assert snap["enabled"] and snap["verdicts"]
    finally:
        for a in g:
            a.deinit()


def test_emulator_divergence_detection_is_deterministic():
    """Same plan, same seed, same traffic -> same convicted rank and
    same mismatched window (the chaos plane's determinism contract
    extended to fingerprints)."""
    verdicts = []
    for _ in range(2):
        g = emulated_group(3)
        try:
            g[0].engine.fabric.install_fault_plan(FaultPlan(
                rules=[FaultRule(action="diverge", rank=1, nth=2)], seed=99
            ))
            for a in g:
                a.set_contract_verify(True, interval=1)
            errs, _ = _drive(g, _allreduce_loop(n_calls=6))
            assert errs, "divergence was not detected"
            # assert on a CONFORMING rank's verdict (0 or 2): the
            # diverging rank's own pairwise blame is two-party-ambiguous
            e = errs[0] if 0 in errs else errs[2]
            assert e.code == ErrorCode.CONTRACT_VIOLATION
            verdicts.append((
                e.details["diverging_rank"],
                e.details["contract"]["window"],
            ))
        finally:
            for a in g:
                a.deinit()
    assert verdicts[0] == verdicts[1] == (1, 1)


def test_verifier_quiet_on_matched_sequences():
    g = emulated_group(4)
    try:
        for a in g:
            a.set_contract_verify(True, interval=2)
        errs, _ = _drive(g, _allreduce_loop(n_calls=6))
        assert errs == {}
        snap = g[0].telemetry_snapshot()["contract"]
        assert snap["calls_verified"] == 6
        assert snap["windows_exchanged"] == 3
        assert snap["verdicts"] == {}
        caps = g[0].capabilities()["contract_verify"]
        assert caps == {"interval": 2, "calls_verified": 6}
    finally:
        for a in g:
            a.deinit()


def test_verifier_off_by_default_and_disarmable():
    g = emulated_group(2)
    try:
        assert g[0].capabilities()["contract_verify"] is None
        snap = g[0].telemetry_snapshot()["contract"]
        assert snap == {"enabled": False}
        v = g[0].set_contract_verify(True, interval=4)
        assert v is g[0].set_contract_verify(True)  # idempotent
        g[0].set_contract_verify(False)
        assert g[0].capabilities()["contract_verify"] is None
        assert g[0].engine.contract_verifier is None
    finally:
        for a in g:
            a.deinit()


def test_verify_env_arms_per_handle(monkeypatch):
    monkeypatch.setenv("ACCL_VERIFY", "1")
    monkeypatch.setenv("ACCL_VERIFY_INTERVAL", "3")
    g = emulated_group(2)
    try:
        caps = g[0].capabilities()["contract_verify"]
        assert caps is not None and caps["interval"] == 3
    finally:
        for a in g:
            a.deinit()


def test_soft_reset_recovers_after_divergence_verdict():
    g = emulated_group(3)
    try:
        inj_host = g[0].engine.fabric
        inj_host.install_fault_plan(FaultPlan(
            rules=[FaultRule(action="diverge", rank=1, count=1)], seed=3
        ))
        for a in g:
            a.set_contract_verify(True, interval=1)
        errs, _ = _drive(g, _allreduce_loop(n_calls=4))
        assert errs and all(
            e.code == ErrorCode.CONTRACT_VIOLATION for e in errs.values()
        )
        # recovery: heal the plan, collective soft_reset, then a clean
        # run must pass (verdicts cleared, fresh digest generation)
        inj_host.fault_injector.clear()
        for a in g:
            a.soft_reset()
        errs, _ = _drive(g, _allreduce_loop(n_calls=4))
        assert errs == {}
    finally:
        for a in g:
            a.deinit()


def test_kill_rank_is_death_not_divergence():
    """Under kill_rank the PR 2 dead-peer machinery answers, not the
    contract verifier: the health map names the rank dead and calls
    fail with SEND/RECEIVE_TIMEOUT — never CONTRACT_VIOLATION blaming
    a corpse for 'diverging'."""
    g = emulated_group(2)
    try:
        for a in g:
            a.set_contract_verify(True, interval=1)
            a.set_timeout(1.0)
        g[0].engine.fabric.install_fault_plan(FaultPlan(
            rules=[FaultRule(action="kill_rank", rank=1, nth=0)], seed=1
        ))

        def work(a, rank):
            if rank != 0:
                return  # rank 1 is dead; only rank 0 issues
            s = a.create_buffer_from(np.ones(8, np.float32))
            d = a.create_buffer(8, np.float32)
            for _ in range(4):
                a.allreduce(s, d, 8)

        errs, _ = _drive(g, work)
        assert 0 in errs
        assert errs[0].code != ErrorCode.CONTRACT_VIOLATION
        assert errs[0].code & (
            ErrorCode.SEND_TIMEOUT | ErrorCode.RECEIVE_TIMEOUT
        )
    finally:
        for a in g:
            a.deinit()


# ---------------------------------------------------------------------------
# socket tier: wire piggyback
# ---------------------------------------------------------------------------


def test_socket_seeded_divergence_fails_fast_via_wire_piggyback():
    last = None
    for _ in range(3):  # pre-picked ports can be re-grabbed: retry
        try:
            addrs = _free_addresses(2)
            g = [socket_group_member(i, addrs) for i in range(2)]
            break
        except OSError as e:
            last = e
    else:
        raise last
    try:
        plan = FaultPlan(
            rules=[FaultRule(action="diverge", rank=1)], seed=5
        )
        for a in g:
            # each per-process fabric carries the plan (the env-
            # inheritance path real socket groups use); only rank 1's
            # verifier perturbs since rule.rank == 1
            a.engine.fabric.install_fault_plan(plan)
            a.set_contract_verify(True, interval=2)
        errs, elapsed = _drive(g, _allreduce_loop())
        assert elapsed < 10
        assert set(errs) == {0, 1}
        for e in errs.values():
            assert e.code == ErrorCode.CONTRACT_VIOLATION
            assert e.details["contract"]["basis"] == "pairwise"
        # pairwise blame names the PEER: correct on the conforming
        # rank (0), which is where production reads the verdict
        assert errs[0].details["diverging_rank"] == 1
        assert errs[0].details["contract"]["kind"] == "divergence"
        assert errs[0].details["contract"]["local_recent_calls"]
    finally:
        for a in g:
            a.deinit()


# ---------------------------------------------------------------------------
# XLA gang tier: shared-board exchange
# ---------------------------------------------------------------------------


def test_gang_seeded_divergence_fails_fast_naming_rank():
    from accl_tpu.core import xla_group

    g = xla_group(4)
    contract_mod.install_fault_plan(FaultPlan(
        rules=[FaultRule(action="diverge", rank=2, nth=3)], seed=9
    ))
    try:
        for a in g:
            a.set_contract_verify(True, interval=2)
        errs, elapsed = _drive(g, _allreduce_loop(count=16))
        assert elapsed < 15
        assert set(errs) == {0, 1, 2, 3}
        for e in errs.values():
            assert e.code == ErrorCode.CONTRACT_VIOLATION
            assert e.details["diverging_rank"] == 2
            assert e.details["contract"]["basis"] == "majority"
        # the board's first-mismatch evidence carries both sides' calls
        any_v = errs[0].details["contract"]
        assert "first_mismatch" in any_v
        assert "diverging_flight_recorder" in any_v
    finally:
        contract_mod.install_fault_plan(None)
        for a in g:
            a.deinit()


def test_gang_real_op_mismatch_detected_pre_dispatch():
    """Not a seeded perturbation: one rank genuinely issues a different
    collective.  The majority convicts it at the window boundary and
    every rank — including peers whose calls were already parked in a
    gang slot — fails with CONTRACT_VIOLATION instead of the watchdog
    timeout."""
    from accl_tpu.core import xla_group

    g = xla_group(4)
    try:
        for a in g:
            a.set_contract_verify(True, interval=1)

        def work(a, rank):
            s = a.create_buffer_from(np.full(8, rank + 1.0, np.float32))
            d = a.create_buffer(8, np.float32)
            r = a.create_buffer(32, np.float32)
            a.allreduce(s, d, 8)
            if rank == 3:
                a.allgather(s, r, 8)  # the torn sequence
            else:
                a.allreduce(s, d, 8)
            a.allreduce(s, d, 8)

        errs, elapsed = _drive(g, work)
        assert elapsed < 15
        assert errs
        for e in errs.values():
            assert e.code == ErrorCode.CONTRACT_VIOLATION
            assert e.details["diverging_rank"] == 3
    finally:
        for a in g:
            a.deinit()


# ---------------------------------------------------------------------------
# diverge fault-rule mechanics
# ---------------------------------------------------------------------------


def test_diverge_rule_requires_rank_and_round_trips():
    from accl_tpu.faults import FaultInjector

    with pytest.raises(ValueError):
        FaultRule(action="diverge")
    plan = FaultPlan(
        rules=[FaultRule(action="diverge", rank=1, nth=2, count=3)],
        seed=42,
    )
    again = FaultPlan.from_json(plan.to_json())
    assert again.rules[0].action.value == "diverge"
    assert (again.rules[0].rank, again.rules[0].nth, again.rules[0].count) \
        == (1, 2, 3)
    inj = FaultInjector(again)
    assert inj.on_fingerprint(0, 0) == 0  # wrong rank: never fires
    assert inj.on_fingerprint(0, 1) == 0  # nth=2: first match skipped
    masks = [inj.on_fingerprint(0, 1) for _ in range(5)]
    assert all(m != 0 for m in masks[:3]) and masks[3] == masks[4] == 0
    # deterministic: a fresh injector from the same plan fires the same
    inj2 = FaultInjector(FaultPlan.from_json(plan.to_json()))
    inj2.on_fingerprint(0, 1)
    assert inj2.on_fingerprint(0, 1) == masks[0]
    assert inj.stats()["by_action"].get("diverge") == 3


def test_diverge_rules_do_not_touch_wire_traffic():
    """A diverge rule must never fire on (or count) wire messages —
    the wire stays bit-correct; only fingerprints bend."""
    g = emulated_group(2)
    try:
        g[0].engine.fabric.install_fault_plan(FaultPlan(
            rules=[FaultRule(action="diverge", rank=0)], seed=1
        ))
        # verifier OFF: traffic flows, nothing fires
        s = g[0].create_buffer_from(np.ones(8, np.float32))
        d0 = g[0].create_buffer(8, np.float32)
        d1 = g[1].create_buffer(8, np.float32)
        s1 = g[1].create_buffer_from(np.full(8, 2.0, np.float32))
        errs, _ = _drive(g, _allreduce_loop(n_calls=3))
        assert errs == {}
        stats = g[0].engine.fabric.fault_injector.stats()
        assert stats["fired_total"] == 0
    finally:
        for a in g:
            a.deinit()


def test_corrupt_verify_frame_is_discarded_not_adopted():
    """A corrupt-fault VERIFY frame must be dropped by the checksum
    guard BEFORE the contract hook can consume it as a verdict (review
    finding: the hook originally ran ahead of the csum check)."""
    import json as _json
    import zlib

    from accl_tpu.backends.emulator.fabric import Endpoint, Message, MsgType

    ep = Endpoint()
    seen = []
    ep.contract_hook = seen.append
    payload = _json.dumps({"kind": "divergence", "comm": 0}).encode()
    good = Message(MsgType.VERIFY, 0, 1, 0, 0, payload=payload,
                   csum=zlib.crc32(payload))
    ep.deliver(good)
    assert len(seen) == 1
    bad_payload = bytearray(payload)
    bad_payload[3] ^= 0x40
    bad = Message(MsgType.VERIFY, 0, 1, 0, 0, payload=bytes(bad_payload),
                  csum=zlib.crc32(payload))
    ep.deliver(bad)
    assert len(seen) == 1  # corrupt frame never reached the hook
    assert ep.corrupt_drops == 1


def test_subcomm_divergence_blames_comm_relative_rank_with_session():
    """Verdict rank spaces on a SUBcommunicator: blame is comm-relative
    and the majority threshold is the subcomm's size, not the world's
    (world=4, subcomm of 3 on the board-only gang tier — a world-sized
    threshold could never convict 2-vs-1).  The verdict also maps the
    blame to the global session (diverging_session)."""
    from accl_tpu.core import xla_group

    g = xla_group(4)
    # the subcomm is ranks [1, 2, 3]; world rank 3 == subcomm rank 2
    # diverges ON THE SUBCOMM ONLY (rule scoped by comm id)
    try:
        subs = {}
        for r, a in enumerate(g):
            sub = a.create_communicator([1, 2, 3])
            if sub is not None:
                subs[r] = sub
        assert sorted(subs) == [1, 2, 3]
        sub_id = subs[1].id
        contract_mod.install_fault_plan(FaultPlan(
            rules=[FaultRule(action="diverge", rank=2, comm=sub_id)],
            seed=31,
        ))
        for a in g:
            a.set_contract_verify(True, interval=2)
        errs = {}

        def work(a, rank):
            if rank not in subs:
                return
            s = a.create_buffer_from(np.full(8, rank + 1.0, np.float32))
            d = a.create_buffer(8, np.float32)
            try:
                for _ in range(8):
                    a.allreduce(s, d, 8, comm=subs[rank])
            except ACCLError as e:
                errs[rank] = e

        threads = [
            threading.Thread(
                target=work, args=(a, r), name=f"accl-test-sub{r}",
                daemon=True,
            )
            for r, a in enumerate(g)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        elapsed = time.monotonic() - t0
        assert all(not t.is_alive() for t in threads)
        assert elapsed < 15
        # all three subcomm members fail fast; the verdict names the
        # diverging member in COMM-relative terms (rank 2 of the
        # subcomm) and maps it to the global session (world rank 3)
        assert sorted(errs) == [1, 2, 3]
        for e in errs.values():
            assert e.code == ErrorCode.CONTRACT_VIOLATION
            v = e.details["contract"]
            assert v["comm"] == sub_id
            assert v["basis"] == "majority"
            assert e.details["diverging_rank"] == 2
            assert v["diverging_session"] == 3
    finally:
        contract_mod.install_fault_plan(None)
        for a in g:
            a.deinit()


def test_board_retract_on_disarm_prevents_stale_conviction():
    """Collective disarm + re-arm must not let a rank's STALE board
    posts vote against its fresh digest stream (review finding: the
    re-armed verifier restarts at generation 1, colliding keys)."""
    g = emulated_group(3)
    try:
        for a in g:
            a.set_contract_verify(True, interval=2)
        errs, _ = _drive(g, _allreduce_loop(n_calls=4))
        assert errs == {}
        # collective re-arm with a different interval (disarm + arm)
        for a in g:
            a.set_contract_verify(True, interval=4)
        # a DIFFERENT but still matched sequence: digests at the same
        # (comm, gen=1, window) keys differ from the first life's
        errs, _ = _drive(g, _allreduce_loop(n_calls=8, count=16))
        assert errs == {}, errs
        snap = g[0].telemetry_snapshot()["contract"]
        assert snap["verdicts"] == {}
    finally:
        for a in g:
            a.deinit()
