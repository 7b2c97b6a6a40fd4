"""The gang engine's command-ring sessions: arm / refill / teardown.

This is the engine half of the TPU CCLO analog (the device half is
``ops/cmdring.py``, the slot codec ``accl_tpu/cmdring.py``): host code
that used to *issue* collectives becomes code that *refills a queue*.
A warm batched window of N eligible collectives is encoded into N slots
of the per-communicator ring and dispatched as ONE program — the window
program decodes the slot words on the device, executes every slot and
returns the per-slot status words.  A refill is a doorbell is a program
launch: ``refills == doorbells == dispatches``, one host interaction a
window, on every platform.  A window's control words make no trip of
their own: slot words the chips already hold are not put again (the
ring's ``KeptSlots``; ``stats()`` counts ``slot_hits`` / ``slot_puts``),
and the status words' copy to the host is asked for at launch.

The opcode space is the FULL warm set (``constants.CMDRING_OPCODES``):
allreduce, bcast, reduce-scatter, allgather, alltoall, barrier, and
matched send/recv pairs; compressed (wire-cast) windows ride the ring
with the cast lowered into the decode loop.  Everything else — cold
calls, oversized payloads, host operands, mixed dtypes, unpaired p2p —
falls back to the ordinary host-dispatch paths with the reason counted
in :meth:`GangCommandRing.stats`.

Lifecycle:

* **parked** — no window in flight: nothing of the ring is on the
  device (no spin, no occupancy).  The next refill arms with one
  dispatch.
* **armed** — windows in flight; the in-flight window
  (``overlap.InflightWindow``) is the refill window: its drain points
  block on the device status words the window program returned.
* **teardown/reset** — ``soft_reset`` clears every session and realigns
  seqn/head at 0 (the gang has already drained the in-flight window).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from ...cmdring import (
    WindowShape,
    complementary_pair,
    encode_fparam,
    encode_slot,
    fused_slot_eligible,
    ring_widths,
)
from ...constants import (
    CMDRING_DEPTH_DEFAULT,
    CMDRING_DEPTH_ENV,
    CMDRING_ENV,
    CMDRING_FIELDS,
    CMDRING_FUSED_OPCODES,
    CMDRING_MAX_BYTES_ENV,
    CMDRING_MAX_DEPTH,
    CMDRING_MAX_PAYLOAD_BYTES,
    CMDRING_OPCODES,
    CMDRING_ST_OK,
    ErrorCode,
    FusedCompute,
    Operation,
    dtype_to_numpy,
)
from ...membership import CircuitBreaker
from ...ops import cmdring as devring
from ...overlap import drain_deadline_s
from ...utils.profiling import annotate

_F = CMDRING_FIELDS

#: ring-session circuit breaker (membership plane): window failures
#: against a dying peer strike the per-comm breaker; OPEN degrades the
#: comm's dispatch ring -> host (counted ``circuit_open``), HALF_OPEN
#: lets one window through after the cool-down, success restores the
#: ring.
CMDRING_BREAKER_COOLDOWN_ENV = "ACCL_CMDRING_COOLDOWN_S"
CMDRING_BREAKER_COOLDOWN_S = 2.0
CMDRING_BREAKER_THRESHOLD = 2

#: ops whose operand/result widths scale with world size ('P' slots)
_P_WIDE = (Operation.REDUCE_SCATTER, Operation.ALLTOALL)


def _env_mode() -> str:
    return os.environ.get(CMDRING_ENV, "1").strip().lower()


#: opcode word chaos poisoning writes into a refill's first slot —
#: out of the opcode range, so the window program reports
#: BAD_OP and the slot fails fast with INVALID_OPERATION
_CHAOS_BAD_OPCODE = 0x7F


class _RingMsgType:
    """Message-type token for ring-refill pseudo-messages shown to the
    fault injector (``FaultRule(msg_type="RING")`` matches them; int
    rules never do — the ring is not a wire MsgType)."""

    name = "RING"

    def __int__(self) -> int:
        return -1

    def __str__(self) -> str:
        return "RING"


_RING_MSG_TYPE = _RingMsgType()


class _RingRefillMsg:
    """One refill window as the fault injector sees it: the host encode
    (src 0) ringing the gang's doorbell.  ``dst`` is None — only
    wildcard-dst rules reach the ring path."""

    __slots__ = ("comm_id", "src", "dst", "tag", "msg_type", "seqn")

    def __init__(self, comm_id: int, seqn: int):
        self.comm_id = comm_id
        self.src = 0
        self.dst = None
        self.tag = 0
        self.msg_type = _RING_MSG_TYPE
        self.seqn = seqn


class _WindowPark:
    """One in-flight refill window's completion record (the status-FIFO
    side of the ring)."""

    __slots__ = ("window_id", "event", "status", "plans",
                 "reqs_per_slot", "calls_per_slot", "t0", "settled",
                 "slots_info", "logged")

    def __init__(self, window_id: int, plans, reqs_per_slot,
                 calls_per_slot, t0):
        self.window_id = window_id
        self.event = threading.Event()
        self.status: Optional[np.ndarray] = None
        self.plans = plans
        self.reqs_per_slot = reqs_per_slot
        self.calls_per_slot = calls_per_slot
        self.t0 = t0
        # session bookkeeping (written-ledger decrement, last_status)
        # done exactly once, by whichever completion path ran
        self.settled = False
        # introspection: per-slot facts for the window log (seqn,
        # opcode, the issuing call's trace id) and the logged-once latch
        self.slots_info: list = []
        self.logged = False


class _RingSession:
    """Per-communicator ring state: the persistent host mirror of the
    device ring (wrap-around is real — slot i of refill k+1 reuses the
    words of slot i of refill k-depth), the monotone seqn, and the
    cross-window write-dependency ledger."""

    __slots__ = ("ring", "head", "seqn", "parks", "written",
                 "next_window", "last_status")

    def __init__(self, depth: int):
        from ...constants import CMDRING_SLOT_WORDS

        self.ring = np.zeros((depth, CMDRING_SLOT_WORDS), np.int32)
        self.head = 0
        self.seqn = 0
        self.parks: List[_WindowPark] = []   # outstanding, refill order
        self.written: Dict[int, int] = {}    # result-root id -> pending
        self.next_window = 0
        self.last_status: Optional[np.ndarray] = None


class GangCommandRing:
    """One gang context's command ring (all communicators' sessions)."""

    def __init__(self, gang):
        self.gang = gang
        mode = _env_mode()
        self.enabled = mode not in ("0", "off", "false", "")
        self.eager = mode == "eager"
        try:
            depth = int(
                os.environ.get(CMDRING_DEPTH_ENV, CMDRING_DEPTH_DEFAULT)
            )
        except ValueError:
            depth = CMDRING_DEPTH_DEFAULT
        self.depth = max(1, min(depth, CMDRING_MAX_DEPTH))
        try:
            self.max_bytes = int(
                os.environ.get(
                    CMDRING_MAX_BYTES_ENV, CMDRING_MAX_PAYLOAD_BYTES
                )
            )
        except ValueError:
            self.max_bytes = CMDRING_MAX_PAYLOAD_BYTES
        self._lock = threading.Lock()
        self._sessions: Dict[int, _RingSession] = {}
        self._inflight_windows = 0
        # cached committed zeros shards for token/dummy slots (barrier,
        # the p2p pair's non-source ranks): first use dispatches the
        # zeros program (counted), warm windows reuse with no dispatch
        self._zeros: Dict[tuple, object] = {}
        # the slot words the chips already hold, a window's by its
        # content: a warm window puts nothing (ops/cmdring.py)
        self._kept_slots = devring.KeptSlots()
        # lifetime counters (telemetry_report()["cmdring"]).  One
        # counter backs both the refill and doorbell stats keys: every
        # refill rings the doorbell exactly once, as a program dispatch.
        self.refills = 0          # refill windows (= doorbells)
        self.dispatches = 0       # window program launches
        self.slots_enqueued = 0   # collectives executed ring-resident
        self.wraps = 0            # head wrapped past the ring depth
        self.resets = 0           # soft_reset teardowns
        self.max_window = 0
        self.last_window = 0
        self.op_slots: Dict[str, int] = {}  # per-opcode residency
        self.fallbacks: Dict[str, int] = {}
        # introspection plane: a bounded log of completed windows
        # (per-slot seqn/opcode/retcode/trace-id next to the host-side
        # timing — basis "host": the window program writes no device
        # clock next to the status word, and the snapshot says so
        # instead of faking device time), a window-latency log2-us
        # histogram, and the facade's failure hook (postmortem plane:
        # drain deadline / dispatch error)
        from collections import deque as _deque

        try:
            log_cap = int(os.environ.get("ACCL_CMDRING_WINDOW_LOG", "64"))
        except ValueError:
            log_cap = 64
        self._window_log = _deque(maxlen=max(8, log_cap))
        self.windows_logged = 0
        self.window_latency: Dict[int, int] = {}
        self.window_latency_sum_us = 0.0
        self.on_failure = None
        # per-comm ring circuit breakers (membership plane): window
        # failures degrade that comm's dispatch ring -> host,
        # re-probing after a cool-down — a dying peer no longer needs a
        # full soft_reset to get the ring back
        try:
            cooldown = float(os.environ.get(
                CMDRING_BREAKER_COOLDOWN_ENV, CMDRING_BREAKER_COOLDOWN_S
            ))
        except ValueError:
            cooldown = CMDRING_BREAKER_COOLDOWN_S
        self.breaker_cooldown_s = cooldown
        self._breakers: Dict[int, CircuitBreaker] = {}
        # QoS arbiter plane (SET_TENANT_RING_SLOTS): per-comm slot
        # budgets — a budgeted tenant's warm batches chunk into refill
        # windows of at most its budget, so a flooder pays extra
        # doorbells instead of monopolizing whole ring windows.  Plus
        # per-comm slot residency totals, the counter the fairness
        # tests assert ring-share against.
        self._slot_budgets: Dict[int, int] = {}
        self.comm_slots: Dict[int, int] = {}
        self.budgeted_windows = 0
        # chaos plane: per-action counts of fault-injector verdicts
        # applied to refill windows (tests assert fail-fast + recovery)
        self.chaos_faults: Dict[str, int] = {}

    # -- introspection -------------------------------------------------------
    def supports(self, op) -> bool:
        """Whether ``op`` has a sequencer opcode — the ONE definition of
        the ring's warm-path subset lives in
        ``constants.CMDRING_OPCODES`` (the engine's eager hook and the
        batch eligibility both ask here)."""
        return op in CMDRING_OPCODES

    def p2p_eligible(self, options) -> bool:
        """SPMD-uniform gang eligibility for a batched SEND/RECV: both
        ends of a pair must classify identically — INCLUDING the legal
        mismatched pairs the channel supports (cross-dtype cast,
        compressed-one-side), where count/dtype/compression differ
        between the ends.  So only genuinely pair-symmetric facts gate
        here (ring enabled, world size); everything per-call — size,
        dtype, compression, buffer residency — is screened by the ring
        planner with BOTH calls visible, and disqualified positions
        re-route through the channel with unbatched semantics."""
        return self.enabled and options.comm.size == 2 and options.count > 0

    @property
    def parked(self) -> bool:
        """True when no refill window is in flight — nothing of the
        ring is on the device (no device work, no spin, no occupancy)."""
        with self._lock:
            return not self._inflight_windows

    def last_status(self, comm_id: int) -> Optional[np.ndarray]:
        """The most recent window's device status words for a session
        (the determinism test replays a window and compares these)."""
        with self._lock:
            s = self._sessions.get(comm_id)
            return None if s is None or s.last_status is None else (
                s.last_status.copy()
            )

    def stats(self) -> dict:
        breakers = self._breaker_snapshots()
        with self._lock:
            return {
                "enabled": self.enabled,
                "mode": "eager" if self.eager else
                        ("batch" if self.enabled else "off"),
                # a constant: perfbench's sweep driver records it as a
                # fact of every run and is not this module's to edit
                "lowering": "xla",
                "depth": self.depth,
                "state": "armed" if self._inflight_windows else "parked",
                "refills": self.refills,
                "doorbells": self.refills,  # every refill rings once
                "dispatches": self.dispatches,
                # windows whose slot words were already on the chips /
                # were put: hits + puts == dispatches
                "slot_hits": self._kept_slots.hits,
                "slot_puts": self._kept_slots.puts,
                "slots": self.slots_enqueued,
                "wraps": self.wraps,
                "resets": self.resets,
                "max_window": self.max_window,
                # refill occupancy: how full the last doorbell's window
                # filled the ring (1.0 = a full ring per refill)
                "occupancy": round(self.last_window / self.depth, 3)
                if self.last_window else 0.0,
                "ops": dict(self.op_slots),
                "fallbacks": dict(self.fallbacks),
                "chaos_faults": dict(self.chaos_faults),
                "breakers": breakers,
                # QoS arbiter plane: configured per-comm slot budgets,
                # per-comm ring-slot residency (the fairness evidence)
                # and how many windows a budget actually clamped
                "slot_budgets": {
                    str(c): b for c, b in sorted(self._slot_budgets.items())
                },
                "comm_slots": {
                    str(c): n for c, n in sorted(self.comm_slots.items())
                },
                "budgeted_windows": self.budgeted_windows,
                # introspection plane: the refill-window timeline (per-
                # slot seqn/opcode/retcode/trace-id, host-basis timing)
                # and the window-latency histogram
                "windows_logged": self.windows_logged,
                "window_latency_sum_us": round(
                    self.window_latency_sum_us, 3
                ),
                "window_latency_log2_us": {
                    str(k): v
                    for k, v in sorted(self.window_latency.items())
                },
                "windows": list(self._window_log)[-16:],
            }

    def _breaker_snapshots(self) -> dict:
        with self._lock:
            items = list(self._breakers.items())
        # breaker locks taken OUTSIDE the ring lock (leaf discipline)
        return {str(c): brk.snapshot() for c, brk in items}

    def _fallback(self, reason: str) -> bool:
        with self._lock:
            self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1
        return False

    def note_fallback(self, reason: str) -> None:
        """Count a ring miss decided OUTSIDE run_batch (the engine's
        fused host decomposition) on the same fallback table the
        evidence gates read."""
        self._fallback(reason)

    def set_slot_budget(self, comm_id: int,
                        slots: Optional[int]) -> None:
        """Per-comm refill-window slot budget (the QoS arbiter's
        SET_TENANT_RING_SLOTS lever): ``comm_id``'s warm batches chunk
        into windows of at most ``slots`` ring slots; None clears."""
        with self._lock:
            if slots is None:
                self._slot_budgets.pop(int(comm_id), None)
            else:
                self._slot_budgets[int(comm_id)] = max(
                    1, min(int(slots), self.depth)
                )

    def slot_budget_of(self, comm_id: int) -> Optional[int]:
        with self._lock:
            return self._slot_budgets.get(int(comm_id))

    def breaker_for(self, comm_id: int) -> CircuitBreaker:
        """The comm's ring circuit breaker (membership plane): strikes
        on window failures, degrades ring -> host, re-probes after the
        cool-down."""
        with self._lock:
            brk = self._breakers.get(comm_id)
            if brk is None:
                brk = self._breakers[comm_id] = CircuitBreaker(
                    threshold=CMDRING_BREAKER_THRESHOLD,
                    cooldown_s=self.breaker_cooldown_s,
                )
            return brk

    # -- teardown ------------------------------------------------------------
    def reset(self) -> None:
        """soft_reset: realign every session's seqn/head at 0 (the gang
        has already drained the in-flight window — the full-flush
        contract) and forget the slot words kept on the chips with
        them."""
        self._kept_slots.clear()
        with self._lock:
            self._sessions.clear()
            self._inflight_windows = 0
            self.resets += 1
            self._breakers.clear()  # full recovery re-closes the ring

    # -- position planning ---------------------------------------------------
    def _plan_collective(self, comm, calls, lead, mesh):
        """Plan one collective position (the device-residency screen of
        the ordinary path, shared): None means host operands."""
        return self.gang._plan_device_call(comm, calls, lead, mesh)

    def _plan_barrier(self, comm, mesh, npdt) -> dict:
        devs = list(mesh.devices.flat)
        return {
            "op": Operation.BARRIER, "size": comm.size, "n": 1,
            "in_w": 1, "out_w": 1, "devs": devs,
            "npdt": npdt, "compressed": False, "wire_npdt": None,
            "writers": set(),
        }

    def _plan_p2p(self, comm, calls, mesh) -> Optional[dict]:
        """Plan a matched SEND/RECV pair position (world-2 gangs): one
        slot with root=src, peer=dst.  None when the position is not a
        complementary pair — the caller counts the reason and the
        ordinary paths (``_execute_p2p_pair``) own it."""
        if comm.size != 2:
            return None
        pair = complementary_pair(calls)
        if pair is None:
            return None
        src, dst = pair
        snd, rcv = calls[src], calls[dst]
        # the ring is for the floor-bound regime (same bound as the
        # collective slots; the pair decision sees BOTH calls, so the
        # verdict is symmetric by construction)
        if (
            snd.count * snd.arithcfg.uncompressed_elem_bytes
            > self.max_bytes
        ):
            return None
        from ...buffer import DeviceBuffer

        devs = list(mesh.devices.flat)
        op0 = snd.op0
        res = rcv.res
        n = snd.count
        if not (
            isinstance(op0, DeviceBuffer) and not op0.is_dummy
            and op0.device == devs[src] and op0.count >= n
        ):
            return None
        if not (
            isinstance(res, DeviceBuffer) and not res.is_dummy
            and res.device == devs[dst] and res.count >= n
        ):
            return None
        npdt = dtype_to_numpy(snd.arithcfg.uncompressed)
        return {
            "op": snd.op, "size": comm.size, "n": n,
            "in_w": n, "out_w": n, "devs": devs, "npdt": npdt,
            "compressed": False, "wire_npdt": None,
            "writers": {dst}, "p2p": (src, dst),
        }

    def _plan_fused(self, comm, calls, lead, plan, fuse: int):
        """Re-validate a planned position against the fused-slot
        geometry and patch the plan to the packed operand widths.
        Returns the patched plan dict, or the fallback REASON string
        (the shared :func:`accl_tpu.cmdring.fused_slot_eligible`
        predicate — the numpy-only CI smoke gates the same verdicts)."""
        in_w, out_w = ring_widths(
            lead.op, lead.count, comm.size, fuse=fuse
        )
        # the smallest packed operand across the gang decides width
        # eligibility: every rank must have staged the full fused row
        opn = in_w
        for c in calls:
            buf = c.op0
            if buf is None or buf.is_dummy:
                opn = 0
                break
            if buf.count < in_w:
                opn = min(opn, int(buf.count))
        reason = fused_slot_eligible(
            fuse, lead.op, comm.size, lead.count, opn, plan["npdt"],
            compressed=bool(plan["compressed"]),
        )
        if reason is not None:
            return reason
        patched = dict(plan)
        patched["fuse"] = int(fuse)
        patched["fparam"] = float(getattr(lead, "fuse_param", 0.0))
        patched["in_w"] = in_w
        patched["out_w"] = out_w
        # the hop offset of an attn-hop slot rides the call's root_src
        # (SPMD-uniform — the same value on every rank by _sig match)
        if FusedCompute(fuse) == FusedCompute.ATTN_HOP:
            patched["hop"] = int(lead.root_src) % comm.size
        return patched

    def _slot_opcode(self, plan):
        """The CmdOpcode one planned slot encodes as (fused slots remap
        their base op through CMDRING_FUSED_OPCODES)."""
        fuse = plan.get("fuse", 0)
        if fuse:
            return CMDRING_FUSED_OPCODES[FusedCompute(fuse)]
        return CMDRING_OPCODES[plan["op"]]

    # -- the refill path -----------------------------------------------------
    def run_batch(self, comm, entries, npos: int,
                  t0: Optional[int] = None) -> bool:
        """Try to execute a fully matched batch slot ring-resident.
        Returns False — having dispatched NOTHING — when any position
        disqualifies (the ordinary fused/sequential paths then own the
        batch); True once dispatch begins (request completion is owned
        by the ring's window parks)."""
        if not self.enabled or npos == 0:
            return False
        with annotate("accl.ring::batch", comm=comm.id, n=npos):
            if t0 is None:
                t0 = time.perf_counter_ns()
            mesh = self.gang.submesh(comm)
            with annotate("accl.ring::plan"):
                plans = self._plan_batch(comm, entries, npos, mesh)
            if not plans:
                return False
            self._dispatch_windows(comm, mesh, entries, plans, t0)
            return True

    def _plan_batch(self, comm, entries, npos: int, mesh):
        """The breaker and tuning gates, then a plan a position: the
        planned ``(calls, lead, plan)`` list, or False — a fallback
        counted, nothing dispatched — when any position disqualifies."""
        gang = self.gang
        # ring circuit breaker (membership plane): an OPEN comm rides
        # host dispatch until the cool-down; HALF_OPEN lets one window
        # through, and its success restores the ring
        brk = self.breaker_for(comm.id)
        if brk.allow() == CircuitBreaker.OPEN:
            return self._fallback("circuit_open")
        # explicit algorithm registers (global or per-call TuningPlan
        # overlay) selecting a non-XLA lowering keep their meaning: the
        # ring is its own lowering and must not shadow a requested one
        # (mirrors _run_batch_fused's disqualifiers)
        keys = gang._BATCH_TUNING_KEYS
        if any(gang.tuning.get(k, "xla") != "xla" for k in keys):
            return self._fallback("tuning_override")
        for options_list, _ in entries:
            for c in options_list:
                if c.tuning and any(
                    c.tuning.get(k, "xla") != "xla" for k in keys
                ):
                    return self._fallback("tuning_override")

        plans = []
        written: set = set()  # result roots of earlier positions
        window_npdt = None
        barrier_positions = []
        for i in range(npos):
            calls = [e[0][i] for e in entries]
            lead = calls[0]
            if lead.op in (Operation.SEND, Operation.RECV):
                plan = self._plan_p2p(comm, calls, mesh)
                if plan is None:
                    # not a complementary pair (or host operands): the
                    # ordinary paths own the whole batch
                    return self._fallback("p2p_unpaired")
            elif lead.op not in CMDRING_OPCODES:
                return self._fallback("unsupported_op")
            elif any(gang._sig(c) != gang._sig(lead) for c in calls[1:]):
                return False  # torn gang: surface through the host path
            elif lead.op == Operation.BARRIER:
                plan = None  # dtype-agnostic; filled once npdt is known
                barrier_positions.append(i)
                plans.append((calls, lead, plan))
                continue
            else:
                fuse = int(getattr(lead, "fuse", 0))
                if fuse:
                    # fused slots size by their packed operand geometry
                    # (grads ‖ param tail, kv ‖ q), not the base op's
                    n_eff, _ = ring_widths(
                        lead.op, lead.count, comm.size, fuse=fuse
                    )
                else:
                    n_eff = lead.count * (
                        comm.size if lead.op in _P_WIDE else 1
                    )
                nbytes = n_eff * lead.arithcfg.uncompressed_elem_bytes
                if nbytes > self.max_bytes:
                    return self._fallback("oversized")
                plan = self._plan_collective(comm, calls, lead, mesh)
                if plan is None:
                    return self._fallback("host_operands")
                if fuse:
                    plan = self._plan_fused(comm, calls, lead, plan, fuse)
                    if isinstance(plan, str):
                        return self._fallback(plan)
            # one payload dtype per window (WindowShape.npdt keys the
            # window program)
            if window_npdt is None:
                window_npdt = plan["npdt"]
            elif plan["npdt"] != window_npdt:
                return self._fallback("mixed_dtype")
            # all operands assemble BEFORE dispatch: a position
            # reading an earlier position's result would see pre-window
            # bytes — only the sequential path orders such chains
            for call in calls:
                buf = call.op0
                if (
                    buf is not None
                    and not buf.is_dummy
                    and id(buf._root()) in written
                ):
                    return self._fallback("data_dependency")
            for r in plan["writers"]:
                res = calls[r].res
                if res is not None and not res.is_dummy:
                    written.add(id(res._root()))
            plans.append((calls, lead, plan))
        if window_npdt is None:
            window_npdt = np.dtype(np.float32)  # all-barrier window
        for i in barrier_positions:
            calls, lead, _ = plans[i]
            plans[i] = (calls, lead,
                        self._plan_barrier(comm, mesh, window_npdt))
        return plans

    def _dispatch_windows(self, comm, mesh, entries, plans, t0) -> None:
        npos = len(plans)
        # windows of at most `depth` slots — clamped to the comm's QoS
        # slot budget when one is configured (the flooder pays extra
        # doorbells; unbudgeted tenants keep full windows): each window
        # is one refill (doorbell), one program dispatch
        with self._lock:
            budget = self._slot_budgets.get(comm.id)
        eff_depth = min(self.depth, budget) if budget else self.depth
        for lo in range(0, npos, eff_depth):
            window = plans[lo:lo + eff_depth]
            if budget and npos > eff_depth:
                with self._lock:
                    self.budgeted_windows += 1
            reqs_per_slot = [
                [e[1][i] for e in entries]
                for i in range(lo, lo + len(window))
            ]
            try:
                self._dispatch_window(
                    comm, mesh, window, reqs_per_slot, t0
                )
            except Exception:
                # this window's dispatch failed: fail ITS slots and the
                # not-yet-dispatched remainder — earlier windows are in
                # flight and complete (or fail) from their own parks;
                # never re-execute a collective
                import traceback

                traceback.print_exc()
                self.breaker_for(comm.id).record_failure("dispatch_error")
                # postmortem plane: a failed window DISPATCH is a ring
                # failure too (on_error covers in-flight failures)
                if self.on_failure is not None:
                    try:
                        self.on_failure(comm.id, "dispatch_error")
                    except Exception:
                        pass
                dt = time.perf_counter_ns() - t0
                for i in range(lo, npos):
                    for e in entries:
                        req = e[1][i]
                        if not req.done():  # side-effect-free probe
                            req.ring_resident = True
                            req.complete(ErrorCode.INVALID_OPERATION, dt)
                break

    # -- slot encoding -------------------------------------------------------
    def _encode(self, session: _RingSession, lead, plan) -> np.ndarray:
        """Encode one collective into the session's next ring slot —
        through the CollectivePlan's cached slot template when the call
        carries a plan (the plan -> slot encoding cache), patching only
        the per-call fields (seqn, count, root, peer, function)."""
        op = plan["op"]
        opcode = self._slot_opcode(plan)
        wire = 0
        if plan["compressed"] and plan["wire_npdt"] is not None:
            wire = int(lead.arithcfg.compressed)
        fp = getattr(lead, "plan", None)
        tmpl = fp.cmdring_slot if fp is not None else None
        if tmpl is None:
            tmpl = encode_slot(
                0,
                opcode,
                0,
                dtype=int(lead.arithcfg.uncompressed),
                function=lead.reduce_function,
                root=0,
                nseg=1,
                wire=wire,
            )
            if fp is not None:
                fp.cmdring_slot = tmpl
        words = np.array(tmpl, np.int32)
        words[_F["seqn"]] = session.seqn & 0x7FFFFFFF
        words[_F["opcode"]] = int(opcode)
        words[_F["count"]] = plan["n"]
        words[_F["function"]] = int(lead.reduce_function)
        words[_F["wire"]] = wire
        # quantized wire plane: the call's SR seed rides the flags word
        # as slot DATA (rank-mixed inside the decode loop) — seed churn
        # on a warm compressed stream never recompiles the window
        words[_F["flags"]] = int(getattr(lead, "wire_seed", 0)) & 0x7FFFFFFF
        # fused compute slots: the epilogue scalar rides the fparam
        # word Q16.16; an attn-hop slot's hop OFFSET rides the peer
        # word (SPMD-uniform — each rank derives its source on device)
        words[_F["fparam"]] = (
            encode_fparam(plan["fparam"]) if plan.get("fuse") else 0
        )
        if "p2p" in plan:
            words[_F["root"]] = plan["p2p"][0]
            words[_F["peer"]] = plan["p2p"][1]
        else:
            words[_F["root"]] = (
                lead.root_src if op == Operation.BCAST else 0
            )
            words[_F["peer"]] = plan.get("hop", 0)
        slot_idx = session.head % self.ring_depth_of(session)
        session.ring[slot_idx] = words
        session.head += 1
        session.seqn += 1
        return words

    @staticmethod
    def ring_depth_of(session: _RingSession) -> int:
        return session.ring.shape[0]

    # -- window shape + payload ----------------------------------------------
    def _window_shape(self, comm, window) -> WindowShape:
        in_ws, out_ws, wires = [], [], []
        npdt = None
        for _, lead, plan in window:
            in_w, out_w = ring_widths(
                plan["op"], plan["n"], comm.size,
                fuse=plan.get("fuse", 0),
            )
            in_ws.append(in_w)
            out_ws.append(out_w)
            wires.append(
                np.dtype(plan["wire_npdt"]).name
                if plan["compressed"] and plan["wire_npdt"] is not None
                else None
            )
            npdt = plan["npdt"]
        return WindowShape(len(window), in_ws, out_ws, wires, npdt)

    def _wait_written_dependencies(self, session: _RingSession,
                                   window) -> None:
        """Cross-window ordering: a refill whose OPERAND was written by
        a still-in-flight earlier window must wait for that window's
        completion before assembling its operands (within one batch
        the data_dependency fallback already rejects such chains; this
        covers chains across batches)."""
        roots = set()
        for calls, _, plan in window:
            for call in calls:
                buf = call.op0
                if buf is not None and not buf.is_dummy:
                    roots.add(id(buf._root()))
        with self._lock:
            pending = bool(roots & set(session.written))
            parks = list(session.parks) if pending else []
        deadline = time.monotonic() + drain_deadline_s(
            self.gang.timeout_s
        )
        for park in parks:
            if not park.event.wait(
                max(0.01, deadline - time.monotonic())
            ):
                # NEVER assemble stale operand bytes: surfacing beats
                # silently computing on pre-write data (the caller
                # fails this window's requests)
                raise TimeoutError(
                    "command-ring refill blocked on an in-flight "
                    "window writing its operand past the drain "
                    "deadline"
                )

    def _chaos_hook(self, comm, window, slots_np):
        """The chaos plane's reach into the ring path.  Refills never
        cross the emulated fabric, so the installed fault injector sees
        each window as ONE pseudo-message of type ``"RING"``:
        ``corrupt``/``drop`` poison the first slot's opcode word to an
        out-of-range value — the window program reports BAD_OP and that
        slot's requests complete INVALID_OPERATION fast, never a hang
        (a silently vanished refill would strand its waiters);
        ``delay`` sleeps a bounded interval before the doorbell.
        Returns the (possibly poisoned) slot rows."""
        from ...contract import _injector_for

        inj = _injector_for(getattr(self.gang, "fabric", None))
        if inj is None:
            return slots_np
        msg = _RingRefillMsg(comm.id, int(slots_np[0, _F["seqn"]]))
        v = inj.on_send(msg)
        action = None
        if v.corrupt or v.drop or v.dead_dst:
            action = "corrupt" if v.corrupt else "drop"
            slots_np = slots_np.copy()
            slots_np[0, _F["opcode"]] = _CHAOS_BAD_OPCODE
        if v.delay_s > 0:
            with self._lock:
                self.chaos_faults["delay"] = (
                    self.chaos_faults.get("delay", 0) + 1
                )
            time.sleep(min(float(v.delay_s), 1.0))
        if action is not None:
            with self._lock:
                self.chaos_faults[action] = (
                    self.chaos_faults.get(action, 0) + 1
                )
        return slots_np

    # -- dispatch ------------------------------------------------------------
    def _dispatch_window(self, comm, mesh, window, reqs_per_slot,
                         t0) -> None:
        gang = self.gang
        with self._lock:
            session = self._sessions.get(comm.id)
            if session is None:
                session = self._sessions[comm.id] = _RingSession(self.depth)
            # the span's label only: a comm's windows dispatch one at a
            # time, so the id taken under the lock below is this one
            window_id = session.next_window
        with annotate("accl.ring::deps"):
            self._wait_written_dependencies(session, window)
        with annotate("accl.ring::encode", window=window_id):
            shape = self._window_shape(comm, window)
            park, slots_np = self._encode_window(
                comm, session, window, reqs_per_slot, t0
            )

        try:
            gang.interactions.bump()  # THE refill: one host interaction
            # for the whole window
            st, st_shard = self._launch_window(
                comm, mesh, shape, park, slots_np, window
            )
            with annotate("accl.ring::park", window=park.window_id):
                self._park_window(comm, session, park, st, st_shard, t0)
        except BaseException:
            # the window never parked: the armed count must not leak
            # (the parked/no-spin posture is part of the contract)
            with self._lock:
                self._inflight_windows = max(0, self._inflight_windows - 1)
                if park in session.parks:
                    session.parks.remove(park)
            raise

    def _encode_window(self, comm, session, window, reqs_per_slot, t0):
        """Under the lock: the window's slot rows into the session's
        ring, the counters, its ``_WindowPark`` with the per-slot
        introspection, the written-root ledger; then the chaos hook.
        Returns ``(park, slot rows as one array)``."""
        n = len(window)
        with self._lock:
            start = session.head
            slot_rows = [
                self._encode(session, lead, plan)
                for _, lead, plan in window
            ]
            if (start % self.depth) + n > self.depth:
                self.wraps += 1
            self.refills += 1
            self.slots_enqueued += n
            # per-comm residency: the ring-share counter the QoS
            # fairness evidence reads (tenant = communicator)
            self.comm_slots[comm.id] = self.comm_slots.get(comm.id, 0) + n
            self.last_window = n
            self.max_window = max(self.max_window, n)
            for _, _, plan in window:
                name = self._slot_opcode(plan).name
                self.op_slots[name] = self.op_slots.get(name, 0) + 1
            window_id = session.next_window
            session.next_window += 1
            park = _WindowPark(
                window_id,
                [plan for _, _, plan in window],
                reqs_per_slot,
                [calls for calls, _, _ in window],
                t0,
            )
            # introspection: per-slot facts captured at encode time —
            # the (seqn, opcode) written into the ring words plus the
            # issuing call's trace id (flow linkage into the merged
            # timeline)
            for k, (_calls, _, plan) in enumerate(window):
                tid = None
                for req in reqs_per_slot[k]:
                    m = getattr(req, "_tmeta", None)
                    if m and m.get("trace_id"):
                        tid = m["trace_id"]
                        break
                park.slots_info.append({
                    "seqn": int(slot_rows[k][_F["seqn"]]),
                    "opcode": self._slot_opcode(plan).name,
                    "trace_id": tid,
                })
            session.parks.append(park)
            for k, (calls, _, plan) in enumerate(window):
                for r in plan["writers"]:
                    res = calls[r].res
                    if res is not None and not res.is_dummy:
                        rid = id(res._root())
                        session.written[rid] = (
                            session.written.get(rid, 0) + 1
                        )
            self._inflight_windows += 1
        return park, self._chaos_hook(comm, window, np.stack(slot_rows))

    def _settle_window(self, session, park) -> None:
        """Session bookkeeping at window completion, exactly once per
        window: decrement the written-root ledger (cross-window
        dependency releases) and stash the status words for
        introspection."""
        with self._lock:
            if park.settled:
                return
            park.settled = True
            if park.status is not None:
                session.last_status = np.asarray(park.status, np.int32)
            for k, plan in enumerate(park.plans):
                for r in plan["writers"]:
                    res = park.calls_per_slot[k][r].res
                    if res is not None and not res.is_dummy:
                        rid = id(res._root())
                        left = session.written.get(rid, 1) - 1
                        if left <= 0:
                            session.written.pop(rid, None)
                        else:
                            session.written[rid] = left

    def _log_window(self, comm_id: int, park: _WindowPark, status,
                    end_ns: int, error=None) -> None:
        """One completed (or failed) window into the bounded window
        log: per-slot (seqn, opcode, retcode, trace id) next to the
        host-side timing — basis ``"host"`` labeled honestly (the
        window program writes no device clock next to its status
        words).  Logged exactly once per window whichever completion
        path ran."""
        from ...telemetry import _perf_to_epoch_us

        with self._lock:
            if park.logged:
                return
            park.logged = True
        slots = []
        for k, info in enumerate(park.slots_info):
            ret = None
            if status is not None and k < len(status):
                ret = int(status[k][1])
            slots.append(dict(info, retcode=ret))
        t0_us = _perf_to_epoch_us(park.t0)
        end_us = _perf_to_epoch_us(end_ns)
        entry = {
            "window_id": park.window_id,
            "comm": comm_id,
            "ts_us": round(t0_us, 3),
            "dur_us": round(max(end_us - t0_us, 0.001), 3),
            "slots": slots,
            "basis": "host",
        }
        if error is not None:
            entry["error"] = str(error)[:200]
        with self._lock:
            self._window_log.append(entry)
            self.windows_logged += 1
            lat_us = max(end_us - t0_us, 0.001)
            b = max(1, int(lat_us)).bit_length() - 1
            self.window_latency[b] = self.window_latency.get(b, 0) + 1
            self.window_latency_sum_us += lat_us

    def window_log(self, last: Optional[int] = None) -> List[dict]:
        with self._lock:
            log = list(self._window_log)
        return log if last is None else log[-last:]

    def trace_events(self) -> List[dict]:
        """The window log as Chrome/Perfetto events: one span per
        refill window and one span per slot nested under it (cat
        ``cmdring`` so merge_traces dedups the shared-gang rows), each
        slot flow-linked (``f`` phase) to the issuing call's trace id —
        intake→refill→window-execution→completion reads as connected
        arrows in the merged timeline."""
        pid = os.getpid()
        events: List[dict] = []
        log = self.window_log()
        if not log:
            return events
        events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 2,
            "args": {"name": f"cmdring (pid {pid})"},
        })
        events.append({
            "ph": "M", "name": "thread_name", "pid": pid, "tid": 2,
            "args": {"name": "ring windows"},
        })
        for entry in log:
            ts, dur = entry["ts_us"], entry["dur_us"]
            events.append({
                "name": f"cmdring::window[{len(entry['slots'])}]",
                "cat": "cmdring",
                "ph": "X",
                "ts": ts,
                "dur": dur,
                "pid": pid,
                "tid": 2,
                "args": {
                    k: v for k, v in entry.items() if k != "slots"
                },
            })
            n = max(1, len(entry["slots"]))
            for k, slot in enumerate(entry["slots"]):
                # slots execute in order within the window: render
                # them as equal sub-spans parented (by containment)
                # under the refill window span
                s_ts = ts + dur * k / n
                s_dur = dur / n
                events.append({
                    "name": f"cmdring::{slot['opcode'].lower()}",
                    "cat": "cmdring",
                    "ph": "X",
                    "ts": round(s_ts, 3),
                    "dur": round(s_dur, 3),
                    "pid": pid,
                    "tid": 2,
                    "args": dict(slot, window=entry["window_id"]),
                })
                if slot.get("trace_id"):
                    # a STEP (`t`) on the issuing call's flow: the
                    # arrow renders without claiming a flow END — the
                    # call's own s/f pair lives on the rank rows, and
                    # a slot whose issuing record rolled out of the
                    # flight ring must not fail flow validation
                    events.append({
                        "name": "accl::flow",
                        "cat": "cmdring",
                        "ph": "t",
                        "id": f"0x{slot['trace_id']:08x}",
                        "ts": round(s_ts + s_dur / 2, 3),
                        "pid": pid,
                        "tid": 2,
                        "args": {"window": entry["window_id"]},
                    })
        return events

    def _launch_window(self, comm, mesh, shape, park, slots_np, window):
        """The window's one form: ONE async program executes the window
        on zero-copy assembled operand globals; a flushed batch larger
        than the ring depth dispatches once per depth window, in order.
        Results are adopted at launch, in issue order (a pointer swap,
        or a deferred store layered on the buffer), so successive
        windows writing one buffer land newest-last.
        Returns the status global the park's waiter blocks on and the
        one shard of it the waiter then reads (its copy to the host
        already asked for)."""
        gang = self.gang
        with annotate("accl.ring::assemble"):
            globals_ = [
                self._assemble_ring_global(calls, plan, mesh)
                for calls, lead, plan in window
            ]
        with annotate(f"accl::cmdring[{len(window)}]"):
            st, st_shard, results = devring.run_windows(
                [(slots_np, globals_)], mesh, shape, self._kept_slots
            )
        with self._lock:
            self.dispatches += 1
        with annotate("accl.ring::adopt"):
            for k, (calls, lead, plan) in enumerate(window):
                gang._adopt_out_shards(
                    results[0][k], calls, plan, park.reqs_per_slot[k]
                )
        return st, st_shard

    def _zeros_shard(self, w: int, npdt, dev):
        key = (int(w), np.dtype(npdt).str, dev)
        arr = self._zeros.get(key)
        if arr is None:
            from ...buffer import dev_zeros

            self.gang.interactions.bump()  # the one-time zeros program
            arr = self._zeros[key] = dev_zeros((int(w),), npdt, dev)
        return arr

    def _assemble_ring_global(self, calls, plan, mesh):
        """Zero-copy operand global for one ring slot.  Collective
        slots use the gang's assembled-flat machinery (raw committed
        shards, cached); BARRIER tokens and SEND/RECV pair slots build
        theirs from cached zeros shards plus (for p2p) the source
        rank's raw array — warm windows assemble with no dispatch."""
        op = plan["op"]
        if op != Operation.BARRIER and "p2p" not in plan:
            g, _prep, _raw = self.gang._assemble_flat(calls, plan, mesh)
            return g
        from jax.sharding import NamedSharding, PartitionSpec

        from ...ops import driver as opdriver

        size, in_w = plan["size"], plan["in_w"]
        devs, npdt = plan["devs"], plan["npdt"]
        src = plan.get("p2p", (None, None))[0]
        shards = []
        for r, call in enumerate(calls):
            if src is not None and r == src:
                arr = call.op0.device_array()
                if arr.shape[0] != in_w:
                    from .engine import _prep_program

                    self.gang.interactions.bump()
                    arr = _prep_program(in_w, None, devs[r], True)(arr)
                shards.append(arr)
            else:
                shards.append(self._zeros_shard(in_w, npdt, devs[r]))
        return jax.make_array_from_single_device_arrays(
            (size * in_w,),
            NamedSharding(mesh, PartitionSpec(opdriver.AXIS)),
            shards,
        )

    # -- completion ----------------------------------------------------------
    @staticmethod
    def _window_status(park, words) -> np.ndarray:
        """``park.status`` from the device's status words of that
        window: the retcodes as read; the ``seqn`` the device echoes is
        window-relative (the kept slot words carry 0 … n-1), so the
        window's base — the first ``seqn`` the host encoded — goes back
        on, wrapped as ``_encode`` wraps it."""
        status = np.array(words[: len(park.plans)], np.int64)
        status[:, 0] = (
            park.slots_info[0]["seqn"] + status[:, 0]
        ) & 0x7FFFFFFF
        return status.astype(np.int32)

    def _park_window(self, comm, session, park, st, st_shard, t0) -> None:
        """Hand the window's completion to the in-flight window (the
        refill window): the drainer blocks on the status global — THE
        device status words — then reads them from ``st_shard`` and
        completes every slot's requests with its per-slot retcode."""
        gang = self.gang

        def window_done():
            with self._lock:
                self._inflight_windows = max(0, self._inflight_windows - 1)
                if park in session.parks:
                    session.parks.remove(park)

        def waiter(park=park, st=st, st_shard=st_shard):
            with annotate("accl.ring::wait", window=park.window_id):
                jax.block_until_ready(st)
            with annotate("accl.ring::status", window=park.window_id):
                park.status = self._window_status(
                    park, devring.status_view(st_shard)
                )
            with annotate("accl.ring::settle", window=park.window_id):
                self._settle_window(session, park)
                park.event.set()

        def on_ready(overlap_ns, depth, ready_ns, park=park, t0=t0):
            sv = park.status
            dt = max(ready_ns - t0, 1)
            self._log_window(comm.id, park, sv, ready_ns)
            window_done()
            # a completed window closes (or restores) the comm's ring
            # circuit breaker — per-slot BAD_OP retcodes are opcode
            # errors, not transport failures, and don't strike
            self.breaker_for(comm.id).success()
            for i, slot_reqs in enumerate(park.reqs_per_slot):
                code = (
                    ErrorCode.OK
                    if sv is not None and i < len(sv)
                    and int(sv[i, 1]) == CMDRING_ST_OK
                    else ErrorCode.INVALID_OPERATION
                )
                for req in slot_reqs:
                    if req.done():  # side-effect-free engine probe
                        continue
                    req.overlap_ns = overlap_ns or None
                    req.inflight_depth = depth
                    req.ring_resident = True
                    req.complete(code, dt)

        def on_error(exc, park=park, t0=t0, comm_id=comm.id):
            dt = max(time.perf_counter_ns() - t0, 1)
            err = f"{type(exc).__name__}: {exc}"
            self._log_window(
                comm_id, park, park.status, time.perf_counter_ns(),
                error=err,
            )
            # postmortem plane: the ring failure latch — the facade's
            # BlackBox captures the window log + flight evidence
            if self.on_failure is not None:
                try:
                    self.on_failure(comm_id, err)
                except Exception:  # must never mask the failure path
                    pass
            window_done()
            # window failure (drain deadline, device error): strike the
            # comm's ring breaker — repeated strikes open it and the
            # comm degrades to host dispatch until the cool-down probe
            self.breaker_for(comm_id).record_failure(
                type(exc).__name__
            )
            ctx = {
                "comm": comm_id,
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }
            for slot_reqs in park.reqs_per_slot:
                for req in slot_reqs:
                    if not req.done():  # side-effect-free engine probe
                        req.ring_resident = True
                        req.complete(
                            ErrorCode.INVALID_OPERATION, dt,
                            context=dict(ctx, op=req.op_name),
                        )

        gang.window.park(comm.id, waiter, on_ready, on_error, ring=True)
