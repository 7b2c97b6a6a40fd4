"""The gang engine's command-ring sessions: arm / refill / teardown.

This is the host half of the TPU CCLO analog (the device half is
``ops/pallas/cmdring.py``, the mailbox protocol ``accl_tpu/cmdring.py``):
host code that used to *issue* collectives becomes code that *refills a
queue*.  A warm batched window of N eligible collectives is encoded
into N slots of the per-communicator ring and handed to the
**persistent sequencer**:

* first window of a burst: ONE program dispatch arms a sequencer *run*
  (``dispatches`` counter) and the window rides it;
* every further window while the run is live: a **mailbox post** — the
  doorbell is a host memory write, zero program launches
  (``mailbox_posts`` counter).  A warm sustained stream of K windows
  therefore executes with 0 re-dispatches after the first
  (counter-asserted by tests/test_cmdring.py), which is the reference
  firmware's actual execution model: the run loop lives on the device
  and the host only writes commands into the FIFO.

The opcode space is the FULL warm set (``constants.CMDRING_OPCODES``):
allreduce, bcast, reduce-scatter, allgather, alltoall, barrier, and
matched send/recv pairs; compressed (wire-cast) windows ride the ring
with the cast lowered into the decode loop, and f16 windows ride the
f32 compute view.  Everything else — cold calls, oversized payloads,
host operands, mixed dtypes, unpaired p2p — falls back to the ordinary
host-dispatch paths with the reason counted in
:meth:`GangCommandRing.stats`.

Lifecycle (the ``run loop`` states of the reference firmware):

* **parked** — no run accepting, no window in flight: the sequencer
  program has returned and the device stream is free (no spin, no
  occupancy).  The next refill re-arms with one dispatch.
* **resident** — a run is live and lingering on the mailbox; a refill
  is a doorbell write.
* **armed** — windows in flight; the in-flight window
  (``overlap.InflightWindow``) is the refill window: its drain points
  block on the device status words the sequencer pushed.
* **teardown/reset** — ``soft_reset`` halts every run's mailbox (the
  ``HALT`` opcode marks this transition in the slot schema), clears
  every session and realigns seqn/head at 0.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ...cmdring import (
    SequencerMailbox,
    WindowShape,
    complementary_pair,
    default_linger_s,
    default_run_windows,
    encode_fparam,
    encode_slot,
    fused_slot_eligible,
    register_mailbox,
    ring_widths,
    unregister_mailbox,
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
from ...overlap import drain_deadline_s
from ...utils.profiling import annotate

_F = CMDRING_FIELDS

#: ring-session circuit breaker (membership plane): window failures
#: against a dying peer strike the per-comm breaker; OPEN degrades the
#: comm's dispatch ring -> host (counted ``circuit_open``), HALF_OPEN
#: re-probes with an INLINE window (one-shot program, no persistent
#: run to wedge) after the cool-down, success restores the ring.
CMDRING_BREAKER_COOLDOWN_ENV = "ACCL_CMDRING_COOLDOWN_S"
CMDRING_BREAKER_COOLDOWN_S = 2.0
CMDRING_BREAKER_THRESHOLD = 2

#: ops whose operand/result widths scale with world size ('P' slots)
_P_WIDE = (Operation.REDUCE_SCATTER, Operation.ALLTOALL)


def _env_mode() -> str:
    return os.environ.get(CMDRING_ENV, "1").strip().lower()


#: opcode word chaos poisoning writes into a refill's first slot —
#: out of every lowering's opcode range, so the sequencer reports
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


def default_lowering() -> str:
    """Sequencer lowering: ``"xla"`` on every backend, unless
    ``ACCL_CMDRING_LOWERING`` names one.

    The Pallas mega-window kernel was the TPU default until it first met
    a TPU with more than one chip (chip run, PR 21, four v5e, jax 0.9.0):
    its slot epilogue slices VALUES dynamically inside the kernel and
    Mosaic has no lowering for that —

        NotImplementedError: Unimplemented primitive in Pallas TPU
        lowering for KernelType.TC: dynamic_slice
        (ops/pallas/cmdring.py slot_epilogue, lax.dynamic_slice_in_dim)

    — at every payload tried (64 KiB to 4 MiB a slot, 3 and 8 slots); on
    ONE chip it compiles only because a world of one returns before the
    epilogue.  The xla lowering's one-shot inline form compiled and ran
    the same windows on one chip and on four, so it is what a TPU gets;
    the Pallas kernel stays reachable by name (the CPU mesh runs it
    interpreted) and fails loudly on a multi-chip TPU.  Observed, not
    tuned: D4 decides which lowering stays."""
    explicit = os.environ.get("ACCL_CMDRING_LOWERING")
    if explicit in ("xla", "pallas"):
        return explicit
    return "xla"


def persistent_runs_lower() -> bool:
    """Whether the xla lowering's PERSISTENT form — the resident
    ``while_loop`` of ordered ``io_callback`` mailbox pulls — can be
    used on this backend.  On a TPU it cannot: jax 0.9.0 / libtpu
    0.0.34 refuse to lower it (chip run, PR 21, one v5e, 64 KiB x 3
    slots x 4 windows posted ahead)::

        ValueError: Cannot lower jaxpr with verifier errors:
          'stablehlo.recv' op result 0 - sharding doesn't match tensor
          rank: 0 != 2   at loc("jit(body)/io_callback" ... run_session)

    so there every xla-lowered window takes the one-shot INLINE form
    (one program a window, no mailbox), which compiles and runs.  The
    Pallas lowering never had a persistent form.  Observed, not
    configured: D4 decides what stays."""
    import jax

    return jax.default_backend() != "tpu"


class _RowAdopter:
    """Deferred host-row adoption with COLLAPSING: park the result
    placement on the buffer (the PR 1 lazy-adoption discipline) so a
    fire-and-forget window never pays the writeback at completion
    time — and when a later ring window writes the SAME buffer before
    anyone read it, update the parked row in place instead of chaining
    another thunk.  A warm stream writing one result buffer K times
    otherwise replays K chained stores (K device interactions) at
    first read.  Collapsing is allowed ONLY when no other deferred
    write slipped in between (the buffer's ``_defer_seq`` proves it) —
    partial/foreign writes must keep layering in issue order."""

    def __init__(self, gang):
        self._gang = gang
        self._lock = threading.Lock()
        self._gen = 0
        # (root id, arm generation) -> (buf, row, n): every armed thunk
        # owns its own generation slot, so an interleaved foreign defer
        # can never make an EARLIER thunk drain a LATER generation's row
        self._rows: Dict[tuple, tuple] = {}
        self._armed: Dict[int, tuple] = {}  # root id -> (defer_seq, gen)
        # one weakref per tracked root, with an eviction callback: a
        # buffer dropped with its deferred store unresolved must not
        # strand its parked row (unbounded growth over a fire-and-
        # forget loop), and a recycled id(root) must never match a dead
        # buffer's stale entries (the callback runs before the id can
        # be reused)
        self._reaper: Dict[int, object] = {}

    def _track(self, root, key: int) -> None:
        """Caller holds self._lock."""
        if key in self._reaper:
            return
        import weakref

        def evict(_ref, self=self, key=key):
            with self._lock:
                self._reaper.pop(key, None)
                self._armed.pop(key, None)
                for k in [k for k in self._rows if k[0] == key]:
                    self._rows.pop(k, None)

        self._reaper[key] = weakref.ref(root, evict)

    def adopt(self, buf, row: np.ndarray, n: int) -> None:
        root = buf._root()
        key = id(root)
        with root._plock:
            with self._lock:
                self._track(root, key)
                armed = self._armed.get(key)
                if armed is not None and armed[0] == root._defer_seq:
                    parked = self._rows.get((key, armed[1]))
                    # collapse ONLY a rewrite of the SAME destination
                    # region (same buffer object, same width): two ring
                    # writes to different slices of one root must
                    # layer, not replace each other
                    if (
                        parked is not None
                        and parked[0] is buf
                        and parked[2] == n
                    ):
                        self._rows[(key, armed[1])] = (buf, row, n)
                        return
                self._gen += 1
                gen = self._gen
                self._rows[(key, gen)] = (buf, row, n)

            def place(self=self, key=key, gen=gen):
                with self._lock:
                    parked = self._rows.pop((key, gen), None)
                    if (
                        self._armed.get(key) is not None
                        and self._armed[key][1] == gen
                    ):
                        self._armed.pop(key, None)
                if parked is not None:
                    from .engine import _write_host_result

                    _write_host_result(
                        parked[0], parked[1], parked[2],
                        self._gang.interactions,
                    )

            buf.defer_store(place)
            with self._lock:
                self._armed[key] = (root._defer_seq, gen)


class _WindowPark:
    """One in-flight refill window's completion record (the status-FIFO
    side of the mailbox protocol)."""

    __slots__ = ("window_id", "event", "status", "results", "plans",
                 "reqs_per_slot", "calls_per_slot", "t0", "settled",
                 "slots_info", "form", "logged")

    def __init__(self, window_id: int, plans, reqs_per_slot,
                 calls_per_slot, t0):
        self.window_id = window_id
        self.event = threading.Event()
        self.status: Optional[np.ndarray] = None
        self.results: Optional[dict] = None
        self.plans = plans
        self.reqs_per_slot = reqs_per_slot
        self.calls_per_slot = calls_per_slot
        self.t0 = t0
        # session bookkeeping (written-ledger decrement, last_status)
        # done exactly once, by whichever completion path ran
        self.settled = False
        # introspection: per-slot facts for the window log (seqn,
        # opcode, the issuing call's trace id), the dispatch form
        # (inline / mailbox), and the logged-once latch
        self.slots_info: list = []
        self.form = "inline"
        self.logged = False


class _ResidentRun:
    """One live sequencer run: its mailbox, the dispatch thread that
    owns the long-running program, and the failure latch.

    The program is dispatched from a dedicated ``accl-cmdring-run``
    thread: XLA executes callback-bearing programs synchronously on the
    dispatching thread (single-device CPU meshes always; others per
    runtime), and the refill path must never become the run loop — the
    host's doorbell returns immediately whatever the runtime does.  The
    thread exists per RUN, not per window: a warm sustained stream of K
    windows costs one thread spawn, the same amortization as the one
    dispatch."""

    __slots__ = ("mbox", "mbox_id", "shape", "thread", "failed", "exc")

    def __init__(self, mbox, mbox_id, shape):
        self.mbox = mbox
        self.mbox_id = mbox_id
        self.shape = shape
        self.thread: Optional[threading.Thread] = None
        self.failed = threading.Event()
        self.exc: Optional[BaseException] = None

    def launch(self, mesh, run_windows: int) -> None:
        from ...ops.pallas import cmdring as devring

        def drive(self=self, mesh=mesh, run_windows=run_windows):
            try:
                handle = devring.run_session(
                    mesh, self.shape, self.mbox_id, run_windows
                )
                import jax

                jax.block_until_ready(handle)
            except BaseException as e:  # surface to every parked window
                self.exc = e
                self.failed.set()
                self.mbox.halt()
                import traceback

                traceback.print_exc()

        t = threading.Thread(
            target=drive, name="accl-cmdring-run", daemon=True
        )
        self.thread = t
        t.start()


class _RingSession:
    """Per-communicator ring state: the persistent host mirror of the
    device ring (wrap-around is real — slot i of refill k+1 reuses the
    words of slot i of refill k-depth), the monotone seqn, the live
    resident run, and the cross-window write-dependency ledger."""

    __slots__ = ("ring", "head", "seqn", "run", "parks", "written",
                 "next_window", "last_status")

    def __init__(self, depth: int):
        from ...constants import CMDRING_SLOT_WORDS

        self.ring = np.zeros((depth, CMDRING_SLOT_WORDS), np.int32)
        self.head = 0
        self.seqn = 0
        self.run: Optional[_ResidentRun] = None
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
        self.lowering = default_lowering()
        self.persistent = persistent_runs_lower()
        self.run_windows = default_run_windows()
        self.linger_s = default_linger_s()
        self._lock = threading.Lock()
        self._sessions: Dict[int, _RingSession] = {}
        self._inflight_windows = 0
        # cached committed zeros shards for token/dummy slots (barrier,
        # the p2p pair's non-source ranks): first use dispatches the
        # zeros program (counted), warm windows reuse with no dispatch
        self._zeros: Dict[tuple, object] = {}
        # collapsing deferred adoption for mailbox-window results
        self._adopter = _RowAdopter(gang)
        self._drained_runs: List[_ResidentRun] = []  # awaiting unregister
        # lifetime counters (telemetry_report()["cmdring"]).  One
        # counter backs both the refill and doorbell stats keys: every
        # refill rings the doorbell exactly once (as a program dispatch
        # arming a run, or as a mailbox post into a live one).
        self.refills = 0          # refill windows (= doorbells)
        self.dispatches = 0       # sequencer program launches (runs)
        self.mailbox_posts = 0    # refills that rode a live run
        self.slots_enqueued = 0   # collectives executed ring-resident
        self.wraps = 0            # head wrapped past the ring depth
        self.resets = 0           # soft_reset teardowns (runs halted)
        self.max_window = 0
        self.last_window = 0
        self.op_slots: Dict[str, int] = {}  # per-opcode residency
        self.fallbacks: Dict[str, int] = {}
        # introspection plane: a bounded log of completed windows
        # (per-slot seqn/opcode/retcode/trace-id next to the host-side
        # timing — basis "host": neither lowering can write a device
        # clock next to the status word on this mesh, and the snapshot
        # says so instead of faking device time), a window-latency
        # log2-us histogram, and the facade's failure hook (postmortem
        # plane: run latch / drain deadline / dispatch error)
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
        # failures degrade that comm's dispatch ring -> inline -> host,
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
        """True when no refill window is in flight AND no run still
        accepts posts — the sequencer program has returned the device
        stream (no device work, no spin, no occupancy)."""
        with self._lock:
            if self._inflight_windows:
                return False
            return not any(
                s.run is not None and s.run.mbox.accepting
                for s in self._sessions.values()
            )

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
            live_mboxes = [
                s.run.mbox for s in self._sessions.values()
                if s.run is not None
            ]
        # mailbox locks taken OUTSIDE the ring lock (leaf discipline,
        # like the breaker snapshots): queued-but-unpulled refill
        # windows across every live run — how far the host runs ahead
        mailbox_depth = sum(m.depth() for m in live_mboxes)
        with self._lock:
            resident = any(
                s.run is not None and s.run.mbox.accepting
                for s in self._sessions.values()
            )
            state = (
                "armed" if self._inflight_windows
                else ("resident" if resident else "parked")
            )
            return {
                "enabled": self.enabled,
                "mode": "eager" if self.eager else
                        ("batch" if self.enabled else "off"),
                "lowering": self.lowering,
                "persistent": self.persistent,
                "depth": self.depth,
                "run_windows": self.run_windows,
                "linger_ms": round(self.linger_s * 1e3, 3),
                "state": state,
                "refills": self.refills,
                "doorbells": self.refills,  # every refill rings once
                "dispatches": self.dispatches,
                "mailbox_posts": self.mailbox_posts,
                "slots": self.slots_enqueued,
                "wraps": self.wraps,
                "resets": self.resets,
                "max_window": self.max_window,
                # refill occupancy: how full the last doorbell's window
                # filled the ring (1.0 = a full ring per refill)
                "occupancy": round(self.last_window / self.depth, 3)
                if self.last_window else 0.0,
                # sustained occupancy: refill windows served per program
                # dispatch — the persistence gauge (>1 means the
                # sequencer survived across refills; the warm target is
                # the full run budget)
                "sustained_occupancy": round(
                    self.refills / self.dispatches, 3
                ) if self.dispatches else 0.0,
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
                # slot seqn/opcode/retcode/trace-id, host-basis timing),
                # the window-latency histogram, and the mailbox depth
                "mailbox_depth": mailbox_depth,
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
        on window failures, degrades ring -> inline -> host, re-probes
        after the cool-down."""
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
        """soft_reset: halt every run's mailbox (the sequencer programs
        drain their backlog and return — the HALT transition) and
        realign every session's seqn/head at 0 (the gang has already
        drained the in-flight window — the full-flush contract)."""
        with self._lock:
            runs = [
                s.run for s in self._sessions.values() if s.run is not None
            ]
            self._sessions.clear()
            self._inflight_windows = 0
            self.resets += 1
            self._breakers.clear()  # full recovery re-closes the ring
            self._drained_runs.extend(runs)
        for run in runs:
            run.mbox.halt()
        self._prune_retired_runs()

    def _prune_retired_runs(self) -> None:
        """Unregister the mailboxes of retired runs whose programs have
        actually RETURNED (every rank pulled the HALT) — a halted run
        still draining its queued windows must keep its registry entry,
        or its pulls degrade to HALT payloads and the queued windows'
        requests strand (halt() promises queued windows execute)."""
        with self._lock:
            keep, drop = [], []
            for run in self._drained_runs:
                (drop if run.mbox.drained.is_set() else keep).append(run)
            self._drained_runs = keep
        for run in drop:
            unregister_mailbox(run.mbox_id)

    def halt_sessions(self) -> None:
        """Engine shutdown: same run teardown as reset, without touching
        the counters or session mirrors — and the run threads are
        JOINED (bounded): a sequencer program still draining while the
        interpreter tears the XLA runtime down aborts the process."""
        with self._lock:
            runs = [
                s.run for s in self._sessions.values() if s.run is not None
            ]
            runs += self._drained_runs
            self._drained_runs = []
        for run in runs:
            run.mbox.halt()
        for run in runs:
            if run.thread is not None:
                run.thread.join(timeout=10.0)
            unregister_mailbox(run.mbox_id)

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
        if not self.enabled:
            return False
        gang = self.gang
        mesh = gang.submesh(comm)
        if npos == 0:
            return False
        # ring circuit breaker (membership plane): an OPEN comm rides
        # host dispatch until the cool-down; HALF_OPEN probes with the
        # inline window form (no persistent run to wedge on a dying
        # peer); a probe success restores the ring
        brk = self.breaker_for(comm.id)
        verdict = brk.allow()
        if verdict == CircuitBreaker.OPEN:
            return self._fallback("circuit_open")
        probe = verdict == "probe"
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
        if t0 is None:
            t0 = time.perf_counter_ns()

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
            # one payload dtype per window: the pallas lowering packs
            # every slot into ONE concatenated buffer, where a mixed
            # window would silently promote
            if window_npdt is None:
                window_npdt = plan["npdt"]
            elif plan["npdt"] != window_npdt:
                return self._fallback("mixed_dtype")
            # all operands assemble BEFORE dispatch/post: a position
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

        # windows of at most `depth` slots — clamped to the comm's QoS
        # slot budget when one is configured (the flooder pays extra
        # doorbells; unbudgeted tenants keep full windows): each window
        # is one refill (doorbell) — a program dispatch only when no
        # run is live
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
                    comm, mesh, window, reqs_per_slot, t0, probe=probe
                )
            except Exception:
                # this window's dispatch failed: fail ITS slots and the
                # not-yet-dispatched remainder — earlier windows are in
                # flight and complete (or fail) from their own parks;
                # never re-execute a collective
                import traceback

                traceback.print_exc()
                brk.record_failure("dispatch_error")
                # postmortem plane: a failed window DISPATCH is a ring
                # failure too (the latch path covers in-flight wedges)
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
        return True

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
        # on a warm compressed stream never recompiles the sequencer
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

    def _payload_rows(self, comm, window, shape: WindowShape):
        """Per-slot per-rank operand rows — the refill's command
        payload, as VIEWS of the committed device arrays (zero-copy
        snapshots: jax arrays are immutable and later stores swap
        pointers, so what the mailbox holds can never mutate; the only
        copy on the wire is the pull's host→device move).  ``None``
        rows (dummy operands, barrier tokens, the p2p pair's non-source
        ranks) pull as zeros."""
        payload = []
        for k, (calls, lead, plan) in enumerate(window):
            w = shape.in_ws[k]
            if plan["op"] == Operation.BARRIER:
                payload.append(None)
                continue
            src_only = plan.get("p2p")
            rows = []
            for r, call in enumerate(calls):
                buf = call.op0
                if (
                    (src_only is not None and r != src_only[0])
                    or buf is None
                    or buf.is_dummy
                ):
                    rows.append(None)
                    continue
                view = np.asarray(buf.device_view()[:w])
                if view.shape[0] < w:
                    padded = np.zeros((w,), shape.npdt)
                    padded[: view.shape[0]] = view
                    view = padded
                rows.append(view)
            payload.append(rows)
        return payload

    def _wait_written_dependencies(self, session: _RingSession,
                                   window) -> None:
        """Cross-window ordering: a refill whose OPERAND was written by
        a still-in-flight earlier window must wait for that window's
        completion before snapshotting payload bytes (within one batch
        the data_dependency fallback already rejects such chains; this
        covers chains across batches riding one live run)."""
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
                # NEVER snapshot stale operand bytes: surfacing beats
                # silently computing on pre-write data (the caller
                # fails this window's requests, same as the waiter's
                # wedged-run path)
                raise TimeoutError(
                    "command-ring refill blocked on an in-flight "
                    "window writing its operand past the drain "
                    "deadline"
                )

    def _window_posture(self, window):
        """Per-window sequencer posture: the lead call's tuning-register
        overlay (``CMDRING_RUN_WINDOWS`` / ``CMDRING_LINGER_US``, raced
        as autotuner axes and dispatched per plan key) over the gang's
        env-default registers.  0 = default — the env knobs keep
        steering any call without an overlay."""
        lead = window[0][1]
        t = lead.effective_tuning(getattr(self.gang, "tuning", None) or {})
        rw = int(t.get("cmdring_run_windows", 0) or 0)
        lus = int(t.get("cmdring_linger_us", 0) or 0)
        run_windows = rw if rw > 0 else self.run_windows
        linger_s = (lus / 1e6) if lus > 0 else self.linger_s
        return run_windows, linger_s

    def _chaos_hook(self, comm, window, slots_np):
        """The chaos plane's reach into the ring path.  Refills never
        cross the emulated fabric, so the installed fault injector sees
        each window as ONE pseudo-message of type ``"RING"``:
        ``corrupt``/``drop`` poison the first slot's opcode word to an
        out-of-range value — the sequencer reports BAD_OP and that
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
                         t0, probe: bool = False) -> None:
        gang = self.gang
        n = len(window)
        shape = self._window_shape(comm, window)
        lowering = self._effective_lowering(shape, window)
        with self._lock:
            session = self._sessions.get(comm.id)
            if session is None:
                session = self._sessions[comm.id] = _RingSession(self.depth)
        self._wait_written_dependencies(session, window)
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
        slots_np = self._chaos_hook(comm, window, np.stack(slot_rows))

        try:
            gang.interactions.bump()  # THE refill: one host interaction
            # for the whole window (an inline dispatch, a dispatch
            # arming a resident run, or a mailbox write into one)
            run = None
            waiter_st = None
            if lowering == "xla":
                with self._lock:
                    live = (
                        session.run is not None
                        and session.run.shape == shape
                        and session.run.mbox.accepting
                    )
                    # the stream detector: an earlier window of this
                    # session is still in flight — the host is running
                    # ahead of the device, the regime the resident run
                    # exists for.  A lone window takes the inline form
                    # (zero-copy operands, async dispatch, no mailbox
                    # round trip on its latency path).
                    streaming = len(session.parks) > 1
                if self.persistent and (live or streaming) and not probe:
                    # (a half-open probe window stays INLINE — the
                    # ring -> inline degradation step: one-shot
                    # program, no persistent run to wedge)
                    payload = self._payload_rows(comm, window, shape)
                    park.form = "mailbox"
                    run = self._post_or_dispatch(
                        comm, mesh, session, shape, window_id, slots_np,
                        payload, self._window_posture(window),
                    )
                else:
                    waiter_st = self._dispatch_inline(
                        comm, mesh, shape, park, slots_np, window, "xla"
                    )
            else:
                waiter_st = self._dispatch_inline(
                    comm, mesh, shape, park, slots_np, window, lowering
                )
            self._park_window(comm, session, park, run, waiter_st, t0)
        except BaseException:
            # the window never parked: the armed count must not leak
            # (the parked/no-spin posture is part of the contract)
            with self._lock:
                self._inflight_windows = max(0, self._inflight_windows - 1)
                if park in session.parks:
                    session.parks.remove(park)
            raise

    def _effective_lowering(self, shape: WindowShape, window) -> str:
        """Per-window lowering.  The Pallas mega-window kernel cannot
        take f16 wire casts (no Mosaic f16 — the f32 compute view
        cannot express the f16 rounding lane on the VPU), and BARRIER
        tokens / SEND-RECV pair slots assemble their payload through
        the mailbox rather than the zero-copy flat globals; such
        windows ride the XLA session INSTEAD of falling back to host
        dispatch — still ring-resident, fallback counters untouched."""
        if self.lowering != "pallas":
            return self.lowering
        f16 = np.dtype(np.float16)
        if np.dtype(shape.npdt) == f16:
            return "xla"
        if any(w is not None and np.dtype(w) == f16 for w in shape.wires):
            return "xla"
        return "pallas"

    def _post_or_dispatch(self, comm, mesh, session, shape, window_id,
                          slots_np, payload, posture) -> "_ResidentRun":
        """The persistent doorbell: post into the live run when one
        accepts this shape, else arm a fresh run (ONE dispatch) and
        post the window as its first pull.  Returns the run the window
        rode (its failure latch feeds the window's waiter).  ``posture``
        is the arming window's (run_windows, linger_s) from its tuning
        overlay — a live run keeps the posture it launched with."""
        run_windows, linger_s = posture
        with self._lock:
            run = session.run
        if run is not None and run.shape == shape:
            if run.mbox.post(window_id, slots_np, payload):
                with self._lock:
                    self.mailbox_posts += 1
                return run
        if run is not None:
            run.mbox.halt()  # stale shape / spent budget: let it drain
            with self._lock:
                self._drained_runs.append(run)
            self._prune_retired_runs()
        mbox = SequencerMailbox(
            comm.size, shape,
            run_windows=run_windows,
            linger_s=linger_s,
            on_window_done=self._make_window_done(comm.id),
        )
        mid = register_mailbox(mbox)
        ok = mbox.post(window_id, slots_np, payload)
        assert ok  # fresh mailbox always accepts its first window
        new_run = _ResidentRun(mbox, mid, shape)
        new_run.launch(mesh, run_windows)
        with self._lock:
            session.run = new_run
            self.dispatches += 1
        return new_run

    def _settle_window(self, session, park) -> None:
        """Session bookkeeping at window completion, exactly once per
        window whichever completion path ran: decrement the
        written-root ledger (cross-window dependency releases) and
        stash the status words for introspection."""
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
                    end_ns: int, run=None, error=None) -> None:
        """One completed (or failed) window into the bounded window
        log: per-slot (seqn, opcode, retcode, trace id) next to the
        host-side timing — basis ``"host"`` labeled honestly (neither
        lowering can write a device clock next to its status words on
        this mesh; the mailbox's posted/pulled/pushed stamps are the
        closest observable refill timeline).  Logged exactly once per
        window whichever completion path ran."""
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
            "form": park.form,
            "ts_us": round(t0_us, 3),
            "dur_us": round(max(end_us - t0_us, 0.001), 3),
            "slots": slots,
            "basis": "host",
        }
        if error is not None:
            entry["error"] = str(error)[:200]
        if run is not None:
            timing = run.mbox.take_timing(park.window_id)
            if timing is not None:
                entry["mailbox_us"] = {
                    k2.replace("_ns", "_us"):
                        round(_perf_to_epoch_us(v), 3)
                    for k2, v in timing.items()
                }
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

    def _make_window_done(self, comm_id: int):
        """Completion hook one mailbox carries: adopt results (deferred
        stores), stash status, complete the slots' requests, release
        the park's event.  Runs on the run thread (the push callback's
        context), outside every mailbox lock.  Completing HERE — not in
        the drainer's on_ready — saves two thread handoffs per window
        on the latency path; ordering holds because one run pushes its
        windows strictly in order on one thread, and the park entry
        still rides the in-flight window so every drain point sees
        it."""

        def on_done(window_id, status, results, comm_id=comm_id):
            with self._lock:
                session = self._sessions.get(comm_id)
                park = None
                if session is not None:
                    for p in session.parks:
                        if p.window_id == window_id:
                            park = p
                            break
            if park is None:
                return  # torn down (soft_reset) while in flight
            for k, plan in enumerate(park.plans):
                out_w = plan["out_w"] if "p2p" not in plan else plan["n"]
                for r in sorted(plan["writers"]):
                    res = park.calls_per_slot[k][r].res
                    if res is None or res.is_dummy:
                        continue
                    row = results.get(r)
                    if row is None:
                        continue
                    self._adopter.adopt(res, row[k][:out_w], out_w)
            park.status = np.asarray(status, np.int32)
            if session is not None:
                self._settle_window(session, park)
            # Complete the slots' requests NOW (the latency path): the
            # drainer's on_ready then finds them done and only settles
            # the window-plane accounting.  Guarded: a LATE push racing
            # the waiter's drain-deadline failure must not flip
            # already-failed requests back to OK.  Cross-window WRITE
            # ordering needs no extra fence here: XLA serializes
            # program execution per device, so every rank's run-R2
            # pushes strictly follow its run-R1 pushes — window
            # completions (all-ranks fan-in) therefore fire in
            # execution order, and successive adoptions of one buffer
            # land newest-last.
            sv = park.status
            dt = max(time.perf_counter_ns() - park.t0, 1)
            for i, slot_reqs in enumerate(park.reqs_per_slot):
                code = (
                    ErrorCode.OK
                    if i < len(sv) and int(sv[i, 1]) == CMDRING_ST_OK
                    else ErrorCode.INVALID_OPERATION
                )
                for req in slot_reqs:
                    if req.done():  # side-effect-free engine probe
                        continue
                    req.ring_resident = True
                    req.complete(code, dt)
            park.event.set()

        return on_done

    def _dispatch_inline(self, comm, mesh, shape, park, slots_np,
                         window, lowering):
        """The one-shot window form: ONE async program executes the
        window on zero-copy assembled operand globals (no mailbox on
        the latency path — a lone drained window costs exactly what the
        pre-persistent ring charged).  On the pallas lowering this is
        the mega-window Mosaic kernel with a backlog of one; a flushed
        batch larger than the ring depth dispatches once per depth
        window, in order.  Returns the status global the park's waiter
        blocks on."""
        from ...ops.pallas import cmdring as devring

        gang = self.gang
        globals_ = [
            self._assemble_ring_global(calls, plan, mesh)
            for calls, lead, plan in window
        ]
        with annotate(f"accl::cmdring[{len(window)}]"):
            st, results = devring.run_windows(
                [(slots_np, globals_)], mesh, shape, lowering=lowering,
            )
        with self._lock:
            self.dispatches += 1
        for k, (calls, lead, plan) in enumerate(window):
            gang._adopt_out_shards(
                results[0][k], calls, plan, park.reqs_per_slot[k]
            )
        return st

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
        import jax
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
    def _park_window(self, comm, session, park, run, waiter_st,
                     t0) -> None:
        """Hand the window's completion to the in-flight window (the
        refill window): the drainer blocks on the device status words
        — the mailbox park event on the resident path, the status
        global on the inline path — then completes every slot's
        requests with its per-slot retcode."""
        gang = self.gang

        def window_done():
            with self._lock:
                self._inflight_windows = max(0, self._inflight_windows - 1)
                if park in session.parks:
                    session.parks.remove(park)

        if waiter_st is not None:
            # inline form: the status global IS the completion word
            def waiter(park=park, st=waiter_st):
                import jax

                from ...ops.pallas.cmdring import status_view

                jax.block_until_ready(st)
                park.status = status_view(st)[: len(park.plans)]
                self._settle_window(session, park)
                park.event.set()
        else:
            def waiter(park=park, run=run):
                deadline = time.monotonic() + drain_deadline_s(
                    gang.timeout_s
                )
                while True:
                    if park.event.wait(0.2):
                        return
                    if run is not None and run.failed.is_set():
                        raise RuntimeError(
                            "sequencer run failed: "
                            f"{type(run.exc).__name__}: {run.exc}"
                        )
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            "command-ring window never completed "
                            "(sequencer run wedged past the drain "
                            "deadline)"
                        )

        def on_ready(overlap_ns, depth, ready_ns, park=park, t0=t0,
                     run=run):
            # the xla mailbox path completed the requests on the run
            # thread already (on_window_done, the latency path); this
            # settles anything still pending (the pallas backlog path,
            # torn-down sessions) and the window-plane accounting
            sv = park.status
            dt = max(ready_ns - t0, 1)
            self._log_window(comm.id, park, sv, ready_ns, run=run)
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

        def on_error(exc, park=park, run=run, t0=t0, comm_id=comm.id):
            dt = max(time.perf_counter_ns() - t0, 1)
            err = f"{type(exc).__name__}: {exc}"
            self._log_window(
                comm_id, park, park.status, time.perf_counter_ns(),
                run=run, error=err,
            )
            # postmortem plane: the ring failure latch — the facade's
            # BlackBox captures the window log + flight evidence
            if self.on_failure is not None:
                try:
                    self.on_failure(comm_id, err)
                except Exception:  # must never mask the failure path
                    pass
            window_done()
            # window failure (run latch, drain deadline, dispatch
            # error): strike the comm's ring breaker — repeated strikes
            # open it and the comm degrades to host dispatch until the
            # cool-down probe
            self.breaker_for(comm_id).record_failure(
                type(exc).__name__
            )
            # tear down the run THIS window rode (an inline window rode
            # none) — never whatever run the session points at now,
            # which may be a healthy successor serving later windows.
            # The mailbox stays registered until the program actually
            # returns (queued windows still drain), then prunes.
            if run is not None:
                with self._lock:
                    if session.run is run:
                        session.run = None
                    self._drained_runs.append(run)
                run.mbox.halt()
                self._prune_retired_runs()
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
