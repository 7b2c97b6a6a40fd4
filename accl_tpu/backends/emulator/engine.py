"""The emulated collective engine: a cooperative scheduler running collective
algorithms over the fake wire.

This is the TPU-build analog of the reference's control-plane firmware main
loop (``ccl_offload_control.c:2308-2483``): calls arrive on a command queue,
each executes as a *generator* that yields wait-conditions (see
``engine_conditions.py``); calls whose condition is unmet are parked and
re-polled round-robin — the same cooperative retry-queue semantics the
firmware implements with ``NOT_READY_ERROR`` recirculation and
``current_step`` resume state (``:2460-2478``), expressed idiomatically as
Python coroutines instead of a hand-rolled step machine.

One engine == one rank.  Data lives in numpy "device" memory; the dataplane
(RX pool, reductions, casts, streams) is in ``dataplane.py``; the wire in
``fabric.py``; the algorithms in ``algorithms.py``.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
import traceback
import weakref
from typing import Callable, Dict, List, Optional

from ...communicator import Communicator
from ...constants import (
    ConfigFunction,
    DEFAULT_RETRY_BACKOFF_S,
    DEFAULT_RX_BUFFER_COUNT,
    DEFAULT_RX_BUFFER_SIZE,
    DEFAULT_TIMEOUT_S,
    EAGER_THRESHOLD_DEFAULT,
    ErrorCode,
    MAX_EAGER_SIZE_LIMIT,
    MAX_RETRY_LIMIT,
    Operation,
    TUNING_DEFAULTS,
)
from ...contract import verdict_context
from ...faults import PeerDeadError, SeqnLedger
from ...request import CommandQueue, Request
from ..base import BaseEngine, CallOptions
from . import algorithms
from .dataplane import RxBuffer, RxBufferPool, RxStatus, StreamPorts
from .engine_conditions import WaitCondition
from .fabric import Endpoint, Fabric, Message, MsgType

# Scheduler threads that outlived their shutdown join: a leak here means an
# engine wedged mid-call and the process is carrying a zombie scheduler.
# Registered by EmuEngine.shutdown, reaped as threads actually exit —
# exposed so soak/churn tests can assert none leaked.
_leaked_threads: List[weakref.ref] = []
_leaked_lock = threading.Lock()


def leaked_scheduler_threads() -> List[str]:
    """Names of engine scheduler threads that failed to join at shutdown
    and are STILL alive."""
    with _leaked_lock:
        alive = []
        live_refs = []
        for ref in _leaked_threads:
            t = ref()
            if t is not None and t.is_alive():
                alive.append(t.name)
                live_refs.append(ref)
        _leaked_threads[:] = live_refs
        return alive


#: operations that talk to peers (fail-fast candidates against a dead rank)
_COMM_OPS = frozenset((
    Operation.SEND, Operation.RECV, Operation.BCAST, Operation.SCATTER,
    Operation.GATHER, Operation.ALLGATHER, Operation.REDUCE,
    Operation.ALLREDUCE, Operation.REDUCE_SCATTER, Operation.ALLTOALL,
    Operation.BARRIER,
))


class _RetransEntry:
    __slots__ = ("msg", "address", "attempts", "due")

    def __init__(self, msg: Message, address: str, due: float):
        self.msg = msg
        self.address = address
        self.attempts = 0
        self.due = due


class _CallTask:
    __slots__ = ("request", "gen", "cond", "deadline", "started_ns",
                 "options")

    def __init__(self, request: Request, gen, timeout_s: float,
                 options: Optional[CallOptions] = None):
        self.request = request
        self.gen = gen
        self.cond: Optional[WaitCondition] = None
        self.deadline = time.monotonic() + timeout_s
        self.started_ns = time.perf_counter_ns()
        self.options = options


class EmuEngine(BaseEngine):
    def __init__(
        self,
        fabric: Fabric,
        address: str,
        rx_buffer_count: int = DEFAULT_RX_BUFFER_COUNT,
        rx_buffer_size: int = DEFAULT_RX_BUFFER_SIZE,
    ):
        self.fabric = fabric
        self.address = address
        self.endpoint = Endpoint()
        fabric.attach(address, self.endpoint)
        self.rx_pool = RxBufferPool(rx_buffer_count, rx_buffer_size)
        self.streams = StreamPorts()
        self.timeout_s = DEFAULT_TIMEOUT_S
        self.max_eager_size = EAGER_THRESHOLD_DEFAULT
        self.max_rendezvous_size = MAX_EAGER_SIZE_LIMIT
        self.tuning = dict(TUNING_DEFAULTS)
        self.transport_enabled = False
        # retry policy (ConfigFunction.SET_RETRY_LIMIT / SET_RETRY_BACKOFF,
        # ACCL.set_retry_policy): limit 0 = the classic fire-and-forget
        # eager send; limit > 0 arms per-segment ACKs + retransmit with
        # exponential backoff (receiver-side seqn dedup keeps duplicates
        # value-correct)
        self.retry_limit = 0
        self.retry_backoff_s = DEFAULT_RETRY_BACKOFF_S
        # overlap-plane parity knob (ConfigFunction.SET_INFLIGHT_WINDOW):
        # this tier completes requests from its own scheduler threads —
        # launches never block on completion — so the window depth is
        # accepted + reported for portability, not enforced as a bound
        from ...overlap import default_window_depth

        self.inflight_window = default_window_depth()
        # QoS arbiter plane: engine-side mirror of SET_TENANT_* writes
        # (comm id -> {class, weight, window_share, ring_slots, rate})
        self.tenants: Dict[int, dict] = {}

        # contract plane (accl_tpu.contract, ACCL_VERIFY=1): armed by the
        # facade via set_contract_verifier — intake screens and active
        # calls fail fast on a standing cross-rank divergence verdict
        self.contract_verifier = None

        self._rndzv_inits: List[Message] = []
        self._rndzv_done: List[Message] = []
        self._notif_lock = threading.Lock()
        self._vaddr_counter = itertools.count(1)
        # retransmit window (engine-thread only):
        # (comm, peer, epoch, seqn) -> entry
        self._retrans: Dict[tuple, _RetransEntry] = {}
        # receiver-side duplicate detection (engine-thread only)
        self._ledger = SeqnLedger()
        # per-peer-address health: timeout/retry accounting feeding the
        # graceful-degradation map (capabilities()["health"]); a peer
        # marked "dead" fails new collectives fast at call intake
        self._health: Dict[str, dict] = {}
        # telemetry counters (accl_tpu.telemetry snapshot): recovery-
        # protocol event totals the metrics registry absorbs
        self._retransmits_total = 0
        self._dedup_discards_total = 0
        # membership plane: pre-shrink straggler frames discarded by
        # the epoch screen (see Message.mbr).  The fence is COMM-scoped
        # (_mbr_floor: comm id -> minimum accepted epoch, written at
        # cutover): traffic on communicators that never shrank must
        # keep flowing whatever the sender's global epoch says.
        self._mbr_drops = 0
        self._mbr_floor: Dict[int, int] = {}
        # cutover purges queued by the facade thread, applied ON the
        # scheduler thread (the rx pool / ledger / retransmit window /
        # health map are scheduler-owned state; a cross-thread mutation
        # races _route_inbox mid-iteration)
        self._mbr_cutovers: List[tuple] = []
        self.leaked_scheduler_thread = False

        self._queue = CommandQueue()
        self._wake = threading.Event()
        self.endpoint.on_activity = self._wake.set
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name=f"accl-engine-{address}", daemon=True
        )
        self._thread.start()

    # -- public API ---------------------------------------------------------
    def start(self, options: CallOptions) -> Request:
        req = Request(op_name=options.op.name)
        self._queue.push((req, options))
        self._wake.set()
        return req

    def shutdown(self, join_timeout: float = 5.0) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=join_timeout)
        if self._thread.is_alive():
            # the scheduler thread is wedged (a call stuck in non-yielding
            # work): don't mask it — log loudly and register the zombie so
            # soak/churn tests can assert no leaked scheduler threads
            self.leaked_scheduler_thread = True
            with _leaked_lock:
                _leaked_threads.append(weakref.ref(self._thread))
            print(
                f"[accl engine {self.address}] LEAK: scheduler thread "
                f"{self._thread.name!r} did not exit within "
                f"{join_timeout}s of shutdown — a call is wedged; the "
                "thread is now a daemon zombie",
                file=sys.stderr,
            )
        detach = getattr(self.fabric, "detach", None)
        if detach is not None:
            # leave the fabric honestly: later sends to this rank fail
            # fast with SEND_TIMEOUT instead of being silently dropped
            detach(self.address)
        self.fabric.close()

    def stream_push(self, stream_id: int, data: bytes) -> None:
        self.streams.push(stream_id, data)
        self._wake.set()

    def stream_pop(self, stream_id: int, timeout: Optional[float] = None) -> bytes:
        return self.streams.pop(stream_id, timeout=timeout)

    def new_vaddr(self) -> int:
        return next(self._vaddr_counter)

    # -- contract plane (accl_tpu.contract) ----------------------------------
    def contract_anchor(self):
        """The object the contract plane's in-process exchange board
        anchors on: the InProc fabric — shared by every InProc rank
        engine, so their verifiers meet on one board.  A SocketFabric
        serves exactly one rank per process: no board (single-poster
        boards only cost ring copies), the wire piggyback does the
        comparing."""
        from .fabric import InProcFabric

        return self.fabric if isinstance(self.fabric, InProcFabric) else None

    def set_contract_verifier(self, verifier) -> None:
        """Arm (or with ``None`` disarm) cross-rank contract checks on
        this engine: inbound digest claims are observed at delivery, and
        a standing divergence verdict fails queued + active calls fast
        (CONTRACT_VIOLATION) instead of letting them time out."""
        self.contract_verifier = verifier
        if verifier is None:
            self.endpoint.contract_hook = None
            return

        def observe(msg, v=verifier):
            if msg.msg_type == MsgType.VERIFY:
                # a peer convicted a divergence and relayed the verdict:
                # adopt it so this rank's in-flight calls fail fast too
                import json as _json

                try:
                    verdict = _json.loads(msg.payload.decode())
                except (ValueError, UnicodeDecodeError):
                    return
                v.adopt_verdict(msg.comm_id, verdict, src_rank=msg.src)
                return
            v.observe_claim(
                msg.comm_id, msg.src, msg.vfy_gen, msg.vfy_window,
                msg.vfy_digest,
            )

        self.endpoint.contract_hook = observe
        verifier.add_verdict_listener(lambda _vd: self._wake.set())

    # -- monitor plane (accl_tpu.monitor) ------------------------------------
    def set_skew_tracker(self, tracker) -> None:
        """Arm straggler-skew exchange: peers' piggybacked (window,
        mean_wait) claims are observed at delivery — same cadence and
        hook shape as the contract digest piggyback.  On the InProc
        fabric the shared judge already exchanges in-process; the hook
        is still wired so the one mechanism covers both fabrics."""
        self.skew_tracker = tracker
        if tracker is None:
            self.endpoint.skew_hook = None
            return

        def observe(msg, tracker=tracker):
            if msg.sent_ns:
                tracker.on_message(
                    msg.comm_id, msg.src, time.time_ns() - msg.sent_ns
                )
            tracker.observe_claim(
                msg.comm_id, msg.src, msg.skw_window, msg.skw_mean_us
            )

        self.endpoint.skew_hook = observe

    def skew_exchange_mode(self) -> str:
        from .fabric import InProcFabric

        return "board" if isinstance(self.fabric, InProcFabric) else "wire"

    # -- postmortem plane (accl_tpu.monitor.BlackBox) -------------------------
    def set_postmortem(self, handler) -> None:
        """Route POSTMORTEM solicitation frames to the facade's
        BlackBox handler at delivery — the wire half of the bundle
        solicitation on one-process-per-rank fabrics (the board tiers
        solicit in process and never send frames)."""
        self.postmortem_handler = handler
        self.endpoint.postmortem_hook = handler

    # -- membership plane (accl_tpu.membership) ------------------------------
    def set_membership(self, view) -> None:
        """Arm (or with ``None`` disarm) the membership plane: MEMBER
        agreement frames are observed at delivery (the wire exchange on
        socket fabrics; harmless duplicate tallies on InProc where the
        board already exchanged), and a confirmed eviction wakes the
        scheduler so in-flight calls against the evicted rank fail
        fast instead of burning their deadline."""
        self.membership = view
        if view is None:
            self.endpoint.membership_hook = None
            return

        def observe(msg, v=view):
            from ...membership import member_payload

            payload = member_payload(msg.payload)
            if payload is not None:
                v.observe_wire(payload, msg.src)

        self.endpoint.membership_hook = observe
        view.add_listener(lambda _evt: self._wake.set())

    def _membership_failure(self, options: Optional[CallOptions],
                            peer_rank: Optional[int],
                            default_code: ErrorCode) -> tuple:
        """(code, extra_context) for a failed call against ``peer_rank``
        (comm-relative): RANK_EVICTED + agreement evidence when the
        membership plane holds a confirmed (or applied) eviction
        covering that peer — the structured terminal the shrink
        protocol promises for in-flight work — else the tier's own
        timeout code."""
        mv = self.membership
        if (
            mv is None or options is None or options.comm is None
            or peer_rank is None
        ):
            return default_code, {}
        try:
            session = options.comm.ranks[peer_rank].session
        except IndexError:
            return default_code, {}
        if mv.plan_covers(session):
            return ErrorCode.RANK_EVICTED, {"membership": mv.evidence()}
        return default_code, {}

    def _evicted_peer_for(self, options: CallOptions) -> Optional[int]:
        """Comm-relative rank of a participating peer under a confirmed
        eviction, or None — the active-task sweep's screen (mirrors
        ``_dead_peer_for`` but consults the agreed plan, which can
        land while the health map still says ``suspect``)."""
        mv = self.membership
        comm = options.comm
        if mv is None or comm is None or options.op not in _COMM_OPS:
            return None
        if not (mv.cutover_ready() or mv.evicted):
            return None
        if options.op == Operation.SEND:
            candidates = [options.root_dst]
        elif options.op == Operation.RECV:
            candidates = [options.root_src]
        else:
            candidates = [
                r for r in range(comm.size) if r != comm.local_rank
            ]
        for r in candidates:
            if mv.plan_covers(comm.ranks[r].session):
                return r
        return None

    def on_membership_cutover(self, plan: dict, addresses: tuple = (),
                              comm_ids: tuple = ()) -> None:
        """Queue the post-shrink purge for the SCHEDULER thread (the rx
        pool, dedup ledger, retransmit window and health map are
        scheduler-owned; mutating them from the facade thread races
        _route_inbox mid-iteration) and raise the shrunk comms'
        stale-frame fence floors.  The scheduler drains the queue
        before popping any later intake item, so the purge strictly
        precedes the first post-shrink collective."""
        mv = self.membership
        if mv is not None:
            for cid in comm_ids:
                self._mbr_floor[cid] = mv.epoch
        with self._notif_lock:
            self._mbr_cutovers.append(
                (tuple(addresses), tuple(comm_ids))
            )
        self._wake.set()

    def _apply_membership_purge(self, addresses: tuple,
                                comm_ids: tuple) -> None:
        """The purge itself (scheduler thread only), the per-comm
        analog of the soft-reset full flush: drop the shrunk comms'
        STALE parked rx segments, inbox frames, retransmit and
        rendezvous entries of the ABORTED pre-shrink collective (its
        chunk geometry differs from the post-shrink one, and seqn
        matching ignores epochs, so a stale chunk would corrupt the
        first shrunk collective).  Epoch-aware via the fence floors: a
        fast peer that cut over first may already have POST-shrink
        frames parked here — those carry the new membership epoch and
        survive.  The dedup ledger is deliberately NOT purged: its
        keys carry the sender's communicator-instance epoch, which the
        shrink refreshed, so post-shrink segments never collide with
        pre-shrink floors (the PR 2 epoch design).  Also drops the
        evicted peers' health entries and clears the suspect strikes
        the failure cascade accrued against the SURVIVORS (a rank
        stalled behind the dead one is not sick)."""
        ids = set(comm_ids)
        if ids:
            floors = {c: self._mbr_floor.get(c, 0) for c in ids}

            def stale(m, floors=floors):
                floor = floors.get(m.comm_id)
                return floor is not None and m.mbr < floor

            self.rx_pool.purge(floors)
            while self.endpoint.take_matching(stale) is not None:
                pass
            # retransmit entries for the shrunk comms are pre-cutover
            # by construction (this engine's own post-cutover sends
            # cannot precede the drain that runs this purge)
            for key in [k for k in self._retrans if k[0] in ids]:
                del self._retrans[key]
            with self._notif_lock:
                self._rndzv_inits = [
                    m for m in self._rndzv_inits if not stale(m)
                ]
                self._rndzv_done = [
                    m for m in self._rndzv_done if not stale(m)
                ]
        for a in addresses:
            self._health.pop(a, None)
        for h in self._health.values():
            if h["state"] == "suspect":
                h["state"] = "ok"
                h["timeouts"] = 0

    def _drain_membership_cutovers(self) -> None:
        """Apply queued cutover purges (scheduler thread).  Called
        before every intake pop: the cutover marker is queued strictly
        before the facade issues its first post-shrink collective, so
        draining here orders purge-before-serve."""
        if not self._mbr_cutovers:
            return
        with self._notif_lock:
            cutovers, self._mbr_cutovers = self._mbr_cutovers, []
        for addresses, comm_ids in cutovers:
            self._apply_membership_purge(addresses, comm_ids)

    def _contract_verdict_for(self, options: Optional[CallOptions]):
        v = self.contract_verifier
        if (
            v is None or not v.has_verdict or options is None
            or options.comm is None or options.op not in _COMM_OPS
        ):
            return None
        return v.check(options.comm.id)

    # -- wire helpers used by algorithms ------------------------------------
    def post(self, comm: Communicator, dst: int, msg: Message) -> None:
        addr = comm.ranks[dst].address
        mv = self.membership
        if mv is not None:
            # membership-epoch stamp: globally aligned by the eviction
            # agreement, so receivers can discard stale pre-shrink
            # frames (see Message.mbr)
            msg.mbr = mv.epoch
        try:
            self.fabric.send(addr, msg)
        except PeerDeadError:
            self._health_note(addr, "peer_dead", dead=True)
            raise

    def post_eager(self, comm: Communicator, dst: int, msg: Message) -> None:
        """Post an eager segment; with a retry policy armed (retry_limit >
        0) the segment requests an ACK and enters the retransmit window —
        unacked segments are re-sent with exponential backoff up to the
        retry limit (the recovery loop the reference's NOT_READY_ERROR
        stream plays for its transports)."""
        if self.retry_limit > 0:
            msg.ack = 1
            msg.reply_to = self.address
        self.post(comm, dst, msg)
        if self.retry_limit > 0:
            key = (msg.comm_id, dst, msg.epoch, msg.seqn)
            self._retrans[key] = _RetransEntry(
                msg,
                comm.ranks[dst].address,
                time.monotonic() + self.retry_backoff_s,
            )

    # -- peer health (graceful degradation) ----------------------------------
    def _health_note(self, addr: str, event: str, dead: bool = False) -> None:
        h = self._health.setdefault(
            addr, {"state": "ok", "timeouts": 0, "failures": 0,
                   "last_event": ""}
        )
        old = h["state"]
        if event == "timeout":
            h["timeouts"] += 1
        else:
            h["failures"] += 1
        h["last_event"] = event
        # one timeout makes a peer suspect; repeated timeouts (2 strikes,
        # matching the XLA gang watchdog policy) or a hard failure mark it
        # dead — later collectives addressing it fail fast until a
        # soft_reset clears the verdict
        if dead or h["timeouts"] >= 2:
            h["state"] = "dead"
        elif h["state"] != "dead":
            h["state"] = "suspect"
        hook = self.on_health_transition
        if hook is not None and h["state"] != old:
            # the facade's transition hook: health-event ring + counter
            # and, under elastic membership, the dead->propose edge
            try:
                hook(addr, old, h["state"])
            except Exception:  # pragma: no cover - must never fail a call
                pass

    def health_report(self, comm: Communicator) -> Dict[int, dict]:
        """Per-peer health for ``comm``'s members, keyed by comm-relative
        rank (the graceful-degradation map of capabilities()["health"])."""
        report: Dict[int, dict] = {}
        for i, r in enumerate(comm.ranks):
            if i == comm.local_rank:
                continue
            h = self._health.get(r.address)
            report[i] = dict(h) if h else {
                "state": "ok", "timeouts": 0, "failures": 0, "last_event": ""
            }
        return report

    def _dead_peer_for(self, options: CallOptions) -> Optional[tuple]:
        """(rank, address) of a participating peer already marked dead, or
        None.  Only communicating ops are screened, and only against the
        peers the op actually addresses — local copy/combine/config must
        keep working next to a dead neighbor."""
        comm = options.comm
        if comm is None or options.op not in _COMM_OPS or not self._health:
            return None
        if options.op == Operation.SEND:
            candidates = [options.root_dst]
        elif options.op == Operation.RECV:
            candidates = [options.root_src]
        else:
            candidates = [r for r in range(comm.size) if r != comm.local_rank]
        for r in candidates:
            addr = comm.ranks[r].address
            h = self._health.get(addr)
            if h is not None and h["state"] == "dead":
                return r, addr
        return None

    def take_rndzv_init(self, pred: Callable[[Message], bool]):
        with self._notif_lock:
            for i, m in enumerate(self._rndzv_inits):
                if pred(m):
                    return self._rndzv_inits.pop(i)
        return None

    def take_rndzv_done(self, pred: Callable[[Message], bool]):
        with self._notif_lock:
            for i, m in enumerate(self._rndzv_done):
                if pred(m):
                    return self._rndzv_done.pop(i)
        return None

    def rx_seek_overflow(self, comm_id: int, src: int, tag: int, seqn: int):
        """Head-of-line escape for a fully parked pool.  When every rx slot
        holds eager segments for OTHER signatures — e.g. a rank that isn't
        a member of the current subcommunicator op racing ahead into the
        next collective and fire-hosing its segments first — the segment
        the CURRENT op needs waits in the unbounded inbox and could never
        be parked: a deadlock the multi-process soak caught.  Consume it
        straight from the inbox instead.  The pool stays the normal path
        (the gate below) so slot-lifecycle accounting keeps meaning; the
        reference's single shared link cannot reorder like this, but its
        seek loop + retry queue serve the same role of decoupling match
        order from arrival order (rxbuf_seek, dma_mover.cpp:587-611)."""
        used, total = self.rx_pool.occupancy()
        if used < total:
            return None  # pool has room: routing will park it normally
        msg = self.endpoint.take_matching(
            lambda m: (
                m.msg_type == MsgType.EAGER
                and m.comm_id == comm_id
                and m.src == src
                and m.tag == tag
                and m.seqn == seqn
            )
        )
        if msg is None:
            return None
        # inbox-consumed segments still join the dedup ledger and get
        # acked, exactly like the pool path, so retransmits/duplicates of
        # them are discarded instead of leaking into the pool later
        self._ledger.seen((msg.comm_id, msg.src, msg.epoch), msg.seqn)
        self._maybe_ack(msg)
        return RxBuffer(-1, len(msg.payload), RxStatus.CLAIMED, msg)

    def _maybe_ack(self, msg: Message) -> None:
        """ACK a delivered eager segment when the sender asked for one
        (retransmit protocol).  Duplicates are re-acked — the original ACK
        may have been the thing the network lost."""
        if not msg.ack or not msg.reply_to:
            return
        ack = Message(
            MsgType.ACK, msg.comm_id, msg.dst, msg.src, msg.tag,
            seqn=msg.seqn, epoch=msg.epoch,
        )
        try:
            self.fabric.send(msg.reply_to, ack)
        except Exception:
            pass  # a dead/fault-dropped ack path: the sender's backoff rules

    # -- debug dumps (ref ACCL::dump_eager_rx_buffers) -----------------------
    def dump_rx_buffers(self) -> str:
        return "\n".join(self.rx_pool.dump())

    def telemetry_report(self) -> dict:
        """Emulator-tier counters for the telemetry snapshot: rx-pool
        depth, inbox backlog, the recovery protocol's live window and
        event totals, and the armed fault plan's fire counters."""
        used, total = self.rx_pool.occupancy()
        inj = getattr(self.fabric, "fault_injector", None)
        return {
            "device_interactions": None,
            "rx_pool": {"used": used, "total": total},
            "inbox_depth": self.endpoint.pending(),
            "retransmit_window": len(self._retrans),
            "retransmits_total": self._retransmits_total,
            "dedup_discards_total": self._dedup_discards_total,
            "membership_drops_total": self._mbr_drops,
            "retry_limit": self.retry_limit,
            "inflight_window": self.inflight_window,
            # QoS arbiter plane: the engine-side tenant quota mirror
            "tenants": {str(k): dict(v) for k, v in
                        sorted(self.tenants.items())},
            "faults": inj.stats() if inj is not None else None,
            # monitor plane: how this rank's straggler samples reach
            # its peers (board = shared in-process judge, wire = the
            # per-message piggyback on the socket fabric)
            "skew_exchange": self.skew_exchange_mode(),
            # topology plane: per-link-class byte/message counters +
            # modeled rates (shared across ranks on the in-proc
            # fabric; None until a topology registers)
            "wire_classes": (
                self.fabric.wire_class_stats()
                if getattr(self.fabric, "_topologies", None)
                else None
            ),
        }

    # -- scheduler ----------------------------------------------------------
    def _route_inbox(self) -> None:
        """Move arrived messages to their stations (the rxbuf_enqueue/dequeue
        + depacketizer-routing roles).  EAGER messages stay in the inbox while
        the pool is exhausted — backpressure, not drop."""
        while True:
            routed_any = False
            msg = self.endpoint.take_matching(
                lambda m: m.msg_type != MsgType.EAGER
            )
            if msg is not None:
                routed_any = True
                if msg.msg_type == MsgType.RNDZV_INIT:
                    with self._notif_lock:
                        self._rndzv_inits.append(msg)
                elif msg.msg_type == MsgType.RNDZV_WR_DONE:
                    with self._notif_lock:
                        self._rndzv_done.append(msg)
                elif msg.msg_type == MsgType.STREAM:
                    self.streams.push(msg.strm, msg.payload)
                elif msg.msg_type == MsgType.ACK:
                    # a peer confirmed an eager segment: retire it from
                    # the retransmit window (ack.src is the acking peer)
                    self._retrans.pop(
                        (msg.comm_id, msg.src, msg.epoch, msg.seqn), None
                    )
            used, total = self.rx_pool.occupancy()
            if used < total:
                emsg = self.endpoint.take_matching(
                    lambda m: m.msg_type == MsgType.EAGER
                )
                if emsg is not None:
                    routed_any = True
                    floor = self._mbr_floor.get(emsg.comm_id)
                    if floor is not None and emsg.mbr < floor:
                        # a pre-shrink straggler frame on a SHRUNK comm
                        # (the sender's membership epoch lags the
                        # cutover floor): discard — its chunk geometry
                        # belongs to the aborted collective and seqn
                        # matching would hand it to the first
                        # post-shrink receive.  Comm-scoped: traffic on
                        # communicators that never shrank keeps flowing
                        # whatever the sender's global epoch says.
                        self._mbr_drops += 1
                        continue
                    self._maybe_ack(emsg)
                    if not self._ledger.seen(
                        (emsg.comm_id, emsg.src, emsg.epoch), emsg.seqn
                    ):
                        self.rx_pool.fill(emsg, timeout=0)
                    else:
                        # duplicate (fault-injected or a retransmit whose
                        # original arrived) — re-acked above, then
                        # discarded so it can never occupy a pool slot
                        self._dedup_discards_total += 1
            if not routed_any:
                return

    @staticmethod
    def _rank_of_address(options: Optional[CallOptions],
                         addr: Optional[str]) -> Optional[int]:
        """Comm-relative rank behind a transport address, or None."""
        if options is None or options.comm is None or addr is None:
            return None
        for i, r in enumerate(options.comm.ranks):
            if r.address == addr:
                return i
        return None

    def _task_context(self, task: _CallTask, peer=None, attempts=None) -> dict:
        """Structured ACCLError context for a failed call (op, comm, peer,
        attempts, elapsed) — the diagnosable trail the chaos tests assert."""
        ctx = {
            "op": task.request.op_name,
            "elapsed_s": round(
                (time.perf_counter_ns() - task.started_ns) / 1e9, 3
            ),
        }
        if task.options is not None and task.options.comm is not None:
            ctx["comm"] = task.options.comm.id
        if peer is not None:
            ctx["peer"] = peer
        if attempts is not None:
            ctx["attempts"] = attempts
        return ctx

    def _service_retransmits(self, now: float) -> None:
        """Re-send unacked eager segments past their backoff deadline;
        exponential backoff doubles per attempt.  Retry exhaustion marks
        the peer dead — the graceful-degradation path that turns a
        blackholed link into fast failures instead of hangs."""
        if not self._retrans:
            return
        for key, ent in list(self._retrans.items()):
            if now < ent.due:
                continue
            if ent.attempts >= self.retry_limit:
                del self._retrans[key]
                self._health_note(ent.address, "retry_exhausted", dead=True)
                continue
            ent.attempts += 1
            ent.due = now + self.retry_backoff_s * (2 ** ent.attempts)
            self._retransmits_total += 1
            try:
                self.fabric.send(ent.address, ent.msg)
            except (PeerDeadError, KeyError, OSError):
                del self._retrans[key]
                self._health_note(ent.address, "peer_dead", dead=True)

    def _run(self) -> None:
        active: List[_CallTask] = []
        while not self._stop:
            while True:
                # cutover purges strictly precede any intake item
                # queued after them (the marker is appended before the
                # facade returns from _apply_cutover, hence before its
                # first post-shrink collective is queued)
                self._drain_membership_cutovers()
                item = self._queue.pop(timeout=0)
                if item is None:
                    break
                req, options = item
                req.mark_executing()
                verdict = self._contract_verdict_for(options)
                if verdict is not None:
                    # the contract verifier proved this communicator's
                    # ranks diverged: fail at intake instead of burning
                    # the call deadline on traffic that cannot match
                    req.complete(
                        ErrorCode.CONTRACT_VIOLATION, 0,
                        context=verdict_context(verdict, options.op.name),
                    )
                    continue
                mv = self.membership
                if (
                    mv is not None and mv.self_evicted
                    and options.op in _COMM_OPS and options.comm is not None
                ):
                    # this rank was voted out of the group: every comm
                    # op fails fast with the agreement evidence (local
                    # copy/combine/config keep working)
                    req.complete(ErrorCode.RANK_EVICTED, 0, context={
                        "op": options.op.name,
                        "comm": options.comm.id,
                        "membership": mv.evidence(),
                        "elapsed_s": 0.0,
                    })
                    continue
                dead = self._dead_peer_for(options)
                if dead is not None:
                    # fail fast: the peer is already known dead — don't
                    # burn the full call deadline discovering it again
                    rank_d, addr = dead
                    code = (
                        ErrorCode.RECEIVE_TIMEOUT
                        if options.op == Operation.RECV
                        else ErrorCode.SEND_TIMEOUT
                    )
                    code, extra = self._membership_failure(
                        options, rank_d, code
                    )
                    h = self._health.get(addr, {})
                    req.complete(code, 0, context=dict({
                        "op": options.op.name,
                        "comm": options.comm.id,
                        "peer": addr,
                        "attempts": h.get("failures", 0),
                        "elapsed_s": 0.0,
                    }, **extra))
                    continue
                evicted = (
                    self._evicted_peer_for(options)
                    if mv is not None else None
                )
                if evicted is not None:
                    # the surviving majority agreed this peer is out
                    # (possibly before local health caught up): the
                    # structured terminal, carrying the evidence
                    req.complete(ErrorCode.RANK_EVICTED, 0, context={
                        "op": options.op.name,
                        "comm": options.comm.id,
                        "peer": options.comm.ranks[evicted].address,
                        "membership": mv.evidence(),
                        "elapsed_s": 0.0,
                    })
                    continue
                gen = algorithms.dispatch(self, options)
                active.append(_CallTask(req, gen, self.timeout_s, options))

            self._route_inbox()
            self._service_retransmits(time.monotonic())

            cv = self.contract_verifier
            if cv is not None and cv.has_verdict and active:
                # a divergence verdict landed (boundary exchange or a
                # peer's piggybacked claim) while calls are in flight:
                # those calls' traffic can never match — fail them fast
                # instead of letting each burn its full deadline
                for task in list(active):
                    verdict = self._contract_verdict_for(task.options)
                    if verdict is None:
                        continue
                    task.gen.close()
                    task.request.complete(
                        ErrorCode.CONTRACT_VIOLATION,
                        time.perf_counter_ns() - task.started_ns,
                        context=verdict_context(
                            verdict, task.request.op_name
                        ),
                    )
                    active.remove(task)

            mv = self.membership
            if mv is not None and active and (
                mv.cutover_ready() or mv.self_evicted
            ):
                # a confirmed eviction landed while calls are in
                # flight: work addressing the evicted rank can never
                # complete — fail it fast with the agreement evidence
                # instead of letting each call burn its deadline
                for task in list(active):
                    if task.options is None:
                        continue
                    hit = (
                        mv.self_evicted
                        and task.options.op in _COMM_OPS
                        and task.options.comm is not None
                    ) or self._evicted_peer_for(task.options) is not None
                    if not hit:
                        continue
                    task.gen.close()
                    task.request.complete(
                        ErrorCode.RANK_EVICTED,
                        time.perf_counter_ns() - task.started_ns,
                        context=dict(
                            self._task_context(task),
                            membership=mv.evidence(),
                        ),
                    )
                    active.remove(task)

            progressed = False
            now = time.monotonic()
            for task in list(active):
                value = None
                if task.cond is not None:
                    value = task.cond.poll(self)
                    if value is None:
                        if now > task.deadline:
                            peer = getattr(task.cond, "peer_addr", None)
                            if peer is not None:
                                self._health_note(peer, "timeout")
                            code = task.cond.timeout_code
                            ctx = self._task_context(task, peer=peer)
                            peer_rank = self._rank_of_address(
                                task.options, peer
                            )
                            code, extra = self._membership_failure(
                                task.options, peer_rank, code
                            )
                            ctx.update(extra)
                            task.request.complete(
                                code,
                                time.perf_counter_ns() - task.started_ns,
                                context=ctx,
                            )
                            active.remove(task)
                            progressed = True
                        continue
                    task.cond = None
                try:
                    task.cond = task.gen.send(value)
                    progressed = True
                except StopIteration as stop:
                    ret = stop.value if stop.value is not None else ErrorCode.OK
                    task.request.complete(
                        ret, time.perf_counter_ns() - task.started_ns
                    )
                    active.remove(task)
                    progressed = True
                except PeerDeadError as dead_exc:
                    # a send hit a dead/detached endpoint: fast, diagnosable
                    # SEND_TIMEOUT (the silent-drop fix of fabric.py:222) —
                    # or RANK_EVICTED when the group already agreed the
                    # peer is out (membership plane)
                    ctx = self._task_context(task, peer=dead_exc.address)
                    code, extra = self._membership_failure(
                        task.options,
                        self._rank_of_address(
                            task.options, dead_exc.address
                        ),
                        ErrorCode.SEND_TIMEOUT,
                    )
                    ctx.update(extra)
                    task.request.complete(
                        code,
                        time.perf_counter_ns() - task.started_ns,
                        context=ctx,
                    )
                    active.remove(task)
                    progressed = True
                except Exception:
                    traceback.print_exc()
                    task.request.complete(
                        ErrorCode.INVALID_OPERATION,
                        time.perf_counter_ns() - task.started_ns,
                    )
                    active.remove(task)
                    progressed = True

            if not progressed:
                timeout = 0.001 if active else 0.05
                if self._retrans:
                    timeout = min(timeout, self.retry_backoff_s / 2)
                self._wake.wait(timeout=timeout)
                self._wake.clear()

        self._queue.close()

    # -- config ops (Operation.CONFIG) --------------------------------------
    def apply_config(self, options: CallOptions) -> ErrorCode:
        fn = ConfigFunction(options.cfg_function)
        val = options.cfg_value
        if fn == ConfigFunction.RESET:
            with self._notif_lock:
                self._rndzv_inits.clear()
                self._rndzv_done.clear()
            self.transport_enabled = False
            if val >= 1:
                # FULL reset (soft_reset recovery, never plain init — a
                # flush at init would race the socket tier's pre-attach
                # replay and drop fast peers' first segments): abandon all
                # stale wire state so a group that lost a collective to a
                # fault can realign
                self.rx_pool.reset()
                self.endpoint.clear()
                self._retrans.clear()
                self._ledger.clear()
                self._health.clear()
                # membership restore rides soft_reset: the stale-frame
                # fence floors belong to the pre-reset epochs (runs on
                # the scheduler thread, like the rest of the flush)
                self._mbr_floor.clear()
                with self._notif_lock:
                    self._mbr_cutovers.clear()
        elif fn == ConfigFunction.ENABLE_TRANSPORT:
            self.transport_enabled = True
        elif fn == ConfigFunction.SET_RETRY_LIMIT:
            if not 0 <= val <= MAX_RETRY_LIMIT:
                return ErrorCode.CONFIG_ERROR
            self.retry_limit = int(val)
        elif fn == ConfigFunction.SET_RETRY_BACKOFF:
            if val <= 0:
                return ErrorCode.CONFIG_ERROR
            self.retry_backoff_s = float(val)
        elif fn == ConfigFunction.SET_TIMEOUT:
            if val <= 0:
                return ErrorCode.CONFIG_ERROR
            self.timeout_s = float(val)
        elif fn == ConfigFunction.SET_MAX_EAGER_SIZE:
            if not 0 < val <= MAX_EAGER_SIZE_LIMIT:
                return ErrorCode.CONFIG_ERROR
            self.max_eager_size = int(val)
        elif fn == ConfigFunction.SET_MAX_RENDEZVOUS_SIZE:
            if val <= 0:
                return ErrorCode.CONFIG_ERROR
            self.max_rendezvous_size = int(val)
        elif fn == ConfigFunction.SET_INFLIGHT_WINDOW:
            from ...constants import MAX_INFLIGHT_WINDOW

            if not 1 <= val <= MAX_INFLIGHT_WINDOW:
                return ErrorCode.CONFIG_ERROR
            self.inflight_window = int(val)
        elif fn in (
            ConfigFunction.SET_TENANT_CLASS,
            ConfigFunction.SET_TENANT_WEIGHT,
            ConfigFunction.SET_TENANT_WINDOW_SHARE,
            ConfigFunction.SET_TENANT_RING_SLOTS,
            ConfigFunction.SET_TENANT_RATE,
        ):
            # QoS arbiter plane: this tier has no device window or ring
            # — enforcement lives in the facade's shared arbiter, which
            # bounds a tenant's outstanding admissions by its window
            # share.  ONE shared validator (arbiter.tenant_config_valid)
            # so a write accepted here can never be CONFIG_ERROR on
            # another tier.
            from ...arbiter import tenant_config_field, tenant_config_valid

            if not tenant_config_valid(fn, val):
                return ErrorCode.CONFIG_ERROR
            self.tenants.setdefault(
                int(options.cfg_key), {}
            )[tenant_config_field(fn)] = val
        elif fn == ConfigFunction.SET_TUNING:
            from ...constants import (
                ALGORITHM_TUNING_KEYS,
                AllreduceAlgorithm,
                ROOTED_ALGORITHMS,
                TUNING_KEY_NAMES,
                TuningKey,
            )

            try:
                key = TuningKey(int(options.cfg_key))
            except ValueError:
                return ErrorCode.CONFIG_ERROR
            if val < 0:
                return ErrorCode.CONFIG_ERROR
            # per-key validation matches the XLA/native tiers so code
            # validated against the emulator doesn't skew on device
            if key == TuningKey.GATHER_FLAT_TREE_MAX_FANIN and val < 1:
                return ErrorCode.CONFIG_ERROR
            if key == TuningKey.RING_SEGMENTS and val < 1:
                return ErrorCode.CONFIG_ERROR
            if key in (
                TuningKey.WIRE_DTYPE,
                TuningKey.WIRE_DTYPE_ICI,
                TuningKey.WIRE_DTYPE_DCN,
            ) and int(val) != 0:
                from ...wire import is_wire_dtype

                if not is_wire_dtype(int(val)):
                    return ErrorCode.CONFIG_ERROR
            if key == TuningKey.HIERARCHICAL and int(val) > 1:
                return ErrorCode.CONFIG_ERROR
            if key in ALGORITHM_TUNING_KEYS:
                try:
                    algo = AllreduceAlgorithm(int(val))
                except ValueError:
                    return ErrorCode.CONFIG_ERROR
                if (
                    key != TuningKey.ALLREDUCE_ALGORITHM
                    and algo not in ROOTED_ALGORITHMS
                ):
                    return ErrorCode.CONFIG_ERROR
            # device-tier registers (algorithm select) are accepted and
            # stored but don't affect the emulated firmware algorithms
            self.tuning[TUNING_KEY_NAMES[key]] = int(val)
        else:
            return ErrorCode.CONFIG_ERROR
        return ErrorCode.OK
