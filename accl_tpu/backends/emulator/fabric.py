"""The emulated wire: message format and transports between rank engines.

Role model: the reference's ``eth_intf`` message header {count, tag, src,
seqn, strm, dst, msg_type, host, vaddr} (``kernels/cclo/hls/eth_intf/
eth_intf.h:114-151``) and the emulator's ZMQ pub/sub "ethernet"
(``test/model/zmq/zmq_server.h:39-45``).  Two transports:

* ``InProcFabric`` — rank engines in one process, per-rank thread-safe
  inboxes.  This is the CI workhorse tier.
* ``SocketFabric`` — one process per rank, length-prefixed messages over TCP
  sockets (the multi-process tier, mirroring the reference's one-emulator-
  process-per-rank layout).

Message types follow the reference wire protocol (``eth_intf.h:42-45``):
EAGER data messages, rendezvous INIT (address exchange) and WR_DONE
(completion notification).  Rendezvous data is a one-sided write: the fabric
delivers it straight into pre-registered receiver memory, then surfaces a
WR_DONE notification — mirroring an RDMA WRITE executed by the NIC with no
receiver-CPU involvement (``dummy_cyt_rdma_stack``).
"""

from __future__ import annotations

import dataclasses
import enum
import pickle
import socket
import struct
import sys
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional

from ...faults import FaultInjector, FaultPlan, PeerDeadError
from ...utils.logging import Log, LogLevel

# Per-message wire tracing (ACCL_DEBUG=TRACE): events route through the
# telemetry plane's buffered ring (accl_tpu.telemetry.wire_event) instead
# of synchronous stderr writes, so tracing no longer perturbs the
# timings being traced; ACCL_TRACE_STDERR=1 opts the stderr sink back in.
# One level compare per send when tracing is off.
_WIRE_LOG = Log("wire")


class MsgType(enum.IntEnum):
    EAGER = 0  # tag/seqn-matched segment into an RX buffer
    RNDZV_INIT = 2  # receiver announces a writable address
    RNDZV_WR_DONE = 3  # write completed into receiver memory
    RNDZV_DATA = 4  # the one-sided write itself (fabric-internal)
    STREAM = 5  # routed directly to a device stream port
    ACK = 6  # eager-segment delivery acknowledgment (retransmit protocol)
    VERIFY = 7  # contract-plane verdict relay (JSON payload): a rank
    # that convicted a divergence tells its peers so their in-flight
    # calls fail fast too instead of waiting out the engine deadline
    MEMBER = 8  # membership-plane agreement frame (JSON payload): the
    # shrink protocol's propose/confirm exchange on one-process-per-
    # rank fabrics (board-anchored tiers exchange in process instead)
    POSTMORTEM = 9  # postmortem-bundle solicitation (JSON payload): a
    # failing rank asks its peers for their evidence tails and peers
    # reply best-effort within the requester's bounded deadline
    # (board-anchored tiers solicit in process instead)


@dataclasses.dataclass
class Message:
    msg_type: MsgType
    comm_id: int
    src: int  # sender rank within the communicator
    dst: int  # destination rank within the communicator
    tag: int
    seqn: int = 0
    vaddr: int = 0  # rendezvous buffer token
    count: int = 0  # payload bytes (redundant w/ len(payload), kept for parity)
    strm: int = 0  # stream id for MsgType.STREAM
    payload: bytes = b""
    ack: int = 0  # 1 = sender requests an ACK (retransmit protocol armed)
    reply_to: str = ""  # sender's fabric address for ACKs
    csum: int = 0  # crc32 of payload; stamped by the fabric on first send
    epoch: int = 0  # sender's communicator-instance epoch (seqn dedup scope)
    # contract plane (accl_tpu.contract, ACCL_VERIFY=1): the sender's
    # latest completed verification window piggybacks on every message —
    # three ints of header, zero extra traffic.  vfy_window -1 = no
    # stamp (verifier off or no window completed yet).
    vfy_gen: int = 0
    vfy_window: int = -1
    vfy_digest: int = 0
    # monitor plane (accl_tpu.monitor): the sender's latest completed
    # straggler-skew window (window index + mean wait in us) rides the
    # same piggyback cadence — two header fields, zero extra traffic.
    # skw_window -1 = no stamp (monitor off or no window completed).
    skw_window: int = -1
    skw_mean_us: float = 0.0
    # membership plane (accl_tpu.membership): the sender's membership
    # EPOCH — globally aligned by the eviction agreement (unlike the
    # process-local communicator epochs), so receivers can discard
    # stale pre-shrink frames still in flight at cutover (seqn matching
    # ignores epochs; a stale chunk of the aborted collective would
    # otherwise corrupt the first post-shrink collective's receives)
    mbr: int = 0
    # send wall-timestamp (time_ns; 0 = unstamped): receivers measure
    # per-source arrival latency from it — the straggler analyzer's
    # direct observable of a slow sender/link.  Wall clock because it
    # is the only clock two processes share; cross-host skew is
    # whatever NTP leaves (same-host fabrics are exact).
    sent_ns: int = 0
    # causal trace plane (accl_tpu.telemetry): the sender's CURRENT
    # collective trace id piggybacks on every message (one int; same
    # one-probe-per-send discipline as vfy_/skw_) — receivers record a
    # wire-hop flow step, so a merged timeline links send→recv across
    # processes.  0 = unstamped (flows off, or no call in flight).
    trc: int = 0


class Endpoint:
    """Receiving side of a rank: inbox + rendezvous write registry.

    The engine registers writable memory under a vaddr token; incoming
    RNDZV_DATA is copied there by the fabric (the "NIC") and converted into a
    WR_DONE notification in the inbox.
    """

    def __init__(self, deliver_cb: Optional[Callable[[Message], None]] = None):
        self._lock = threading.Lock()
        self._inbox: List[Message] = []
        self._wr_registry: Dict[int, memoryview] = {}
        self._deliver_cb = deliver_cb
        self.on_activity: Optional[Callable[[], None]] = None
        # contract plane: the receiving rank's verifier hook — observes
        # peers' piggybacked digest claims on every delivered message
        self.contract_hook: Optional[Callable[[Message], None]] = None
        # monitor plane: the receiving rank's skew hook — observes
        # peers' piggybacked straggler-window claims the same way
        self.skew_hook: Optional[Callable[[Message], None]] = None
        # membership plane: the receiving rank's agreement hook —
        # observes MEMBER propose/confirm frames at delivery
        self.membership_hook: Optional[Callable[[Message], None]] = None
        # postmortem plane: the receiving rank's solicitation hook —
        # observes POSTMORTEM request/reply frames at delivery (frames
        # are consumed here, never parked in the inbox: they carry no
        # collective matching signature)
        self.postmortem_hook: Optional[Callable[[Message], None]] = None
        # wire-integrity accounting: payloads whose crc32 no longer matches
        # the stamped csum are discarded here (the rx dataplane's bit-error
        # detection; the sender's retransmit protocol recovers them)
        self.corrupt_drops = 0

    def register_write_target(self, vaddr: int, mem: memoryview) -> None:
        with self._lock:
            self._wr_registry[vaddr] = mem

    def deliver(self, msg: Message) -> None:
        if msg.payload and msg.csum and zlib.crc32(msg.payload) != msg.csum:
            with self._lock:
                self.corrupt_drops += 1
                if msg.msg_type == MsgType.RNDZV_DATA:
                    # the one-sided write can never complete now (there is
                    # no rendezvous retransmit; the receiver will time out)
                    # — drop the write target so the registry doesn't pin
                    # the buffer forever
                    self._wr_registry.pop(msg.vaddr, None)
            if self.on_activity is not None:
                self.on_activity()
            return
        # contract hook AFTER the csum guard: a corrupt-fault frame is
        # discarded above and must never be consumed as a digest claim
        # or a relayed VERIFY verdict
        hook = self.contract_hook
        if hook is not None and (
            msg.vfy_window >= 0 or msg.msg_type == MsgType.VERIFY
        ):
            try:
                hook(msg)  # a verifier failure must never drop traffic
            except Exception:  # pragma: no cover - defensive
                pass
        mhook = self.membership_hook
        if mhook is not None and msg.msg_type == MsgType.MEMBER:
            # after the csum guard like the contract hook: a corrupt
            # frame must never be consumed as an agreement vote
            try:
                mhook(msg)
            except Exception:  # pragma: no cover - defensive
                pass
        shook = self.skew_hook
        if shook is not None and (msg.skw_window >= 0 or msg.sent_ns):
            # after the csum guard like the contract hook: a corrupt
            # frame's skew claim must not poison the judge
            try:
                shook(msg)
            except Exception:  # pragma: no cover - defensive
                pass
        if msg.trc:
            # causal trace plane: a piggybacked trace id records one
            # wire-hop flow step (sampled bounded ring — never raises,
            # never drops traffic)
            try:
                from ...telemetry import wire_flow

                wire_flow(msg.trc, msg.src, msg.dst, msg.comm_id)
            except Exception:  # pragma: no cover - defensive
                pass
        if msg.msg_type == MsgType.POSTMORTEM:
            phook = self.postmortem_hook
            if phook is not None:
                try:
                    phook(msg)
                except Exception:  # pragma: no cover - defensive
                    pass
            if self.on_activity is not None:
                self.on_activity()
            return
        if msg.msg_type == MsgType.RNDZV_DATA:
            with self._lock:
                mem = self._wr_registry.pop(msg.vaddr)
            mem[: len(msg.payload)] = msg.payload
            done = Message(
                MsgType.RNDZV_WR_DONE,
                msg.comm_id,
                msg.src,
                msg.dst,
                msg.tag,
                vaddr=msg.vaddr,
                count=msg.count,
            )
            self._push(done)
        else:
            self._push(msg)

    def _push(self, msg: Message) -> None:
        with self._lock:
            self._inbox.append(msg)
        if self._deliver_cb is not None:
            self._deliver_cb(msg)
        if self.on_activity is not None:
            self.on_activity()

    def take_matching(self, pred: Callable[[Message], bool]) -> Optional[Message]:
        """Remove and return the first inbox message satisfying ``pred``."""
        with self._lock:
            for i, m in enumerate(self._inbox):
                if pred(m):
                    return self._inbox.pop(i)
        return None

    def pending(self) -> int:
        with self._lock:
            return len(self._inbox)

    def clear(self) -> int:
        """Drop every parked message and stale rendezvous write targets
        (soft-reset recovery); returns the number of messages discarded."""
        with self._lock:
            n = len(self._inbox)
            self._inbox.clear()
            self._wr_registry.clear()
            return n


class Fabric:
    """Abstract transport: address -> endpoint delivery.

    The base class owns the chaos-plane hook: :meth:`send` stamps the wire
    checksum, consults the installed :class:`FaultInjector` (drop / delay /
    duplicate / corrupt / kill / partition), then hands surviving copies to
    the transport's :meth:`_transmit`."""

    _injector: Optional[FaultInjector] = None
    _delay_lock: Optional[threading.Lock] = None
    #: modeled link rate in bytes/s (None = unpaced, the default): the
    #: emulated wire's bandwidth model.  The in-process transports move
    #: frames at memcpy speed (~10 GB/s), which is no wire at all — a
    #: compression sweep measured there reads codec cost only.  With a
    #: rate set (``set_wire_rate``; the autotuner's ``--wire-gbps``),
    #: every transmit pays payload_bytes/rate of wall clock, serialized
    #: per sender like a real NIC — deterministic, byte-proportional,
    #: honest about WHAT is being measured.
    _wire_rate_Bps: Optional[float] = None

    def set_wire_rate(self, gbps: Optional[float]) -> None:
        """Model the link at ``gbps`` gigabits/s (None disables)."""
        self._wire_rate_Bps = (
            None if not gbps else float(gbps) * 1e9 / 8.0
        )

    # -- topology plane (accl_tpu.topology): two-class paced model ----------
    #: per-link-class modeled rates in bytes/s (the two-tier wire: fast
    #: ICI within a slice, slow DCN across).  None entries fall back to
    #: the single-class ``_wire_rate_Bps`` (which may itself be None =
    #: unpaced).  Classification consults the topology registered per
    #: communicator — comm-relative rank spaces, consistent because
    #: each registered topology lives in its own comm's space.
    _ici_rate_Bps: Optional[float] = None
    _dcn_rate_Bps: Optional[float] = None

    def set_wire_rates(self, ici_gbps: Optional[float] = None,
                       dcn_gbps: Optional[float] = None) -> None:
        """Model the two link classes separately (gigabits/s; None
        disables that class's override)."""
        self._ici_rate_Bps = (
            None if not ici_gbps else float(ici_gbps) * 1e9 / 8.0
        )
        self._dcn_rate_Bps = (
            None if not dcn_gbps else float(dcn_gbps) * 1e9 / 8.0
        )

    def register_topology(self, comm_id: int, topology) -> None:
        """Attach (or with ``None`` detach) the slice descriptor for one
        communicator's rank space — the send path classifies (and
        counts) every wire byte of that comm as ICI vs DCN with one
        dict probe, the contract/skew/trace stamp discipline."""
        topos = getattr(self, "_topologies", None)
        if topos is None:
            topos = self._topologies = {}
            self._class_lock = threading.Lock()
            self._class_bytes = {"ici": 0, "dcn": 0, "loopback": 0,
                                 "unclassified": 0}
            self._class_msgs = {"ici": 0, "dcn": 0, "loopback": 0,
                                "unclassified": 0}
        if topology is None:
            topos.pop(comm_id, None)
        else:
            topos[comm_id] = topology

    def _link_class_of(self, msg: "Message") -> str:
        topos = getattr(self, "_topologies", None)
        if not topos:
            return "unclassified"
        topo = topos.get(msg.comm_id)
        if topo is None:
            return "unclassified"
        try:
            cls = topo.link_class(msg.src, msg.dst)
        except KeyError:
            return "unclassified"
        return cls.name.lower()

    def wire_class_stats(self) -> dict:
        """Per-link-class byte/message counters + the modeled rates —
        the telemetry evidence tests/test_topology.py counter-asserts
        (hierarchical must cut DCN bytes by ~the slice factor)."""
        lock = getattr(self, "_class_lock", None)
        if lock is None:
            bytes_, msgs = {}, {}
        else:
            with lock:
                bytes_ = dict(self._class_bytes)
                msgs = dict(self._class_msgs)
        return {
            "bytes": bytes_,
            "messages": msgs,
            "rates_gbps": {
                "ici": (
                    None if self._ici_rate_Bps is None
                    else self._ici_rate_Bps * 8.0 / 1e9
                ),
                "dcn": (
                    None if self._dcn_rate_Bps is None
                    else self._dcn_rate_Bps * 8.0 / 1e9
                ),
                "default": (
                    None if self._wire_rate_Bps is None
                    else self._wire_rate_Bps * 8.0 / 1e9
                ),
            },
        }

    def reset_wire_class_stats(self) -> None:
        lock = getattr(self, "_class_lock", None)
        if lock is not None:
            with lock:
                for k in self._class_bytes:
                    self._class_bytes[k] = 0
                    self._class_msgs[k] = 0

    def _pace(self, msg: "Message") -> None:
        rate = self._wire_rate_Bps
        if getattr(self, "_topologies", None):
            cls = self._link_class_of(msg)
            with self._class_lock:
                self._class_bytes[cls] += len(msg.payload)
                self._class_msgs[cls] += 1
            if cls == "ici" and self._ici_rate_Bps is not None:
                rate = self._ici_rate_Bps
            elif cls == "dcn" and self._dcn_rate_Bps is not None:
                rate = self._dcn_rate_Bps
            elif cls == "loopback":
                rate = None  # self-delivery is never paced
        if rate and msg.payload:
            time.sleep(len(msg.payload) / rate)

    def install_fault_plan(self, plan: Optional[FaultPlan]) -> Optional[FaultInjector]:
        """Arm (or with ``None``, disarm) a fault plan on this fabric."""
        if self._delay_lock is None:
            # the ordered-delay state is created HERE (setup time,
            # single-threaded) rather than lazily on the send path: two
            # senders racing a lazy first-touch could each build their
            # own queue dict and orphan one side's delayed frames
            self._delay_lock = threading.Lock()
            self._delayed = {}
        self._injector = FaultInjector(plan) if plan is not None else None
        return self._injector

    @property
    def fault_injector(self) -> Optional[FaultInjector]:
        return self._injector

    # -- contract plane (accl_tpu.contract) ----------------------------------
    def register_contract(self, comm_id: int, rank: int, verifier) -> None:
        """Arm outbound digest stamping for (communicator, sending rank):
        the send path piggybacks ``verifier.stamp(comm_id)`` onto every
        message that rank sends on that communicator."""
        stamps = getattr(self, "_contract_stamps", None)
        if stamps is None:
            stamps = self._contract_stamps = {}
        stamps[(comm_id, rank)] = verifier

    def unregister_contract(self, verifier) -> None:
        stamps = getattr(self, "_contract_stamps", None)
        if stamps:
            for key in [k for k, v in stamps.items() if v is verifier]:
                del stamps[key]

    # -- monitor plane (accl_tpu.monitor) ------------------------------------
    def register_skew(self, comm_id: int, rank: int, tracker) -> None:
        """Arm outbound straggler-skew stamping for (communicator,
        sending rank): the send path piggybacks ``tracker.stamp(
        comm_id)`` — the latest completed (window, mean_wait) — onto
        every message that rank sends on that communicator, exactly
        like the contract digest stamp."""
        stamps = getattr(self, "_skew_stamps", None)
        if stamps is None:
            stamps = self._skew_stamps = {}
        stamps[(comm_id, rank)] = tracker

    def unregister_skew(self, tracker) -> None:
        stamps = getattr(self, "_skew_stamps", None)
        if stamps:
            for key in [k for k, v in stamps.items() if v is tracker]:
                del stamps[key]

    # -- causal trace plane (accl_tpu.telemetry flows) ------------------------
    def register_trace(self, comm_id: int, rank: int, provider) -> None:
        """Arm outbound trace-id stamping for (communicator, sending
        rank): the send path piggybacks ``provider.trace_stamp(
        comm_id)`` — the id assigned to that rank's latest collective
        intake — onto every message it sends on the communicator,
        exactly like the contract/skew stamps.  Best-effort by design:
        a message of call k+1 racing call k's tail is window-grade
        attribution, same as the skew stamp."""
        stamps = getattr(self, "_trace_stamps", None)
        if stamps is None:
            stamps = self._trace_stamps = {}
        stamps[(comm_id, rank)] = provider

    def unregister_trace(self, provider) -> None:
        stamps = getattr(self, "_trace_stamps", None)
        if stamps:
            for key in [k for k, v in stamps.items() if v is provider]:
                del stamps[key]

    def attach(self, address: str, endpoint: Endpoint) -> None:
        raise NotImplementedError

    def send(self, address: str, msg: Message) -> None:
        if _WIRE_LOG.level >= LogLevel.TRACE:
            _WIRE_LOG.trace(
                f"send {msg.msg_type.name} comm={msg.comm_id} "
                f"src={msg.src} dst={msg.dst} tag={msg.tag} "
                f"seqn={msg.seqn} bytes={len(msg.payload)} -> {address}"
            )
        stamps = getattr(self, "_contract_stamps", None)
        if stamps:
            # contract plane piggyback: stamp the sending rank's latest
            # completed digest window onto the outgoing message (one
            # dict probe when verification is armed, one getattr when
            # not — the ~0%-off budget)
            verifier = stamps.get((msg.comm_id, msg.src))
            if verifier is not None:
                msg.vfy_gen, msg.vfy_window, msg.vfy_digest = (
                    verifier.stamp(msg.comm_id)
                )
        skews = getattr(self, "_skew_stamps", None)
        if skews:
            # monitor plane piggyback: the sending rank's latest
            # completed skew window rides the same one-probe-per-send
            # discipline as the contract stamp above, plus the send
            # timestamp receivers measure arrival latency from
            tracker = skews.get((msg.comm_id, msg.src))
            if tracker is not None:
                msg.skw_window, msg.skw_mean_us = tracker.stamp(msg.comm_id)
                msg.sent_ns = time.time_ns()
        traces = getattr(self, "_trace_stamps", None)
        if traces:
            # causal trace piggyback: the sending rank's current
            # collective trace id (one dict probe when armed)
            provider = traces.get((msg.comm_id, msg.src))
            if provider is not None:
                msg.trc = provider.trace_stamp(msg.comm_id)
        self._pace(msg)  # modeled link rate (no-op when unpaced)
        inj = self._injector
        if inj is None:
            self._transmit(address, msg)
            return
        # checksums only matter when someone can corrupt the wire: the
        # fault-free hot path skips both the stamp and the verify
        # (delivery checks csum only when non-zero)
        if msg.payload and msg.csum == 0:
            msg.csum = zlib.crc32(msg.payload)
        v = inj.on_send(msg)
        if v.dead_dst:
            raise PeerDeadError(address)
        if v.drop:
            return
        if v.corrupt:
            # the csum keeps the ORIGINAL digest: the receiving dataplane
            # detects the bit error and discards the segment
            msg = dataclasses.replace(
                msg, payload=inj.corrupt_payload(msg.payload)
            )
        copies = 2 if v.duplicate else 1
        if v.delay_s > 0:
            self._delay_enqueue(address, msg, copies, v.delay_s)
        elif not self._delay_enqueue_if_pending(address, msg, copies):
            self._transmit_copies(address, msg, copies, False)

    # -- ordered delayed transmit --------------------------------------------
    # A congested link delays everything BEHIND the stalled frame — it
    # does not reorder.  The old Timer-per-message path let every later
    # send to the same peer overtake the delayed one, which on the
    # multi-rank socket tier (strictly seqn-consuming receivers, one
    # recv thread per link) wedged ranks into RECEIVE_TIMEOUT (the PR 8
    # pre-existing issue).  Delayed sends now park in a per-address FIFO
    # drained by one worker in order; while the queue exists, later
    # undelayed sends to that address queue behind it instead of
    # overtaking.  Other addresses are unaffected (per-peer ordering is
    # the wire's contract; cross-peer ordering never was).

    def _delay_state(self):
        # created by install_fault_plan (the only way an injector — and
        # so a delay verdict — can exist); never lazily on the send path
        return self._delay_lock, self._delayed

    def _delay_enqueue(self, address: str, msg: Message, copies: int,
                       delay_s: float) -> None:
        lock, delayed = self._delay_state()
        with lock:
            q = delayed.get(address)
            fresh = q is None
            if fresh:
                q = delayed[address] = []
            q.append((time.monotonic() + float(delay_s), msg, copies))
        if fresh:
            t = threading.Thread(
                target=self._drain_delayed, args=(address,),
                name=f"accl-fabric-delay-{address}", daemon=True,
            )
            t.start()

    def _delay_enqueue_if_pending(self, address: str, msg: Message,
                                  copies: int) -> bool:
        """Queue an UNDELAYED send behind the address's pending delayed
        frames (due immediately — no extra delay beyond head-of-line
        blocking); False when nothing is pending and the caller should
        transmit directly.  The probe and the append are one locked
        step, so a send can never observe the queue draining away and
        then append to an orphaned list."""
        lock, delayed = self._delay_state()
        with lock:
            q = delayed.get(address)
            if q is None:
                return False
            q.append((time.monotonic(), msg, copies))
            return True

    def _drain_delayed(self, address: str) -> None:
        """One worker per delayed address: transmit the FIFO in order,
        sleeping out each frame's residual delay; exits (and removes the
        queue, restoring the direct-send fast path) once empty.  Frames
        are popped only AFTER their transmit, so the queue stays
        non-empty — and later sends keep queuing behind — until the last
        pending frame is really on the wire."""
        lock, delayed = self._delay_state()
        while True:
            with lock:
                q = delayed.get(address)
                if not q:
                    delayed.pop(address, None)
                    return
                due, msg, copies = q[0]
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            try:
                try:
                    self._transmit_copies(address, msg, copies, False)
                except Exception as e:
                    # a queued frame has no caller to raise into, but
                    # the failure must not vanish silently: the sender
                    # believed the send succeeded.  Log loudly; the
                    # transports' own dead-marking (SocketFabric) makes
                    # the NEXT direct send fail fast.
                    print(
                        f"[accl fabric] delayed-queue transmit to "
                        f"{address} failed: {type(e).__name__}: {e}",
                        file=sys.stderr,
                    )
            finally:
                with lock:
                    q.pop(0)

    def _transmit_copies(
        self, address: str, msg: Message, copies: int, swallow: bool
    ) -> None:
        for _ in range(copies):
            try:
                self._transmit(address, msg)
            except Exception:
                if not swallow:  # delayed delivery has no caller to tell
                    raise

    def _transmit(self, address: str, msg: Message) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class InProcFabric(Fabric):
    """All ranks in one process; delivery is a direct endpoint call."""

    def __init__(self, fault_plan: Optional[FaultPlan] = None):
        self._endpoints: Dict[str, Endpoint] = {}
        self._dead: set = set()
        self._lock = threading.Lock()
        if fault_plan is not None:
            self.install_fault_plan(fault_plan)

    def attach(self, address: str, endpoint: Endpoint) -> None:
        with self._lock:
            if address in self._endpoints:
                raise ValueError(f"address {address} already attached")
            self._dead.discard(address)
            self._endpoints[address] = endpoint

    def detach(self, address: str) -> None:
        """Tear an endpoint out of the fabric (engine shutdown / simulated
        rank death): later sends to it fail fast with PeerDeadError instead
        of being silently dropped."""
        with self._lock:
            self._endpoints.pop(address, None)
            self._dead.add(address)

    def _transmit(self, address: str, msg: Message) -> None:
        with self._lock:
            if address in self._dead:
                raise PeerDeadError(address)
            ep = self._endpoints.get(address)
        if ep is None:
            raise KeyError(f"no endpoint at {address}")
        ep.deliver(msg)


class SocketFabric(Fabric):
    """One process per rank; messages are pickled with a u32 length prefix.

    Address format: ``"host:port"``.  Each fabric instance owns one listening
    socket (this rank's address) and lazily opened client connections to
    peers.  Mirrors the per-rank ZMQ endpoints of the reference emulator
    (``test/model/emulator/run.py``).
    """

    def __init__(self, bind_address: str):
        self._bind_address = bind_address
        self._endpoint: Optional[Endpoint] = None
        # the one-process-per-rank tier inherits its chaos plan from the
        # environment (FaultPlan.to_env -> ACCL_FAULT_PLAN in the spawner)
        env_plan = FaultPlan.from_env()
        if env_plan is not None:
            self.install_fault_plan(env_plan)
        # peers that had a live connection and then died: sends fail fast
        # with PeerDeadError instead of silently vanishing (or re-dialing
        # through the full startup grace period)
        self._dead: set = set()
        self._ever_connected: set = set()
        host, port = bind_address.rsplit(":", 1)
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, int(port)))
        self._listener.listen(64)
        self._conns: Dict[str, socket.socket] = {}
        self._accepted: list = []  # inbound conns; torn down on close()
        self._conn_lock = threading.Lock()
        # peers' dials succeed the moment listen() is up — BEFORE this
        # rank's engine exists.  Messages that land in that window must
        # be parked and replayed at attach(), not dropped (a dropped
        # first eager chunk wedges the whole ring: every rank times out
        # in its first collective — caught by the multi-process soak)
        self._attach_lock = threading.Lock()
        self._pre_attach: list = []
        self._closing = False
        self._accept_thread = threading.Thread(
            target=self._accept_loop,
            name=f"accl-fabric-accept-{bind_address}", daemon=True,
        )
        self._accept_thread.start()

    def attach(self, address: str, endpoint: Endpoint) -> None:
        if address != self._bind_address:
            raise ValueError("socket fabric serves exactly its bind address")
        with self._attach_lock:
            # replay the backlog while still holding the lock: a message
            # arriving concurrently must not overtake a parked one (stream
            # bytes are order-sensitive; deliver only appends to the
            # endpoint inbox, so holding the lock here cannot deadlock)
            self._endpoint = endpoint
            backlog, self._pre_attach = self._pre_attach, []
            for msg in backlog:
                endpoint.deliver(msg)

    def _accept_loop(self) -> None:
        while not self._closing:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            with self._conn_lock:
                if self._closing:
                    conn.close()
                    return
                self._accepted.append(conn)
            threading.Thread(
                target=self._recv_loop, args=(conn,),
                name="accl-fabric-recv", daemon=True,
            ).start()

    def _recv_loop(self, conn: socket.socket) -> None:
        try:
            while True:
                hdr = self._recv_exact(conn, 4)
                if hdr is None:
                    return
                (n,) = struct.unpack("<I", hdr)
                body = self._recv_exact(conn, n)
                if body is None:
                    return
                msg: Message = pickle.loads(body)
                with self._attach_lock:
                    endpoint = self._endpoint
                    if endpoint is None:
                        self._pre_attach.append(msg)
                if endpoint is not None:
                    try:
                        endpoint.deliver(msg)
                    except Exception:
                        # a poisoned message must not kill this link: the
                        # recv thread owns the peer's ONLY path in, and
                        # its death silently drops every later message
                        # (wedging collectives ranks downstream).  Log
                        # loudly, keep receiving.
                        import traceback

                        print(
                            f"[accl fabric {self._bind_address}] deliver "
                            f"failed for {msg.msg_type!r} src={msg.src} "
                            f"comm={msg.comm_id} seqn={msg.seqn} "
                            f"vaddr={msg.vaddr:#x}:",
                            file=sys.stderr,
                        )
                        traceback.print_exc()
        finally:
            conn.close()

    @staticmethod
    def _recv_exact(conn: socket.socket, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            try:
                chunk = conn.recv(n - len(buf))
            except OSError:
                return None  # connection torn down under us (close())
            if not chunk:
                return None
            buf += chunk
        return buf

    def _connect(self, address: str, grace_s: float = 15.0) -> socket.socket:
        """Dial a peer, retrying until its listener is up (peers start
        concurrently; the reference leans on MPI barriers for this,
        fixture.hpp:124-132 — we self-synchronize instead).  Re-dials of a
        peer that was ALREADY connected get no grace period: its process is
        gone and the caller needs a fast failure, not a 15 s stall."""
        import time as _time

        host, port = address.rsplit(":", 1)
        deadline = _time.monotonic() + grace_s
        while True:
            try:
                conn = socket.create_connection((host, int(port)), 2.0)
                break
            except OSError:
                if _time.monotonic() > deadline:
                    raise
                _time.sleep(0.05)
        conn.settimeout(None)  # connect timeout must not outlive the dial
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return conn

    def _mark_dead(self, address: str) -> None:
        with self._conn_lock:
            self._dead.add(address)
            conn = self._conns.pop(address, None)
        if conn is not None:
            try:
                conn.close()
            except OSError:
                pass

    def _transmit(self, address: str, msg: Message) -> None:
        with self._conn_lock:
            if address in self._dead:
                raise PeerDeadError(address)
            conn = self._conns.get(address)
        if conn is None:
            # dial OUTSIDE the lock so a slow-starting peer doesn't stall
            # sends to already-connected peers
            try:
                grace = 0.0 if address in self._ever_connected else 15.0
                conn = self._connect(address, grace_s=grace)
            except OSError:
                self._mark_dead(address)
                raise PeerDeadError(address) from None
            with self._conn_lock:
                self._ever_connected.add(address)
                winner = self._conns.setdefault(address, conn)
            if winner is not conn:
                conn.close()
                conn = winner
        body = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
        try:
            with self._conn_lock:
                conn.sendall(struct.pack("<I", len(body)) + body)
        except OSError:
            # the peer process died under an established connection: fail
            # the send fast (the engine converts this to SEND_TIMEOUT)
            # instead of silently dropping every later message
            self._mark_dead(address)
            raise PeerDeadError(address) from None

    def close(self) -> None:
        self._closing = True
        try:
            self._listener.close()
        except OSError:
            pass
        with self._conn_lock:
            # accepted (inbound) connections must die too: leaving them
            # open keeps peers' sends "succeeding" into a rank that no
            # longer exists — the silent-drop failure mode.  Closing them
            # gives peers a prompt RST -> PeerDeadError -> SEND_TIMEOUT.
            for c in self._conns.values():
                try:
                    c.close()
                except OSError:
                    pass
            self._conns.clear()
            for c in self._accepted:
                try:
                    c.close()
                except OSError:
                    pass
            self._accepted.clear()
