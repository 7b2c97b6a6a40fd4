"""The ACCL facade: user-facing MPI-like API over a collective engine.

Role model: ``class ACCL`` in ``driver/xrt/include/accl.hpp:45-1131`` /
``src/accl.cpp`` — all collectives, buffer factories, communicator
management, request objects, config surface, debug dumps.  Call preparation
(dtype -> arithmetic config resolution, compression flags) mirrors
``prepare_call`` (accl.cpp:1236-1356); the sync path mirrors
``call_sync`` + ``check_return_value`` (accl.cpp:1379-1397, 1210-1234).

Ops default to synchronous; pass ``run_async=True`` to get the Request and
overlap calls (the reference's ``run_async`` flag).
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from typing import List, Optional, Sequence, Union

import numpy as np

from .arithconfig import DEFAULT_ARITH_CONFIG, ArithConfig
from .backends.base import BaseEngine, CallOptions
from .buffer import BaseBuffer, DummyBuffer, EmuBuffer
from .communicator import Communicator, Rank
from .constants import (
    ACCLError,
    CompressionFlags,
    ConfigFunction,
    DataType,
    DEFAULT_RX_BUFFER_SIZE,
    ErrorCode,
    FusedCompute,
    HostFlags,
    Operation,
    ReduceFunction,
    StreamFlags,
    dtype_size,
    numpy_to_dtype,
    pipeline_segment_tag,
)
from .contract import ContractVerifier, board_for, env_enabled as _verify_env
from .contract import verdict_context
from .errorfeedback import ResidualStore
from . import wire as _wire
from .faults import HealthTransitions
from . import arbiter as _arb
from . import membership as _mbr
from .overlap import drain_deadline_s
from .plans import CollectivePlan, PlanCache, size_bucket
from .request import Request
from .utils.profiling import annotate, annotated
from .telemetry import (
    Telemetry,
    chrome_trace,
    collective_trace_id,
    p2p_trace_id,
    to_json,
    to_prometheus,
)

DTypeLike = Union[DataType, str, np.dtype, type]


def _as_datatype(dt: DTypeLike) -> DataType:
    if isinstance(dt, DataType):
        return dt
    return numpy_to_dtype(np.dtype(dt))


class ACCL:
    """One rank's handle onto the collective engine."""

    def __init__(
        self,
        engine: BaseEngine,
        ranks: Sequence[Rank],
        local_rank: int,
        arith_config: Optional[dict] = None,
        timeout_s: float = 30.0,
        max_eager_size: int = 32 * 1024,
        max_rendezvous_size: int = 16 * 1024 * 1024,
        topology=None,
    ):
        self.engine = engine
        self._arith = dict(arith_config or DEFAULT_ARITH_CONFIG)
        self._world = Communicator(ranks, local_rank, comm_id=0)
        self._communicators: List[Communicator] = [self._world]
        # topology plane (accl_tpu.topology): the slice / link-class
        # descriptor, explicit or from ACCL_TOPOLOGY / ACCL_SLICE_SIZE /
        # jax.distributed facts.  Attached to the world communicator and
        # inherited by splits; hierarchical decomposition and per-class
        # wire verdicts key on it.  _hier_comms caches the derived
        # intra/cross subcomms per (comm id, epoch) — an epoch bump
        # (shrink/grow/reset) re-derives naturally.
        if topology is None:
            from .topology import Topology as _Topology

            topology = _Topology.from_env(len(ranks))
        if topology is not None:
            if topology.world != len(ranks):
                raise ValueError(
                    f"topology describes world={topology.world}, this "
                    f"group is world={len(ranks)}"
                )
            self._world.topology = topology
        self._hier_comms: dict = {}
        self._initialized = False
        # single-interaction batching: while a batch is open, collective
        # calls queue here and flush() hands them to the engine as ONE
        # dispatch unit (see CommandQueue / BaseEngine.start_batch).
        # _batch_depth makes nested batch() contexts safe: only the
        # outermost exit flushes and closes.
        self._pending: Optional["CommandQueue"] = None
        self._batch_depth = 0
        # cached per-call dispatch plans (accl_tpu.plans): warm collective
        # = pool lookup -> dispatch; invalidated on SET_TUNING/RESET/eager
        # threshold writes and re-keyed by communicator epoch
        self._plans = PlanCache()
        # measurement-driven register selections (accl_tpu.tuning): set by
        # load_tuning_plan / the ACCL_TUNING_PLAN env; per-size-bucket
        # register overlays ride the plan cache into CallOptions.tuning
        self._tuning_plan = None
        # telemetry plane (accl_tpu.telemetry): flight recorder + metrics
        # registry, None under the ACCL_TELEMETRY=0 kill switch.  Last
        # plan-cache verdict (hit/miss) stamped per call by _plan_for —
        # THREAD-local: _plan_for and _launch run on the caller's
        # thread, and concurrent async callers on one handle must not
        # swap each other's verdicts between the two
        self._telemetry = Telemetry.create(
            rank=local_rank, tier=type(engine).__name__
        )
        self._call_tls = threading.local()
        # segmented-pipelining call counter per communicator id: every
        # rank advances it identically (the split decision is register-
        # driven and SPMD-uniform), so the reserved per-segment tags it
        # derives match across ranks — concurrent segment tasks of one
        # pipelined collective must never share a (comm, src, tag)
        # matching signature on the fabric tiers (the cross-segment
        # steal race test_segmented_pipelining_emulator caught)
        self._pipeline_ctr: dict = {}
        # quantized wire plane (accl_tpu.wire / accl_tpu.errorfeedback):
        # per-comm stochastic-rounding call counters (SPMD-uniform —
        # every rank issues the same compressed-collective sequence, so
        # derived seeds match with zero wire bytes; cleared by
        # soft_reset with the rest of the sequence space) and the
        # error-feedback residual store, living BESIDE the plan cache
        # with the plan cache's lifecycle (invalidation hook below).
        # Error feedback arms via ACCL_ERROR_FEEDBACK=1 /
        # set_error_feedback() — opt-in: the pre-dispatch residual
        # accounting reads the operand on the host, which the warm
        # 1-interaction gang path must not pay by default.
        self._wire_ctr: dict = {}
        self._residuals = ResidualStore()
        self._plans.add_invalidation_hook(self._residuals.invalidate)
        self._error_feedback = (
            os.environ.get("ACCL_ERROR_FEEDBACK", "0") == "1"
        )
        # monitor plane (accl_tpu.monitor): continuous observability —
        # straggler tracker + anomaly watchdog riding the telemetry
        # completion observer, plus the opt-in scrape service
        # (ACCL_MONITOR_PORT / start_monitor()) and streaming trace
        # writer (ACCL_TRACE_STREAM).  None when telemetry is killed.
        self._monitor = None
        if self._telemetry is not None:
            from . import monitor as _monitor

            self._monitor = _monitor.Monitor(
                rank=local_rank, world=len(ranks),
                telemetry=self._telemetry,
                anchor=engine.contract_anchor(),
                tier=type(engine).__name__,
            )
            # one-process-per-rank fabrics exchange skew windows by
            # piggybacking (window, mean_wait) on outgoing messages —
            # the contract plane's stamp cadence, reused
            self._monitor.tracker.begin_comm(
                self._world.id, local_rank, len(ranks)
            )
            fabric = getattr(engine, "fabric", None)
            if fabric is not None and hasattr(fabric, "register_skew"):
                fabric.register_skew(
                    self._world.id, local_rank, self._monitor.tracker
                )
            engine.set_skew_tracker(self._monitor.tracker)
        # contract plane (accl_tpu.contract): the opt-in cross-rank
        # runtime verifier — every collective call fingerprinted into a
        # per-communicator rolling digest, exchanged with the other
        # ranks every ACCL_VERIFY_INTERVAL calls; divergence fails fast
        # with CONTRACT_VIOLATION instead of hanging.  Armed by
        # ACCL_VERIFY=1 (read per handle) or set_contract_verify().
        self._contract: Optional[ContractVerifier] = None
        # membership plane (accl_tpu.membership): always-on sensing
        # (health transition events, the membership snapshot); the
        # ACTING half — communicator shrink on dead verdicts, straggler
        # demotion routing — arms via ACCL_ELASTIC=1 / set_elastic().
        # Exchange rides the contract anchor's shared board in process
        # and MEMBER wire frames on one-process-per-rank fabrics.
        anchor = engine.contract_anchor()
        self._membership = _mbr.MembershipView(
            rank=ranks[local_rank].session,
            world=len(ranks),
            board=_mbr.board_for(anchor),
            ledger=_mbr.ledger_for(anchor),
            send_fn=self._membership_send,
        )
        self._membership.elastic = _mbr.env_elastic()
        # warm handoff (elastic expansion): the artifact exporter the
        # JOIN agreement attaches to its confirm — contract baselines,
        # tuning plan, plan verdicts — so an admitted rank's first
        # window is contract-conformant
        self._membership.handoff_fn = self._membership_handoff
        self._health_events = HealthTransitions()
        self._demote_seq: dict = {}  # comm id -> routing call index
        self._demoted_seen: set = set()  # (comm, rank) demotions counted
        engine.set_membership(self._membership)
        engine.on_health_transition = self._on_health_transition
        # QoS arbiter plane (accl_tpu.arbiter): per-communicator tenant
        # registry + deficit-weighted round-robin admission in front of
        # engine dispatch.  Shared per process anchor (the contract-
        # board discipline) so every in-process rank handle meets on ONE
        # grant order and ONE decision latch; one-process-per-rank tiers
        # run a per-process arbiter over identical per-comm streams.
        # Registration/quotas are always accepted; the acting half (DRR
        # queueing, throttles) arms via ACCL_ARBITER=1 / set_arbiter().
        self._arbiter = _arb.arbiter_for(anchor) or _arb.QosArbiter()
        if _arb.env_arbiter():
            self._arbiter.armed = True
        self._arbiter_seq: dict = {}  # comm id -> admission call index
        # this handle's admission owner identity (one owner = one rank
        # handle; the per-rank window-share bound keys on it)
        self._arbiter_owner = ranks[local_rank].session
        # cross-process tenant registry (ACCL_ARBITER_LEDGER=1 on a tier
        # whose engine exposes a KV plane): per-process arbiters share
        # tenant weights through the same KV the contract-digest ledger
        # rides, and re-derive token-bucket rates as fabric shares
        self._arbiter_exchange_ctr = 0
        if (
            _arb.env_ledger()
            and self._arbiter.ledger is None
            and hasattr(engine, "arbiter_kv")
        ):
            self._arbiter.attach_ledger(_arb.TenantLedger(
                process_key=f"proc-{ranks[local_rank].session}",
            ))
        # causal trace plane (accl_tpu.telemetry): deterministic
        # trace/span ids assigned at facade intake — per-comm collective
        # seqn counters plus directed p2p channel counters, both
        # SPMD-uniform so every rank of a collective derives the SAME
        # id with zero wire bytes; the generation re-keys on soft_reset
        # like the contract digests.  _trace_last is the lock-free wire
        # piggyback stamp (Fabric.register_trace).
        self._trace_seq: dict = {}
        self._p2p_seq: dict = {}
        self._trace_gen = 1
        self._trace_last: dict = {}
        self._batch_trace = None
        self._batch_ctr = 0
        fabric = getattr(engine, "fabric", None)
        if self._telemetry is not None and fabric is not None and hasattr(
            fabric, "register_trace"
        ):
            fabric.register_trace(self._world.id, local_rank, self)
        # two-class paced bandwidth model: hand the emulator fabric the
        # world topology so it classifies (and counts) every wire byte
        # as ICI vs DCN — the per-link-class telemetry counters
        if (
            self._world.topology is not None
            and fabric is not None
            and hasattr(fabric, "register_topology")
        ):
            fabric.register_topology(self._world.id, self._world.topology)
        # postmortem plane (accl_tpu.monitor.BlackBox): automatic
        # evidence bundles on structured failures.  In-process peers
        # solicit over an anchored registry (the contract-board
        # discipline); one-process-per-rank fabrics use POSTMORTEM wire
        # frames with a bounded best-effort wait.  Disabled (one None
        # check per failure) unless ACCL_POSTMORTEM_DIR is set.
        self._blackbox = None
        if self._telemetry is not None:
            from . import monitor as _monitor
            from .contract import anchored as _anchored

            pm_registry = _anchored(
                anchor, "_accl_blackbox_registry", dict
            )
            session = ranks[local_rank].session
            if pm_registry is not None:
                pm_registry[session] = self._postmortem_evidence
            self._blackbox = _monitor.BlackBox(
                rank=session, world=len(ranks),
                evidence_fn=self._postmortem_evidence,
                peers_fn=(
                    (lambda reg=pm_registry: reg)
                    if pm_registry is not None else None
                ),
                solicit_fn=(
                    self._postmortem_solicit
                    if pm_registry is None and fabric is not None
                    else None
                ),
                metrics=self._telemetry.metrics,
            )
            engine.set_postmortem(self._on_postmortem_frame)
            # command-ring failure latch → postmortem hook (the run
            # latch / drain deadline / dispatch error paths)
            ring = getattr(getattr(engine, "gang", None), "cmdring", None)
            if ring is not None:
                ring.on_failure = self._on_ring_failure
        self._initialize(timeout_s, max_eager_size, max_rendezvous_size)
        if _verify_env():
            self.set_contract_verify(True)
        if self._monitor is not None:
            from . import monitor as _monitor

            if _monitor.env_port() is not None:
                try:
                    self.start_monitor()
                except OSError as e:
                    # in-process multi-rank groups race for one port:
                    # the first handle serves, the rest log and skip
                    # (pass port=0 / per-rank ports to serve them all)
                    import sys

                    print(
                        f"[accl] monitor port busy, not serving rank "
                        f"{local_rank}: {e}",
                        file=sys.stderr,
                    )
            tdir = os.environ.get(_monitor.TRACE_STREAM_ENV)
            if tdir:
                try:
                    self._monitor.start_trace_stream(tdir)
                except OSError as e:  # a bad dir must not brick startup
                    import sys

                    print(
                        f"[accl] ignoring ACCL_TRACE_STREAM={tdir!r}: {e}",
                        file=sys.stderr,
                    )
        env_plan = os.environ.get("ACCL_TUNING_PLAN")
        if env_plan:
            try:
                self.load_tuning_plan(env_plan, strict=False)
            except Exception as e:  # a stale plan must not brick startup
                import sys

                print(
                    f"[accl] ignoring ACCL_TUNING_PLAN={env_plan!r}: {e}",
                    file=sys.stderr,
                )

    # -- init sequence (ref ACCL::initialize, accl.cpp:1066-1114) ------------
    def _initialize(
        self, timeout_s: float, max_eager_size: int, max_rendezvous_size: int
    ) -> None:
        self._timeout_s = float(timeout_s)
        self._max_eager_size = int(max_eager_size)
        self._config(ConfigFunction.RESET, 0)
        self._config(ConfigFunction.SET_TIMEOUT, timeout_s)
        self._config(ConfigFunction.SET_MAX_EAGER_SIZE, max_eager_size)
        self._config(ConfigFunction.SET_MAX_RENDEZVOUS_SIZE, max_rendezvous_size)
        self._config(ConfigFunction.ENABLE_TRANSPORT, 1)
        self._initialized = True

    # configs whose effect is baked into cached plans: a successful write
    # drops the whole pool (stale algorithm/protocol choices must never
    # serve another call)
    _PLAN_INVALIDATING = frozenset((
        ConfigFunction.RESET,
        ConfigFunction.SET_TUNING,
        ConfigFunction.SET_MAX_EAGER_SIZE,
    ))

    def _config(self, fn: ConfigFunction, value: float, key: int = 0) -> None:
        self.flush()  # config must not overtake queued batch calls
        req = self.engine.start(
            CallOptions(
                op=Operation.CONFIG,
                cfg_function=int(fn),
                cfg_value=value,
                cfg_key=int(key),
            )
        )
        # bounded like every other drain point: a wedged engine must
        # surface as DEADLOCK_SUSPECTED, not hang the config writer
        # (acclint: unbounded-wait found the original bare wait here)
        if not req.wait(timeout=drain_deadline_s(self._timeout_s)):
            raise self._deadlock_error(f"config {fn.name}")
        req.check(f"config {fn.name}")
        if fn in self._PLAN_INVALIDATING:
            self._plans.invalidate(fn.name.lower())

    # -- introspection -------------------------------------------------------
    @property
    def comm(self) -> Communicator:
        return self._world

    @property
    def rank(self) -> int:
        return self._world.local_rank

    @property
    def size(self) -> int:
        return self._world.size

    # -- config surface ------------------------------------------------------
    def soft_reset(self) -> None:
        """Abandon stale engine state after a failed/timed-out collective
        (ref ``ACCL::soft_reset``, accl.cpp:57-89).  Collective by
        contract: every rank handle of the group must call it, with no
        new collectives in flight, before any rank resumes work —
        afterwards gang sequence counters are realigned and the engine is
        fully usable.  RESET value 1 requests the FULL flush (rx pool,
        inbox, retransmit window, dedup ledger, health map) on the
        emulated tiers — the recovery path after injected faults — and
        the facade realigns its communicators' per-peer sequence counters
        to match.  Transport is re-enabled the same way ``_initialize``
        does."""
        self._config(ConfigFunction.RESET, 1)
        # membership plane: soft_reset is the RESTORE point — after the
        # operator heals the fabric, the collective reset re-admits
        # every evicted rank (full pre-shrink membership, fresh epoch)
        # and clears standing demotions.  Collective by contract like
        # the reset itself, so every rank restores at the same point.
        restored = self._membership.restore()
        if restored is not None:
            for comm in self._communicators:
                if comm.restore():
                    if self._contract is not None:
                        self._contract.begin_comm(
                            comm.id, comm.local_rank,
                            tuple(r.session for r in comm.ranks),
                            fresh=False,
                        )
                    fabric = getattr(self.engine, "fabric", None)
                    if fabric is not None:
                        if self._contract is not None and hasattr(
                            fabric, "register_contract"
                        ):
                            fabric.register_contract(
                                comm.id, comm.local_rank, self._contract
                            )
            self.engine.on_membership_restore()
            self._plans.invalidate("membership_restore")
            for s in restored.get("readmitted", ()):
                self._health_events.note(s, "evicted", "restored")
        elif self._membership.ledger is not None:
            # demotion-only state (no eviction pending, so restore()
            # was a no-op) clears with the reset too: the demote-seq
            # counter restarts at 0 below, and stale latched decisions
            # for those indices would otherwise replay pre-reset
            # routing against a now-healthy rank
            self._membership.ledger.reset()
        self._demote_seq.clear()
        self._demoted_seen.clear()
        # arbiter plane: admission call-index counters restart at 0
        # with the rest of the sequence space, so the latched decision
        # ledger clears with them (stale throttles must never replay
        # against post-reset indices).  Collective by contract, like
        # the reset itself — registrations and quotas survive (config
        # state, exactly like the tuning registers).
        self._arbiter_seq.clear()
        self._arbiter.reset_ledger()
        # quantized wire plane: SR-seed counters restart with the rest
        # of the sequence space (collective by contract, so derived
        # seeds stay aligned across ranks); the residual store already
        # cleared via the plan-cache invalidation hook on RESET
        self._wire_ctr.clear()
        for comm in self._communicators:
            comm.reset_sequences()
        self._config(ConfigFunction.ENABLE_TRANSPORT, 1)
        if self._contract is not None:
            # recovery clears contract verdicts and starts a fresh
            # digest generation — collective by contract (like the reset
            # itself), so generations stay aligned across ranks
            self._contract.reset()
        if self._monitor is not None:
            # skew baselines and standing slow_rank verdicts are about
            # the PRE-reset regime; recovery starts them fresh too —
            # but the memberships survive (like the contract verifier's
            # reset), so early post-reset claims keep resolving in the
            # right rank space
            self._monitor.reset()
            for comm in self._communicators:
                self._monitor.tracker.begin_comm(
                    comm.id, comm.local_rank, comm.size
                )
        # causal trace plane: a new generation re-keys every trace id
        # (collective by contract, like the verifier's generation — so
        # post-reset ids keep matching across ranks and never collide
        # with pre-reset ones), and the postmortem latches clear (a
        # fresh regime's failures deserve fresh bundles)
        self._trace_gen += 1
        self._trace_seq.clear()
        self._p2p_seq.clear()
        self._trace_last.clear()
        if self._blackbox is not None:
            self._blackbox.reset()

    def set_timeout(self, seconds: float) -> None:
        self._config(ConfigFunction.SET_TIMEOUT, seconds)
        self._timeout_s = float(seconds)

    def set_max_eager_size(self, nbytes: int) -> None:
        self._config(ConfigFunction.SET_MAX_EAGER_SIZE, nbytes)
        self._max_eager_size = int(nbytes)

    def set_max_rendezvous_size(self, nbytes: int) -> None:
        self._config(ConfigFunction.SET_MAX_RENDEZVOUS_SIZE, nbytes)

    def set_inflight_window(self, depth: int) -> None:
        """Size the overlap plane's per-communicator in-flight window:
        up to ``depth`` collectives may be launched before the first
        completes (the reference's host-FIFO-ahead-of-the-CCLO
        discipline; JAX async dispatch makes the overlap free).  The
        write is itself a drain point — nothing launched under the old
        bound is still in flight when it returns.  Default: small and
        conservative (:data:`~accl_tpu.constants.DEFAULT_INFLIGHT_WINDOW`,
        or the ``ACCL_INFLIGHT_WINDOW`` env var read at engine
        construction).  Tiers whose schedulers already complete
        asynchronously (emulator/native) accept and report the knob."""
        self._config(ConfigFunction.SET_INFLIGHT_WINDOW, int(depth))

    def set_contract_verify(
        self, enabled: bool = True, interval: Optional[int] = None
    ) -> Optional[ContractVerifier]:
        """Arm (or with ``enabled=False`` disarm) the cross-rank
        collective contract verifier on this handle.  Collective by
        contract: every rank of the group arms it at the same point of
        its call sequence, with the same ``interval`` (default
        ``ACCL_VERIFY_INTERVAL``, 8) — the verifier exists to check
        exactly that kind of agreement, so arming it divergently is
        self-defeating.  Facade-local: no engine config write, no
        device traffic; the per-call cost is one crc32 + a ring append."""
        if not enabled:
            v, self._contract = self._contract, None
            if v is not None:
                self.engine.set_contract_verifier(None)
                fabric = getattr(self.engine, "fabric", None)
                if fabric is not None and hasattr(
                    fabric, "unregister_contract"
                ):
                    fabric.unregister_contract(v)
                v.close()
            return None
        if self._contract is not None:
            if interval is None or interval == self._contract.interval:
                return self._contract
            self.set_contract_verify(False)
        tel = self._telemetry
        v = ContractVerifier(
            rank=self._world.local_rank,
            world=self._world.size,
            interval=interval,
            board=board_for(self.engine.contract_anchor()),
            fabric=getattr(self.engine, "fabric", None),
            tail_fn=tel.tail_dicts if tel is not None else None,
            health_fn=lambda: self.engine.health_report(self._world),
        )
        self._contract = v
        self.engine.set_contract_verifier(v)
        # membership registration: every rank field of a communicator's
        # contract traffic (wire src, board posts, blame) is COMM-
        # relative — the verifier needs each comm's local rank + rank->
        # session map, or subcomm verdicts would misblame (fresh=False:
        # arming is not a new comm instance, no begin marker)
        for comm in self._communicators:
            v.begin_comm(
                comm.id, comm.local_rank,
                tuple(r.session for r in comm.ranks), fresh=False,
            )
        fabric = getattr(self.engine, "fabric", None)
        if fabric is not None and hasattr(fabric, "register_contract"):
            for comm in self._communicators:
                fabric.register_contract(comm.id, comm.local_rank, v)

            def _relay(verdict, v=v, fabric=fabric):
                # a locally-convicted verdict is relayed to the comm's
                # peers over the wire (one small VERIFY message each):
                # a rank that detects pre-dispatch stops sending, and
                # without the relay its peers would sit blocked in
                # flight until their engine deadline — the exact hang
                # this plane exists to remove.  Relayed verdicts are
                # marked so receivers don't re-broadcast (no storms).
                if verdict.get("relayed"):
                    return
                comm = next(
                    (c for c in self._communicators
                     if c.id == verdict.get("comm")), None,
                )
                if comm is None:
                    return
                import json as _json

                from .backends.emulator.fabric import Message, MsgType

                payload = _json.dumps(verdict, default=str).encode()
                for i, r in enumerate(comm.ranks):
                    if i == comm.local_rank:
                        continue
                    try:
                        fabric.send(r.address, Message(
                            MsgType.VERIFY, comm.id, comm.local_rank, i,
                            0, payload=payload,
                        ))
                    except Exception:
                        pass  # a dead/partitioned peer: nothing to tell

            v.add_verdict_listener(_relay)
        return v

    # -- membership plane (accl_tpu.membership) -------------------------------
    def set_elastic(self, enabled: bool = True) -> None:
        """Arm (or disarm) elastic membership on this handle: a ``dead``
        health verdict proposes eviction, a confirmed majority shrinks
        the communicator at the next call boundary and the group keeps
        serving at the new world size, and convicted stragglers are
        demoted out of root/relay roles (board-anchored tiers).
        Collective by contract: every rank of the group arms it, like
        the contract verifier — a lone elastic rank would shrink alone
        and diverge (the ``__shrink__`` digest marker then names it
        within one verification window).  Also read from
        ``ACCL_ELASTIC=1`` at handle construction."""
        self._membership.elastic = bool(enabled)

    def evict_rank(self, rank: int, comm: Optional[Communicator] = None):
        """Explicitly propose evicting ``rank`` (comm-relative in
        ``comm``, default the world communicator) — the operator's
        lever when external knowledge (a draining host, a failed
        chassis) precedes the health map.  Collective by contract:
        every surviving rank calls it; the eviction confirms by strict
        majority of the survivors and this call applies the cutover
        before returning.  Returns the applied plan record, or None
        when confirmation did not arrive within the bounded window
        (``ACCL_EVICT_CONFIRM_S``) — the proposal stands and a later
        call's boundary applies it."""
        comm = comm or self._world
        self._check_rank(comm, rank)
        session = comm.ranks[rank].session
        mv = self._membership
        if session == self._world.ranks[self._world.local_rank].session:
            mv.propose({session}, reason="evict_rank_self")
            raise self._structured_failure(ACCLError(
                ErrorCode.RANK_EVICTED, "evict_rank",
                details={"membership": mv.evidence(), "rank": rank},
            ))
        mv.propose({session}, reason="evict_rank")
        plan = mv.wait_confirmed(timeout=_mbr.env_confirm_s())
        if plan is None:
            return None
        self._apply_cutover()
        return plan

    def suggest_root(self, comm: Optional[Communicator] = None) -> int:
        """The lowest comm-relative rank NOT currently flagged slow —
        the advisory root/relay choice for callers that pick their own
        roots.  Board-anchored tiers read the straggler circuit
        breaker's demotion ledger (majority-grade verdicts); wire tiers
        have no shared ledger, so the monitor plane's PAIRWISE
        slow-rank verdicts feed in instead — annotation-only advice
        from this rank's own observations (each side may suggest a
        different root; callers that need agreement use the
        ledger-latched ``_barrier_root`` path, which wire tiers never
        take).  0 (the stock choice) when nothing is flagged or the
        monitor is off."""
        comm = comm or self._world
        demoted = set(self._membership.demoted(comm.id))
        if (
            self._membership.ledger is None
            and self._monitor is not None
        ):
            # wire tier: pairwise verdicts as advisory input (no
            # demotion ledger — nothing is ever demoted, routing by
            # callers' choice only)
            demoted |= set(self._monitor.slow_ranks(comm.id))
        for r in range(comm.size):
            if r not in demoted:
                return r
        return 0

    def join_rank(self, timeout: Optional[float] = None):
        """The candidate's side of elastic EXPANSION: petition the live
        group for admission, wait (bounded, ``ACCL_JOIN_CONFIRM_S``)
        for the strict-majority confirm, and cut this handle over to
        the grown membership — fresh comm epochs, the ``__join__``
        digest marker, and the group's warm-handoff artifacts (contract
        baselines, tuning plan, plan verdicts) adopted so the first
        window is contract-conformant.  The natural caller is a
        previously-evicted rank re-joining after the operator healed
        its fault (the kill→shrink→serve→join→serve cycle); survivors
        apply their half of the cutover at their next call boundary,
        exactly like eviction.  Returns the applied join record, or
        None when confirmation did not arrive in time (the petition
        stands; re-calling retries)."""
        mv = self._membership
        if not mv.elastic:
            raise ACCLError(
                ErrorCode.INVALID_OPERATION,
                "join_rank needs elastic membership "
                "(ACCL_ELASTIC=1 / set_elastic())",
                details={"op": "join_rank"},
            )
        mv.petition_join()
        plan = mv.wait_confirmed(
            timeout=_mbr.env_join_s() if timeout is None else timeout
        )
        if plan is None or plan.get("kind") != "join":
            return None
        return self._apply_cutover()

    def join_decision(self) -> dict:
        """The latched admission-decision accessor (the
        ``demote_decision``/``suggest_root`` discipline): the latest
        APPLIED join record — majority-confirmed and cutover-applied,
        identical on every member — safe to branch collective sequences
        on, unlike raw membership/health state."""
        return self._membership.join_decision()

    def _membership_send(self, payload: dict, exclude) -> None:
        """MEMBER agreement frames to the world peers minus ``exclude``
        (the wire exchange path; board-anchored tiers never call this).
        Iterates the FULL pre-shrink membership when one is stashed:
        eviction phases exclude the condemned explicitly, but JOIN
        phases must reach the candidate — a session outside the shrunk
        group that the survivors' world communicator no longer lists."""
        fabric = getattr(self.engine, "fabric", None)
        if fabric is None:
            return
        import json as _json

        from .backends.emulator.fabric import Message, MsgType

        comm = self._world
        ranks = getattr(comm, "_full_ranks", None) or comm.ranks
        local_session = comm.ranks[comm.local_rank].session
        data = _json.dumps(payload).encode()
        for i, r in enumerate(ranks):
            if r.session == local_session or r.session in exclude:
                continue
            try:
                fabric.send(r.address, Message(
                    MsgType.MEMBER, comm.id, comm.local_rank, i, 0,
                    payload=data,
                ))
            except Exception:
                pass  # a dead/partitioned peer: nothing to tell

    def _membership_handoff(self) -> dict:
        """The warm-handoff artifact bundle a JOIN confirm carries (the
        ``MembershipView.handoff_fn`` exporter): everything an admitted
        rank needs for a contract-conformant first window.  Bounded,
        JSON-safe, side-effect-free — it rides a board plan or one
        MEMBER wire frame."""
        contract = (
            self._contract.export_handoff()
            if self._contract is not None else None
        )
        tuning = (
            self._tuning_plan.to_json()
            if self._tuning_plan is not None else None
        )
        return {
            "contract": contract,
            "tuning_plan": tuning,
            "plan_verdicts": self._plans.export_verdicts(),
            "trace_gen": self._trace_gen,
            # SPMD-uniform per-comm counters the joiner must resume at
            # (stochastic-rounding seeds and pipelined-segment tags
            # derive from these with zero wire bytes)
            "wire_ctr": {str(k): v for k, v in self._wire_ctr.items()},
            "pipeline_ctr": {
                str(k): v for k, v in self._pipeline_ctr.items()
            },
        }

    # -- postmortem plane (accl_tpu.monitor.BlackBox) -------------------------
    def _postmortem_evidence(self) -> dict:
        """This rank's evidence for a bundle: the flight-recorder tail
        plus the full merged telemetry snapshot (which carries the
        command ring's state, the membership event ring, skew baselines
        and contract window digests).  Called from the failing thread
        locally and from peers' capture paths (board registry / wire
        request) — must stay bounded and side-effect-free."""
        tel = self._telemetry
        return {
            "rank": self._world.local_rank,
            "session": self._world.ranks[self._world.local_rank].session,
            "tier": type(self.engine).__name__,
            "flight_recorder": tel.tail_dicts(64) if tel else [],
            "snapshot": self.telemetry_snapshot(),
        }

    def _postmortem_solicit(self, token: int) -> int:
        """Wire solicitation (one-process-per-rank fabrics): POSTMORTEM
        request frames to every surviving world peer; replies land via
        the engine's postmortem hook.  Returns how many peers were
        asked — the BlackBox's bounded wait counts replies against it,
        and a dead peer simply never answers (documented absent)."""
        fabric = getattr(self.engine, "fabric", None)
        if fabric is None:
            return 0
        import json as _json

        from .backends.emulator.fabric import Message, MsgType

        comm = self._world
        me = comm.ranks[comm.local_rank]
        payload = _json.dumps({
            "kind": "request", "token": int(token),
            "reply_to": me.address, "rank": me.session,
        }).encode()
        n = 0
        for i, r in enumerate(comm.ranks):
            if i == comm.local_rank or r.session in self._membership.evicted:
                continue
            try:
                fabric.send(r.address, Message(
                    MsgType.POSTMORTEM, comm.id, comm.local_rank, i, 0,
                    payload=payload,
                ))
                n += 1
            except Exception:
                pass  # dead/partitioned peer: documented absent
        return n

    def _on_postmortem_frame(self, msg) -> None:
        """POSTMORTEM wire frames (fabric delivery thread): a peer's
        request gets this rank's evidence back best-effort; a reply
        feeds the bounded collection of our own in-flight capture."""
        bb = self._blackbox
        if bb is None:
            return
        import json as _json

        try:
            payload = _json.loads(msg.payload.decode())
        except (ValueError, UnicodeDecodeError):
            return
        kind = payload.get("kind")
        if kind == "reply":
            bb.deliver_reply(
                payload.get("token", 0), payload.get("rank", -1),
                payload.get("evidence") or {},
            )
            return
        if kind != "request":
            return
        fabric = getattr(self.engine, "fabric", None)
        reply_to = payload.get("reply_to")
        if fabric is None or not reply_to:
            return
        try:
            evidence = self._postmortem_evidence()
        except Exception as e:  # half evidence beats a dropped reply
            evidence = {"error": f"{type(e).__name__}: {e}"[:200]}
        from .backends.emulator.fabric import Message, MsgType

        me = self._world.ranks[self._world.local_rank]
        body = _json.dumps({
            "kind": "reply", "token": payload.get("token", 0),
            "rank": me.session, "evidence": evidence,
        }, default=str).encode()
        try:
            fabric.send(reply_to, Message(
                MsgType.POSTMORTEM, msg.comm_id, msg.dst, msg.src, 0,
                payload=body,
            ))
        except Exception:
            pass  # requester died mid-capture: nothing to tell

    def _on_ring_failure(self, comm_id: int, error: str) -> None:
        """Command-ring failure latch (run latch / drain deadline /
        dispatch error): capture the ring's postmortem evidence — the
        window that wedged is in the ring's window log and the
        requests' flight records ride the snapshot."""
        if self._blackbox is not None:
            self._blackbox.capture(
                "RING_FAILURE", f"cmdring comm {comm_id}",
                details={"comm": comm_id, "error": error},
                key=("RING_FAILURE", comm_id),
            )

    def _on_health_transition(self, peer, old: str, new: str) -> None:
        """Engine health-map transition hook (engine scheduler / gang
        watchdog threads): record the edge (flap visibility — the
        instantaneous map can't show a transition that self-clears
        between scrapes) and, under elastic membership, turn a fresh
        ``dead`` verdict into an eviction proposal."""
        self._health_events.note(peer, old, new)
        mv = self._membership
        if new != "dead" or not mv.elastic:
            return
        session = self._session_of_peer(peer)
        if session is None or session in mv.evicted:
            return
        mv.propose(
            {session},
            reason=f"health:{old}->dead",
            evidence={"peer": str(peer), "event": f"{old}->dead"},
        )

    def _session_of_peer(self, peer) -> Optional[int]:
        """World session behind an engine health key (a transport
        address on the emulator tiers, a session int on the gang)."""
        if isinstance(peer, int):
            return peer
        for r in self._world.ranks:
            if r.address == peer:
                return r.session
        # the world comm may already have shrunk past this peer: fall
        # back to the pre-shrink membership if one is stashed
        full = getattr(self._world, "_full_ranks", None) or ()
        for r in full:
            if r.address == peer:
                return r.session
        return None

    def _apply_cutover(self) -> Optional[dict]:
        """Atomically cut over to the confirmed shrunk membership:
        drain the in-flight window, shrink every affected communicator
        (fresh epoch — plans/tuning overlays re-key), fold the
        ``__shrink__`` marker into the contract digest stream,
        re-register the monitor/contract rank spaces, and let the
        engine tear down + re-arm its per-comm sessions over the
        survivors.  Idempotent per confirmed plan (take_cutover is the
        one-shot); self-evicted handles only mark — their group is
        gone."""
        mv = self._membership
        plan = mv.take_cutover()
        if plan is None:
            return None
        if plan.get("kind") == "join":
            return self._apply_join(plan)
        evicted_sessions = set(plan["evict"])
        if mv.self_evicted:
            return plan  # out of the group: nothing local to shrink
        # in-flight work first: nothing launched under the old
        # membership may still be running when the rank spaces move
        self.engine.drain_inflight()
        addresses = []
        shrunk_ids = []
        fabric = getattr(self.engine, "fabric", None)
        for comm in self._communicators:
            sessions = [r.session for r in comm.ranks]
            hit = evicted_sessions & set(sessions)
            if not hit:
                continue
            addresses.extend(
                r.address for r in comm.ranks if r.session in hit
            )
            keep = [
                i for i, s in enumerate(sessions)
                if s not in evicted_sessions
            ]
            if comm.shrink(keep) is None:
                continue
            shrunk_ids.append(comm.id)
            if self._contract is not None:
                self._contract.shrink_comm(
                    comm.id, comm.local_rank,
                    tuple(r.session for r in comm.ranks), plan["epoch"],
                )
                if fabric is not None and hasattr(
                    fabric, "register_contract"
                ):
                    fabric.register_contract(
                        comm.id, comm.local_rank, self._contract
                    )
            if self._monitor is not None:
                self._monitor.tracker.begin_comm(
                    comm.id, comm.local_rank, comm.size
                )
                if fabric is not None and hasattr(fabric, "register_skew"):
                    fabric.register_skew(
                        comm.id, comm.local_rank, self._monitor.tracker
                    )
            if self._telemetry is not None and fabric is not None and (
                hasattr(fabric, "register_trace")
            ):
                # the shrunk comm's new local rank stamps trace ids
                fabric.register_trace(comm.id, comm.local_rank, self)
        self.engine.on_membership_cutover(
            plan, addresses=tuple(sorted(set(addresses))),
            comm_ids=tuple(shrunk_ids),
        )
        # stale algorithm/prepared state must never serve the shrunk
        # group (the epoch re-key already misses; this drops the pool)
        self._plans.invalidate("membership_shrink")
        for s in plan["evict"]:
            self._health_events.note(s, "dead", "evicted")
        if self._telemetry is not None:
            self._telemetry.metrics.inc("accl_membership_evictions_total")
        if self._blackbox is not None:
            # membership cutover is a covered structured-failure path:
            # the evidence (who voted, who died, the pre-shrink tails)
            # is exactly what ROADMAP's p99 forensics need collected
            # automatically.  Latched on the membership epoch — the
            # RANK_EVICTED raise paths share the key, so one eviction
            # yields ONE bundle however many paths observe it.
            self._blackbox.capture(
                "RANK_EVICTED", "membership_cutover",
                details={"plan": plan},
                key=("RANK_EVICTED", self._membership.epoch),
            )
        return plan

    def _apply_join(self, plan: dict) -> dict:
        """Apply a consumed JOIN record (``take_cutover`` already
        realigned the view): grow every communicator that knew the
        admitted sessions (fresh epoch, zeroed seqns, original world
        slots — the ``Communicator.grow`` ordering rule, so every
        member derives the same post-join rank order with zero extra
        wire bytes), rebase the contract digest streams on the
        handoff's agreed baseline and fold the ``__join__`` marker,
        migrate error-feedback residuals per bucket (lazy, behind each
        bucket's drain point), re-register the monitor/contract/trace
        rank spaces, and re-arm the engine at the grown world.  The
        candidate additionally adopts the warm-handoff artifacts —
        contract generation, tuning plan, plan verdicts, SPMD-uniform
        counters — so its first window is contract-conformant."""
        mv = self._membership
        admit = {int(s) for s in plan.get("admit") or ()}
        local_session = self._world.ranks[self._world.local_rank].session
        candidate = local_session in admit
        handoff = plan.get("handoff") or {}
        # in-flight work first: the incremental migrations below are
        # "behind the drain point" by construction — nothing launched
        # under the old membership is still running
        self.engine.drain_inflight()
        fabric = getattr(self.engine, "fabric", None)
        cdoc = handoff.get("contract") or {}
        addresses = []
        grown_ids = []
        for comm in self._communicators:
            sessions = {r.session for r in comm.ranks}
            full = getattr(comm, "_full_ranks", None) or ()
            known = {r.session for r in full} | sessions
            hit = admit & known
            if not hit:
                continue
            old_epoch = comm.epoch
            if comm.grow(hit) is None:  # pragma: no cover - grow never
                continue                # drops the local rank
            grown_ids.append(comm.id)
            addresses.extend(
                r.address for i, r in enumerate(comm.ranks)
                if (r.session in hit if not candidate
                    else i != comm.local_rank)
            )
            if not candidate:
                # survivors carry their residual streams across the
                # epoch bump; the candidate's previous life is stale
                # by the whole absence and restarts at zeros
                self._residuals.migrate_epoch(
                    comm.id, old_epoch, comm.epoch
                )
            if self._contract is not None:
                entry = (cdoc.get("comms") or {}).get(str(comm.id))
                base = None
                if entry is not None:
                    base = (entry.get("calls", 0), entry.get("digest", 0))
                self._contract.join_comm(
                    comm.id, comm.local_rank,
                    tuple(r.session for r in comm.ranks),
                    plan.get("applied_epoch", mv.epoch), base=base,
                )
                if fabric is not None and hasattr(
                    fabric, "register_contract"
                ):
                    fabric.register_contract(
                        comm.id, comm.local_rank, self._contract
                    )
            if self._monitor is not None:
                self._monitor.tracker.begin_comm(
                    comm.id, comm.local_rank, comm.size
                )
                if fabric is not None and hasattr(fabric, "register_skew"):
                    fabric.register_skew(
                        comm.id, comm.local_rank, self._monitor.tracker
                    )
            if self._telemetry is not None and fabric is not None and (
                hasattr(fabric, "register_trace")
            ):
                fabric.register_trace(comm.id, comm.local_rank, self)
        if candidate:
            # warm handoff: adopt the group's artifacts BEFORE the plan
            # pool clears so the first post-join window meets the same
            # verdicts/generation the survivors run
            if self._contract is not None and cdoc.get(
                "generation"
            ) is not None:
                self._contract.adopt_generation(cdoc["generation"])
            tp = handoff.get("tuning_plan")
            if tp:
                try:
                    from .tuning import TuningPlan

                    self.load_tuning_plan(
                        TuningPlan.from_json(tp), strict=False
                    )
                except Exception:  # a stale plan must never fail a join
                    pass
            for attr, key in (
                ("_wire_ctr", "wire_ctr"), ("_pipeline_ctr", "pipeline_ctr"),
            ):
                carried = handoff.get(key) or {}
                try:
                    getattr(self, attr).update(
                        {int(k): int(v) for k, v in carried.items()}
                    )
                except (TypeError, ValueError):
                    pass
            gen = handoff.get("trace_gen")
            if isinstance(gen, int) and gen > self._trace_gen:
                self._trace_gen = gen
        self.engine.on_membership_cutover(
            plan, addresses=tuple(sorted(set(addresses))),
            comm_ids=tuple(grown_ids),
        )
        # stale pre-join plans must never serve the grown group; the
        # "membership_join" reason keeps migrated residuals (the one
        # invalidation that preserves — wire verdicts did not change)
        self._plans.invalidate("membership_join")
        if candidate:
            self._plans.adopt_verdicts(handoff.get("plan_verdicts"))
        for s in sorted(admit):
            self._health_events.note(s, "evicted", "joined")
        if self._telemetry is not None:
            self._telemetry.metrics.inc("accl_membership_joins_total")
        return plan

    def _membership_report(self) -> dict:
        """The merged membership view (``telemetry_snapshot()
        ["membership"]`` and the ``/membership`` route): the elastic
        state machine's snapshot plus the advisory traffic-aware scale
        recommendation from the arbiter's per-tenant p99 histograms —
        advisory ONLY (the ``suspect_slow`` annotation discipline):
        nothing ever acts on it automatically."""
        doc = self._membership.snapshot()
        doc["scale_advice"] = (
            self._monitor.scale_advice(
                self._arbiter.snapshot(), self._world.size
            )
            if self._monitor is not None else None
        )
        return doc

    def _membership_intake(self, options: CallOptions,
                           context: str) -> None:
        """Pre-dispatch membership screen: apply a cutover that
        confirmed between calls (the SPMD-uniform application point —
        every survivor applies before its next collective), and fail a
        self-evicted handle's comm ops fast."""
        mv = self._membership
        comm = options.comm
        if comm is None:
            return
        if mv.self_evicted and (
            options.op in self._CONTRACT_OPS
            or options.op in (Operation.SEND, Operation.RECV)
        ):
            raise self._structured_failure(ACCLError(
                ErrorCode.RANK_EVICTED, context,
                details={"membership": mv.evidence(), "comm": comm.id},
            ))
        if mv.elastic and mv.cutover_ready() and self._pending is None:
            self._apply_cutover()

    def _membership_after_failure(self, options: CallOptions,
                                  req: Request, context: str) -> None:
        """Post-failure membership gate (sync paths): a timed-out
        collective during an in-flight eviction waits (bounded) for the
        confirmation, applies the cutover, and surfaces the structured
        RANK_EVICTED instead of the raw timeout — so every survivor
        fails the SAME call and resumes aligned at the new world size.
        Unrelated timeouts (no proposal pending) pass straight
        through."""
        mv = self._membership
        if not mv.elastic or options.comm is None:
            return
        code = req.get_retcode()
        if code & ErrorCode.RANK_EVICTED:
            self._apply_cutover()  # engine converted; align before raise
            return
        if not code & (ErrorCode.SEND_TIMEOUT | ErrorCode.RECEIVE_TIMEOUT):
            return
        if not mv.proposing():
            return
        plan = mv.wait_confirmed(timeout=_mbr.env_confirm_s())
        if plan is None:
            return  # unconfirmed: surface the raw timeout
        self._apply_cutover()
        details = {
            "membership": mv.evidence(),
            "comm": options.comm.id,
            "op": options.op.name,
        }
        if self._telemetry is not None:
            details["flight_recorder"] = self._telemetry.tail_dicts()
        raise self._structured_failure(ACCLError(
            ErrorCode.RANK_EVICTED, context, details=details,
        ))

    def _barrier_root(self, comm: Communicator) -> int:
        """The barrier's internal gather root, re-routed around demoted
        stragglers where topology allows.  SPMD-uniform: the decision
        derives from the EXCHANGED slow_rank verdict (the shared judge
        on board-anchored tiers) and is latched per (comm, call index)
        on the shared demotion ledger — the first rank to a call index
        decides, every other rank reads the same decision.  Wire tiers
        (pairwise verdicts) and unarmed handles keep the stock root."""
        mv = self._membership
        if (
            not mv.elastic or mv.ledger is None or self._monitor is None
            or not self._monitor.tracker.shared_judge
        ):
            return 0
        seq = self._demote_seq.get(comm.id, 0)
        self._demote_seq[comm.id] = seq + 1
        judge = self._monitor.tracker.judge
        slow = judge.slow_ranks(comm.id)
        # recovery evidence pre-computed OUTSIDE the ledger lock (the
        # judge takes its own lock; no cross-family hold)
        candidates = mv.ledger.candidates(comm.id) | set(slow)
        recovered = {
            r: judge.recovered(comm.id, r) for r in sorted(candidates)
        }
        decision = mv.demote_decision(
            comm.id, comm.size, seq, slow, recovered
        )
        for r in decision.get("restored", ()):
            # re-admission clears the standing verdict so the health
            # map's suspect_slow annotation lifts with the demotion
            judge.clear_slow(comm.id, r)
            self._health_events.note(r, "demoted", "restored")
            if self._telemetry is not None:
                self._telemetry.metrics.inc(
                    "accl_membership_restores_total"
                )
        for r in decision.get("demoted", ()):
            if (comm.id, r) not in self._demoted_seen:
                self._demoted_seen.add((comm.id, r))
                self._health_events.note(r, "ok", "demoted")
                if self._telemetry is not None:
                    self._telemetry.metrics.inc(
                        "accl_membership_demotions_total"
                    )
        for r in decision.get("restored", ()):
            self._demoted_seen.discard((comm.id, r))
        return int(decision.get("root", 0))

    # -- QoS arbiter plane (accl_tpu.arbiter) ---------------------------------
    def set_arbiter(self, enabled: bool = True) -> None:
        """Arm (or disarm) the multi-tenant QoS arbiter on this
        handle's shared arbiter: registered tenants' collectives pass
        the deficit-weighted round-robin admission queue at intake, and
        quota throttles apply.  Collective by contract: every rank of
        every participating group arms it at the same call-sequence
        point (the set_elastic discipline) — admission delays are
        uniform per (comm, call index), so a lone armed rank would
        merely pace itself.  Also read from ``ACCL_ARBITER=1`` at
        handle construction."""
        self._arbiter.armed = bool(enabled)

    def set_tenant_class(self, tenant_class, comm=None,
                         weight: Optional[int] = None,
                         name: Optional[str] = None):
        """Register communicator ``comm`` (default: the world) as a
        tenant of the QoS arbiter with priority class ``tenant_class``
        (:class:`~accl_tpu.arbiter.TenantClass`, its name, or its int)
        and an optional explicit DRR ``weight`` (default: the class
        weight).  Collective by contract: every rank of the
        communicator registers with the same class/weight at the same
        point of its call sequence — the write rides the CONFIG drain
        path like every other register, so nothing launched under the
        old class is still in flight when it returns.  Returns the
        registered tenant record."""
        from .arbiter import coerce_class

        comm = comm or self._world
        cls = coerce_class(tenant_class)
        self._config(
            ConfigFunction.SET_TENANT_CLASS, int(cls), key=comm.id
        )
        if weight is not None:
            self._config(
                ConfigFunction.SET_TENANT_WEIGHT, int(weight), key=comm.id
            )
        return self._arbiter.register(
            comm.id, name=name, cls=cls, weight=weight, world=comm.size
        )

    def set_tenant_quota(self, comm=None,
                         window_share: Optional[int] = None,
                         ring_slots: Optional[int] = None,
                         bytes_per_s: Optional[float] = None):
        """Quota writes for tenant ``comm`` (default: the world), at
        the two places cross-tenant contention actually lives plus the
        wire-rate cap:

        * ``window_share`` — this tenant's per-rank share of the
          overlap plane's in-flight window depth (device tiers bound
          the tenant's launched-but-incomplete calls by it; the
          arbiter bounds admissions by ``share x world`` everywhere);
        * ``ring_slots`` — this tenant's slot budget per command-ring
          refill window (gang tier): its warm batches chunk into
          windows of at most this many slots, so a flooder pays more
          refill doorbells instead of monopolizing the ring;
        * ``bytes_per_s`` — optional token-bucket wire-rate cap
          (0 clears it), enforced at admission with the throttle
          latched per (comm, call index).

        Collective by contract, like every config write.  Returns the
        tenant record, or None when ``comm`` was never registered."""
        comm = comm or self._world
        if window_share is not None:
            self._config(
                ConfigFunction.SET_TENANT_WINDOW_SHARE,
                int(window_share), key=comm.id,
            )
        if ring_slots is not None:
            self._config(
                ConfigFunction.SET_TENANT_RING_SLOTS,
                int(ring_slots), key=comm.id,
            )
        if bytes_per_s is not None:
            self._config(
                ConfigFunction.SET_TENANT_RATE,
                float(bytes_per_s), key=comm.id,
            )
        return self._arbiter.set_quota(
            comm.id, window_share=window_share, ring_slots=ring_slots,
            bytes_per_s=bytes_per_s,
        )

    def _arbiter_gate(self, options: CallOptions) -> None:
        """Admission intake (the client_arbiter analog): a registered
        tenant's collective passes the shared DRR queue before engine
        dispatch — out-of-credit or over-quota tenants wait (bounded)
        here, absorbing backpressure at the facade instead of inside
        the fabric.  One attribute check when disarmed.  The decision
        record (class, throttle) is latched per (comm, call index) on
        the shared arbiter, so every rank admits the same call with
        the same delay."""
        arb = self._arbiter
        self._call_tls.qos = None
        comm = options.comm
        if (
            not arb.armed or comm is None
            or options.op not in self._ARBITER_OPS
        ):
            return
        # only COLLECTIVES consume the shared per-comm call index (the
        # latch key): p2p is rank-asymmetric by design, and letting it
        # bump the counter would desync collective indices across ranks
        # (seq -1 = admit without latching; the p2p side charges its
        # own bucket share directly)
        if options.op in self._CONTRACT_OPS:
            seq = self._arbiter_seq.get(comm.id, 0)
            self._arbiter_seq[comm.id] = seq + 1
        else:
            seq = -1
        cfg = options.arithcfg
        cost = options.count * (
            cfg.uncompressed_elem_bytes if cfg is not None else 1
        )
        # calls queued into an open batch are charged, not paced: their
        # dispatch unit is the flushed window (the ring slot budget is
        # that unit's quota), and holding an admission slot for a call
        # that cannot complete before its batch flushes would wedge any
        # batch deeper than the tenant's limit
        self._call_tls.qos = arb.admit(
            comm.id, seq, cost, self._timeout_s,
            self._pending is None, self._arbiter_owner,
        )

    def _arbiter_async(self, options: CallOptions, req: Request,
                       dec: dict) -> None:
        """Completion hook for an ASYNC admitted call: free the
        tenant's outstanding-admission slot and fold the call's latency
        into its live histogram when the request completes.  Sync calls
        account inline on the calling thread instead
        (:meth:`_arbiter_done`) — a done-callback takes the arbiter
        lock on the completer thread at exactly the moment the caller's
        next admission wants it, and that contention measured ~25 us
        per warm call."""
        arb = self._arbiter
        comm_id = options.comm.id
        paced = bool(dec.get("paced"))
        owner = self._arbiter_owner

        def _done(arb=arb, comm_id=comm_id, req=req, paced=paced,
                  owner=owner):
            # charged-only (batched) calls hold no slot: release=False
            arb.complete(
                comm_id, req.get_duration_ns(), owner=owner,
                release=paced,
            )

        req.add_done_callback(_done)

    def _arbiter_done(self, options: CallOptions, req: Request,
                      dec: dict) -> None:
        """Inline completion accounting for a SYNC admitted call (the
        calling thread, after its wait) — no cross-thread lock handoff
        on the warm path."""
        self._arbiter.complete(
            options.comm.id, req.get_duration_ns(),
            owner=self._arbiter_owner, release=bool(dec.get("paced")),
        )
        if self._arbiter.ledger is not None:
            # periodic (not per-call) cross-process weight exchange: KV
            # round-trips are milliseconds, admissions are microseconds
            self._arbiter_exchange_ctr += 1
            if self._arbiter_exchange_ctr % 32 == 1:
                self.arbiter_ledger_exchange()

    def arbiter_ledger_exchange(self) -> Optional[dict]:
        """Run one cross-process tenant-weight exchange through the
        engine's KV plane and re-derive fabric-share rates; returns the
        exchange counters, or None when no ledger is attached or the KV
        plane is unreachable (exchange is advisory — admission never
        blocks on it)."""
        if self._arbiter.ledger is None:
            return None
        try:
            kv = self.engine.arbiter_kv()
        except Exception:
            return None
        notfound = getattr(self.engine, "_is_notfound", None)
        try:
            return self._arbiter.ledger_exchange(kv, is_notfound=notfound)
        except Exception:
            self._arbiter.ledger.errors += 1
            return None

    def set_retry_policy(self, limit: int, backoff_s: float = 0.05) -> None:
        """Arm (or with ``limit=0`` disarm) the eager retransmit protocol
        on the emulated tiers: each eager segment requests an ACK and is
        re-sent up to ``limit`` times with exponential backoff starting at
        ``backoff_s`` while unacked; receiver-side seqn dedup keeps the
        duplicates value-correct.  Retry exhaustion marks the peer dead in
        the health map (``capabilities()["health"]``) so later collectives
        fail fast instead of hanging.  Device tiers accept and store the
        knobs (their fabric is XLA's; there is no host retransmit)."""
        self._config(ConfigFunction.SET_RETRY_LIMIT, limit)
        self._config(ConfigFunction.SET_RETRY_BACKOFF, backoff_s)

    def set_tuning(self, key, value) -> None:
        """Write a runtime tuning register (ref configure_tuning_parameters,
        accl.cpp:1198-1208): flat-vs-tree thresholds on the engine tiers,
        allreduce algorithm / ring segmentation on the device tier.

        ``key``: a :class:`TuningKey`, its name, or its int value.
        ``value``: a number, or an algorithm name ("xla" / "ring" /
        "pallas_ring" / "pallas_ring_bidir") for ``ALLREDUCE_ALGORITHM``
        ("xla" / "pallas_ring" for the rooted registers).
        """
        from .constants import AllreduceAlgorithm, TuningKey

        if isinstance(key, str):
            try:
                key = TuningKey[key.upper()]
            except KeyError:
                raise ValueError(
                    f"unknown tuning key {key!r}; valid: "
                    f"{[k.name for k in TuningKey]}"
                ) from None
        else:
            key = TuningKey(key)
        if isinstance(value, str):
            if key in (
                TuningKey.WIRE_DTYPE,
                TuningKey.WIRE_DTYPE_ICI,
                TuningKey.WIRE_DTYPE_DCN,
            ):
                from .tuning import wire_dtype_value

                value = wire_dtype_value(value)
            else:
                try:
                    value = AllreduceAlgorithm[value.upper()]
                except KeyError:
                    raise ValueError(
                        f"unknown algorithm {value!r}; valid: "
                        f"{[a.name.lower() for a in AllreduceAlgorithm]}"
                    ) from None
        self._config(ConfigFunction.SET_TUNING, float(value), key=int(key))

    def load_tuning_plan(self, plan, strict: bool = True,
                         apply_defaults: bool = True):
        """Adopt a measured :class:`~accl_tpu.tuning.TuningPlan` (object
        or JSON path): plan *defaults* apply immediately through the
        SET_TUNING / SET_MAX_EAGER_SIZE config path (every engine tier
        honors those registers), and the per-size-bucket register
        overrides ride the plan cache — each collective call is
        dispatched with the register set measured best for its size
        bucket.  ``strict=False`` (the ``ACCL_TUNING_PLAN`` env path)
        skips a plan whose world size doesn't match instead of raising.
        ``apply_defaults=False`` adopts only the per-bucket overlays —
        no register writes — for callers that know the defaults are
        already in effect (the paired A/B sweep's weightless flip).

        Returns the adopted plan, or None when skipped."""
        from .tuning import TuningPlan

        if not isinstance(plan, TuningPlan):
            plan = TuningPlan.load(os.fspath(plan))
        if plan.world and plan.world != self._world.size:
            if strict:
                raise ValueError(
                    f"tuning plan is for world={plan.world}, "
                    f"this group is world={self._world.size}"
                )
            return None
        if plan.topology is not None:
            # topology provenance: a hierarchical / per-link-class wire
            # winner was raced on a specific link-class layout — adopting
            # it on a different one (or on a flat group) would dispatch
            # decompositions the measurement never covered
            here = (
                None if self._world.topology is None
                else self._world.topology.signature()
            )
            if plan.topology != here:
                if strict:
                    raise ValueError(
                        f"tuning plan was raced on topology "
                        f"{plan.topology!r}, this group's link-class "
                        f"layout is {here!r}"
                    )
                return None
        if apply_defaults:
            for name, val in sorted((plan.defaults or {}).items()):
                if name == "max_eager_size":
                    self.set_max_eager_size(int(val))
                else:
                    self.set_tuning(name, val)
        self._tuning_plan = plan
        self._plans.invalidate("load_tuning_plan")
        return plan

    def unload_tuning_plan(self, restore_defaults: bool = True) -> None:
        """Drop the adopted TuningPlan; by default also put every
        register it may have touched back to stock.
        ``restore_defaults=False`` drops only the overlays (the paired
        A/B sweep's weightless flip)."""
        if self._tuning_plan is None:
            return
        self._tuning_plan = None
        if restore_defaults:
            from .tuning import REGISTER_DEFAULTS

            self.set_max_eager_size(REGISTER_DEFAULTS["max_eager_size"])
            for name, val in sorted(REGISTER_DEFAULTS.items()):
                if name != "max_eager_size":
                    self.set_tuning(name, val)
        self._plans.invalidate("unload_tuning_plan")

    # -- call-plan pool (accl_tpu.plans) -------------------------------------
    def _engine_tuning(self) -> dict:
        """The tuning table backing this rank's engine (engine-held on
        the emulator/dist tiers, gang-held on the XLA tier; {} on tiers
        whose registers live out of Python, e.g. the native C engine)."""
        tuning = getattr(self.engine, "tuning", None)
        if tuning is None:
            gang = getattr(self.engine, "gang", None)
            tuning = getattr(gang, "tuning", None)
        return tuning if tuning is not None else {}

    def _algorithm_snapshot(self, op: Operation):
        """The algorithm-register value steering ``op`` right now, read
        from whichever tuning table backs this rank's engine (the
        reference reads its exchange-memory registers per call; we read
        once per plan)."""
        tuning = self._engine_tuning()
        if not tuning:
            return None
        if op == Operation.ALLREDUCE:
            return tuning.get("allreduce_algorithm")
        return tuning.get(f"{op.name.lower()}_algorithm")

    #: collectives eligible for host-level segmented pipelining: width-1
    #: elementwise ops where a contiguous operand slice maps onto the
    #: same contiguous result slice (allgather/alltoall-family outputs
    #: interleave rank-major and cannot be split this way) AND whose
    #: operands are buffers on EVERY rank.  REDUCE is excluded: its
    #: per-rank stream-operand overload means one rank could split while
    #: a streaming peer cannot — the registers are SPMD-uniform but the
    #: operand kinds are not, and a half-split collective deadlocks.
    _PIPELINE_OPS = frozenset((Operation.ALLREDUCE, Operation.BCAST))

    #: collectives the per-bucket WIRE_DTYPE verdict may compress
    #: automatically: the reduction whose wire bytes dominate training
    #: steps (and the one the error-feedback plane covers).  Explicit
    #: ``compress_dtype=`` keeps working on every op that accepts it.
    _WIRE_VERDICT_OPS = frozenset((Operation.ALLREDUCE,))

    @annotated("accl.facade::plan")
    def _plan_for(
        self,
        op: Operation,
        comm: Communicator,
        dtype: DataType,
        count: int,
        compress_dtype,
        host: HostFlags,
        extra: tuple = (),
    ) -> CollectivePlan:
        """The cached-dispatch lookup: one :class:`CollectivePlan` per
        (op, communicator id+epoch, dtype, size bucket, options
        fingerprint).  A hit returns everything a call previously
        resolved — arithcfg, compression flags, wire dtype, protocol
        verdict, algorithm snapshot, per-bucket tuning overlay, engine
        prepared state — so the warm path constructs CallOptions and
        dispatches with no re-derivation."""
        cdt = None if compress_dtype is None else _as_datatype(compress_dtype)
        bucket = size_bucket(count)
        # topology plane: the communicator's topology signature is a
        # plan-key axis (set_topology re-keys every cached plan like an
        # epoch bump), and the comm's uniform link class steers the
        # per-class wire verdict below.  The signature sits BEFORE
        # ``extra`` — CollectivePlan.fuse reads key[-1] as the extra
        # tuple.
        topo = comm.topology
        tsig = None if topo is None else topo.signature()
        lc = None if topo is None else topo.comm_link_class()
        key = (
            op, comm.id, comm.epoch, dtype, bucket, cdt, int(host),
            tsig, extra,
        )
        plan, hit = self._plans.get_with_flag(key)
        self._call_tls.plan_hit = hit  # stamped onto this call's record
        if plan is not None:
            return plan
        overlay = None
        if self._tuning_plan is not None:
            overlay = self._tuning_plan.registers_for(
                op.name.lower(), bucket
            ) or None
        # quantized wire plane: when the caller requested no explicit
        # compress_dtype, the per-bucket WIRE_DTYPE register (TuningPlan
        # overlay over the engine's global table) decides the wire lane
        # — off / f16 / bf16 / fp8 / int8 as a measured verdict, raced
        # by the autotuner like any algorithm register.  SPMD-uniform:
        # registers and overlays are identical across ranks, and the
        # verdict is baked into the cached plan (register writes and
        # plan loads invalidate the pool).  Scoped to the wire-verdict
        # op set; an operand dtype with no registered arith pair for
        # the verdict dtype keeps the uncompressed wire.
        # fused-slot calls keep the uncompressed wire: the ring planner
        # refuses compressed fused slots (fused_slot_eligible), and a
        # verdict-compressed plan would force every fused call into the
        # counted host decomposition
        if cdt is None and op in self._WIRE_VERDICT_OPS and (
            "fuse" not in extra
        ):
            # per-link-class ladder: a comm whose wire is uniformly ICI
            # or DCN consults its class register first (overlay over
            # table, like the generic); 0 — or a mixed-class comm —
            # defers to the generic wire_dtype register.  fp8 on the
            # slow DCN with full width on ICI is exactly two registers.
            from .topology import LinkClass as _LC

            reg = {_LC.ICI: "wire_dtype_ici", _LC.DCN: "wire_dtype_dcn"}.get(lc)
            wd = None
            if reg is not None:
                wd = (overlay or {}).get(reg)
                if wd is None:
                    wd = self._engine_tuning().get(reg, 0)
                if not int(wd or 0):
                    wd = None
            if wd is None:
                wd = (overlay or {}).get("wire_dtype")
            if wd is None:
                wd = self._engine_tuning().get("wire_dtype", 0)
            try:
                verdict = DataType(int(wd or 0))
            except ValueError:
                verdict = DataType.NONE
            # the verdict ops carry the reduce function as extra[0]:
            # a lane whose arith pair cannot run this call's function
            # (the SUM-only int8 pair under a MAX allreduce) keeps the
            # uncompressed wire instead of breaking a call that worked
            # before the register was armed
            fn_ok = True
            if extra and (dtype, verdict) in self._arith:
                try:
                    fn_ok = self._arith[(dtype, verdict)].supports(
                        ReduceFunction(int(extra[0]))
                    )
                except (ValueError, TypeError):
                    fn_ok = True
            if (
                verdict != DataType.NONE
                and verdict != dtype
                and _wire.is_wire_dtype(verdict)
                and (dtype, verdict) in self._arith
                and fn_ok
            ):
                cdt = verdict
        cfg, flags = self._resolve_arithcfg(dtype, cdt)
        wire = cfg.compressed if flags & CompressionFlags.ETH_COMPRESSED else None
        eager_limit = (overlay or {}).get(
            "max_eager_size", self._max_eager_size
        )
        # the protocol verdict is only cached when it holds for the WHOLE
        # bucket (the threshold may fall inside [2^b, 2^(b+1)) bytes);
        # None = mixed — engines always re-derive per call, this field is
        # the introspection/debug snapshot
        lo = (1 << bucket) * dtype_size(dtype)
        hi = ((1 << (bucket + 1)) - 1) * dtype_size(dtype)
        eager = True if hi <= eager_limit else (
            False if lo > eager_limit else None
        )
        # overlap plane: the segmented-pipelining verdict, resolved once
        # per plan from the per-bucket TuningPlan overlay over the global
        # registers — payloads above pipeline_threshold bytes split into
        # ring_segments pipelined sub-launches (accl_tpu.overlap).  The
        # register set is identical across ranks (collective SET_TUNING /
        # shared plan file), so the split stays SPMD-uniform.
        pthresh, psegs = 0, 1
        if op in self._PIPELINE_OPS:
            table = self._engine_tuning()
            pthresh = int((overlay or {}).get(
                "pipeline_threshold", table.get("pipeline_threshold", 0)
            ) or 0)
            psegs = int((overlay or {}).get(
                "ring_segments", table.get("ring_segments", 1)
            ) or 1)
        # topology plane: the hierarchical-dispatch verdict — the
        # HIERARCHICAL register (overlay over table, raced by the
        # autotuner) armed AND the topology shape actually decomposes
        # this op.  The count-divisibility half of eligibility is
        # re-checked per call in the entry point (counts vary within a
        # bucket); this is the bucket-wide register half.
        hier = False
        if topo is not None and "fuse" not in extra:
            from . import hierarchical as _hier

            opname = op.name.lower()
            if opname in _hier.HIER_OPS and _hier.multi_slice(topo):
                hv = (overlay or {}).get("hierarchical")
                if hv is None:
                    hv = self._engine_tuning().get("hierarchical", 0)
                hier = bool(int(hv or 0))
        plan = CollectivePlan(
            key, cfg, flags,
            wire_dtype=wire,
            bucket=bucket,
            eager=eager,
            algorithm=self._algorithm_snapshot(op),
            tuning=overlay,
            pipeline_threshold=pthresh,
            pipeline_segments=psegs,
            hierarchical=hier,
            link_class=lc,
        )
        return self._plans.store(plan)

    # -- buffer factories (ref ACCL::create_buffer family) -------------------
    def create_buffer(
        self, count: int, dtype: DTypeLike, host_only: bool = False
    ) -> BaseBuffer:
        """Backend-appropriate buffer: HBM-resident jax.Array on device
        tiers, host pair on the emulator (ref ACCL::create_buffer
        dispatching to XRTBuffer/SimBuffer)."""
        return self.engine.create_buffer(
            count, _as_datatype(dtype), host_only=host_only
        )

    def create_buffer_from(
        self, array: np.ndarray, host_only: bool = False
    ) -> BaseBuffer:
        """Wrap an existing host array: the buffer's host side ALIASES
        ``array`` when it is already contiguous 1-D (mutate + sync to
        update the device side, ref Buffer-from-pointer ctor), and the
        device side is synced on return."""
        array = np.ascontiguousarray(array).reshape(-1)
        return self.engine.create_buffer(
            array.size, numpy_to_dtype(array.dtype),
            host_only=host_only, data=array,
        )

    # -- communicator management --------------------------------------------
    def create_communicator(
        self, members: Sequence[int], base: Optional[Communicator] = None
    ) -> Optional[Communicator]:
        """Collective: every member calls with the same ``members`` list.

        The new communicator id is derived deterministically from the parent
        id + membership, so all ranks (including separate processes on the
        socket tier) agree without extra wire traffic.
        """
        base = base or self._world
        comm_id = zlib.crc32(repr((base.id, tuple(members))).encode()) & 0x7FFFFFFF
        comm = base.split(members, comm_id=comm_id)
        if comm is not None:
            self._communicators.append(comm)
            if comm.topology is not None:
                # split() derived the subcomm's topology from the base;
                # hand it to the fabric so paced classes / per-class
                # byte counters stay truthful in the subcomm's rank
                # space too
                fabric = getattr(self.engine, "fabric", None)
                if fabric is not None and hasattr(
                    fabric, "register_topology"
                ):
                    fabric.register_topology(comm.id, comm.topology)
            if self._monitor is not None:
                # straggler windows on the subcomm piggyback like the
                # world comm's; membership registered up front so a
                # peer's early claims resolve in the subcomm's rank
                # space (board tiers need no fabric registration — the
                # shared judge keys on comm id)
                self._monitor.tracker.begin_comm(
                    comm.id, comm.local_rank, comm.size
                )
                fabric = getattr(self.engine, "fabric", None)
                if fabric is not None and hasattr(fabric, "register_skew"):
                    fabric.register_skew(
                        comm.id, comm.local_rank, self._monitor.tracker
                    )
            if self._telemetry is not None:
                fabric = getattr(self.engine, "fabric", None)
                if fabric is not None and hasattr(
                    fabric, "register_trace"
                ):
                    fabric.register_trace(comm.id, comm.local_rank, self)
            if self._contract is not None:
                # register membership + fold a begin marker into the
                # digest stream (a rank that re-creates a subcomm its
                # peers keep using diverges at the next window — the
                # epoch-skew failure) and arm outbound wire stamping
                self._contract.begin_comm(
                    comm.id, comm.local_rank,
                    tuple(r.session for r in comm.ranks),
                )
                fabric = getattr(self.engine, "fabric", None)
                if fabric is not None and hasattr(
                    fabric, "register_contract"
                ):
                    fabric.register_contract(
                        comm.id, comm.local_rank, self._contract
                    )
        return comm

    # -- topology plane (accl_tpu.topology) ----------------------------------
    @property
    def topology(self):
        """The world communicator's :class:`~accl_tpu.topology.Topology`
        (None = flat)."""
        return self._world.topology

    def set_topology(self, topology,
                     comm: Optional[Communicator] = None) -> None:
        """Attach (or with ``None`` detach) a slice/link-class
        :class:`~accl_tpu.topology.Topology` to ``comm`` (default: the
        world).  Collective by contract — every rank must attach an
        EQUAL descriptor, exactly like a register write: the topology
        signature is a plan-key axis and the hierarchical decomposition
        derives subcomms from it, so a skewed attach diverges dispatch.
        Cached plans and derived subcomms drop; the fabric's paced
        link-class model re-registers."""
        comm = comm or self._world
        if topology is not None and topology.world != comm.size:
            raise ValueError(
                f"topology describes world={topology.world}, "
                f"communicator {comm.id} is size={comm.size}"
            )
        comm.topology = topology
        comm._full_topology = None
        self._plans.invalidate("set_topology")
        self._hier_comms = {
            k: v for k, v in self._hier_comms.items() if k[0] != comm.id
        }
        fabric = getattr(self.engine, "fabric", None)
        if fabric is not None and hasattr(fabric, "register_topology"):
            fabric.register_topology(comm.id, topology)

    # -- call plumbing -------------------------------------------------------
    def _resolve_arithcfg(
        self, dtype: DataType, compress_dtype: Optional[DTypeLike]
    ) -> tuple:
        """(arithcfg, compression flags) from operand dtype + requested wire
        compression (ref prepare_call's arithcfg address resolution)."""
        if compress_dtype is None:
            key = (dtype, dtype)
            flags = CompressionFlags.NO_COMPRESSION
        else:
            cdt = _as_datatype(compress_dtype)
            key = (dtype, cdt)
            flags = (
                CompressionFlags.ETH_COMPRESSED
                if cdt != dtype
                else CompressionFlags.NO_COMPRESSION
            )
        if key not in self._arith:
            raise ACCLError(
                ErrorCode.INVALID_DTYPE,
                f"no arithmetic config for {key[0].name}->{key[1].name}",
                details={
                    "dtype": key[0].name,
                    "compressed": key[1].name,
                    "available": sorted(
                        f"{u.name}->{c.name}" for u, c in self._arith
                    ),
                },
            )
        return self._arith[key], flags

    def _host_flags(self, *bufs: Optional[BaseBuffer]) -> HostFlags:
        flags = HostFlags.NO_HOST
        slots = (HostFlags.OP0_HOST, HostFlags.OP1_HOST, HostFlags.RES_HOST)
        for slot, buf in zip(slots, bufs):
            if buf is not None and buf.is_host_only:
                flags |= slot
        return flags

    # -- batched dispatch (single-interaction command queue) -----------------
    def begin_batch(self) -> None:
        """Open a batch: subsequent calls queue instead of dispatching,
        until :meth:`flush` (explicit, or automatic on a queued request's
        ``wait``/a sync call/:meth:`end_batch`).  On the device tiers a
        flushed batch of N collectives executes as ONE fused program —
        one device interaction — so a training step that issues its
        collectives inside ``with accl.batch():`` pays the host's
        dispatch cost once, not N times.  Collective by contract: every
        rank of the communicator must open/flush batches at the same
        points of its call sequence (the SPMD ordering contract, extended
        to batches).
        """
        self._batch_depth += 1
        if self._pending is None:
            from .request import CommandQueue

            self._pending = CommandQueue()
            # batch parent span id: deterministic from the per-handle
            # batch counter (batches are collective by contract, so
            # every rank's counter agrees) — queued calls' flow events
            # step on it, nesting the fused window under one parent
            self._batch_ctr += 1
            self._batch_trace = collective_trace_id(
                "__batch__", 0, self._trace_gen, self._batch_ctr
            )

    def flush(self) -> None:
        """Dispatch everything queued in the open batch, then drain the
        overlap plane: when :meth:`flush` returns, every DEVICE call
        this handle launched has completed (the in-flight window's
        explicit drain point; ``wait()``, barriers, config writes and
        ``soft_reset`` are the others).  Scope: the gang tier's window
        and the dist tier's executor backlog — note the dist backlog is
        the WHOLE serialized program stream, so a pending blocking op
        (an async ``recv`` whose peer has not sent yet) gates the drain
        until it completes or times out, exactly as it gates every
        later call on that tier.  On the emulator/native tiers requests
        complete from their own schedulers independent of the launch
        path — ``flush`` does not wait for those (a pending ``recv``
        may legitimately outlive it), use ``Request.wait`` per call.
        Still safe inside a batch — the
        batch stays open for further calls; :meth:`end_batch` closes it."""
        # the batch counter is equal on every rank (batches are
        # collective by contract): the rank threads' spans of one
        # window share it
        batch = self._batch_ctr
        with annotate("accl.batch::flush", comm=self._world.id, batch=batch):
            self._dispatch_pending()
            # overlap drain point: launched-but-incomplete device calls
            # finish before flush() returns (no-op on windowless tiers).
            # A failed (timed-out) drain must SURFACE — callers trust the
            # documented contract and read result buffers next
            with annotate("accl.batch::drain", batch=batch):
                drained = self.engine.drain_inflight()
            if not drained:
                raise self._deadlock_error("flush")

    def _dispatch_pending(self) -> None:
        """Dispatch the open batch WITHOUT draining the in-flight
        window: the auto-dispatch hook behind ``Request.wait``/``test``
        on queued calls — ``test`` stays a (near) non-blocking probe and
        ``wait`` synchronizes on its own request, not the whole window
        (:meth:`flush` is the drain point)."""
        q = self._pending
        if q is not None:
            items = q.drain()
            if items:
                # disarm the auto-dispatch hooks: once dispatched, a
                # later wait()/test() on these requests must not flush
                # whatever UNRELATED batch happens to be open at that
                # point
                for _, req in items:
                    req._pre_wait = None
                with annotate("accl.batch::submit", batch=self._batch_ctr,
                              n=len(items)):
                    self.engine.start_batch(items)

    def end_batch(self) -> None:
        """Close the (outermost) batch: flush queued work and return to
        immediate dispatch.  Nested ``batch()`` contexts only decrement
        the depth — the outer batch stays intact."""
        if self._batch_depth > 1:
            self._batch_depth -= 1
            return
        self._batch_depth = 0
        self.flush()
        self._pending = None
        self._batch_trace = None

    def batch(self):
        """Context manager form::

            with accl.batch():
                accl.allreduce(a, b, n, run_async=True)
                accl.allgather(c, d, n, run_async=True)
            # exit flushes: both collectives dispatched as one program
        """
        import contextlib

        @contextlib.contextmanager
        def _cm():
            self.begin_batch()
            try:
                yield self
            finally:
                self.end_batch()

        return _cm()

    # -- causal trace plane (accl_tpu.telemetry flows) -----------------------
    def _assign_trace(self, options: CallOptions) -> tuple:
        """(trace_id, flow_phase, parent_id) for one call at intake.

        Collectives derive ``collective_trace_id`` from the per-comm
        intake counter (SPMD-uniform: every rank issues the contract
        ops in matching order, the invariant the contract plane
        verifies); plain SEND/RECV derive ``p2p_trace_id`` from the
        directed channel's match counter (sends and receives on one
        (comm, src, dst, tag) channel match strictly in order).
        Stream-port p2p variants get no flow phase — their far end
        never posts a matching CallRecord.  The flow phase is this
        rank's role in the merged flow: lowest comm rank starts (s),
        highest finishes (f), middles step (t)."""
        comm = options.comm
        if comm is None:
            return None, None, None
        parent = getattr(self._call_tls, "parent_trace", None)
        if parent is None and self._pending is not None:
            parent = self._batch_trace
        op = options.op
        if op in self._CONTRACT_OPS:
            tid, phase = self._derive_collective_trace(
                op.name.lower(), comm
            )
            return tid, phase, parent
        if op in (Operation.SEND, Operation.RECV):
            if op == Operation.SEND:
                src, dst = comm.local_rank, options.root_dst
            else:
                src, dst = options.root_src, comm.local_rank
            key = (comm.id, src, dst, options.tag, int(options.stream))
            seqn = self._p2p_seq.get(key, 0)
            self._p2p_seq[key] = seqn + 1
            tid = p2p_trace_id(
                comm.id, src, dst, options.tag, seqn,
                stream=int(options.stream),
            )
            self._trace_last[comm.id] = tid
            phase = None
            if options.stream == StreamFlags.NO_STREAM:
                phase = "s" if op == Operation.SEND else "f"
            return tid, phase, parent
        return None, None, parent

    def _derive_collective_trace(self, op_name: str, comm) -> tuple:
        """(trace_id, flow_phase) for one collective: consume the
        comm's SPMD-uniform intake counter, derive the deterministic
        id, stamp the wire-piggyback slot, and pick this rank's flow
        role.  THE one implementation — single calls and pipelined
        aggregates must share it, or their cross-rank ids/phases
        silently diverge (the exact failure flow validation reports)."""
        seqn = self._trace_seq.get(comm.id, 0)
        self._trace_seq[comm.id] = seqn + 1
        tid = collective_trace_id(
            op_name, comm.id, self._trace_gen, seqn
        )
        self._trace_last[comm.id] = tid
        if comm.size < 2:
            phase = None
        elif comm.local_rank == 0:
            phase = "s"
        elif comm.local_rank == comm.size - 1:
            phase = "f"
        else:
            phase = "t"
        return tid, phase

    def trace_stamp(self, comm_id: int) -> int:
        """The wire piggyback provider (``Fabric.register_trace``):
        this rank's latest intake trace id on the communicator, 0 when
        none.  Lock-free read on the per-send hot path — values are
        ints replaced whole, a racing reader sees old or new (both
        valid window-grade attribution, like the skew stamp)."""
        return self._trace_last.get(comm_id, 0)

    def _call_meta(self, options: CallOptions,
                   qos: Optional[dict] = None) -> dict:
        """The CallRecord facts known at launch (accl_tpu.telemetry):
        resolved once per call — a handful of attribute reads, no device
        work — and carried to Request.complete by Telemetry.attach.
        ``qos`` is the admission decision (passed explicitly — the tls
        slot is already consumed by the time meta is built)."""
        comm = options.comm
        plan = options.plan
        dt = options.arithcfg.uncompressed if options.arithcfg else None
        trace_id, trace_phase, parent_id = self._assign_trace(options)
        return {
            # arbiter plane: which tenant admitted this call (None when
            # the arbiter is disarmed / the comm unregistered)
            "tenant": qos["tenant"] if qos else None,
            "trace_id": trace_id,
            "trace_phase": trace_phase,
            "parent_id": parent_id,
            "op": options.op.name.lower(),
            "comm": comm.id if comm is not None else None,
            "epoch": comm.epoch if comm is not None else None,
            # comm-relative identity for the monitor plane's skew
            # tracker (a subcomm's straggler blame lives in ITS rank
            # space, like every contract-plane rank field)
            "comm_rank": comm.local_rank if comm is not None else None,
            "comm_world": comm.size if comm is not None else None,
            "dtype": dt.name if dt is not None else None,
            "count": options.count,
            "nbytes": (
                options.count * dtype_size(dt) if dt is not None else 0
            ),
            "bucket": (
                plan.bucket if plan is not None
                else size_bucket(options.count)
            ),
            "algorithm": plan.algorithm if plan is not None else None,
            "plan_hit": (
                getattr(self._call_tls, "plan_hit", None)
                if plan is not None else None
            ),
            "eager": plan.eager if plan is not None else None,
        }

    #: structured-failure codes the postmortem plane covers: every
    #: facade raise of one of these reaches the BlackBox hook (machine-
    #: checked by acclint's postmortem-path rule)
    _POSTMORTEM_CODES = (
        ErrorCode.CONTRACT_VIOLATION
        | ErrorCode.RANK_EVICTED
        | ErrorCode.DEADLOCK_SUSPECTED
    )

    def _structured_failure(self, err: ACCLError) -> ACCLError:
        """The postmortem hook every covered structured-failure path
        funnels through: capture an evidence bundle (one per failure —
        latched) and name it in ``ACCLError.details["postmortem"]``.
        No-op (one None/flag check) when the plane is disabled."""
        bb = self._blackbox
        if bb is None or not bb.enabled:
            return err
        if not (err.code & self._POSTMORTEM_CODES):
            return err
        if err.code & ErrorCode.RANK_EVICTED:
            code_name = "RANK_EVICTED"
            # one eviction = one bundle, however many paths observe it
            # (the cutover hook, the intake screen, the post-failure
            # gate): latch on the epoch the eviction HAS ONCE APPLIED —
            # take_cutover bumps the epoch at plan consumption, so a
            # raise observing the confirmed-but-unapplied plan must key
            # one ahead to collapse onto the cutover hook's bundle
            mv = self._membership
            key = (
                "RANK_EVICTED",
                mv.epoch + (1 if mv.cutover_ready() else 0),
            )
        elif err.code & ErrorCode.CONTRACT_VIOLATION:
            code_name = "CONTRACT_VIOLATION"
            key = (code_name, err.details.get("comm"))
        else:
            code_name = "DEADLOCK_SUSPECTED"
            key = (code_name, self._trace_gen)
        path = bb.capture(
            code_name, context=str(err), details=err.details, key=key
        )
        if path is not None:
            err.details["postmortem"] = path
        return err

    def _deadlock_error(self, context: str) -> ACCLError:
        """DEADLOCK_SUSPECTED with the flight-recorder tail attached —
        the watchdog/timeout paths ship their recent history too."""
        details = None
        if self._telemetry is not None:
            self._telemetry.metrics.inc("accl_deadlock_suspected_total")
            details = {"flight_recorder": self._telemetry.tail_dicts()}
        return self._structured_failure(ACCLError(
            ErrorCode.DEADLOCK_SUSPECTED, context, details=details,
        ))

    def _seg_tag(self) -> int:
        """The reserved wire tag for the pipelined segment currently
        being launched on this thread (0 outside a pipelined launch, and
        on fabric-less engines — see _launch_pipelined)."""
        return getattr(self._call_tls, "pipeline_tag", 0) or 0

    def _derive_wire_seed(self, plan, comm: Communicator,
                          op: Operation) -> int:
        """Per-call stochastic-rounding seed for a compressed collective
        (0 = deterministic rounding — the f16/bf16 lanes, and every
        uncompressed call).  Derived from SPMD-uniform facts only (comm
        id + epoch + a per-comm counter every rank advances for the
        same calls — the contract-sequence discipline), so all ranks
        hold the same seed with zero wire bytes; each rank then mixes
        its own rank in at the point of encoding (wire.rank_seed), so
        streams stay independent across ranks.  Scoped to the contract
        collectives: p2p pairs keep deterministic lanes (one-shot
        transfers have no bias accumulation to fight, and a directed-
        channel counter is not worth the machinery)."""
        wire = plan.wire_dtype
        if (
            wire is None
            or op not in self._CONTRACT_OPS
            or not _wire.is_stochastic(wire)
        ):
            return 0
        ctr = self._wire_ctr.get(comm.id, 0)
        self._wire_ctr[comm.id] = ctr + 1
        return _wire.call_seed(comm.id, comm.epoch, ctr, int(wire))

    def set_error_feedback(self, enabled: bool = True) -> None:
        """Arm (or disarm) error-feedback accounting for compressed
        allreduce on this handle: contributions carry the previous
        call's compression residual (``compress(grad + residual)``,
        ``residual = grad_eff - decompress(wire)``) so quantized-wire
        gradient sums converge to the uncompressed series (EF-SGD).
        Collective by contract — every rank of the group arms it at the
        same point (the residual add changes what crosses the wire).
        Residuals live beside the plan cache and clear with it
        (register writes, soft_reset, epoch churn); also armable via
        ``ACCL_ERROR_FEEDBACK=1`` at handle construction.  Opt-in: the
        accounting reads the operand on the host pre-dispatch — a
        per-call cost the default zero-copy warm path must not pay."""
        was = self._error_feedback
        self._error_feedback = bool(enabled)
        if was and not enabled:
            self._residuals.invalidate("error_feedback_off")

    def _error_feedback_operand(
        self, plan, comm: Communicator, sendbuf: BaseBuffer, n: int,
        function: ReduceFunction, seed: int,
    ):
        """The EF pre-dispatch step for one allreduce contribution:
        returns a staging buffer holding ``grad + residual`` (what the
        engine should compress and dispatch), or None when error
        feedback does not apply to this call.  The gate reads only
        SPMD-uniform facts (armed flag, plan wire verdict, reduce
        function) — never buffer identity or rank."""
        wire = plan.wire_dtype
        if (
            not self._error_feedback
            or wire is None
            or function != ReduceFunction.SUM
        ):
            return None
        # Residual identity: (comm, epoch, op, exact count, segment
        # position).  Count — not the pow2 bucket — keys the stream:
        # two same-bucket tensors must never blend residuals (each
        # would inject the OTHER's quantization error and break the EF
        # telescoping sum).  Pipelined segments add their POSITION
        # index (a TLS fact set on every tier — the reserved tag is
        # fabric-only and its call-counter half varies per call, which
        # would orphan residuals every step).  Remaining assumption,
        # documented: one logical gradient stream per (comm, count) —
        # the flat fused-gradient-buffer practice; two distinct
        # equal-count tensors alternating on one comm would still
        # alias.
        seg = getattr(self._call_tls, "pipeline_seg_index", 0)
        # topology plane: residual streams key per LINK CLASS too — a
        # hierarchical decomposition runs the DCN stage under a
        # different wire verdict than its ICI siblings (the per-class
        # ladder), and blending those residuals would inject one lane's
        # quantization error into the other's telescoping sum.  The
        # subcomm axis is already covered by comm.id; the link class
        # covers a topology swap re-classing the SAME comm.  Appended
        # at the END: errorfeedback's epoch migration reconstructs keys
        # as key[0], key[1], key[2:].
        lc = -1
        if comm.topology is not None:
            cls = comm.topology.comm_link_class()
            lc = int(cls) if cls is not None else -1
        key = (comm.id, comm.epoch, Operation.ALLREDUCE, n, seg, lc)
        x = np.asarray(sendbuf.device_view()[:n])
        x_eff = self._residuals.apply(
            key, x.astype(np.float32, copy=False), wire,
            _wire.rank_seed(seed, comm.local_rank),
        )
        tel = self._telemetry
        if tel is not None:
            tel.metrics.inc(
                "accl_compression_ef_updates_total", (wire.name,)
            )
        return self.engine.create_buffer(
            n, sendbuf.dtype, data=x_eff.astype(x.dtype, copy=False)
        )

    def _pipeline_segments_for(self, plan, count: int, dtype) -> int:
        """Sub-launch count for this call, from the plan's cached
        pipelining verdict; 1 when the split does not apply (below
        threshold, disabled registers, or already inside a pipelined
        parent — segments never re-split)."""
        if getattr(self._call_tls, "pipelining", False):
            return 1
        nseg = plan.pipeline_for(count * dtype_size(dtype))
        return min(nseg, count) if count > 0 else 1

    def _launch_pipelined(
        self, op_name: str, plan, comm, count: int, nseg: int,
        run_async: bool, launch_seg, context: str,
    ) -> Optional[Request]:
        """The segmented-pipelining launch: split ``count`` into ``nseg``
        contiguous chunks and fire one async sub-collective per chunk
        back-to-back — host staging of chunk k overlaps device execution
        of chunk k-1 through the engine's in-flight window.  Returns ONE
        aggregate Request that completes when the last segment does
        (first failing segment's retcode + context win); its deferred
        result resolves every segment's parked adoption in issue order.
        """
        base, rem = divmod(count, nseg)
        bounds = []
        start = 0
        for i in range(nseg):
            stop = start + base + (1 if i < rem else 0)
            if stop > start:
                bounds.append((start, stop))
            start = stop

        # On the fabric tiers, concurrent segment sub-collectives of one
        # pipelined call MUST NOT share a (comm, src, tag) matching
        # signature: eager matching is strictly seqn-ordered per peer
        # with no per-task discrimination, and under scheduler stalls a
        # segment task can consume a chunk addressed to its sibling
        # (the test_segmented_pipelining_emulator ~1/25 corruption).
        # Each segment therefore rides a RESERVED tag derived from a
        # per-comm pipelined-call counter — SPMD-uniform, because every
        # rank's registers select the same splits in the same order.
        # Device tiers (no fabric) keep tag 0: their ordering contract
        # is the gang's SPMD seqn slots, and a varying tag would churn
        # their program cache keys for nothing.
        seg_tags = None
        call_idx = self._pipeline_ctr.get(comm.id, 0)
        self._pipeline_ctr[comm.id] = call_idx + 1
        if getattr(self.engine, "fabric", None) is not None:
            seg_tags = [
                pipeline_segment_tag(call_idx, i)
                for i in range(len(bounds))
            ]

        outer = Request(op_name=op_name.upper())
        outer.mark_executing()
        if self._pending is not None:
            # segments queued into an open batch: waiting the aggregate
            # must flush them (the same auto-flush contract single calls
            # carry) — but ONLY while that very batch is still the open
            # one; a later wait() must never flush whatever unrelated
            # batch happens to be open at that point
            batch_q = self._pending

            def _pw(batch_q=batch_q):
                if self._pending is batch_q:
                    self._dispatch_pending()

            outer._pre_wait = _pw
        tel = self._telemetry
        meta = None
        agg_tid = None
        if tel is not None:
            # the aggregate's CallRecord covers the FULL payload; each
            # segment also records itself (honest per-launch history).
            # The aggregate consumes one trace-seq slot like any
            # collective (the split is SPMD-uniform, so every rank's
            # counters stay aligned) and parents its segments' spans.
            agg_tid, agg_phase = self._derive_collective_trace(
                op_name, comm
            )
            dt = plan.arithcfg.uncompressed
            meta = {
                "op": op_name, "comm": comm.id, "epoch": comm.epoch,
                "comm_rank": comm.local_rank, "comm_world": comm.size,
                "dtype": dt.name, "count": count,
                "nbytes": count * dtype_size(dt),
                "bucket": plan.bucket, "algorithm": plan.algorithm,
                "plan_hit": getattr(self._call_tls, "plan_hit", None),
                "eager": plan.eager,
                "trace_id": agg_tid,
                "trace_phase": agg_phase,
                "parent_id": (
                    self._batch_trace if self._pending is not None
                    else None
                ),
            }
        t0 = time.perf_counter_ns()
        self._call_tls.pipelining = True
        self._call_tls.parent_trace = agg_tid
        try:
            inner = []
            for i, (s0, s1) in enumerate(bounds):
                self._call_tls.pipeline_tag = (
                    seg_tags[i] if seg_tags is not None else 0
                )
                # segment POSITION, tier-uniform (device tiers keep tag
                # 0, but the error-feedback residual key still needs
                # per-segment identity — equal-count segments must
                # never blend residual streams)
                self._call_tls.pipeline_seg_index = i
                inner.append(launch_seg(s0, s1))
        finally:
            self._call_tls.pipelining = False
            self._call_tls.pipeline_tag = 0
            self._call_tls.pipeline_seg_index = 0
            self._call_tls.parent_trace = None

        def _resolve(inner=inner):
            for q in inner:
                q.materialize()
            for q in inner:
                if q.get_retcode() != ErrorCode.OK:
                    # a segment's deferred adoption failed after the
                    # aggregate completed OK: raising here downgrades the
                    # aggregate's retcode so check() surfaces it
                    raise RuntimeError(
                        f"pipelined segment failed: "
                        f"{ErrorCode.describe(q.get_retcode())}"
                    )

        outer.defer_result(_resolve)
        if tel is not None:
            tel.attach(outer, meta)
        lock = threading.Lock()
        state = {"left": len(inner)}

        def _seg_done():
            with lock:
                state["left"] -= 1
                if state["left"]:
                    return
            code, ctx = ErrorCode.OK, None
            depth = None
            for q in inner:
                rc = q.get_retcode()
                if rc != ErrorCode.OK and code == ErrorCode.OK:
                    code, ctx = rc, q.error_context
                if q.inflight_depth:
                    depth = max(depth or 0, q.inflight_depth)
            # each SEGMENT already recorded its own overlap_ns — the
            # aggregate must not record the sum again (that would
            # double-count accl_overlap_ns_total vs the window's stats)
            outer.inflight_depth = depth
            outer.complete(
                code, max(time.perf_counter_ns() - t0, 1), context=ctx
            )

        for q in inner:
            q.add_done_callback(_seg_done)
        if run_async:
            return outer
        if not outer.wait(timeout=drain_deadline_s(self._timeout_s)):
            raise self._deadlock_error(context)
        self._check_failed(outer, context)
        return outer

    # -- hierarchical dispatch (accl_tpu.hierarchical) -----------------------
    def _hier_state(self, comm: Communicator) -> dict:
        """The per-(comm id, epoch) cache of derived slice/cross-slice
        subcomms.  An epoch bump (shrink/grow/soft reset) re-derives
        naturally — stale epochs of the same comm are pruned here so
        elastic churn can't grow the cache unboundedly."""
        key = (comm.id, comm.epoch)
        st = self._hier_comms.get(key)
        if st is None:
            for k in [k for k in self._hier_comms if k[0] == comm.id]:
                del self._hier_comms[k]
            st = {}
            self._hier_comms[key] = st
        return st

    def _hier_subcomm(self, comm, st, name, members):
        """Derive (once) the subcomm over ``members`` of ``comm``.
        create_communicator's deterministic ids need zero wire bytes,
        and every member derives the same list from the shared topology
        — the SPMD-uniform subcomm discipline; non-members never call
        (each rank only derives the subcomms it belongs to)."""
        sub = st.get(name)
        if sub is None:
            sub = self.create_communicator(list(members), base=comm)
            st[name] = sub
        return sub

    def _hier_fingerprint(self, op_name, comm, dtype, count,
                          root=0, context="") -> None:
        """Contract-plane record of the DECOMPOSED call on the PARENT
        communicator, op name ``"<op>.hier"``: a rank dispatching flat
        where its peers went hierarchical (or vice versa) diverges
        within one verification window, exactly like a fused-vs-plain
        skew.  The sub-collectives additionally fingerprint on their
        own subcomms like any other call."""
        c = self._contract
        if c is None:
            return
        verdict = c.record(
            op=f"{op_name}.hier",
            comm_id=comm.id,
            dtype=dtype.name,
            count=count,
            root=f"{root}/0",
            tag=0,
        )
        if verdict is not None:
            raise self._contract_error(verdict, context or op_name)

    def _hier_eligible_call(self, plan, comm, compress_dtype,
                            op_name: str, count: int) -> bool:
        """The per-call half of the hierarchical verdict: the plan's
        register half armed, no explicit compression lane (an explicit
        ``compress_dtype`` is honored exactly — only register-driven
        wire verdicts ride the per-class ladders), not inside an open
        batch (queued dispatch units stay flat), not already a stage of
        a hierarchical or pipelined launch, and the (topology, count)
        shape actually decomposes."""
        if (
            not plan.hierarchical
            or compress_dtype is not None
            or self._pending is not None
            or getattr(self._call_tls, "hier", False)
            or getattr(self._call_tls, "pipelining", False)
        ):
            return False
        from . import hierarchical as _hier

        return _hier.eligible(op_name, comm.topology, count)

    def _launch_hier_stages(self, op_name, plan, comm, count, dtype,
                            stages, run_async, context):
        """Run a hierarchical decomposition as an async CHAIN of
        sub-collective stages, returning ONE aggregate Request (the
        :meth:`_launch_pipelined` aggregate discipline).  Each stage
        thunk launches its sub-collective with ``run_async=True`` and
        returns the Request — or None when this rank does not
        participate in the stage (a non-leader during the cross-slice
        stage), which advances straight to the next stage.  Chaining
        rides done-callbacks, never a blocking wait: the test harness
        posts every rank's call from one thread, and a stage that
        blocked inside the entry call would deadlock the group."""
        outer = Request(op_name=op_name.upper())
        outer.mark_executing()
        tel = self._telemetry
        meta = None
        tid = None
        if tel is not None:
            tid, phase = self._derive_collective_trace(op_name, comm)
            meta = {
                "op": op_name, "comm": comm.id, "epoch": comm.epoch,
                "comm_rank": comm.local_rank, "comm_world": comm.size,
                "dtype": dtype.name, "count": count,
                "nbytes": count * dtype_size(dtype),
                "bucket": plan.bucket, "algorithm": plan.algorithm,
                "plan_hit": getattr(self._call_tls, "plan_hit", None),
                "eager": plan.eager,
                "hierarchical": True,
                "trace_id": tid,
                "trace_phase": phase,
                "parent_id": None,
            }
        t0 = time.perf_counter_ns()
        inner: list = []
        lock = threading.Lock()
        state = {"i": 0, "done": False}

        def _finish(code, ctx):
            with lock:
                if state["done"]:
                    return
                state["done"] = True
            depth = None
            for q in inner:
                if q.inflight_depth:
                    depth = max(depth or 0, q.inflight_depth)
            outer.inflight_depth = depth
            outer.complete(
                code, max(time.perf_counter_ns() - t0, 1), context=ctx
            )

        def _advance():
            while True:
                with lock:
                    if state["done"]:
                        return
                    idx = state["i"]
                    state["i"] += 1
                if idx >= len(stages):
                    _finish(ErrorCode.OK, None)
                    return
                # stages launch from completion-callback threads: the
                # TLS guard (no re-decomposition) and the parent trace
                # id must be set on WHATEVER thread runs the thunk
                self._call_tls.hier = True
                self._call_tls.parent_trace = tid
                try:
                    req = stages[idx]()
                except ACCLError as e:
                    _finish(e.code, dict(e.details) or None)
                    return
                except Exception as e:
                    # a stage must fail the aggregate, never kill a
                    # fabric completion thread
                    _finish(ErrorCode.INVALID_OPERATION, {
                        "op": op_name, "hier_stage": idx,
                        "error": repr(e),
                    })
                    return
                finally:
                    self._call_tls.hier = False
                    self._call_tls.parent_trace = None
                if req is None:
                    continue  # non-participant: straight to next stage
                inner.append(req)

                def _done(q=req):
                    rc = q.get_retcode()
                    if rc != ErrorCode.OK:
                        _finish(rc, q.error_context)
                        return
                    # hop to a fresh thread: the callback fires on
                    # whatever thread delivered the final frame — often
                    # a PEER rank's thread — and launching the next
                    # stage inline there would serialize independent
                    # ranks' sends (and their modeled-wire pacing
                    # sleeps) through one thread, flattening exactly
                    # the concurrency the decomposition exists to buy
                    threading.Thread(
                        target=_advance,
                        name=f"accl-hier-{op_name}",
                        daemon=True,
                    ).start()

                req.add_done_callback(_done)
                return

        def _resolve():
            for q in inner:
                q.materialize()

        outer.defer_result(_resolve)
        if tel is not None:
            tel.attach(outer, meta)
        _advance()
        if run_async:
            return outer
        if not outer.wait(timeout=drain_deadline_s(self._timeout_s)):
            raise self._deadlock_error(context)
        self._check_failed(outer, context)
        return outer

    def _hier_allreduce(self, plan, comm, sendbuf, recvbuf, n,
                        function, run_async):
        """Hierarchical allreduce.  Rail mode (symmetric topology,
        count % S == 0): intra-slice reduce-scatter (ICI) -> allreduce
        over the rail holding this chunk (DCN, n/S elements) ->
        intra-slice allgather (ICI) — the slow links carry 1/S of the
        flat ring's bytes.  Leader mode (any other multi-slice shape):
        reduce to the slice leader -> allreduce over leaders (full
        count) -> intra-slice bcast."""
        from . import hierarchical as _hier

        topo = comm.topology
        mode = _hier.allreduce_mode(topo, n)
        st = self._hier_state(comm)
        me = comm.local_rank
        sl = topo.slice_of(me)
        members = list(topo.slice_members(sl))
        intra = self._hier_subcomm(comm, st, ("intra", sl), members)
        self._hier_fingerprint(
            "allreduce", comm, sendbuf.dtype, n, context="allreduce"
        )
        if mode == "rail":
            S = len(topo.slices[0])
            li = topo.local_index(me)
            rail = self._hier_subcomm(
                comm, st, ("rail", li), topo.rail(li)
            )
            chunk = n // S
            scratch = self.engine.create_buffer(chunk, sendbuf.dtype)
            reduced = self.engine.create_buffer(chunk, sendbuf.dtype)
            stages = [
                lambda: self.reduce_scatter(
                    sendbuf, scratch, chunk, function=function,
                    comm=intra, run_async=True,
                ),
                lambda: self.allreduce(
                    scratch, reduced, chunk, function=function,
                    comm=rail, run_async=True,
                ),
                lambda: self.allgather(
                    reduced, recvbuf, chunk, comm=intra, run_async=True,
                ),
            ]
        else:
            lead = topo.slice_leader(me)
            lead_idx = members.index(lead)
            scratch = self.engine.create_buffer(n, sendbuf.dtype)

            def _s1():
                return self.reduce(
                    sendbuf, scratch if me == lead else None, n,
                    root=lead_idx, function=function, comm=intra,
                    run_async=True,
                )

            def _s2():
                if me != lead:
                    return None
                lcomm = self._hier_subcomm(
                    comm, st, "leaders", topo.leaders()
                )
                return self.allreduce(
                    scratch, recvbuf, n, function=function,
                    comm=lcomm, run_async=True,
                )

            def _s3():
                if intra.size == 1:
                    return None
                return self.bcast(
                    recvbuf, n, root=lead_idx, comm=intra,
                    run_async=True,
                )

            stages = [_s1, _s2, _s3]
        return self._launch_hier_stages(
            "allreduce", plan, comm, n, sendbuf.dtype, stages,
            run_async, "allreduce",
        )

    def _hier_allgather(self, plan, comm, sendbuf, recvbuf, n,
                        run_async):
        """Hierarchical allgather (symmetric contiguous topology):
        intra-slice allgather (ICI) -> rail allgather (DCN) — the rail
        stage's slice-major placement equals the flat rank-major
        placement exactly because slices are contiguous ascending."""
        topo = comm.topology
        st = self._hier_state(comm)
        me = comm.local_rank
        sl = topo.slice_of(me)
        li = topo.local_index(me)
        S = len(topo.slices[0])
        intra = self._hier_subcomm(
            comm, st, ("intra", sl), topo.slice_members(sl)
        )
        rail = self._hier_subcomm(comm, st, ("rail", li), topo.rail(li))
        self._hier_fingerprint(
            "allgather", comm, sendbuf.dtype, n, context="allgather"
        )
        scratch = self.engine.create_buffer(S * n, sendbuf.dtype)
        stages = [
            lambda: self.allgather(
                sendbuf, scratch, n, comm=intra, run_async=True
            ),
            lambda: self.allgather(
                scratch, recvbuf, S * n, comm=rail, run_async=True
            ),
        ]
        return self._launch_hier_stages(
            "allgather", plan, comm, n, sendbuf.dtype, stages,
            run_async, "allgather",
        )

    def _hier_reduce_scatter(self, plan, comm, sendbuf, recvbuf, n,
                             function, run_async):
        """Hierarchical reduce-scatter (symmetric contiguous topology):
        permute the W send blocks host-side
        (:func:`~accl_tpu.hierarchical.reduce_scatter_permutation`, so
        chunk s*S+i routes through intra block i / rail block s) ->
        intra-slice reduce-scatter over L*n-element blocks (ICI) ->
        rail reduce-scatter over n-element blocks (DCN) — every rank
        lands exactly its own fully-reduced chunk."""
        from . import hierarchical as _hier

        topo = comm.topology
        st = self._hier_state(comm)
        me = comm.local_rank
        sl = topo.slice_of(me)
        li = topo.local_index(me)
        L, S = topo.num_slices, len(topo.slices[0])
        W = L * S
        intra = self._hier_subcomm(
            comm, st, ("intra", sl), topo.slice_members(sl)
        )
        rail = self._hier_subcomm(comm, st, ("rail", li), topo.rail(li))
        self._hier_fingerprint(
            "reduce_scatter", comm, recvbuf.dtype, n,
            context="reduce_scatter",
        )
        perm = _hier.reduce_scatter_permutation(topo)
        arr = np.asarray(sendbuf.device_view()[: W * n])
        staged = self.engine.create_buffer(
            W * n, sendbuf.dtype,
            data=np.ascontiguousarray(arr.reshape(W, n)[perm].reshape(-1)),
        )
        scratch = self.engine.create_buffer(L * n, sendbuf.dtype)
        stages = [
            lambda: self.reduce_scatter(
                staged, scratch, L * n, function=function, comm=intra,
                run_async=True,
            ),
            lambda: self.reduce_scatter(
                scratch, recvbuf, n, function=function, comm=rail,
                run_async=True,
            ),
        ]
        return self._launch_hier_stages(
            "reduce_scatter", plan, comm, n, recvbuf.dtype, stages,
            run_async, "reduce_scatter",
        )

    def _hier_bcast(self, plan, comm, buf, n, root, run_async):
        """Hierarchical bcast (any multi-slice topology): bcast over
        one representative per slice — the root for its own slice, the
        leader elsewhere — then bcast within each slice from its
        representative.  The payload crosses the DCN once per remote
        slice instead of riding whatever flat tree the registers
        picked."""
        from . import hierarchical as _hier

        topo = comm.topology
        st = self._hier_state(comm)
        me = comm.local_rank
        sl = topo.slice_of(me)
        members = list(topo.slice_members(sl))
        reps = _hier.bcast_representatives(topo, root)
        my_rep = (
            int(root) if sl == topo.slice_of(root) else members[0]
        )
        rep_idx = members.index(my_rep)
        intra = self._hier_subcomm(comm, st, ("intra", sl), members)
        self._hier_fingerprint(
            "bcast", comm, buf.dtype, n, root=root, context="bcast"
        )

        def _s1():
            if me not in reps:
                return None
            cross = self._hier_subcomm(comm, st, ("bcast", root), reps)
            return self.bcast(
                buf, n, root=reps.index(int(root)), comm=cross,
                run_async=True,
            )

        def _s2():
            if intra.size == 1:
                return None
            return self.bcast(
                buf, n, root=rep_idx, comm=intra, run_async=True
            )

        return self._launch_hier_stages(
            "bcast", plan, comm, n, buf.dtype, [_s1, _s2],
            run_async, "bcast",
        )

    #: operations under the cross-rank sequence contract: every rank of
    #: the communicator must issue them with matching op/dtype/count/
    #: root/tag in matching order.  P2P (send/recv/stream_put) and local
    #: ops are rank-asymmetric by design and stay out; CONFIG is
    #: collective by *convention* but carries no wire matching.
    _CONTRACT_OPS = frozenset((
        Operation.BCAST, Operation.SCATTER, Operation.GATHER,
        Operation.ALLGATHER, Operation.REDUCE, Operation.ALLREDUCE,
        Operation.REDUCE_SCATTER, Operation.ALLTOALL, Operation.BARRIER,
    ))

    #: operations the QoS arbiter gates at intake: the contract ops
    #: plus plain p2p — local ops/CONFIG move no fabric bytes
    _ARBITER_OPS = _CONTRACT_OPS | {Operation.SEND, Operation.RECV}

    def _contract_error(self, verdict: dict, context: str) -> ACCLError:
        details = verdict_context(verdict, context)
        if self._telemetry is not None:
            details["flight_recorder"] = self._telemetry.tail_dicts()
        return self._structured_failure(ACCLError(
            ErrorCode.CONTRACT_VIOLATION, context, details=details
        ))

    def _contract_gate(self, options: CallOptions, context: str) -> None:
        """Contract-plane intake: fingerprint this collective into the
        communicator's rolling digest (exchanging at window boundaries)
        and fail PRE-DISPATCH on a standing divergence verdict — the
        call never launches into a fabric it can only wedge."""
        c = self._contract
        if (
            c is None or options.comm is None
            or options.op not in self._CONTRACT_OPS
        ):
            return
        cfg = options.arithcfg
        dt = cfg.uncompressed.name if cfg is not None else None
        # fused compute slots fold the fuse kind into the fingerprinted
        # op name: a rank issuing the PLAIN base op where its peers
        # fused (or vice versa) diverges within one verification window
        opname = options.op.name.lower()
        if getattr(options, "fuse", 0):
            opname += f".fused{int(options.fuse)}"
        verdict = c.record(
            op=opname,
            comm_id=options.comm.id,
            dtype=dt,
            count=options.count,
            # one canonical root field: ops use root_src XOR root_dst,
            # the other stays 0 — fold both so either diverging matters
            root=f"{options.root_src}/{options.root_dst}",
            tag=options.tag,
        )
        if verdict is not None:
            raise self._contract_error(verdict, context)

    def _launch(
        self, options: CallOptions, run_async: bool, context: str
    ) -> Optional[Request]:
        tel = self._telemetry
        with annotate("accl.facade::membership"):
            self._membership_intake(options, context)
        # QoS admission BEFORE the contract fingerprint: the arbiter
        # can only delay a whole call (bounded), never reorder within a
        # comm, so the digest stream the verifier checks is untouched
        with annotate("accl.facade::arbiter"):
            self._arbiter_gate(options)
        qos = getattr(self._call_tls, "qos", None)
        if qos is not None:
            self._call_tls.qos = None
        # between admission and the completion hooks, ANY raise (a
        # contract verdict, a failed engine start) must free the
        # tenant's outstanding slot, or repeated caught-and-retried
        # failures pin the owner at its limit forever; once `tracked`,
        # the async callback / the sync finally owns the release
        tracked = False
        try:
            with annotate("accl.facade::contract"):
                self._contract_gate(options, context)
            with annotate("accl.facade::meta"):
                # quantized wire plane: per-wire-dtype accounting at
                # intake (casts + bytes the narrow lane keeps off the
                # wire for this rank's contribution — the
                # effective-bandwidth evidence's live counterpart)
                if (
                    tel is not None
                    and options.arithcfg is not None
                    and options.compression
                    & CompressionFlags.ETH_COMPRESSED
                ):
                    wname = options.arithcfg.compressed.name
                    payload_b = options.count * dtype_size(
                        options.arithcfg.uncompressed
                    )
                    tel.metrics.inc(
                        "accl_compression_casts_total", (wname,)
                    )
                    tel.metrics.inc(
                        "accl_compression_wire_bytes_saved_total",
                        (wname,),
                        max(0, payload_b - _wire.wire_nbytes(
                            options.count, options.arithcfg.compressed
                        )),
                    )
                # trace/span id assigned at INTAKE — before dispatch —
                # so the fabric's outbound trace stamp covers this
                # call's own wire traffic, not just its successors'
                meta = (
                    self._call_meta(options, qos) if tel is not None
                    else None
                )
            if self._pending is not None:
                req = Request(op_name=options.op.name)
                req._pre_wait = self._dispatch_pending  # dispatch on wait
                if qos is not None and run_async:
                    self._arbiter_async(options, req, qos)
                    tracked = True
                if tel is not None:
                    tel.attach(req, meta)
                self._pending.push((options, req))
                if run_async:
                    return req
                # a sync call inside a batch dispatches the whole run
                # (it cannot complete before its queued predecessors
                # anyway); its own wait below is the synchronization —
                # a full window drain here could fail it over an
                # UNRELATED wedged call
                self._dispatch_pending()
                tracked = True
                return self._wait_sync(options, req, qos, context)
            with annotate("accl.facade::submit"):
                req = self.engine.start(options)
                if qos is not None and run_async:
                    self._arbiter_async(options, req, qos)
                    tracked = True
                if tel is not None:
                    # attach AFTER start: engines that complete
                    # synchronously inside start() are recorded
                    # immediately by attach()
                    tel.attach(req, meta)
            if run_async:
                return req
            tracked = True
            return self._wait_sync(options, req, qos, context)
        except BaseException:
            if qos is not None and not tracked and qos.get("paced"):
                self._arbiter.release(
                    options.comm.id, owner=self._arbiter_owner
                )
            raise

    def _wait_sync(self, options: CallOptions, req: Request, qos,
                   context: str) -> Request:
        """The blocking tail of a sync call.  The facade-level deadline
        follows the shared drain policy so the engine's own
        RECEIVE_TIMEOUT fires first for assembly stalls — and a
        first-call XLA compile of a large program doesn't spuriously
        trip the deadlock detector."""
        with annotate("accl.facade::wait"):
            try:
                if not req.wait(
                    timeout=drain_deadline_s(self._timeout_s)
                ):
                    raise self._deadlock_error(context)
                self._membership_after_failure(options, req, context)
                self._check_failed(req, context)
            finally:
                if qos is not None:  # slot freed however the call ends
                    self._arbiter_done(options, req, qos)
        return req

    def _check_failed(self, req: Request, context: str) -> None:
        """``Request.check`` with the postmortem hook: a structured
        failure surfacing through the sync path (the engine converts
        peer death to RANK_EVICTED, a relayed contract verdict fails
        the in-flight call, ...) captures its evidence bundle before
        it propagates."""
        try:
            req.check(context)
        except ACCLError as e:
            raise self._structured_failure(e)

    @staticmethod
    def _check_rank(comm: Communicator, rank: int) -> None:
        if not 0 <= rank < comm.size:
            raise ACCLError(
                ErrorCode.INVALID_RANK, f"rank {rank}",
                details={"rank": rank, "comm": comm.id, "size": comm.size},
            )

    @staticmethod
    def _count_of(buf: BaseBuffer, count: Optional[int]) -> int:
        n = buf.count if count is None else int(count)
        if n < 0:
            raise ACCLError(
                ErrorCode.INVALID_COUNT, f"count {n}",
                details={"count": n, "buffer_count": buf.count},
            )
        return n

    def get_duration(self, request: Request) -> int:
        """Engine-measured call duration in ns (ref ACCL::get_duration)."""
        return request.get_duration_ns()

    # -- primitives ----------------------------------------------------------
    def nop(self, run_async: bool = False):
        return self._launch(CallOptions(op=Operation.NOP), run_async, "nop")

    def copy(
        self,
        srcbuf: BaseBuffer,
        dstbuf: BaseBuffer,
        count: Optional[int] = None,
        run_async: bool = False,
    ):
        n = self._count_of(srcbuf, count)
        cfg, flags = self._resolve_arithcfg(srcbuf.dtype, None)
        opts = CallOptions(
            op=Operation.COPY,
            comm=self._world,
            count=n,
            arithcfg=cfg,
            compression=flags,
            host=self._host_flags(srcbuf, None, dstbuf),
            op0=srcbuf,
            res=dstbuf,
        )
        return self._launch(opts, run_async, "copy")

    def copy_from_stream(
        self,
        dstbuf: BaseBuffer,
        count: Optional[int] = None,
        stream_id: int = 0,
        run_async: bool = False,
    ):
        """Pull ``count`` elements from the local device stream port into a
        buffer (ref ``copy_from_stream``, accl.hpp:317-333)."""
        n = self._count_of(dstbuf, count)
        cfg, flags = self._resolve_arithcfg(dstbuf.dtype, None)
        opts = CallOptions(
            op=Operation.COPY,
            comm=self._world,
            count=n,
            arithcfg=cfg,
            compression=flags,
            stream=StreamFlags.OP0_STREAM,
            stream_id=stream_id,
            host=self._host_flags(None, None, dstbuf),
            op0=DummyBuffer(n, dstbuf.dtype),
            res=dstbuf,
        )
        return self._launch(opts, run_async, "copy_from_stream")

    def copy_to_stream(
        self,
        srcbuf: BaseBuffer,
        count: Optional[int] = None,
        stream_id: int = 0,
        run_async: bool = False,
    ):
        """Push a buffer into the local device stream port (ref
        ``copy_to_stream``, accl.hpp:334-348)."""
        n = self._count_of(srcbuf, count)
        cfg, flags = self._resolve_arithcfg(srcbuf.dtype, None)
        opts = CallOptions(
            op=Operation.COPY,
            comm=self._world,
            count=n,
            arithcfg=cfg,
            compression=flags,
            stream=StreamFlags.RES_STREAM,
            stream_id=stream_id,
            host=self._host_flags(srcbuf),
            op0=srcbuf,
            res=DummyBuffer(n, srcbuf.dtype),
        )
        return self._launch(opts, run_async, "copy_to_stream")

    def copy_from_to_stream(
        self,
        dtype: DTypeLike,
        count: int,
        stream_id: int = 0,
        run_async: bool = False,
    ):
        """Relay ``count`` elements through the engine from the stream port
        back to the stream port (ref ``copy_from_to_stream``,
        accl.hpp:349-363) — the loopback-kernel data path."""
        dt = _as_datatype(dtype)
        n = int(count)
        cfg, flags = self._resolve_arithcfg(dt, None)
        opts = CallOptions(
            op=Operation.COPY,
            comm=self._world,
            count=n,
            arithcfg=cfg,
            compression=flags,
            stream=StreamFlags.OP0_STREAM | StreamFlags.RES_STREAM,
            stream_id=stream_id,
            op0=DummyBuffer(n, dt),
            res=DummyBuffer(n, dt),
        )
        return self._launch(opts, run_async, "copy_from_to_stream")

    def combine(
        self,
        function: ReduceFunction,
        op0: BaseBuffer,
        op1: BaseBuffer,
        res: BaseBuffer,
        count: Optional[int] = None,
        run_async: bool = False,
    ):
        n = self._count_of(op0, count)
        cfg, flags = self._resolve_arithcfg(op0.dtype, None)
        opts = CallOptions(
            op=Operation.COMBINE,
            comm=self._world,
            count=n,
            reduce_function=function,
            arithcfg=cfg,
            compression=flags,
            host=self._host_flags(op0, op1, res),
            op0=op0,
            op1=op1,
            res=res,
        )
        return self._launch(opts, run_async, "combine")

    @staticmethod
    def _check_p2p_wire(cfg: ArithConfig, flags, opname: str) -> None:
        """Scaled wire lanes (int8) are reduction lanes: the per-segment
        absmax frame exists so quantized gradient SUMS stay accurate,
        and the p2p channels speak plain cast lanes (fp8/f16/bf16 work
        there today).  Requesting an int8 wire on p2p fails loudly at
        intake instead of silently transporting garbage casts."""
        if flags & CompressionFlags.ETH_COMPRESSED and _wire.is_scaled(
            cfg.compressed
        ):
            raise ACCLError(
                ErrorCode.COMPRESSION_ERROR,
                f"{opname}: scaled wire lane {cfg.compressed.name} is "
                "collective-only",
                details={
                    "op": opname,
                    "wire": cfg.compressed.name,
                    "hint": "use a cast lane (float16/bfloat16/fp8) "
                            "for p2p, scaled int8 for allreduce",
                },
            )

    # -- point-to-point ------------------------------------------------------
    def send(
        self,
        srcbuf: BaseBuffer,
        count: Optional[int],
        dst: int,
        tag: int = 0,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        from_stream: bool = False,
        stream_id: int = 0,
        run_async: bool = False,
    ):
        comm = comm or self._world
        self._check_rank(comm, dst)
        dtype = srcbuf.dtype if srcbuf is not None else DataType.FLOAT32
        n = self._count_of(srcbuf, count) if srcbuf is not None else int(count)
        cfg, flags = self._resolve_arithcfg(dtype, compress_dtype)
        self._check_p2p_wire(cfg, flags, "send")
        stream = StreamFlags.OP0_STREAM if from_stream else StreamFlags.NO_STREAM
        opts = CallOptions(
            op=Operation.SEND,
            comm=comm,
            count=n,
            root_dst=dst,
            tag=tag,
            arithcfg=cfg,
            compression=flags,
            stream=stream,
            stream_id=stream_id,
            host=self._host_flags(srcbuf),
            op0=srcbuf if srcbuf is not None else DummyBuffer(n, dtype),
        )
        return self._launch(opts, run_async, "send")

    def recv(
        self,
        dstbuf: BaseBuffer,
        count: Optional[int],
        src: int,
        tag: int = 0,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        to_stream: bool = False,
        stream_id: int = 0,
        run_async: bool = False,
    ):
        comm = comm or self._world
        self._check_rank(comm, src)
        dtype = dstbuf.dtype if dstbuf is not None else DataType.FLOAT32
        n = self._count_of(dstbuf, count) if dstbuf is not None else int(count)
        cfg, flags = self._resolve_arithcfg(dtype, compress_dtype)
        self._check_p2p_wire(cfg, flags, "recv")
        stream = StreamFlags.RES_STREAM if to_stream else StreamFlags.NO_STREAM
        opts = CallOptions(
            op=Operation.RECV,
            comm=comm,
            count=n,
            root_src=src,
            tag=tag,
            arithcfg=cfg,
            compression=flags,
            stream=stream,
            stream_id=stream_id,
            host=self._host_flags(None, None, dstbuf),
            res=dstbuf if dstbuf is not None else DummyBuffer(n, dtype),
        )
        return self._launch(opts, run_async, "recv")

    def stream_put(
        self,
        srcbuf: BaseBuffer,
        count: Optional[int],
        dst: int,
        stream_id: int,
        tag: int = 0,
        comm: Optional[Communicator] = None,
        run_async: bool = False,
    ):
        """Send straight into the destination rank's device stream port —
        the reference's ``stream_put`` (accl.hpp / accl_hls.h:277-298), used
        by device kernels to receive data without tag matching."""
        comm = comm or self._world
        self._check_rank(comm, dst)
        n = self._count_of(srcbuf, count)
        cfg, flags = self._resolve_arithcfg(srcbuf.dtype, None)
        opts = CallOptions(
            op=Operation.SEND,
            comm=comm,
            count=n,
            root_dst=dst,
            tag=tag,
            arithcfg=cfg,
            compression=flags,
            stream=StreamFlags.RES_STREAM,
            stream_id=stream_id,
            op0=srcbuf,
        )
        return self._launch(opts, run_async, "stream_put")

    # -- collectives ---------------------------------------------------------
    @annotated("accl.facade::call")
    def bcast(
        self,
        buf: BaseBuffer,
        count: Optional[int] = None,
        root: int = 0,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        comm = comm or self._world
        self._check_rank(comm, root)
        n = self._count_of(buf, count)
        host = self._host_flags(buf, None, buf)
        plan = self._plan_for(
            Operation.BCAST, comm, buf.dtype, n, compress_dtype, host,
            (root,),
        )
        if self._hier_eligible_call(
            plan, comm, compress_dtype, "bcast", n
        ):
            return self._hier_bcast(plan, comm, buf, n, root, run_async)
        nseg = self._pipeline_segments_for(plan, n, buf.dtype)
        if nseg > 1:
            return self._launch_pipelined(
                "bcast", plan, comm, n, nseg, run_async,
                lambda s0, s1: self.bcast(
                    buf.slice(s0, s1), s1 - s0, root=root, comm=comm,
                    compress_dtype=compress_dtype, run_async=True,
                ),
                "bcast",
            )
        opts = CallOptions(
            op=Operation.BCAST,
            comm=comm,
            count=n,
            root_src=root,
            tag=self._seg_tag(),
            arithcfg=plan.arithcfg,
            compression=plan.compression,
            host=host,
            op0=buf,
            res=buf,
            plan=plan,
            tuning=plan.tuning,
        )
        return self._launch(opts, run_async, "bcast")

    @annotated("accl.facade::call")
    def scatter(
        self,
        sendbuf: Optional[BaseBuffer],
        recvbuf: BaseBuffer,
        count: Optional[int] = None,
        root: int = 0,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        comm = comm or self._world
        self._check_rank(comm, root)
        n = self._count_of(recvbuf, count)
        host = self._host_flags(sendbuf, None, recvbuf)
        plan = self._plan_for(
            Operation.SCATTER, comm, recvbuf.dtype, n, compress_dtype, host,
            (root,),
        )
        opts = CallOptions(
            op=Operation.SCATTER,
            comm=comm,
            count=n,
            root_src=root,
            arithcfg=plan.arithcfg,
            compression=plan.compression,
            host=host,
            op0=sendbuf if sendbuf is not None else DummyBuffer(0, recvbuf.dtype),
            res=recvbuf,
            plan=plan,
            tuning=plan.tuning,
        )
        return self._launch(opts, run_async, "scatter")

    @annotated("accl.facade::call")
    def gather(
        self,
        sendbuf: BaseBuffer,
        recvbuf: Optional[BaseBuffer],
        count: Optional[int] = None,
        root: int = 0,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        comm = comm or self._world
        self._check_rank(comm, root)
        n = self._count_of(sendbuf, count)
        host = self._host_flags(sendbuf, None, recvbuf)
        plan = self._plan_for(
            Operation.GATHER, comm, sendbuf.dtype, n, compress_dtype, host,
            (root,),
        )
        opts = CallOptions(
            op=Operation.GATHER,
            comm=comm,
            count=n,
            root_src=root,
            arithcfg=plan.arithcfg,
            compression=plan.compression,
            host=host,
            op0=sendbuf,
            res=recvbuf if recvbuf is not None else DummyBuffer(0, sendbuf.dtype),
            plan=plan,
            tuning=plan.tuning,
        )
        return self._launch(opts, run_async, "gather")

    @annotated("accl.facade::call")
    def allgather(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: Optional[int] = None,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        with annotate("accl.facade::prepare"):
            comm = comm or self._world
            n = self._count_of(sendbuf, count)
            host = self._host_flags(sendbuf, None, recvbuf)
            plan = self._plan_for(
                Operation.ALLGATHER, comm, sendbuf.dtype, n,
                compress_dtype, host,
            )
            hier = self._hier_eligible_call(
                plan, comm, compress_dtype, "allgather", n
            )
            opts = None if hier else CallOptions(
                op=Operation.ALLGATHER,
                comm=comm,
                count=n,
                arithcfg=plan.arithcfg,
                compression=plan.compression,
                host=host,
                op0=sendbuf,
                res=recvbuf,
                plan=plan,
                tuning=plan.tuning,
            )
        if hier:
            return self._hier_allgather(
                plan, comm, sendbuf, recvbuf, n, run_async
            )
        return self._launch(opts, run_async, "allgather")

    @annotated("accl.facade::call")
    def reduce(
        self,
        sendbuf: Optional[BaseBuffer],
        recvbuf: Optional[BaseBuffer],
        count: Optional[int] = None,
        root: int = 0,
        function: ReduceFunction = ReduceFunction.SUM,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        from_stream: bool = False,
        to_stream: bool = False,
        stream_id: int = 0,
        dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        """Reduce to ``root``.  ``from_stream`` pulls this rank's operand
        from its device stream port (``sendbuf=None``); ``to_stream``
        delivers the root's result to its stream port (``recvbuf=None``) —
        the reference's four reduce overloads incl. stream operands
        (accl.hpp:514-590)."""
        comm = comm or self._world
        self._check_rank(comm, root)
        if sendbuf is not None:
            op_dtype = sendbuf.dtype
            n = self._count_of(sendbuf, count)
        else:
            if not from_stream:
                raise ACCLError(
                    ErrorCode.INVALID_OPERATION,
                    "reduce needs sendbuf unless from_stream",
                    details={"op": "reduce", "from_stream": from_stream},
                )
            op_dtype = (
                _as_datatype(dtype)
                if dtype is not None
                else (recvbuf.dtype if recvbuf is not None else DataType.FLOAT32)
            )
            if count is None and recvbuf is not None:
                n = self._count_of(recvbuf, count)
            elif count is None:
                raise ACCLError(
                    ErrorCode.INVALID_COUNT,
                    "stream reduce needs an explicit count without recvbuf",
                    details={"op": "reduce", "from_stream": from_stream},
                )
            else:
                n = int(count)
        stream = StreamFlags.NO_STREAM
        if from_stream:
            stream |= StreamFlags.OP0_STREAM
        if to_stream:
            stream |= StreamFlags.RES_STREAM
        host = self._host_flags(sendbuf, None, recvbuf)
        plan = self._plan_for(
            Operation.REDUCE, comm, op_dtype, n, compress_dtype, host,
            (root, int(function), int(stream)),
        )
        opts = CallOptions(
            op=Operation.REDUCE,
            comm=comm,
            count=n,
            root_dst=root,
            reduce_function=function,
            arithcfg=plan.arithcfg,
            compression=plan.compression,
            stream=stream,
            stream_id=stream_id,
            host=host,
            op0=sendbuf if sendbuf is not None else DummyBuffer(n, op_dtype),
            res=recvbuf if recvbuf is not None else DummyBuffer(0, op_dtype),
            plan=plan,
            tuning=plan.tuning,
            wire_seed=self._derive_wire_seed(plan, comm, Operation.REDUCE),
        )
        return self._launch(opts, run_async, "reduce")

    @annotated("accl.facade::call")
    def allreduce(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: Optional[int] = None,
        function: ReduceFunction = ReduceFunction.SUM,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        with annotate("accl.facade::prepare"):
            comm = comm or self._world
            n = self._count_of(sendbuf, count)
            host = self._host_flags(sendbuf, None, recvbuf)
            plan = self._plan_for(
                Operation.ALLREDUCE, comm, sendbuf.dtype, n, compress_dtype,
                host, (int(function),),
            )
            # topology plane: hierarchical decomposition BEFORE the
            # pipelining split — the stages are ordinary facade calls on
            # the derived subcomms and may pipeline there
            hier = self._hier_eligible_call(
                plan, comm, compress_dtype, "allreduce", n
            )
            nseg = 1 if hier else self._pipeline_segments_for(
                plan, n, sendbuf.dtype
            )
            opts = (
                None if hier or nseg > 1
                else self._allreduce_options(
                    plan, comm, sendbuf, recvbuf, n, function, host
                )
            )
        if hier:
            return self._hier_allreduce(
                plan, comm, sendbuf, recvbuf, n, function, run_async
            )
        if nseg > 1:
            return self._launch_pipelined(
                "allreduce", plan, comm, n, nseg, run_async,
                lambda s0, s1: self.allreduce(
                    sendbuf.slice(s0, s1), recvbuf.slice(s0, s1),
                    s1 - s0, function=function, comm=comm,
                    compress_dtype=compress_dtype, run_async=True,
                ),
                "allreduce",
            )
        return self._launch(opts, run_async, "allreduce")

    def _allreduce_options(self, plan, comm, sendbuf, recvbuf, n, function,
                           host) -> CallOptions:
        seed = self._derive_wire_seed(plan, comm, Operation.ALLREDUCE)
        staged = self._error_feedback_operand(
            plan, comm, sendbuf, n, function, seed
        )
        return CallOptions(
            op=Operation.ALLREDUCE,
            comm=comm,
            count=n,
            tag=self._seg_tag(),
            reduce_function=function,
            arithcfg=plan.arithcfg,
            compression=plan.compression,
            host=host,
            op0=staged if staged is not None else sendbuf,
            res=recvbuf,
            plan=plan,
            tuning=plan.tuning,
            wire_seed=seed,
        )

    @annotated("accl.facade::call")
    def reduce_scatter(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: Optional[int] = None,
        function: ReduceFunction = ReduceFunction.SUM,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        with annotate("accl.facade::prepare"):
            comm = comm or self._world
            n = self._count_of(recvbuf, count)
            host = self._host_flags(sendbuf, None, recvbuf)
            plan = self._plan_for(
                Operation.REDUCE_SCATTER, comm, recvbuf.dtype, n,
                compress_dtype, host, (int(function),),
            )
            hier = self._hier_eligible_call(
                plan, comm, compress_dtype, "reduce_scatter", n
            )
            opts = None if hier else CallOptions(
                op=Operation.REDUCE_SCATTER,
                comm=comm,
                count=n,
                reduce_function=function,
                arithcfg=plan.arithcfg,
                compression=plan.compression,
                host=host,
                op0=sendbuf,
                res=recvbuf,
                plan=plan,
                tuning=plan.tuning,
                wire_seed=self._derive_wire_seed(
                    plan, comm, Operation.REDUCE_SCATTER
                ),
            )
        if hier:
            return self._hier_reduce_scatter(
                plan, comm, sendbuf, recvbuf, n, function, run_async
            )
        return self._launch(opts, run_async, "reduce_scatter")

    # -- fused compute slots (ref accl_hls kernel-initiated calls) -----------
    def _fused_operand_check(self, sendbuf, need: int, what: str) -> None:
        if sendbuf.count < need:
            raise ValueError(
                f"{what} needs a packed operand of at least {need} "
                f"elements, got {sendbuf.count}"
            )

    def _fused_launch(self, op, fuse, sendbuf, recvbuf, n, function,
                      comm, fuse_param, root_src, run_async, context):
        """Shared tail of the fused facades: plan (fuse folded into the
        cache key — a fused plan never aliases its plain base op's),
        CallOptions with the fuse hint, launch.  Fused calls keep the
        uncompressed wire and NEVER run the plain base op off-ring:
        ring-ineligible calls decompose on host with a counted
        fallback (``fallbacks["fused_decomposed"]``)."""
        host = self._host_flags(sendbuf, None, recvbuf)
        plan = self._plan_for(
            op, comm, recvbuf.dtype, n, None, host,
            (int(function), "fuse", int(fuse)),
        )
        opts = CallOptions(
            op=op,
            comm=comm,
            count=n,
            reduce_function=function,
            root_src=root_src,
            arithcfg=plan.arithcfg,
            compression=plan.compression,
            host=host,
            op0=sendbuf,
            res=recvbuf,
            plan=plan,
            tuning=plan.tuning,
            fuse=int(fuse),
            fuse_param=float(fuse_param),
        )
        return self._launch(opts, run_async, context)

    def fused_matmul_reduce_scatter(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: Optional[int] = None,
        scale: float = 1.0,
        function: ReduceFunction = ReduceFunction.SUM,
        comm: Optional[Communicator] = None,
        run_async: bool = False,
    ):
        """GEMM partials straight into a reduce-scatter slot (the
        ``accl_hls`` vadd_put discipline): ``sendbuf`` holds this
        rank's ``size*count`` output partials laid out as ``size``
        destination chunks; ``recvbuf`` receives ``scale *`` the
        reduced chunk owned by this rank.  One command-ring slot, no
        intermediate host round trip between compute and collective."""
        comm = comm or self._world
        n = self._count_of(recvbuf, count)
        self._fused_operand_check(
            sendbuf, n * comm.size, "fused_matmul_reduce_scatter"
        )
        return self._fused_launch(
            Operation.REDUCE_SCATTER, FusedCompute.MATMUL_RS,
            sendbuf, recvbuf, n, function, comm, scale, 0, run_async,
            "fused_matmul_reduce_scatter",
        )

    def fused_apply(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: Optional[int] = None,
        lr: float = 1.0,
        function: ReduceFunction = ReduceFunction.SUM,
        comm: Optional[Communicator] = None,
        run_async: bool = False,
    ):
        """Optimizer-apply-on-arrival: ``sendbuf`` packs this rank's
        gradient contribution (``size*count``, laid out as ``size``
        destination chunks) followed by its OWN ``count``-wide
        parameter shard; the epilogue applies ``param - lr * grad`` per
        received chunk during the gather, and ``recvbuf`` gets the
        updated shard — SGD step and gradient reduction in one slot."""
        comm = comm or self._world
        n = self._count_of(recvbuf, count)
        self._fused_operand_check(
            sendbuf, n * (comm.size + 1), "fused_apply"
        )
        return self._fused_launch(
            Operation.ALLREDUCE, FusedCompute.APPLY,
            sendbuf, recvbuf, n, function, comm, lr, 0, run_async,
            "fused_apply",
        )

    def fused_attn_hop(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        hop: int,
        count: Optional[int] = None,
        scale: float = 1.0,
        comm: Optional[Communicator] = None,
        run_async: bool = False,
    ):
        """One ring-attention hop as a sequencer slot: ``sendbuf``
        packs this rank's KV block (``count``) followed by its resident
        Q block (``count``); the epilogue computes the partial
        ``scale * q * kv_src`` against the block arriving from the rank
        ``hop`` positions behind on the ring.  ``hop`` is SPMD-uniform
        (same value on every rank — it rides the slot's peer word);
        each rank derives its own source on device."""
        comm = comm or self._world
        n = self._count_of(recvbuf, count)
        self._fused_operand_check(sendbuf, 2 * n, "fused_attn_hop")
        hop = int(hop) % max(comm.size, 1)
        return self._fused_launch(
            Operation.ALLREDUCE, FusedCompute.ATTN_HOP,
            sendbuf, recvbuf, n, ReduceFunction.SUM, comm, scale, hop,
            run_async, "fused_attn_hop",
        )

    @annotated("accl.facade::call")
    def alltoall(
        self,
        sendbuf: BaseBuffer,
        recvbuf: BaseBuffer,
        count: Optional[int] = None,
        comm: Optional[Communicator] = None,
        compress_dtype: Optional[DTypeLike] = None,
        run_async: bool = False,
    ):
        with annotate("accl.facade::prepare"):
            comm = comm or self._world
            if count is None:
                count = sendbuf.count // comm.size
            host = self._host_flags(sendbuf, None, recvbuf)
            plan = self._plan_for(
                Operation.ALLTOALL, comm, sendbuf.dtype, int(count),
                compress_dtype, host,
            )
            opts = CallOptions(
                op=Operation.ALLTOALL,
                comm=comm,
                count=int(count),
                arithcfg=plan.arithcfg,
                compression=plan.compression,
                host=host,
                op0=sendbuf,
                res=recvbuf,
                plan=plan,
                tuning=plan.tuning,
            )
        return self._launch(opts, run_async, "alltoall")

    @annotated("accl.facade::call")
    def barrier(
        self, comm: Optional[Communicator] = None, run_async: bool = False
    ):
        comm = comm or self._world
        cfg, flags = self._resolve_arithcfg(DataType.FLOAT32, None)
        opts = CallOptions(
            op=Operation.BARRIER,
            comm=comm,
            count=0,
            # membership plane: the internal gather root re-routes
            # around demoted stragglers (SPMD-uniform — exchanged
            # verdict + shared latched decision; 0 when demotion
            # routing is off)
            root_src=self._barrier_root(comm),
            tag=0x7FFFFFF0,  # reserved tag space so barriers never cross-match
            arithcfg=cfg,
            compression=flags,
        )
        return self._launch(opts, run_async, "barrier")

    # -- device stream ports -------------------------------------------------
    def stream_push(self, data: np.ndarray, stream_id: int = 0) -> None:
        self.engine.stream_push(stream_id, np.ascontiguousarray(data).tobytes())

    def stream_pop(
        self, count: int, dtype: DTypeLike, stream_id: int = 0, timeout: float = 30.0
    ) -> np.ndarray:
        from .constants import dtype_to_numpy

        npdt = dtype_to_numpy(_as_datatype(dtype))
        need = count * npdt.itemsize
        out = b""
        while len(out) < need:
            out += self.engine.stream_pop(stream_id, timeout=timeout)
        return np.frombuffer(out[:need], dtype=npdt).copy()

    # -- debug / telemetry ----------------------------------------------------
    def dump_rx_buffers(self, as_dict: bool = False):
        """Rx-accounting dump (ref ``ACCL::dump_eager_rx_buffers``).

        ``as_dict=True`` returns the structured form backed by the
        telemetry plane (the engine's ``telemetry_report`` plus the
        per-slot lines); the legacy string is rendered from that dict's
        ``lines`` — one source, two views."""
        text = (
            self.engine.dump_rx_buffers()
            if hasattr(self.engine, "dump_rx_buffers")
            else ""
        )
        doc = {
            "engine": type(self.engine).__name__,
            "lines": text.splitlines(),
        }
        if as_dict:
            # the telemetry_report is built only for the structured
            # form: the legacy string path (soak leak scans poll it)
            # must stay as cheap as the raw engine dump
            doc["report"] = self.engine.telemetry_report()
            return doc
        return "\n".join(doc["lines"])

    def dump_communicator(
        self, comm: Optional[Communicator] = None, as_dict: bool = False
    ):
        """Communicator + per-peer health dump.  ``as_dict=True`` returns
        the structured form (communicator table + the health map the
        telemetry snapshot carries); the legacy string is rendered FROM
        that dict — no hand-maintained parallel format."""
        comm = comm or self._world
        doc = {
            "comm": comm.as_dict(),
            "health": self._annotated_health(comm),
        }
        if as_dict:
            return doc
        c = doc["comm"]
        lines = [
            f"communicator {c['id']}: size={c['size']} local={c['local_rank']}"
        ]
        for i, r in enumerate(c["ranks"]):
            lines.append(
                f"  rank {i}: addr={r['address']} session={r['session']} "
                f"seg={r['max_segment_size']} "
                f"seq_out={r['seq_out']} seq_in={r['seq_in']}"
            )
        for i in sorted(doc["health"]):
            h = doc["health"][i]
            line = (
                f"  health rank {i}: {h.get('state', 'ok')}"
                f" timeouts={h.get('timeouts', 0)}"
                f" failures={h.get('failures', 0)}"
            )
            if h.get("last_event"):
                line += f" last={h['last_event']}"
            lines.append(line)
        return "\n".join(lines)

    def telemetry_snapshot(self) -> dict:
        """ONE merged telemetry dict for this rank handle: the
        flight-recorder tail, the metrics registry, the buffered wire
        trace, and every counter source the earlier PRs scattered
        (plan cache, per-peer health, engine report incl. fault/
        retransmit/dedup counts and rx depths, device interactions).
        Identical shape on all four engine tiers; export with
        :meth:`telemetry_prometheus` / :meth:`telemetry_json`."""
        from . import telemetry as _t

        tel = self._telemetry
        mon = self._monitor
        engine_report = self.engine.telemetry_report()
        return {
            # bumped when the merged shape changes (see telemetry.
            # SCHEMA_VERSION); dashboards key on this, not sniffing
            "schema_version": _t.SCHEMA_VERSION,
            "telemetry_enabled": tel is not None,
            "rank": self._world.local_rank,
            "world": self._world.size,
            "tier": type(self.engine).__name__,
            "flight_recorder": tel.tail_dicts(64) if tel else [],
            "flight_recorder_total": tel.recorder.total if tel else 0,
            "metrics": tel.metrics.snapshot() if tel else {},
            "wire_trace": _t.wire_snapshot(),
            "plan_cache": self._plans.stats(),
            "health": self._annotated_health(self._world),
            "device_interactions": self.engine.device_interactions(),
            "engine": engine_report,
            "faults": engine_report.get("faults"),
            # contract plane: verification counters + standing verdicts
            # (the one-line answer to "did the ranks diverge?")
            "contract": (
                self._contract.snapshot()
                if self._contract is not None else {"enabled": False}
            ),
            # monitor plane: cross-rank straggler verdicts, per-(op x
            # bucket) anomaly alerts, and the live-service state (the
            # one-line answer to "which rank is slow?")
            # membership plane: the elastic state machine (epoch,
            # evictions, admissions, demotion breakers), the advisory
            # traffic-aware scale recommendation, and the health-
            # transition event ring (the one-line answer to "who left
            # the group, and when — and should it grow back?")
            "membership": self._membership_report(),
            "health_events": self._health_events.snapshot(),
            # arbiter plane: per-tenant admission counters, quotas, and
            # the live latency histograms with their p99 tails (the
            # one-line answer to "who is hogging the fabric?")
            "tenants": self._arbiter.snapshot(),
            # quantized wire plane: SR call accounting + error-feedback
            # residual health (the one-line answer to "is the wire
            # verdict safe for this workload?" — a bounded residual
            # norm is the convergence signal)
            "compression": {
                "sr_calls": sum(self._wire_ctr.values()),
                "error_feedback": dict(
                    self._residuals.stats(),
                    enabled=self._error_feedback,
                ),
            },
            "stragglers": (
                mon.straggler_snapshot() if mon is not None
                else {"enabled": False}
            ),
            "anomalies": (
                mon.anomaly_snapshot() if mon is not None
                else {"enabled": False}
            ),
            "monitor": (
                mon.service_snapshot() if mon is not None
                else {"serving": False}
            ),
            # postmortem plane: bundle accounting (the one-line answer
            # to "did the failure leave evidence, and where?")
            "postmortem": (
                self._blackbox.snapshot()
                if self._blackbox is not None else {"enabled": False}
            ),
        }

    def _annotated_health(self, comm: Communicator) -> dict:
        """The engine health map plus the monitor plane's standing
        straggler verdicts as ``suspect_slow`` annotations — annotation
        ONLY: a slow rank is an operator signal, never a fail-fast
        (the dead-rank path stays the health map's own state machine)."""
        health = self.engine.health_report(comm)
        if self._monitor is not None:
            for r in self._monitor.slow_ranks(comm.id):
                if r in health:
                    health[r]["suspect_slow"] = True
        return health

    def telemetry_prometheus(self) -> str:
        """The snapshot in Prometheus text exposition format."""
        return to_prometheus(self.telemetry_snapshot())

    def telemetry_json(self) -> str:
        """The snapshot as canonical JSON."""
        return to_json(self.telemetry_snapshot())

    def telemetry_trace_events(self) -> list:
        """This rank's flight-recorder records (plus buffered wire
        events and the engine's ring-resident spans — the command
        ring's per-slot window timeline, flow-linked to the issuing
        calls) as Chrome/Perfetto trace events; [] when telemetry is
        disabled."""
        if self._telemetry is None:
            return []
        events = self._telemetry.chrome_events()
        try:
            events.extend(self.engine.trace_events())
        except Exception:  # a ring render bug must not kill the export
            pass
        events.sort(key=lambda e: e.get("ts", 0.0))
        return events

    def start_monitor(self, port: Optional[int] = None) -> int:
        """Start the live scrape service for this rank handle: a stdlib
        HTTP server on an ``accl-monitor`` thread serving ``/metrics``
        (Prometheus text), ``/snapshot`` (the ``telemetry_snapshot()``
        JSON) and ``/trace`` (the rolling Chrome-trace window).  Binds
        127.0.0.1; ``port`` 0 (and the default when ``ACCL_MONITOR_PORT``
        is unset) picks an ephemeral port.  Returns the bound port.
        Idempotent while already serving."""
        from . import monitor as _monitor

        if self._monitor is None:
            raise ACCLError(
                ErrorCode.INVALID_OPERATION,
                "telemetry disabled (ACCL_TELEMETRY=0): nothing to serve",
                details={"op": "start_monitor"},
            )
        if self._monitor.server is not None:
            return self._monitor.server.port
        if port is None:
            port = _monitor.env_port() or 0

        def _trace_doc() -> str:
            import json as _json

            return _json.dumps(chrome_trace(self.telemetry_trace_events()))

        def _cmdring_doc() -> str:
            import json as _json

            ring = self.engine.telemetry_report().get("cmdring")
            return _json.dumps(
                ring if ring is not None else {"enabled": False},
                default=str,
            )

        def _tenants_doc() -> str:
            import json as _json

            return _json.dumps(self._arbiter.snapshot(), default=str)

        def _membership_doc() -> str:
            import json as _json

            return _json.dumps(self._membership_report(), default=str)

        srv = _monitor.MonitorServer({
            "/": (self._monitor_index, "text/plain; charset=utf-8"),
            "/metrics": (
                self.telemetry_prometheus,
                "text/plain; version=0.0.4; charset=utf-8",
            ),
            "/snapshot": (self.telemetry_json, "application/json"),
            "/trace": (_trace_doc, "application/json"),
            "/cmdring": (_cmdring_doc, "application/json"),
            "/tenants": (_tenants_doc, "application/json"),
            "/membership": (_membership_doc, "application/json"),
        }, port=int(port))
        srv.start()
        self._monitor.server = srv
        return srv.port

    def _monitor_index(self) -> str:
        """The monitor's ``/`` page: route links plus a live one-screen
        health summary — ring sessions, postmortem bundle count, and
        the last verdict lines (stragglers / anomalies / membership) —
        so a bare browser hit answers "is this mesh healthy" without
        curl-ing three routes."""
        lines = [
            f"accl monitor — rank {self._world.local_rank}/"
            f"{self._world.size} ({type(self.engine).__name__})",
            "routes: /metrics /snapshot /trace /cmdring /tenants "
            "/membership",
            "",
        ]
        ring = self.engine.telemetry_report().get("cmdring") or {}
        if ring:
            lines.append(
                f"cmdring: state={ring.get('state', '?')} "
                f"refills={ring.get('refills', 0)} "
                f"dispatches={ring.get('dispatches', 0)} "
                f"fallbacks={sum((ring.get('fallbacks') or {}).values())}"
            )
        else:
            lines.append("cmdring: (tier has no command ring)")
        bb = self._blackbox.snapshot() if self._blackbox else {}
        lines.append(
            f"postmortem: bundles={bb.get('bundles_written', 0)} "
            f"last={bb.get('last_bundle') or '-'}"
            if bb.get("enabled")
            else "postmortem: disabled (set ACCL_POSTMORTEM_DIR)"
        )
        strag = (
            self._monitor.straggler_snapshot()
            if self._monitor is not None else {}
        )
        standing = strag.get("standing") or {}
        if standing:
            for c, v in sorted(standing.items()):
                lines.append(
                    f"straggler: comm {c} slow_rank={v.get('rank')} "
                    f"ewma={v.get('ewma_latency_us')}us "
                    f"streak={v.get('streak')}"
                )
        else:
            lines.append("straggler: none standing")
        anom = (
            self._monitor.anomaly_snapshot()
            if self._monitor is not None else {}
        )
        alerts = anom.get("alerts") or []
        if alerts:
            a = alerts[-1]
            lines.append(
                f"anomaly: {a.get('op')}/b{a.get('size_bucket')} "
                f"{a.get('duration_us')}us vs baseline "
                f"{a.get('baseline_us')}us "
                f"(total {anom.get('alerts_total', 0)})"
            )
        else:
            lines.append("anomaly: none")
        mem = self._membership_report()
        advice = mem.get("scale_advice") or {}
        lines.append(
            f"membership: epoch={mem.get('epoch')} "
            f"elastic={mem.get('elastic')} "
            f"evicted={sorted(mem.get('evicted') or [])} "
            f"joins={mem.get('joins_total', 0)} "
            f"scale_advice={advice.get('recommendation', '-')}"
        )
        # arbiter plane: the one-line per-tenant QoS summary — class,
        # admission counts, live p99 — so a bare browser hit answers
        # "who is hogging the fabric" without curl-ing /tenants
        arb = self._arbiter.snapshot()
        tenants = arb.get("tenants") or {}
        if not tenants:
            lines.append(
                f"tenants: none registered "
                f"(arbiter {'armed' if arb.get('enabled') else 'disarmed'})"
            )
        else:
            for cid, t in sorted(tenants.items()):
                p99 = (t.get("latency") or {}).get("p99_us")
                lines.append(
                    f"tenant {t.get('name')}: class={t.get('class')} "
                    f"weight={t.get('weight')} "
                    f"admitted={t.get('admitted')} "
                    f"queued={t.get('queued')} "
                    f"p99={p99 if p99 is not None else '-'}us"
                )
        return "\n".join(lines) + "\n"

    def stop_monitor(self) -> bool:
        """Stop the scrape service (bounded join of the ``accl-monitor``
        thread); True when it exited cleanly.  No-op when not serving."""
        if self._monitor is None or self._monitor.server is None:
            return True
        srv, self._monitor.server = self._monitor.server, None
        return srv.stop()

    def export_chrome_trace(self, path: Optional[str] = None) -> dict:
        """Write (or return) this rank's Perfetto-loadable trace.  Merge
        per-rank files with ``python -m accl_tpu.telemetry merge``."""
        doc = chrome_trace(self.telemetry_trace_events())
        if path is not None:
            import json as _json

            with open(path, "w") as f:
                _json.dump(doc, f)
        return doc

    def capabilities(self) -> dict:
        """Capability report — the role of the reference's HWID idcode
        (``parse_hwid``, accl.cpp:1050-1064, bits baked by
        rebuild_bd.tcl:114): what this handle's engine/tier can do.
        Feature bits are runtime-detected instead of build-baked."""
        import sys

        try:
            from .native import available as native_available
        except Exception:  # pragma: no cover
            def native_available() -> bool:
                return False
        wire_dtypes = sorted(
            f"{u.name}->{c.name}" for (u, c) in self._arith if u != c
        )
        engine = type(self.engine).__name__
        caps = {
            "engine": engine,
            # by NAME: importing the class would pull jax into jax-free
            # emulator/native-tier processes just to render a report
            "device_tier": engine in ("XLAEngine", "DistEngine"),
            "native_dataplane": bool(native_available()),
            "wire_compression": wire_dtypes,
            "arithmetic": [f.name for f in ReduceFunction],
            "streams": True,
            "rendezvous": True,
            "world_size": self._world.size,
            # engine-lifetime device-interaction count (None on the
            # device-free tiers): the honest dispatch-cost telemetry of
            # the single-interaction contract — one collective on the
            # gang fast path moves this by exactly 1
            "device_interactions": self.engine.device_interactions(),
            # cached-dispatch telemetry (accl_tpu.plans): a warm
            # collective is a hit; SET_TUNING / soft_reset / eager
            # threshold writes each count one invalidation
            "plan_cache": self._plans.stats(),
            # overlap plane: the in-flight window depth this handle's
            # engine runs (SET_INFLIGHT_WINDOW / ACCL_INFLIGHT_WINDOW)
            "inflight_window": self._inflight_window_depth(),
            # the adopted measurement-driven TuningPlan, if any
            "tuning_plan": (
                None if self._tuning_plan is None else {
                    "tier": self._tuning_plan.tier,
                    "world": self._tuning_plan.world,
                    "collectives": sorted(self._tuning_plan.entries),
                }
            ),
            # graceful-degradation map: per-peer state for the world
            # communicator, keyed by rank — fed by timeout/retry
            # accounting (emulator tiers) and the gang slot watchdog
            # (XLA tier); a peer marked "dead" fails collectives fast,
            # a peer annotated "suspect_slow" is the monitor plane's
            # standing straggler verdict (annotation only)
            "health": self._annotated_health(self._world),
            # telemetry plane armed? (ACCL_TELEMETRY kill switch) — the
            # full merged view is ACCL.telemetry_snapshot()
            "telemetry": self._telemetry is not None,
            # monitor plane: the live scrape service, when serving
            # (ACCL_MONITOR_PORT / start_monitor)
            "monitor": (
                self._monitor.service_snapshot()
                if self._monitor is not None else None
            ),
            # membership plane: elastic state (epoch, evicted sessions,
            # demotions) — the full machine is
            # telemetry_snapshot()["membership"]
            "membership": {
                "elastic": self._membership.elastic,
                "epoch": self._membership.epoch,
                "evicted": sorted(self._membership.evicted),
                "demoted": self._membership.demoted(self._world.id),
                "joins_total": self._membership.joins_total,
            },
            # contract plane armed? (ACCL_VERIFY / set_contract_verify)
            "contract_verify": (
                None if self._contract is None else {
                    "interval": self._contract.interval,
                    "calls_verified": self._contract.calls_verified,
                }
            ),
        }
        # platform only when a jax BACKEND is already initialized: first
        # backend discovery is a side effect a read-only report must not
        # trigger (it takes the chip for this process)
        caps["platform"] = None
        if "jax" in sys.modules:
            try:
                from jax._src import xla_bridge

                if xla_bridge._backends:  # discovery already happened
                    caps["platform"] = sys.modules["jax"].default_backend()
            except Exception:  # pragma: no cover - private-API drift
                pass
        return caps

    def _inflight_window_depth(self) -> Optional[int]:
        """The engine's in-flight window depth (gang-held on the XLA
        tier, engine-held elsewhere; None when the tier has neither)."""
        depth = getattr(self.engine, "inflight_window", None)
        if depth is not None:
            return int(depth)
        gang = getattr(self.engine, "gang", None)
        window = getattr(gang, "window", None)
        return int(window.depth) if window is not None else None

    def deinit(self) -> None:
        if self._initialized:
            # monitor services first: a scrape landing mid-teardown must
            # not race the engine shutdown (stop is a bounded join) —
            # and the skew tracker leaves the shared fabric like the
            # contract verifier does, so a dead handle's tracker can't
            # keep stamping/observing for the fabric's lifetime
            if self._monitor is not None:
                self._monitor.close()
                self.engine.set_skew_tracker(None)
                fabric = getattr(self.engine, "fabric", None)
                if fabric is not None and hasattr(fabric, "unregister_skew"):
                    fabric.unregister_skew(self._monitor.tracker)
            # disarm the contract verifier: its board listener must
            # not outlive the handle (a stale listener would keep failing
            # gang slots for a verifier whose facade is gone)
            self.set_contract_verify(False)
            # causal trace/postmortem planes: the fabric stamp and the
            # anchored evidence registry must not outlive the handle
            # (same stale-listener reason), and the engine's hooks clear
            fabric = getattr(self.engine, "fabric", None)
            if fabric is not None and hasattr(fabric, "unregister_trace"):
                fabric.unregister_trace(self)
            if self._blackbox is not None:
                from .contract import anchored as _anchored

                reg = _anchored(
                    self.engine.contract_anchor(),
                    "_accl_blackbox_registry", dict,
                )
                if reg is not None:
                    reg.pop(self._blackbox.rank, None)
                self.engine.set_postmortem(None)
                ring = getattr(
                    getattr(self.engine, "gang", None), "cmdring", None
                )
                if ring is not None and ring.on_failure == (
                    self._on_ring_failure
                ):
                    ring.on_failure = None
            # and the membership plane's board listener + engine hooks,
            # for the same stale-listener reason
            self._membership.close()
            self.engine.set_membership(None)
            self.engine.on_health_transition = None
            try:
                self.end_batch()  # queued work must not die with the handle
            finally:
                # a wedged in-flight call may make the flush above raise
                # — the engine still shuts down (threads/queues must not
                # leak) and the handle still deinitializes; the error
                # propagates so the wedge stays loud
                self.engine.shutdown()
                self._initialized = False


# ---------------------------------------------------------------------------
# Group construction helpers
# ---------------------------------------------------------------------------


def emulated_group(
    n: int,
    rx_buffer_count: int = 16,
    rx_buffer_size: int = DEFAULT_RX_BUFFER_SIZE,
    **accl_kwargs,
) -> List[ACCL]:
    """N ranks in one process over the in-proc fabric — the CI tier, standing
    in for the reference's `mpirun N emulator processes` harness."""
    from .backends.emulator import EmuEngine, InProcFabric

    fabric = InProcFabric()
    ranks = [
        Rank(address=f"inproc:{i}", session=i, max_segment_size=rx_buffer_size)
        for i in range(n)
    ]
    engines = [
        EmuEngine(
            fabric,
            f"inproc:{i}",
            rx_buffer_count=rx_buffer_count,
            rx_buffer_size=rx_buffer_size,
        )
        for i in range(n)
    ]
    return [ACCL(engines[i], ranks, i, **accl_kwargs) for i in range(n)]


def xla_group(n: int, **accl_kwargs) -> List[ACCL]:
    """N rank handles over the XLA gang backend: collectives execute as one
    jitted shard_map program over an n-device mesh (ICI on real TPU slices,
    virtual CPU devices under XLA_FLAGS host-device forcing)."""
    from .backends.xla.engine import XLAEngine, XLAGangContext, _P2PChannel

    import jax

    devs = jax.devices()
    if n > len(devs):
        # never host-resident stand-ins for the missing chips: their
        # collectives would be numpy under the name of the device tier
        raise ValueError(
            f"xla_group({n}) needs {n} devices; jax found {len(devs)} on "
            f"{jax.default_backend()!r} (one rank owns one device)"
        )
    gang = XLAGangContext()
    p2p = _P2PChannel(gang.deadlines)
    peers: dict = {}
    ranks = [Rank(address=f"xla:{i}", session=i) for i in range(n)]
    group = []
    for i in range(n):
        # rank i owns device i's HBM
        eng = XLAEngine(gang, p2p=p2p, peers=peers, device=devs[i])
        peers[i] = eng
        group.append(ACCL(eng, ranks, i, **accl_kwargs))
    return group


def socket_group_member(
    rank: int,
    addresses: Sequence[str],
    rx_buffer_count: int = 16,
    rx_buffer_size: int = DEFAULT_RX_BUFFER_SIZE,
    **accl_kwargs,
) -> ACCL:
    """This process's member of a multi-process group over TCP sockets (one
    process per rank, like the reference's per-rank emulator processes)."""
    from .backends.emulator import EmuEngine
    from .backends.emulator.fabric import SocketFabric

    fabric = SocketFabric(addresses[rank])
    ranks = [
        Rank(address=a, session=i, max_segment_size=rx_buffer_size)
        for i, a in enumerate(addresses)
    ]
    engine = EmuEngine(
        fabric,
        addresses[rank],
        rx_buffer_count=rx_buffer_count,
        rx_buffer_size=rx_buffer_size,
    )
    return ACCL(engine, ranks, rank, **accl_kwargs)
