"""Backend interface: what the ACCL facade needs from a collective engine.

Role model: the abstract device ``CCLO`` (``driver/xrt/include/accl/
cclo.hpp:35-202``) with its ``Options`` record and
``call/start/wait/test`` surface.  A backend owns the scheduling and data
movement for one rank (emulator) or for a whole mesh (XLA tier).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..analysis.markers import spmd_uniform
from ..arithconfig import ArithConfig
from ..buffer import BaseBuffer
from ..communicator import Communicator
from ..constants import (
    CompressionFlags,
    HostFlags,
    Operation,
    ReduceFunction,
    StreamFlags,
)


@dataclasses.dataclass
class CallOptions:
    """One engine call, fully resolved (ref ``CCLO::Options``)."""

    op: Operation
    comm: Optional[Communicator] = None
    count: int = 0  # element count in *uncompressed* dtype
    root_src: int = 0  # root / source rank (op-dependent)
    root_dst: int = 0  # destination rank for send/recv
    tag: int = 0
    reduce_function: ReduceFunction = ReduceFunction.SUM
    arithcfg: Optional[ArithConfig] = None
    compression: CompressionFlags = CompressionFlags.NO_COMPRESSION
    stream: StreamFlags = StreamFlags.NO_STREAM
    host: HostFlags = HostFlags.NO_HOST
    op0: Optional[BaseBuffer] = None
    op1: Optional[BaseBuffer] = None
    res: Optional[BaseBuffer] = None
    stream_id: int = 0  # destination stream port for stream_put
    # Operation.CONFIG only:
    cfg_function: int = 0
    cfg_value: float = 0.0
    cfg_key: int = 0  # tuning register selector for SET_TUNING
    # cached-dispatch state (accl_tpu.plans): the facade's CollectivePlan
    # for this call (engines park prepared state in plan.engine), and the
    # per-size-bucket tuning-register overlay from a loaded TuningPlan —
    # engines overlay it onto their global registers at execution time
    # via effective_tuning()/eager_limit() below
    plan: Optional[object] = None
    tuning: Optional[dict] = None
    # quantized wire plane (accl_tpu.wire): the stochastic-rounding
    # seed for this call's wire lane.  0 = deterministic rounding (the
    # f16/bf16 lanes); nonzero for the fp8/int8 lanes, derived
    # SPMD-uniformly by the facade (wire.call_seed) and mixed per rank
    # at the point of encoding (wire.rank_seed) so ranks draw
    # independent streams from one shared slot/seed value
    wire_seed: int = 0
    # fused compute slot (constants.FusedCompute value): which compute
    # epilogue rides this call's command-ring slot.  0 = plain
    # collective; nonzero calls pack their compute operands into the
    # operand row (cmdring.ring_widths fused geometry) and NEVER run
    # the plain base op off-ring — ineligible fused calls decompose on
    # host with a counted fallback.  fuse_param is the epilogue scalar
    # (alpha / lr / scale), carried Q16.16 in the slot's fparam word.
    fuse: int = 0
    fuse_param: float = 0.0

    @spmd_uniform
    def eager_limit(self, default: int) -> int:
        """The eager-vs-rendezvous threshold steering THIS call: the
        per-size-bucket TuningPlan overlay's value when present, else
        the engine's global register.  The single definition every tier
        reads — divergent copies would skew protocol choice across
        ranks and break SPMD uniformity."""
        if self.tuning is not None:
            return self.tuning.get("max_eager_size", default)
        return default

    @spmd_uniform
    def effective_tuning(self, table: dict) -> dict:
        """The engine tuning table overlaid with this call's per-bucket
        registers (identical across ranks when every member loaded the
        same plan — the SPMD-uniformity contract)."""
        if not self.tuning:
            return table
        eff = dict(table)
        eff.update(self.tuning)
        return eff


class InteractionCounter:
    """Counts *device interactions*: program dispatches and host<->device
    transfers an engine issues on the data path.  The reference's hostctrl
    discipline is ONE command per collective (hostctrl.cpp:22-63); every
    extra interaction costs the host a dispatch and leaves the device
    idle until it lands, so the engines keep an honest running count —
    exposed via ``ACCL.capabilities()["device_interactions"]`` and
    asserted by
    tests/test_dispatch_overhead.py (one collective == one bump on the
    gang fast path).

    Buffer *creation* (``create_buffer`` staging) is deliberately not
    counted: the contract covers the collective between creation and
    sync, matching the zero-host-copy transfer-guard tests.

    Bumps come from every rank thread of a gang (and from deferred
    adoption running on waiter threads), so the increment is locked —
    ``+=`` alone is load/add/store and can lose counts across threads,
    which would break the tests' strict-equality assertions.
    """

    __slots__ = ("count", "_lock")

    def __init__(self):
        import threading

        self.count = 0
        self._lock = threading.Lock()

    def bump(self, n: int = 1) -> None:
        with self._lock:
            self.count += n

    def read(self) -> int:
        return self.count


class BaseEngine:
    """One rank's collective engine."""

    def start(self, options: CallOptions):
        """Enqueue a call; returns a Request immediately."""
        raise NotImplementedError

    def start_batch(self, items) -> None:
        """Dispatch a flushed command-queue batch: ``items`` is a list of
        ``(CallOptions, Request)`` pairs whose Requests were created by
        the facade at queue time (so ``run_async`` callers already hold
        them).  Engines that can fuse a batch into one device interaction
        override this (XLA gang / dist); the default just serializes,
        bridging each inner engine request onto the caller's."""
        for options, req in items:
            inner = self.start(options)
            inner.add_done_callback(
                lambda i=inner, r=req: r.complete(
                    i.get_retcode(), i.get_duration_ns(),
                    context=i.error_context,
                )
            )

    def device_interactions(self):
        """Engine-lifetime device-interaction count, or ``None`` on tiers
        with no device (emulator/native: the dataplane is host memory)."""
        return None

    def drain_inflight(self, timeout=None) -> bool:
        """Overlap plane: block until every launched-but-incomplete call
        of this engine has completed (the facade's ``flush()``/config/
        ``soft_reset`` drain points).  Tiers without an in-flight window
        (emulator/native: requests complete from their own schedulers)
        are a no-op.  Returns False only on timeout."""
        return True

    def contract_anchor(self):
        """The object the contract plane's in-process digest exchange
        (``accl_tpu.contract.board_for``) anchors on.  Engines whose
        rank handles share a process-wide object override this with it
        (InProc fabric, XLA gang context); the default — ``None`` — on
        one-engine-per-process tiers skips board posting entirely (a
        single-poster board can never convict; copying the evidence
        ring into it every window would be pure overhead against the
        <=5% budget) and leaves verification to the wire piggyback /
        facade intake checks."""
        return None

    #: the facade-armed ContractVerifier (None = verification off)
    contract_verifier = None

    #: the facade's straggler SkewTracker (monitor plane; None = off)
    skew_tracker = None

    #: the facade's MembershipView (accl_tpu.membership; None = off)
    membership = None

    #: facade hook fired on every peer-health state transition
    #: (``(peer, old_state, new_state)``): feeds the transition
    #: counters/event ring and, when elastic membership is armed, the
    #: dead-verdict eviction proposal.  Must be cheap and never raise.
    on_health_transition = None

    def set_membership(self, view) -> None:
        """Arm (or with ``None`` disarm) the membership plane on this
        engine.  Default: store the handle — the facade's intake/
        failure paths do the acting; fabric tiers override to observe
        MEMBER agreement frames at delivery and to fail in-flight work
        against confirmed evictions fast."""
        self.membership = view

    def on_membership_cutover(self, plan: dict, addresses: tuple = (),
                              comm_ids: tuple = ()) -> None:
        """Engine-side shrink hook: tear down / re-arm per-comm session
        state over the survivors (ring sessions on the XLA
        tier; rx/ledger/retransmit purge + health-strike hygiene on the
        emulator).  ``addresses`` are the evicted peers' transport
        addresses; ``comm_ids`` the communicators that shrank.
        Default: no per-comm session state to re-arm."""

    def on_membership_restore(self) -> None:
        """Engine-side restore hook (soft_reset re-admission): the
        reset itself already flushed engine state on every tier."""

    def set_skew_tracker(self, tracker) -> None:
        """Arm (or with ``None`` disarm) the monitor plane's cross-rank
        skew exchange on this engine.  Default: store the handle — on
        board-anchored tiers (InProc emulator, XLA gang) the shared
        judge does the exchanging and the engine has nothing to wire;
        fabric tiers override to observe peers' piggybacked window
        claims at delivery (the contract plane's stamp cadence,
        reused)."""
        self.skew_tracker = tracker

    #: the facade's POSTMORTEM frame handler (None = postmortem off)
    postmortem_handler = None

    def set_postmortem(self, handler) -> None:
        """Arm (or with ``None`` disarm) the postmortem plane's wire
        solicitation on this engine.  Default: store the handle —
        board-anchored tiers solicit in process over the anchored
        registry; fabric tiers override to route POSTMORTEM frames to
        the handler at delivery."""
        self.postmortem_handler = handler

    def trace_events(self) -> list:
        """Engine-owned Chrome/Perfetto trace events merged into the
        facade's export: ring-resident slot spans on the gang tier
        (one span per slot, parented under its refill window and
        flow-linked to the issuing call); [] on tiers with no engine-
        resident execution to introspect."""
        return []

    def skew_exchange_mode(self) -> str:
        """How this tier's straggler samples cross ranks: ``"board"``
        (shared in-process judge via ``contract_anchor()``), ``"wire"``
        (per-message piggyback), or ``"local"`` (single-rank baselines
        only — the dist tier's cross-process exchange rides ROADMAP
        item 2's topology work, like the contract plane's KV
        piggyback)."""
        return "board" if self.contract_anchor() is not None else "local"

    def set_contract_verifier(self, verifier) -> None:
        """Arm (or with ``None`` disarm) engine-side contract checks.
        Default: store the handle — the facade's intake screen is the
        only check on such tiers (native: the C dataplane cannot consult
        a Python verifier mid-call).  Engines with their own schedulers
        or delivery paths override to fail in-flight work fast too."""
        self.contract_verifier = verifier

    def health_report(self, comm) -> dict:
        """Per-peer health map for ``comm``, keyed by comm-relative rank
        (``capabilities()["health"]``).  Engines with timeout/retry
        accounting (emulator) or a gang watchdog (XLA) override this; the
        default reports every peer healthy."""
        return {
            i: {"state": "ok", "timeouts": 0, "failures": 0, "last_event": ""}
            for i in range(comm.size)
            if i != comm.local_rank
        }

    def telemetry_report(self) -> dict:
        """Engine-side counters for ``ACCL.telemetry_snapshot()``: the
        tier-specific live-resource depths and event counters (rx pool,
        retransmit window, fault injector, gang slots, stream ports).
        Each tier overrides with its own facts; the shape is flat
        scalars/small dicts so the Prometheus exporter can fold the
        numbers out as gauges.  Must be cheap and side-effect-free —
        dashboards poll it."""
        return {
            "device_interactions": self.device_interactions(),
            "faults": None,
            "skew_exchange": self.skew_exchange_mode(),
        }

    def create_buffer(self, count: int, dtype, host_only: bool = False,
                      data=None):
        """Backend-appropriate buffer (ref: ACCL::create_buffer dispatching
        to XRTBuffer/SimBuffer per device).  Default: emulator-tier host
        pair; device tiers override with HBM-resident buffers.

        ``data`` (a 1-D numpy array) seeds the buffer: the host side ALIASES
        it (mutating the caller's array mutates host memory, the reference's
        wrap-existing-pointer buffer constructor) and the device side is
        synced on return."""
        from ..buffer import EmuBuffer

        if data is not None:
            buf = EmuBuffer.from_array(data, host_only=host_only)
            buf.sync_to_device()
            return buf
        return EmuBuffer(count, dtype, host_only=host_only)

    def shutdown(self) -> None:
        raise NotImplementedError

    # -- device stream ports (stream_put / streaming operands) --------------
    def stream_push(self, stream_id: int, data: bytes) -> None:
        raise NotImplementedError

    def stream_pop(self, stream_id: int, timeout: Optional[float] = None) -> bytes:
        raise NotImplementedError


class StreamPortMixin:
    """Local device stream ports (the external-kernel AXIS interface) and
    the streaming-operand/result payload helpers, shared by the device-tier
    engines.  Hosts must call :meth:`_init_streams` and provide
    ``self.timeout_s``."""

    def _init_streams(self) -> None:
        import threading

        self._streams: dict = {}
        self._stream_cv = threading.Condition()

    def stream_push(self, stream_id: int, data: bytes) -> None:
        with self._stream_cv:
            self._streams.setdefault(stream_id, []).append(data)
            self._stream_cv.notify_all()

    def stream_pop(self, stream_id: int, timeout: Optional[float] = None) -> bytes:
        with self._stream_cv:
            ok = self._stream_cv.wait_for(
                lambda: self._streams.get(stream_id), timeout
            )
            if not ok:
                raise TimeoutError(f"stream {stream_id} empty")
            return self._streams[stream_id].pop(0)

    def _pop_stream_payload(self, options: CallOptions, count=None):
        """Blocking pop of a full streaming operand from this rank's
        stream port; None on timeout (the engine's DMA deadline)."""
        import time

        import numpy as np

        from ..constants import dtype_to_numpy

        cfg = options.arithcfg
        src_dt = (
            cfg.compressed
            if options.compression & CompressionFlags.OP0_COMPRESSED
            else cfg.uncompressed
        )
        npdt = dtype_to_numpy(src_dt)
        n = options.count if count is None else int(count)
        need = n * npdt.itemsize
        raw = b""
        deadline = time.monotonic() + self.timeout_s
        try:
            while len(raw) < need:
                raw += self.stream_pop(
                    options.stream_id,
                    timeout=max(0.01, deadline - time.monotonic()),
                )
        except TimeoutError:
            return None
        return np.frombuffer(raw[:need], npdt).copy()

    def _push_stream_result(self, options: CallOptions, data) -> None:
        """Result row to this rank's stream port, in the wire dtype the
        compression flags request (the RES_STREAM lane)."""
        import numpy as np

        from ..constants import dtype_to_numpy

        cfg = options.arithcfg
        res_dt = (
            cfg.compressed
            if options.compression & CompressionFlags.RES_COMPRESSED
            else cfg.uncompressed
        )
        npdt = dtype_to_numpy(res_dt)
        self.stream_push(
            options.stream_id,
            np.asarray(data)[: options.count].astype(npdt).tobytes(),
        )
