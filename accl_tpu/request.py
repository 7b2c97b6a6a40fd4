"""Asynchronous request model.

Role model: ``driver/xrt/include/accl/acclrequest.hpp`` — ``BaseRequest``
(mutex + condvar guarded status, return code, device-measured duration,
:39-147) and the thread-safe ``FPGAQueue`` (:153-211) that serializes
operations onto the single offload engine.  Here requests are completed by the
backend's engine thread(s); ``wait``/``test`` expose the same non-blocking /
blocking surface.

Single-interaction dispatch additions: a request may complete with an
*unresolved device handle* — the engine parks the result-adoption work
(writeback/trim programs, each a device interaction of its own)
as a deferred resolver that runs on the first ``wait()``/``test()``/
``check()``, so fire-and-forget and ``run_async`` chains never pay the
result leg at dispatch time.  ``CommandQueue`` doubles as the facade's
batch holder: queued calls ``drain()`` as one flush unit that the device
engines dispatch as a single fused program.
"""

from __future__ import annotations

import enum
import itertools
import threading
from typing import Any, Callable, List, Optional

from .constants import ACCLError, ErrorCode


class RequestStatus(enum.IntEnum):
    QUEUED = 0
    EXECUTING = 1
    COMPLETED = 2


_request_ids = itertools.count(1)


class Request:
    def __init__(self, op_name: str = ""):
        self.id = next(_request_ids)
        self.op_name = op_name
        self._done = threading.Event()
        self._status = RequestStatus.QUEUED
        self._retcode = ErrorCode.OK
        self._duration_ns: int = 0
        # backend-private payload (e.g. the engine call record)
        self.payload: Any = None
        # structured failure context recorded by the engine at completion
        # (op/comm/peer/attempts/elapsed) — surfaced via ACCLError.details
        self.error_context: Optional[dict] = None
        # lazy-adoption state: the unresolved device-side result (e.g. an
        # output shard / p2p payload) and the thunk that materializes it
        # into the user's buffer.  Set by the engine BEFORE complete().
        self.device_handle: Any = None
        self._resolver: Optional[Callable[[], None]] = None
        self._cb_lock = threading.Lock()
        self._callbacks: List[Callable[[], None]] = []
        # batching: flush hook armed by the facade while this request sits
        # in an unflushed command-queue batch (auto-flush on wait/sync)
        self._pre_wait: Optional[Callable[[], None]] = None
        # telemetry plane (accl_tpu.telemetry): armed by the facade via
        # Telemetry.attach; complete() appends one CallRecord — the
        # flight-recorder hook every tier's completion path runs through
        self._telemetry = None
        self._tmeta: Optional[dict] = None
        # overlap plane (accl_tpu.overlap): stamped by the engine's
        # in-flight window drainer just before complete() — how long this
        # call stayed in flight after its launch returned, and the window
        # depth it was parked at.  None on tiers/paths without a window.
        self.overlap_ns: Optional[int] = None
        self.inflight_depth: Optional[int] = None
        # command-ring plane (the TPU CCLO analog): True when this call
        # executed ring-resident — decoded and sequenced on device by
        # a window program, the host only refilling the ring
        self.ring_resident: Optional[bool] = None

    # -- engine side --------------------------------------------------------
    def mark_executing(self) -> None:
        self._status = RequestStatus.EXECUTING

    def complete(
        self,
        retcode: ErrorCode,
        duration_ns: int = 0,
        context: Optional[dict] = None,
    ) -> None:
        self._retcode = ErrorCode(retcode)
        if context is not None:
            self.error_context = context
        self._duration_ns = int(duration_ns)
        self._status = RequestStatus.COMPLETED
        with self._cb_lock:
            self._done.set()
            callbacks, self._callbacks = self._callbacks, []
            tel, meta = self._telemetry, self._tmeta
        if tel is not None:
            # flight-recorder append (host-side ring write only; a
            # telemetry failure must never fail the call it observes)
            try:
                tel.record(meta, self._duration_ns, self._retcode,
                           self.error_context,
                           overlap_ns=self.overlap_ns,
                           inflight_depth=self.inflight_depth,
                           ring_resident=self.ring_resident)
            except Exception:  # pragma: no cover - defensive
                pass
        for cb in callbacks:
            cb()

    def add_done_callback(self, fn: Callable[[], None]) -> None:
        """Run ``fn`` when the request completes (immediately if it
        already has) — the bridge the default ``start_batch`` uses to
        forward inner engine completions onto facade-created requests."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        fn()

    def defer_result(
        self, resolver: Callable[[], None], handle: Any = None
    ) -> None:
        """Engine side: park result materialization (the device
        interaction that adopts the result into the user's buffer) until
        the user waits or touches the data.  Must be called BEFORE
        ``complete()`` so the done event publishes it."""
        self._resolver = resolver
        self.device_handle = handle

    # -- user side ----------------------------------------------------------
    def materialize(self) -> None:
        """Run the deferred result adoption, once.  Invoked automatically
        from ``wait()``/``test()``/``check()`` after completion; safe to
        call any number of times and from concurrent waiters (the locked
        swap guarantees the resolver runs exactly once).  A resolver
        failure (e.g. the deferred writeback program failing to compile)
        downgrades the request's OK retcode to INVALID_OPERATION so
        ``check()`` surfaces it as an ACCLError instead of an arbitrary
        exception escaping a ``wait()`` that already reported success."""
        with self._cb_lock:
            resolver, self._resolver = self._resolver, None
        if resolver is None:
            return
        try:
            resolver()
        except Exception:
            import traceback

            traceback.print_exc()
            if self._retcode == ErrorCode.OK:
                self._retcode = ErrorCode.INVALID_OPERATION
                if self._telemetry is not None and self._tmeta is not None:
                    # the completion-time record said OK; amend the
                    # flight recorder so the failed adoption is visible
                    # in the history (and counted as an error)
                    try:
                        self._telemetry.record(
                            self._tmeta, self._duration_ns,
                            self._retcode, self.error_context,
                            amend=True,
                        )
                    except Exception:  # pragma: no cover - defensive
                        pass
        finally:
            # the handle (an HBM output shard / p2p payload) is dead
            # weight once adopted — dropping it here keeps long-lived
            # Request objects from pinning device memory
            self.device_handle = None

    @property
    def status(self) -> RequestStatus:
        return self._status

    def done(self) -> bool:
        """Side-effect-free completion probe for ENGINE-internal code
        (watchdogs, soft_reset, batch error paths): no batch auto-flush,
        no deferred-result materialization — calling the user-facing
        ``test()`` from an engine thread could re-enter the facade's
        flush mid-failure or drain a batch the user is still building."""
        return self._done.is_set()

    def _auto_flush(self) -> None:
        hook, self._pre_wait = self._pre_wait, None
        if hook is not None:
            hook()  # waiting/polling a queued request flushes its batch

    def test(self) -> bool:
        """Non-blocking completion probe (materializes the deferred
        result on a positive answer — a True test() means the user may
        read the result buffer next).  Also auto-flushes an open batch:
        polling a queued-but-unflushed request would otherwise spin
        forever on a call that was never dispatched."""
        self._auto_flush()
        if not self._done.is_set():
            return False
        self.materialize()
        return True

    def wait(self, timeout: Optional[float] = None) -> bool:
        self._auto_flush()
        ok = self._done.wait(timeout)
        if ok:
            self.materialize()
        return ok

    def get_retcode(self) -> ErrorCode:
        return self._retcode

    def get_duration_ns(self) -> int:
        """Engine-measured duration of the call in nanoseconds.

        The reference reads a free-running device cycle counter
        (``ccl_offload_control.c:2279-2303``); emulator tiers substitute a
        monotonic host clock, the TPU tier device timings.
        """
        return self._duration_ns

    def check(self, context: str = "") -> None:
        # materialize FIRST: a deferred-adoption failure downgrades the
        # retcode, and check() must observe that, not the pre-adoption OK
        if self._done.is_set():
            self.materialize()
        if self._retcode != ErrorCode.OK:
            details = self.error_context
            if self._telemetry is not None:
                # a failure ships with its recent history: the last-N
                # flight-recorder records ride ACCLError.details so a
                # chip-tier timeout is diagnosable without a live session
                details = dict(details or {})
                details["flight_recorder"] = self._telemetry.tail_dicts()
            raise ACCLError(
                self._retcode, context or self.op_name,
                details=details,
            )


class CommandQueue:
    """FIFO serializing calls onto one engine, preserving issue order.

    The reference needs this because a single CCLO executes one host command
    stream (``acclrequest.hpp:153-211``); we keep it so that the async API has
    deterministic ordering regardless of backend threading.

    It is also the batching unit of single-interaction dispatch: the
    facade queues calls here between ``begin_batch()`` and ``flush()``,
    then ``drain()`` hands the whole run to ``engine.start_batch`` as ONE
    flush — which the device engines execute as one fused program (one
    device interaction for N queued collectives).  The dist engine's
    executor likewise pushes a flushed batch as a single queue item so
    every member process sees the identical batch boundary (the SPMD
    contract extends to batches).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._items: list = []
        self._cv = threading.Condition(self._lock)
        self._closed = False

    def push(self, item) -> None:
        with self._cv:
            if self._closed:
                raise RuntimeError("command queue closed")
            self._items.append(item)
            self._cv.notify()

    def pop(self, timeout: Optional[float] = None):
        with self._cv:
            if not self._items:
                self._cv.wait(timeout)
            if not self._items:
                return None
            item = self._items.pop(0)
            # wake backpressure waiters (wait_depth_below); a concurrent
            # popper woken spuriously re-checks and times out harmlessly
            self._cv.notify_all()
            return item

    def wait_depth_below(self, n: int, timeout: Optional[float] = None) -> bool:
        """Overlap-plane backpressure: block until fewer than ``n`` items
        are queued (or the queue closes / the timeout expires).  Bounds
        how far an async caller can run ahead of the serialized executor
        (the dist tier's in-flight window)."""
        import time as _time

        deadline = (
            None if timeout is None else _time.monotonic() + float(timeout)
        )
        with self._cv:
            while len(self._items) >= n and not self._closed:
                rem = None
                if deadline is not None:
                    rem = deadline - _time.monotonic()
                    if rem <= 0:
                        return False
                self._cv.wait(rem if rem is not None else 1.0)
            return True

    def drain(self) -> list:
        """Atomically take every queued item (the batch-flush unit);
        returns [] when empty.  Unlike pop(), never blocks."""
        with self._cv:
            items, self._items = self._items, []
            return items

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)
