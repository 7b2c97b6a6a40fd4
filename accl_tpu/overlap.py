"""The overlap plane: the async in-flight window behind the device tiers.

Role model: the reference keeps the host out of the data path — the CCLO
consumes a command FIFO while the host queues more work, so consecutive
collectives overlap instead of serializing launch -> execute -> complete
(SURVEY §1).  The TPU analog is JAX's async dispatch: a jitted program
returns a future-like array immediately, so the engine can *launch* the
next collective while the device still executes the previous one — it
only has to stop completing requests synchronously on the launch path.

:class:`InflightWindow` is that decoupling, engine-agnostic:

* ``park(key, waiter, on_ready, on_error)`` hands a launched call's
  device future (as a blocking ``waiter`` thunk) to the window; the
  launch thread returns immediately.  A per-key drainer thread waits
  entries **in launch order within their key** (the seqn ordering the
  gang's SPMD contract needs: completions can never reorder across a
  communicator) and fires the completion callback with honest timing +
  overlap facts.  Keys drain independently — a wedged communicator
  never blocks completion of a healthy one.
* ``park`` applies backpressure: when ``key`` already has ``depth``
  entries in flight, the caller blocks until the oldest completes — the
  bound that keeps in-flight output shards from pinning unbounded HBM.
  The wait is BOUNDED (``park_timeout_s``): if the oldest call is
  wedged, the launch proceeds over-depth rather than wedging the
  submitting thread — ``start()`` must always return a ``Request`` so
  the facade's own deadlock deadlines can still fire (the same
  discipline the dist tier's ``wait_depth_below`` applies).
* ``drain()`` blocks until the window is empty — the drain points the
  facade exposes (``wait()``/``flush()``/barrier/config/``soft_reset``).
* ``stop()`` (engine shutdown) drains and degrades: later parks run
  their waiter synchronously on the launch thread, so a torn-down
  engine never strands a request.

``on_ready`` receives ``overlap_ns`` — nonzero ONLY when a later launch
of the same key parked while the call was still in flight (evidence
that device time was genuinely hidden behind host work).  A lone sync
call that merely rode the window reports 0: nothing overlapped it.

Zero jax imports: waiters are opaque thunks (typically
``lambda: jax.block_until_ready(out)``), so the module is unit-testable
with plain threading primitives and importable from jax-free processes
(the drainer's two ``utils.profiling.annotate`` spans are the shared
no-op there).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .constants import DEFAULT_INFLIGHT_WINDOW, MAX_INFLIGHT_WINDOW
from .utils.profiling import annotate

__all__ = ["InflightWindow", "default_window_depth", "drain_deadline_s"]

#: how long an idle per-key drainer lingers for more work before exiting
#: (keeps steady-state at one thread per ACTIVE communicator instead of
#: spawn/exit per call)
_DRAINER_LINGER_S = 5.0


def default_window_depth() -> int:
    """Window depth from ``ACCL_INFLIGHT_WINDOW`` (clamped to
    [1, MAX_INFLIGHT_WINDOW]), defaulting small and conservative."""
    try:
        depth = int(
            os.environ.get("ACCL_INFLIGHT_WINDOW", DEFAULT_INFLIGHT_WINDOW)
        )
    except ValueError:
        depth = DEFAULT_INFLIGHT_WINDOW
    return max(1, min(depth, MAX_INFLIGHT_WINDOW))


def drain_deadline_s(timeout_s: float) -> float:
    """The bounded-drain policy every drain point shares: 4x the
    configured engine/facade timeout with a 60 s floor, so the engine's
    own RECEIVE_TIMEOUT fires first for assembly stalls and a first-call
    XLA compile of a large program doesn't trip the bound spuriously."""
    return max(60.0, 4.0 * float(timeout_s))


class _Entry:
    __slots__ = (
        "key", "waiter", "on_ready", "on_error", "parked_ns", "depth",
        "overlapped", "ring",
    )

    def __init__(self, key, waiter, on_ready, on_error, parked_ns, depth,
                 ring=False):
        self.key = key
        self.waiter = waiter
        self.on_ready = on_ready
        self.on_error = on_error
        self.parked_ns = parked_ns
        self.depth = depth
        # set when a LATER launch of this key parks while this entry is
        # still in flight — the witness that its device time was hidden
        self.overlapped = False
        # command-ring refill window: ONE entry covers a whole window
        # of collectives; its waiter blocks on the window program's
        # status words
        self.ring = ring


class InflightWindow:
    """Bounded per-key FIFO of launched-but-incomplete device calls.

    One drainer thread per ACTIVE key (lazily started, lingers briefly,
    exits when idle) completes that key's entries in park order; per-key
    counts enforce the depth bound.  All counters in :meth:`stats` are
    cumulative over the window's lifetime.
    """

    def __init__(self, depth: Optional[int] = None,
                 park_timeout_s: float = 120.0):
        self.depth = depth if depth is not None else default_window_depth()
        self.park_timeout_s = float(park_timeout_s)
        # QoS arbiter plane: per-key depth overrides — a tenant
        # communicator's share of the window (SET_TENANT_WINDOW_SHARE).
        # Keys without an override ride the global depth.
        self._key_depth: Dict[Any, int] = {}
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # per-key FIFO; the head entry is the one its drainer is waiting
        # on (still counted in flight until its completion ran)
        self._pending: Dict[Any, List[_Entry]] = {}
        self._threads: Dict[Any, threading.Thread] = {}
        self._total = 0
        self._stopped = False
        # cumulative accounting (telemetry_report / bench evidence)
        self.launched = 0
        self.completed = 0
        self.failed = 0
        self.max_depth_seen = 0
        self.overlap_ns_total = 0
        # command-ring plane: refill windows parked with ring=True (each
        # is ONE entry covering a whole window of collectives): parks
        # and completions count WINDOWS.
        self.ring_launched = 0
        self.ring_completed = 0

    # -- engine side ---------------------------------------------------------
    def set_depth(self, depth: int) -> None:
        with self._cv:
            self.depth = max(1, min(int(depth), MAX_INFLIGHT_WINDOW))
            self._cv.notify_all()

    def set_key_depth(self, key: Any, depth: Optional[int]) -> None:
        """Per-key depth override (the QoS arbiter's per-tenant window
        share): ``key``'s launches bound at ``depth`` instead of the
        global depth; ``None`` clears the override.  Widening wakes
        parked launchers like :meth:`set_depth` does."""
        with self._cv:
            if depth is None:
                self._key_depth.pop(key, None)
            else:
                self._key_depth[key] = max(
                    1, min(int(depth), MAX_INFLIGHT_WINDOW)
                )
            self._cv.notify_all()

    def depth_for(self, key: Any) -> int:
        """The depth bound governing ``key`` right now."""
        with self._lock:
            return self._key_depth.get(key, self.depth)

    def park(
        self,
        key: Any,
        waiter: Callable[[], None],
        on_ready: Callable[[int, int, int], None],
        on_error: Callable[[BaseException], None],
        ring: bool = False,
    ) -> None:
        """Queue one launched call.  ``waiter`` blocks until the device
        result is ready; ``on_ready(overlap_ns, depth_at_park,
        ready_perf_ns)`` completes the requests; ``on_error(exc)`` maps a
        device-side failure onto them.  Blocks the caller while ``key``
        is at the depth bound (backpressure, bounded by
        ``park_timeout_s`` — a wedged oldest call must not also wedge
        the submitting thread), and runs synchronously when the window
        was stopped (engine shutdown degraded mode).

        ``ring=True`` marks a command-ring refill window (the TPU CCLO
        plane): for ring-resident traffic THIS window is the refill
        window — its depth bounds how many refill dispatches run ahead
        of completion, and every drain point below blocks on the device
        status word the sequencer wrote (the ``waiter``).  Counted
        separately in :meth:`stats` (``ring_launched``)."""
        with self._cv:
            stopped = self._stopped
            if not stopped:
                # backpressure: the launch that would exceed the window
                # waits for the oldest in-flight call of its key — but
                # only up to the bound; past it we park over-depth so
                # start() still returns and facade deadlines can fire
                deadline = time.monotonic() + self.park_timeout_s
                while (
                    len(self._pending.get(key, ()))
                    >= self._key_depth.get(key, self.depth)
                    and not self._stopped
                ):
                    rem = deadline - time.monotonic()
                    if rem <= 0:
                        break
                    self._cv.wait(min(rem, 1.0))
                stopped = self._stopped
            if not stopped:
                fifo = self._pending.setdefault(key, [])
                for earlier in fifo:
                    # this launch is the witness that every in-flight
                    # call of the key genuinely overlapped host work
                    earlier.overlapped = True
                parked_ns = time.perf_counter_ns()
                depth = len(fifo) + 1
                entry = _Entry(key, waiter, on_ready, on_error,
                               parked_ns, depth, ring=ring)
                fifo.append(entry)
                self._total += 1
                self.launched += 1
                if ring:
                    self.ring_launched += 1
                self.max_depth_seen = max(self.max_depth_seen, depth)
                t = self._threads.get(key)
                if t is None:
                    t = threading.Thread(
                        target=self._run, args=(key,),
                        name=f"accl-overlap-drain-{key}", daemon=True,
                    )
                    self._threads[key] = t
                    t.start()
                self._cv.notify_all()
                return
        # stopped: degrade to the pre-overlap synchronous discipline
        # (still a launch — completed == launched stays the leak-check
        # invariant the soak/overlap tests assert)
        with self._lock:
            self.launched += 1
            if ring:
                self.ring_launched += 1
        self._complete(
            _Entry(key, waiter, on_ready, on_error,
                   time.perf_counter_ns(), 1, ring=ring)
        )

    # -- drain points --------------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every parked call has completed (True) or the
        timeout expired (False).  The drain points of the overlap plane:
        ``Request.wait`` (implicitly, per request), facade ``flush()``,
        barrier, config writes, and ``soft_reset`` all funnel here."""
        deadline = (
            None if timeout is None else time.monotonic() + float(timeout)
        )
        with self._cv:
            while self._total > 0:
                rem = None
                if deadline is not None:
                    rem = deadline - time.monotonic()
                    if rem <= 0:
                        return False
                self._cv.wait(rem if rem is not None else 1.0)
            return True

    def drain_key(self, key: Any, timeout: Optional[float] = None) -> bool:
        """Block until every parked call of ``key`` has completed (True)
        or the timeout expired (False) — the per-communicator ordering
        fence: an inline completion on a communicator must not overtake
        its launched-but-incomplete device calls.  A no-op on the key's
        own drainer thread (a completion callback that re-enters the
        engine must not wait on itself)."""
        deadline = (
            None if timeout is None else time.monotonic() + float(timeout)
        )
        with self._cv:
            if self._threads.get(key) is threading.current_thread():
                return True
            while self._pending.get(key):
                rem = None
                if deadline is not None:
                    rem = deadline - time.monotonic()
                    if rem <= 0:
                        return False
                self._cv.wait(rem if rem is not None else 1.0)
            return True

    def stop(self, timeout: float = 60.0) -> None:
        """Engine shutdown: drain (bounded — shutdown must terminate
        even over a wedged device call), then degrade future parks to
        synchronous completion (no threads left behind)."""
        self.drain(timeout)
        with self._cv:
            self._stopped = True
            threads = list(self._threads.values())
            self._cv.notify_all()
        for t in threads:
            t.join(timeout=2.0)

    # -- introspection -------------------------------------------------------
    def in_flight(self) -> int:
        with self._lock:
            return self._total

    def stats(self) -> dict:
        with self._lock:
            return {
                "depth": self.depth,
                "key_depths": dict(self._key_depth),
                "in_flight": self._total,
                "max_depth_seen": self.max_depth_seen,
                "launched": self.launched,
                "completed": self.completed,
                "failed": self.failed,
                "overlap_ns_total": self.overlap_ns_total,
                "ring_launched": self.ring_launched,
                "ring_completed": self.ring_completed,
            }

    # -- drainer (one per active key) ----------------------------------------
    def _run(self, key) -> None:
        while True:
            with self._cv:
                fifo = self._pending.get(key)
                if not fifo:
                    if self._stopped:
                        self._threads.pop(key, None)
                        return
                    # linger for more work before exiting, so steady
                    # traffic reuses one thread per communicator
                    self._cv.wait_for(
                        lambda: bool(self._pending.get(key))
                        or self._stopped,
                        timeout=_DRAINER_LINGER_S,
                    )
                    fifo = self._pending.get(key)
                    if not fifo:
                        self._threads.pop(key, None)
                        return
                entry = fifo[0]  # stays counted until completion ran
            self._complete(entry)
            with self._cv:
                fifo = self._pending.get(key)
                if fifo and fifo[0] is entry:
                    fifo.pop(0)
                    if not fifo:
                        self._pending.pop(key, None)
                self._total -= 1
                self._cv.notify_all()

    def _complete(self, entry: _Entry) -> None:
        try:
            with annotate("accl.window::ready"):
                entry.waiter()
        except BaseException as e:  # device-side failure
            with self._lock:
                self.failed += 1
                self.completed += 1
                if entry.ring:
                    self.ring_completed += 1
            try:
                entry.on_error(e)
            except Exception:  # pragma: no cover - defensive
                import traceback

                traceback.print_exc()
            return
        ready_ns = time.perf_counter_ns()
        overlap_ns = (
            max(0, ready_ns - entry.parked_ns) if entry.overlapped else 0
        )
        with self._lock:
            self.completed += 1
            if entry.ring:
                self.ring_completed += 1
            self.overlap_ns_total += overlap_ns
        try:
            with annotate("accl.window::complete"):
                entry.on_ready(overlap_ns, entry.depth, ready_ns)
        except Exception:  # pragma: no cover - defensive
            import traceback

            traceback.print_exc()
