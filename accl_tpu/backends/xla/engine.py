"""XLA device backend: the ACCL facade over a real device mesh.

The reference's device tier drives one offload engine per FPGA over the
100G fabric; the TPU equivalent is SPMD — *one* XLA program executes the
collective across every chip at once.  This backend bridges the MPI-like
per-rank call model onto that: rank handles submit their operands into a
shared :class:`XLAGangContext`; when every rank of a communicator has posted
the matching call, the gang runs one jitted ``shard_map`` program over the
mesh (built from ``accl_tpu.ops``) and distributes the per-rank results.

This is the semantic bridge SURVEY.md §7 calls the hard part ("eager/
rendezvous semantics vs XLA's static world"): tag-matched point-to-point
pairs rendezvous *at the gang*, and the data then moves with a
collective-permute on ICI.

Mapping notes (ref -> here):
* communicator        -> sub-``Mesh`` over the first ``comm.size`` devices
                         (ref: comm tables in exchange memory)
* eager/rendezvous    -> collapsed: gang rendezvous + XLA scheduling
                         (ref: protocol select at c:587/667/808)
* compression flags   -> wire-dtype cast stages around the collective
                         (ref: hp_compression lanes)
* per-call perf ctr   -> wall-clock ns around the XLA program
"""

from __future__ import annotations

import functools
import heapq
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...communicator import Communicator
from ...constants import (
    CompressionFlags,
    ConfigFunction,
    DEFAULT_TIMEOUT_S,
    ErrorCode,
    MAX_EAGER_SIZE_LIMIT,
    Operation,
    ReduceFunction,
    StreamFlags,
    dtype_to_numpy,
)
from ...buffer import (
    DeviceBuffer,
    DummyBuffer,
    EmuBuffer,
    dev_zeros as _dev_zeros,
    make_buffer,
)
from ...overlap import InflightWindow, drain_deadline_s
from ...request import Request
from ...utils.profiling import annotate
from ..base import BaseEngine, CallOptions, InteractionCounter, StreamPortMixin
from ...ops import driver as opdriver
from .cmdring import GangCommandRing

#: sentinel returned by the gang execution paths when a call's completion
#: was handed to the in-flight window (the overlap plane): the caller
#: must NOT complete the requests — the window's drainer will, from the
#: device done-probe, in launch order.
IN_FLIGHT = object()


#: the gang call's own host span, by op: constant names on the hot path
_OP_SPAN = {op: f"accl::{op.name.lower()}" for op in Operation}


def _np_stack_op0(
    calls: List[CallOptions], counts: List[int], ic=None
) -> np.ndarray:
    """Stack per-rank operands (rank-major) into one (size, n) array."""
    rows = []
    width = max(counts) if counts else 0
    for call, n in zip(calls, counts):
        if call.op0 is not None and not call.op0.is_dummy:
            if ic is not None and isinstance(call.op0, DeviceBuffer):
                ic.bump()  # D2H read of the operand (fallback staging)
            row = np.asarray(call.op0.device_view()[:n])
            if row.size < width:
                row = np.pad(row, (0, width - row.size))
        else:
            row = np.zeros(width, dtype_to_numpy(call.arithcfg.uncompressed))
        rows.append(row)
    return np.stack(rows)


def _write_host_result(buf, row, n: int, ic=None) -> None:
    """Place a host-computed result row into any buffer type (the fallback
    path's writer; the zero-copy path uses DeviceBuffer.store directly)."""
    if isinstance(buf, DeviceBuffer):
        npdt = dtype_to_numpy(buf.dtype)
        arr = jax.device_put(np.asarray(row)[:n].astype(npdt), buf.device)
        dispatched = buf.store(arr, n)
        if ic is not None:
            ic.bump(1 + int(dispatched))  # the H2D put (+ writeback)
    else:
        dst = buf.device_view()[:n]
        np.copyto(dst, np.asarray(row)[:n].astype(dst.dtype))


# The shard prep/trim steps run as tiny cached jitted programs rather than
# eager ops: eager slicing dispatches its index scalars host->device, which
# would break the zero-host-copy guarantee (and trip transfer guards).
@functools.lru_cache(maxsize=1024)
def _prep_program(width: int, wire_name: Optional[str], device,
                  flat: bool = False):
    """Slice/round a rank's operand into a shard: ``flat`` keeps the
    (width,) 1-D layout (the engine's flat globals), otherwise the stacked
    (1, width) row.  Flat exact-size uncompressed operands never get here —
    they plug in raw with no program at all."""
    from jax.sharding import SingleDeviceSharding

    def f(a):
        a = a[:width]
        if wire_name is not None:
            # the shared lane helper (as opdriver._with_prep): a bare
            # astype pair is one XLA on a TPU sees through and removes
            from ...ops import wire as devwire

            a = devwire.wire_lane_roundtrip(a, jnp.dtype(wire_name))
        return a if flat else a.reshape(1, width)

    return jax.jit(f, out_shardings=SingleDeviceSharding(device))


@functools.lru_cache(maxsize=1024)
def _trim_program(width: int, device):
    from jax.sharding import SingleDeviceSharding

    return jax.jit(
        lambda a: a.reshape(-1)[:width],
        out_shardings=SingleDeviceSharding(device),
    )


@functools.lru_cache(maxsize=1024)
def _cast_program(npdt, device):
    from jax.sharding import SingleDeviceSharding

    return jax.jit(
        lambda a: a.astype(npdt),
        out_shardings=SingleDeviceSharding(device),
    )


@functools.lru_cache(maxsize=512)
def _p2p_hop_program(src_dev, dst_dev):
    """The device-fabric hop for a matched send/recv pair: a jitted
    collective-permute over a two-device mesh [src, dst] — on real TPU
    slices the payload moves over ICI, the analog of the reference's
    packetizer->wire->depacketizer path (ccl_offload_control.c:573-710).
    Returns (mesh, program)."""
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec

    from jax import shard_map

    mesh = Mesh([src_dev, dst_dev], ("p2p",))
    spec = PartitionSpec("p2p")
    prog = jax.jit(
        shard_map(
            lambda x: lax.ppermute(x, "p2p", [(0, 1)]),
            mesh=mesh,
            in_specs=(spec,),
            out_specs=spec,
            check_vma=False,
        )
    )
    return mesh, prog


def _p2p_device_deliver(payload, res: DeviceBuffer, count: int,
                        ic=None) -> None:
    """Move a device-resident p2p payload to the receiver's chip with a
    collective-permute and adopt it into the result buffer — no host in
    the data path.  ``ic`` counts each program dispatch (the p2p leg is
    honestly multi-interaction; the single-interaction contract covers
    the gang collectives, not the rendezvous hop)."""
    from jax.sharding import NamedSharding, PartitionSpec

    bump = ic.bump if ic is not None else (lambda n=1: None)
    if payload.ndim != 1 or payload.shape[0] < count:
        raise ValueError(
            f"p2p payload of shape {payload.shape} into count {count}"
        )
    (src_dev,) = payload.devices()
    dst_dev = res.device
    res_npdt = dtype_to_numpy(res.dtype)
    if src_dev == dst_dev:
        # self-send: a device-local copy (jit output, distinct array)
        arr = _trim_program(count, dst_dev)(payload)
        bump()
    else:
        mesh, prog = _p2p_hop_program(src_dev, dst_dev)
        shards = [
            _prep_program(count, None, src_dev)(payload),
            _dev_zeros((1, count), payload.dtype, dst_dev),
        ]
        global_in = jax.make_array_from_single_device_arrays(
            (2, count),
            NamedSharding(mesh, PartitionSpec("p2p")),
            shards,
        )
        out = prog(global_in)
        arr = next(
            s.data for s in out.addressable_shards if s.device == dst_dev
        )
        arr = _trim_program(count, dst_dev)(arr)
        bump(4)  # prep + zeros + hop program + trim
    if arr.dtype != res_npdt:
        # wire-compressed payload: decompress lane on the receiving chip
        arr = _cast_program(res_npdt, dst_dev)(arr)
        bump()
    if res.store(arr, count):
        bump()



# per-op operand/result widths in units of ``count`` ('P' = size*count)
IN_W = {
    Operation.ALLREDUCE: 1, Operation.REDUCE: 1, Operation.BCAST: 1,
    Operation.ALLGATHER: 1, Operation.GATHER: 1,
    Operation.REDUCE_SCATTER: "P", Operation.SCATTER: "P",
    Operation.ALLTOALL: "P",
}
OUT_W = {
    Operation.ALLREDUCE: 1, Operation.REDUCE: 1, Operation.BCAST: 1,
    Operation.SCATTER: 1, Operation.REDUCE_SCATTER: 1,
    Operation.ALLGATHER: "P", Operation.GATHER: "P",
    Operation.ALLTOALL: "P",
}


def run_rooted_with_tuning(op, global_arr, mesh, lead, tuning, donate=False,
                           prep=None):
    """Rooted collective with algorithm selection from the tuning
    registers: XLA lowering, or the rooted Pallas ring-relay kernels (the
    algorithm-faithful mode of the reference's rooted trees).  Shared by
    the single-process gang and the multi-process dist engine.  ``prep``
    fuses operand staging into the program (opdriver._with_prep)."""
    nseg = int(tuning.get("ring_segments", 1))
    fn = lead.reduce_function
    if op == Operation.REDUCE:
        if tuning.get("reduce_algorithm", "xla") == "pallas_ring":
            return opdriver.run_pallas_reduce(
                global_arr, mesh, lead.root_dst, fn, nseg, prep=prep
            )
        return opdriver.run_reduce(
            global_arr, mesh, lead.root_dst, fn, prep=prep
        )
    if op == Operation.BCAST:
        if tuning.get("bcast_algorithm", "xla") == "pallas_ring":
            return opdriver.run_pallas_bcast(
                global_arr, mesh, lead.root_src, nseg, prep=prep
            )
        return opdriver.run_bcast(
            global_arr, mesh, lead.root_src, donate=donate, prep=prep
        )
    if op == Operation.SCATTER:
        if tuning.get("scatter_algorithm", "xla") == "pallas_ring":
            return opdriver.run_pallas_scatter(
                global_arr, mesh, lead.root_src, nseg, prep=prep
            )
        return opdriver.run_scatter(
            global_arr, mesh, lead.root_src, prep=prep
        )
    if op == Operation.GATHER:
        if tuning.get("gather_algorithm", "xla") == "pallas_ring":
            return opdriver.run_pallas_gather(
                global_arr, mesh, lead.root_src, nseg, prep=prep
            )
        return opdriver.run_gather(
            global_arr, mesh, lead.root_src, prep=prep
        )
    raise ValueError(op)  # pragma: no cover


def apply_tuning(tuning: dict, options) -> ErrorCode:
    """Validate + apply one SET_TUNING register write into a device-tier
    tuning table (shared by the gang and dist engines; identical checks
    to the emulator/native tiers)."""
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
    val = options.cfg_value
    if val < 0:
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
        tuning[TUNING_KEY_NAMES[key]] = algo.name.lower()
    elif key == TuningKey.RING_SEGMENTS:
        if int(val) < 1:
            return ErrorCode.CONFIG_ERROR
        tuning["ring_segments"] = int(val)
    elif key in (
        TuningKey.WIRE_DTYPE,
        TuningKey.WIRE_DTYPE_ICI,
        TuningKey.WIRE_DTYPE_DCN,
    ):
        # quantized wire plane: the per-bucket compression verdict must
        # name a REGISTERED wire lane (or 0 = off) — a typo'd DataType
        # must fail the config write, not surface as an arith-lookup
        # error N calls later.  The per-link-class variants validate
        # identically (0 additionally means "defer to the generic")
        from ...wire import is_wire_dtype

        if int(val) != 0 and not is_wire_dtype(int(val)):
            return ErrorCode.CONFIG_ERROR
        tuning[TUNING_KEY_NAMES[key]] = int(val)
    elif key == TuningKey.HIERARCHICAL:
        if int(val) > 1:
            return ErrorCode.CONFIG_ERROR
        tuning["hierarchical"] = int(val)
    else:
        if key == TuningKey.GATHER_FLAT_TREE_MAX_FANIN and val < 1:
            return ErrorCode.CONFIG_ERROR
        tuning[TUNING_KEY_NAMES[key]] = int(val)
    return ErrorCode.OK


def run_allreduce_with_tuning(global_arr, mesh, fn, wire_dtype, tuning,
                              prep=None):
    """Allreduce with algorithm + segmentation + wire compression from the
    tuning registers; ``prep`` fuses the operand width slice into the
    program (the wire lane already runs in-program on every algorithm)."""
    algo = tuning.get("allreduce_algorithm", "xla")
    nseg = int(tuning.get("ring_segments", 1))
    bidir = algo == "pallas_ring_bidir"
    if wire_dtype is not None:
        wire_name = dtype_to_numpy(wire_dtype).name
        if algo in ("pallas_ring", "pallas_ring_bidir"):
            # compression lanes run inside the kernel
            return opdriver.run_pallas_allreduce(
                global_arr, mesh, fn, nseg, wire_dtype=wire_name,
                bidirectional=bidir, prep=prep,
            )
        return opdriver.run_compressed_allreduce(
            global_arr, mesh, fn, wire_dtype=wire_name, prep=prep
        )
    if algo == "ring":
        return opdriver.run_ring_allreduce(global_arr, mesh, fn, nseg,
                                           prep=prep)
    if algo in ("pallas_ring", "pallas_ring_bidir"):
        return opdriver.run_pallas_allreduce(
            global_arr, mesh, fn, nseg, bidirectional=bidir, prep=prep
        )
    return opdriver.run_allreduce(global_arr, mesh, fn, prep=prep)


def effective_tuning(tuning: dict, lead: CallOptions) -> dict:
    """The register set steering one call — the per-size selection at
    dispatch that generalizes the reference's flat-tree ``*_MAX_COUNT``
    thresholds (one definition for every tier: CallOptions)."""
    return lead.effective_tuning(tuning)


def resolve_lowering(op, lead: CallOptions, tuning: dict, wire_npdt):
    """(driver op name, extra) for the prepared-program handle a plan
    caches — the same selection run_allreduce_with_tuning /
    run_rooted_with_tuning make per call, resolved ONCE at plan-prepare
    time.  BCAST is excluded (its donating form mutates operand arrays,
    which the prepared fast path must not cache around)."""
    nseg = int(tuning.get("ring_segments", 1))
    wire_name = np.dtype(wire_npdt).name if wire_npdt is not None else None
    if op == Operation.ALLREDUCE:
        algo = tuning.get("allreduce_algorithm", "xla")
        bidir = algo == "pallas_ring_bidir"
        if algo in ("pallas_ring", "pallas_ring_bidir"):
            return "pallas_allreduce", (nseg, wire_name, bidir)
        if wire_name is not None:
            return "compressed_allreduce", wire_name
        if algo == "ring":
            return "ring_allreduce", nseg
        return "allreduce", None
    if op == Operation.REDUCE:
        if tuning.get("reduce_algorithm", "xla") == "pallas_ring":
            return "pallas_reduce", (lead.root_dst, nseg)
        return "reduce", lead.root_dst
    if op == Operation.SCATTER:
        if tuning.get("scatter_algorithm", "xla") == "pallas_ring":
            return "pallas_scatter", (lead.root_src, nseg)
        return "scatter", lead.root_src
    if op == Operation.GATHER:
        if tuning.get("gather_algorithm", "xla") == "pallas_ring":
            return "pallas_gather", (lead.root_src, nseg)
        return "gather", lead.root_src
    if op == Operation.ALLGATHER:
        return "allgather", None
    if op == Operation.REDUCE_SCATTER:
        return "reduce_scatter", None
    if op == Operation.ALLTOALL:
        return "alltoall", None
    raise ValueError(op)  # pragma: no cover - callers gate on _FAST_OPS


#: ops eligible for the prepared-program fast path (pure-functional
#: lowerings; BCAST stays on the full path — donation semantics)
_FAST_OPS = frozenset((
    Operation.ALLREDUCE, Operation.REDUCE, Operation.SCATTER,
    Operation.GATHER, Operation.ALLGATHER, Operation.REDUCE_SCATTER,
    Operation.ALLTOALL,
))


class _DeadlineKeeper:
    """The gang tier's ONE deadline thread: a parked call (a gang slot
    short of members, an unmatched p2p post) arms its deadline here, so
    no thread is started for a call.

    An entry is ``(deadline, serial, weakref(parked))`` and nothing
    else: no request, no buffer.  Nothing is ever cancelled.  A parked
    object that assembled or matched leaves its table and dies, so its
    entry is dropped as stale — swept by a later ``arm`` once the heap
    has doubled, or popped when it reaches the head; one that outlives
    its deadline has ``parked.expire()`` run, which decides liveness
    under the owner's own lock (True: it completed the parked requests).

    The thread starts lazily on the first arm and lasts until the
    engine's ``stop()``; an arm wakes it only when the new deadline is
    earlier than the one it sleeps toward (a shorter SET_TIMEOUT)."""

    _SWEEP_MIN = 64  # stale entries tolerated before an arm sweeps

    def __init__(self):
        self._cv = threading.Condition(threading.Lock())
        self._heap: List[tuple] = []
        self._serial = 0  # heap tie-break: refs do not order
        self._sweep_at = self._SWEEP_MIN
        self._wake_at = float("inf")  # the deadline the thread sleeps toward
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        self.armed = self.fired = self.dropped_stale = 0
        self.threads_started = 0

    def arm(self, deadline: float, parked) -> None:
        """Run ``parked.expire()`` at ``deadline`` (``time.monotonic``)
        if ``parked`` is still alive then.  Only a weak reference is
        kept."""
        with self._cv:
            heap = self._heap
            if len(heap) >= self._sweep_at:
                live = [e for e in heap if e[2]() is not None]
                self.dropped_stale += len(heap) - len(live)
                heapq.heapify(live)
                heap[:] = live
                self._sweep_at = max(self._SWEEP_MIN, 2 * len(heap))
            self._serial += 1
            heapq.heappush(heap, (deadline, self._serial, weakref.ref(parked)))
            self.armed += 1
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="accl-xla-deadlines", daemon=True
                )
                self.threads_started += 1
                self._thread.start()
            elif deadline < self._wake_at:
                self._cv.notify()

    def stop(self) -> None:
        """Engine shutdown: the thread ends once nothing live is left
        (a call still parked keeps its deadline; a later arm starts the
        thread anew)."""
        with self._cv:
            self._stopped = True
            self._cv.notify()

    def stats(self) -> dict:
        with self._cv:
            return {
                "armed": self.armed,
                "fired": self.fired,
                "dropped_stale": self.dropped_stale,
                "threads_started": self.threads_started,
                "queued": len(self._heap),
            }

    def _run(self) -> None:
        while True:
            parked = self._next_due()
            if parked is None:
                return
            # expiry runs outside the keeper's lock: it takes the
            # owner's lock and completes requests (user callbacks)
            try:
                fired = parked.expire()
            except Exception:  # a callback must never end the keeper
                import traceback

                traceback.print_exc()
                fired = True
            del parked
            with self._cv:
                if fired:
                    self.fired += 1
                else:
                    self.dropped_stale += 1

    def _next_due(self):
        """Sleep until the earliest live entry is due and pop it; None
        once stopped with nothing left."""
        heap = self._heap
        with self._cv:
            while True:
                while heap and heap[0][2]() is None:
                    heapq.heappop(heap)
                    self.dropped_stale += 1
                if not heap:
                    if self._stopped:
                        self._thread = None
                        return None
                    self._wake_at = float("inf")
                    # acclint: allow[unbounded-wait] an idle keeper has
                    # no deadline to keep: the next arm() or stop()
                    # notifies it
                    self._cv.wait()
                    continue
                deadline = heap[0][0]
                wait_s = deadline - time.monotonic()
                if wait_s > 0:
                    self._wake_at = deadline
                    self._cv.wait(wait_s)
                    continue
                parked = heapq.heappop(heap)[2]()
                if parked is not None:
                    return parked
                self.dropped_stale += 1


class _GangSlot:
    def __init__(self, world: int, timeout_s: float, comm=None,
                 gang=None, key=None):
        self.calls: Dict[int, Tuple[CallOptions, Request]] = {}
        self.world = world
        self.deadline = time.monotonic() + timeout_s
        self.comm = comm  # for absent-rank health attribution on timeout
        self.gang = gang  # the deadline keeper's way back: expire()
        self.key = key

    def expire(self) -> bool:
        return self.gang._expire_slot(self)


class XLAGangContext:
    """Shared per-process rendezvous point for all rank handles on a mesh."""

    def __init__(self, mesh=None):
        self.mesh = mesh  # full mesh; sub-meshes derived per communicator
        self._lock = threading.Lock()
        self._slots: Dict[tuple, _GangSlot] = {}
        self._seq: Dict[Tuple[int, int], int] = {}  # (comm_id, rank) -> call #
        self._submeshes: Dict[int, object] = {}
        self.timeout_s = DEFAULT_TIMEOUT_S
        # assembled-global reuse: repeated calls on the same operand
        # buffers rebuild an identical sharded view, so cache it keyed by
        # shard identity (strong refs keep ids stable; identity re-checked
        # on hit).  Donating ops bypass this (donation would invalidate
        # the cached view).
        self._asm_cache: Dict[tuple, tuple] = {}
        # algorithm-selection tuning registers (the reference's runtime
        # flat-vs-tree threshold registers, accl.cpp:1198-1208):
        #   allreduce_algorithm: "xla" (XLA's scheduler picks),
        #   "ring" (explicit ppermute pipeline), "pallas_ring" (the
        #   Pallas remote-DMA kernel)
        self.tuning = {"allreduce_algorithm": "xla", "ring_segments": 1}
        # monotone register-write counter: prepared per-plan state
        # (templates / program handles parked in CollectivePlan.engine)
        # records the epoch it was built at and dies on mismatch — a
        # SET_TUNING can never leave a stale prepared program serving
        self.tuning_epoch = 0
        # device-interaction accounting (single-interaction dispatch):
        # shared across the gang's rank handles — one collective on the
        # fast path bumps it exactly once, whatever the world size
        self.interactions = InteractionCounter()
        # overlap plane: launched device programs park here and their
        # requests complete from the drainer's done-probe instead of on
        # the launch path — up to `window.depth` collectives per
        # communicator in flight at once (SET_INFLIGHT_WINDOW /
        # ACCL_INFLIGHT_WINDOW).  Drain points: Request.wait (per call),
        # facade flush(), barrier, config writes, soft_reset.
        self.window = InflightWindow()
        # per-GLOBAL-rank (Rank.session) health, fed by the slot watchdog:
        # a rank absent from a timed-out gang slot is "suspect"; two
        # strikes make it "dead" and collectives addressing it fail fast
        # instead of waiting out the watchdog again.  soft_reset clears it.
        self.health: Dict[int, dict] = {}
        # command-ring plane (the TPU CCLO analog): warm batched windows
        # of eligible collectives refill a device-resident slot ring and
        # execute under ONE sequencer dispatch — the host stops issuing
        # collectives and starts refilling a queue.  ACCL_CMDRING=0
        # disables; =eager also routes single warm calls through it.
        self.cmdring = GangCommandRing(self)
        # the shared tag-matched p2p channel (set by the first rank
        # handle): the fallback route for batched SEND/RECV positions
        # that did not pair into a ring slot
        self.p2p = None
        # every parked call's deadline (gang slots here, the p2p
        # channel's posts): one thread for the engine's life
        self.deadlines = _DeadlineKeeper()
        weakref.finalize(self, self.deadlines.stop)

    _DEAD_AFTER_TIMEOUTS = 2

    def add_health_listener(self, fn) -> None:
        """Register a health-transition listener ``fn(session, old,
        new)`` — the membership plane's hook onto the slot-watchdog
        accounting (one per rank handle; each facade records the edge
        and, under elastic membership, proposes eviction on ``dead``)."""
        listeners = getattr(self, "_health_listeners", None)
        if listeners is None:
            listeners = self._health_listeners = []
        if fn not in listeners:
            listeners.append(fn)

    def remove_health_listener(self, fn) -> None:
        """Deregister (engine deinit): the gang outlives individual
        rank handles, and a dead handle's listener must not keep
        firing — or pin the handle — for the gang's lifetime."""
        listeners = getattr(self, "_health_listeners", None)
        if listeners is not None and fn in listeners:
            listeners.remove(fn)

    def _health_note_absent(self, session: int) -> None:
        h = self.health.setdefault(
            session,
            {"state": "ok", "timeouts": 0, "failures": 0, "last_event": ""},
        )
        old = h["state"]
        h["timeouts"] += 1
        h["last_event"] = "gang_timeout"
        h["state"] = (
            "dead" if h["timeouts"] >= self._DEAD_AFTER_TIMEOUTS else "suspect"
        )
        if h["state"] != old:
            for fn in getattr(self, "_health_listeners", ()):
                try:
                    fn(session, old, h["state"])
                except Exception:  # a listener must never fail the gang
                    pass

    def dead_rank_in(self, comm: Communicator) -> Optional[int]:
        """Comm-relative rank of a member already marked dead (excluding
        the local rank), or None."""
        if not self.health:
            return None
        for i, r in enumerate(comm.ranks):
            if i == comm.local_rank:
                continue
            h = self.health.get(r.session)
            if h is not None and h["state"] == "dead":
                return i
        return None

    # -- communicator -> mesh -----------------------------------------------
    def submesh(self, comm: Communicator):
        """Sub-mesh over the communicator's member devices — rank i of the
        communicator executes on the device of its *global* rank identity
        (``Rank.session``), so a subcommunicator of ranks {4..7} runs on
        devices 4-7, not 0-3.  A membership the host has too few devices
        for raises: a gang collective never runs anywhere but on its
        members' devices (``xla_group`` refuses such a group up front)."""
        sessions = tuple(r.session for r in comm.ranks)
        if sessions in self._submeshes:
            return self._submeshes[sessions]
        devs = jax.devices()
        if max(sessions) >= len(devs):
            raise ValueError(
                f"communicator member {max(sessions)} has no device: jax "
                f"found {len(devs)} on {jax.default_backend()!r}"
            )
        from jax.sharding import Mesh

        mesh = Mesh([devs[s] for s in sessions], (opdriver.AXIS,))
        self._submeshes[sessions] = mesh
        return mesh

    # -- gang assembly -------------------------------------------------------
    def submit(self, comm: Communicator, options: CallOptions, request: Request):
        self._submit_entry(comm, (options, request))

    def submit_batch(
        self,
        comm: Communicator,
        options_list: List[CallOptions],
        requests: List[Request],
    ):
        """A whole flushed command-queue batch as ONE gang event: every
        rank of the communicator must flush a batch of the same length at
        the same point of its call sequence (the batched extension of the
        gang's SPMD ordering contract).  A fully matched batch executes
        as one fused jitted program — one device interaction for N
        queued collectives."""
        self._submit_entry(comm, (list(options_list), list(requests)))

    def _submit_entry(self, comm: Communicator, entry: tuple):
        if comm.size == 1:
            # single-member gang (the chip tier's world=1 shape): the
            # submit IS the assembled slot — no seq/slot bookkeeping, no
            # watchdog, no dead peers to screen (there are none)
            slot = _GangSlot(1, 0.0, comm)
            slot.calls[0] = entry
            self._execute(comm, slot)
            return
        with self._lock:
            dead = self.dead_rank_in(comm)
            if dead is not None:
                # fail fast: a member of this communicator is already
                # marked dead by the watchdog accounting — assembling a
                # slot would only burn the full deadline again.  No seq is
                # consumed; recovery is the collective soft_reset.
                h = dict(self.health.get(comm.ranks[dead].session, {}))
        if dead is not None:
            ctx = {
                "comm": comm.id,
                "peer": dead,
                "attempts": h.get("timeouts", 0),
                "elapsed_s": 0.0,
            }
            reqs = entry[1] if isinstance(entry[1], list) else [entry[1]]
            opts = entry[0] if isinstance(entry[0], list) else [entry[0]]
            for o, req in zip(opts, reqs):
                req.complete(
                    ErrorCode.RECEIVE_TIMEOUT,
                    context=dict(ctx, op=o.op.name),
                )
            return
        with self._lock:
            seq_key = (comm.id, comm.local_rank)
            seq = self._seq.get(seq_key, 0)
            self._seq[seq_key] = seq + 1
            slot_key = (comm.id, seq)
            slot = self._slots.get(slot_key)
            arm = False
            if slot is None:
                slot = _GangSlot(comm.size, self.timeout_s, comm=comm,
                                 gang=self, key=slot_key)
                self._slots[slot_key] = slot
                arm = True  # exactly one deadline per slot
            slot.calls[comm.local_rank] = entry
            ready = len(slot.calls) == slot.world
            if ready:
                # nothing to cancel: the keeper finds the slot dead
                del self._slots[slot_key]
        if ready:
            self._execute(comm, slot)
        elif arm:
            self.deadlines.arm(slot.deadline, slot)

    @staticmethod
    def _slot_requests(slot: "_GangSlot"):
        """Every request parked in a slot (batch entries hold lists)."""
        for _, req in slot.calls.values():
            if isinstance(req, list):
                yield from req
            else:
                yield req

    def soft_reset(self) -> None:
        """ref ``ACCL`` soft-reset recovery (accl.cpp:57-89): abandon all
        stale gang state so a world that lost a collective (e.g. one rank
        timed out while a peer never submitted) can realign.

        Collective by contract, like the reference's: every rank handle
        issues CONFIG/RESET with no new collectives in flight; each call
        idempotently clears the shared tables, so after the last rank's
        reset all per-communicator sequence counters restart at 0 and the
        next collective matches at a fresh slot.  Any still-parked call is
        completed with RECEIVE_TIMEOUT (its gang never assembled)."""
        # overlap plane: a FULL drain first — every launched program's
        # requests complete normally before any state is abandoned (the
        # soft_reset drain-point contract).
        # BOUNDED: soft_reset is the recovery path, so a wedged device
        # call must not also wedge recovery — past the bound the reset
        # proceeds and the stragglers complete (or fail) from the
        # drainer whenever their done-probe returns
        self.window.drain(drain_deadline_s(self.timeout_s))
        with self._lock:
            slots = list(self._slots.values())
            self._slots.clear()
            self._seq.clear()
            self._asm_cache.clear()
            self.health.clear()  # degradation state is part of the reset
            self.tuning_epoch += 1  # prepared plan state dies with the reset
        # command ring: park the sequencer and realign every session's
        # seqn/head at 0 (after the full window drain above — no slot
        # can still be in flight when the ring state is abandoned)
        self.cmdring.reset()
        for slot in slots:
            for req in self._slot_requests(slot):
                if not req.done():
                    req.complete(ErrorCode.RECEIVE_TIMEOUT)

    def contract_fail(self, verdict: dict) -> None:
        """Contract plane: a cross-rank divergence verdict landed for
        ``verdict["comm"]`` — complete every PARKED slot on that
        communicator with CONTRACT_VIOLATION immediately.  The detecting
        rank fails pre-dispatch at facade intake; its peers' calls are
        already parked in half-assembled slots and would otherwise
        starve until the watchdog (the hang this plane removes).
        Idempotent: every rank's verifier listener calls this once."""
        from ...contract import verdict_context

        comm_id = verdict.get("comm")
        with self._lock:
            keys = [k for k in self._slots if k[0] == comm_id]
            slots = [self._slots.pop(k) for k in keys]
        for slot in slots:
            for req in self._slot_requests(slot):
                if not req.done():
                    req.complete(
                        ErrorCode.CONTRACT_VIOLATION,
                        context=verdict_context(verdict, req.op_name),
                    )

    def dump_state(self) -> List[str]:
        """Pending-rendezvous lines for the debug dump: every parked gang
        slot (a collective some rank posted that never assembled) is a
        live resource exactly like an occupied reference rx buffer."""
        lines: List[str] = []
        with self._lock:
            for (comm_id, seq), slot in self._slots.items():
                posted = sorted(slot.calls)
                lines.append(
                    f"rxbuf gang-slot comm={comm_id} seq={seq} PENDING "
                    f"posted_ranks={posted} world={slot.world}"
                )
        return lines

    def _expire_slot(self, slot: _GangSlot) -> bool:
        """The deadline keeper's call when ``slot.deadline`` has passed:
        a slot still parked starves no longer.  False: it assembled (or
        a reset / contract verdict completed it) in the meantime."""
        slot_key = slot.key
        with self._lock:
            live = self._slots.get(slot_key) is slot
            if live:
                del self._slots[slot_key]
                # health accounting: every member that never posted to
                # this starved slot takes a strike (graceful
                # degradation — two strikes mark it dead and later
                # collectives fail fast)
                absent = []
                if slot.comm is not None:
                    for r in range(slot.world):
                        if r not in slot.calls:
                            absent.append(r)
                            self._health_note_absent(
                                slot.comm.ranks[r].session
                            )
        if live:
            ctx = {
                "comm": slot_key[0],
                "peer": absent if len(absent) != 1 else absent[0],
                "elapsed_s": round(self.timeout_s, 3),
            }
            for req in self._slot_requests(slot):
                req.complete(
                    ErrorCode.RECEIVE_TIMEOUT,
                    context=dict(ctx, op=req.op_name),
                )
        return live

    # -- execution -----------------------------------------------------------
    @staticmethod
    def _sig(c: CallOptions) -> tuple:
        return (
            c.op, c.count, c.reduce_function, c.root_src, c.root_dst,
            c.compression, c.fuse, c.fuse_param,
        )

    def _execute(self, comm: Communicator, slot: _GangSlot) -> None:
        entries = [slot.calls[r] for r in range(slot.world)]
        batched = [isinstance(e[0], list) for e in entries]
        if any(batched) and not all(batched):
            # one rank flushed a batch where another posted a single call:
            # the gang sequence is torn — fail the whole slot
            for req in self._slot_requests(slot):
                req.complete(ErrorCode.INVALID_OPERATION)
            return
        if all(batched) and entries:
            self._execute_batch(comm, entries)
            return
        self._execute_calls(
            comm, [e[0] for e in entries], [e[1] for e in entries]
        )

    def _execute_calls(
        self,
        comm: Communicator,
        calls: List[CallOptions],
        reqs: List[Request],
    ) -> None:
        t0 = time.perf_counter_ns()
        lead = calls[0]
        try:
            if all(
                c.op in (Operation.SEND, Operation.RECV) for c in calls
            ):
                # a batched p2p position that did not ride the ring
                # (fallback / ring disabled mid-flight): a
                # complementary pair delivers directly, anything else
                # re-routes through the shared channel (which then owns
                # completion — None)
                code = self._execute_p2p_pair(comm, calls, reqs)
                if code is None:
                    return
            elif any(
                c.op in (Operation.SEND, Operation.RECV) for c in calls
            ):
                # a position mixing p2p with a collective is a torn
                # gang (SPMD divergence): fail fast — the channel must
                # not be fed a collective call dressed as a recv
                code = ErrorCode.INVALID_OPERATION
            elif any(self._sig(c) != self._sig(lead) for c in calls[1:]):
                code = ErrorCode.INVALID_OPERATION  # mismatched gang calls
            elif lead.fuse:
                # a fused call that missed the ring: its operand is
                # PACKED for the slot (grads ‖ param tail, kv ‖ q), so
                # the plain base op would compute the wrong thing —
                # decompose with the host reference semantics instead
                # (counted on the ring's fallback table)
                with annotate(f"accl::fused{int(lead.fuse)}_decomposed"):
                    code = self._execute_fused_decomposed(comm, calls)
            else:
                # named range in the xprof timeline (the per-call span the
                # reference's perf counter provides, SURVEY §5 tracing)
                with annotate(_OP_SPAN[lead.op]):
                    code = self._run_op(comm, calls, lead, reqs, t0)
        except Exception:
            import traceback

            traceback.print_exc()
            code = ErrorCode.INVALID_OPERATION
        if code is IN_FLIGHT:
            # overlap plane: completion was handed to the in-flight
            # window — the drainer completes these requests from the
            # device done-probe, in launch order
            return
        # per-communicator ordering fence: an inline completion (host-path
        # collectives, gang-mismatch failures) must not overtake earlier
        # launched-but-incomplete device calls of this communicator — the
        # window's launch-order contract.  Bounded like every drain point:
        # a wedged earlier call must not also wedge this completion
        self.window.drain_key(comm.id, drain_deadline_s(self.timeout_s))
        dt = time.perf_counter_ns() - t0
        for req in reqs:
            req.complete(code, dt)

    def _execute_p2p_pair(self, comm: Communicator,
                          calls: List[CallOptions],
                          reqs: List[Request]) -> Optional[ErrorCode]:
        """A batched p2p position that did not ride the ring.  A
        complementary SEND/RECV pair delivers directly — the slot IS
        the rendezvous (both sides posted at the same batch position):
        the device fabric hop for device-resident ends, a host write
        otherwise.  Any other shape (the classic cross-exchange where
        both ranks batch ``[send, recv]`` and positions pair ACROSS
        slots, or pairs with mismatched tags) routes each call through
        the shared p2p channel exactly as an unbatched call would —
        tag matching across positions keeps working.  Returns None
        when the calls were handed to the channel (it owns their
        completion)."""
        from ...cmdring import complementary_pair

        # THE pair definition, shared with the ring planner (_plan_p2p):
        # a dtype-mismatched or compressed position is not a match on
        # either path — it rides the channel, whose cast-on-deliver /
        # wire-cast semantics the unbatched path already has
        pair = complementary_pair(calls)
        if pair is not None:
            src, dst = pair
            snd, rcv = calls[src], calls[dst]
            if rcv.res is not None and not rcv.res.is_dummy:
                n = snd.count
                ic = self.interactions
                res = rcv.res
                op0 = snd.op0
                if isinstance(op0, DeviceBuffer) and isinstance(
                    res, DeviceBuffer
                ):
                    payload = _trim_program(n, op0.device)(
                        op0.device_array()
                    )
                    ic.bump()  # the payload-copy program
                    _p2p_device_deliver(payload, res, n, ic)
                else:
                    row = np.asarray(op0.device_view()[:n])
                    _write_host_result(res, row, n, ic)
                return ErrorCode.OK
        if self.p2p is None:  # pragma: no cover - engines always set it
            return ErrorCode.INVALID_OPERATION
        for r, (call, req) in enumerate(zip(calls, reqs)):
            self._route_p2p_channel(comm, r, call, req)
        return None

    def _execute_fused_decomposed(
        self, comm: Communicator, calls: List[CallOptions]
    ) -> ErrorCode:
        """Host-reference execution of a fused call that fell off the
        ring.  The operand is packed for the slot, so the plain base op
        has no correct off-ring spelling; the decomposition computes
        the fused semantics itself — the shared width/epilogue
        definitions from :mod:`accl_tpu.cmdring`, in numpy — and counts
        the miss as a ``fused_decomposed`` ring fallback.  Correctness
        over speed: the warm path is the ring slot."""
        from ...cmdring import ring_widths
        from ...constants import FusedCompute, ReduceFunction

        lead = calls[0]
        size = len(calls)
        try:
            fuse = FusedCompute(int(lead.fuse))
        except ValueError:
            return ErrorCode.INVALID_OPERATION
        n = int(lead.count)
        if n <= 0 or fuse == FusedCompute.NONE:
            return ErrorCode.INVALID_OPERATION
        in_w, _ = ring_widths(lead.op, n, size, fuse=fuse)
        rows = []
        for c in calls:
            if c.op0 is None or c.op0.is_dummy:
                return ErrorCode.INVALID_OPERATION
            view = np.asarray(c.op0.device_view())
            if view.shape[0] < in_w:
                return ErrorCode.INVALID_OPERATION
            # copy: the result write below may alias the operand
            rows.append(view[:in_w].copy())
        self.cmdring.note_fallback("fused_decomposed")
        fp = float(lead.fuse_param)
        outs = []
        if fuse == FusedCompute.ATTN_HOP:
            from ...ops.pallas.ring import hop_source

            hop = int(lead.root_src)
            for r in range(size):
                src = hop_source(r, hop, size)
                outs.append(fp * (rows[r][n:2 * n] * rows[src][:n]))
        else:
            stack = np.stack([row[: n * size] for row in rows])
            if lead.reduce_function == ReduceFunction.MAX:
                reduced = stack.max(axis=0)
            else:
                reduced = stack.sum(axis=0)
            for r in range(size):
                chunk = reduced[r * n:(r + 1) * n]
                if fuse == FusedCompute.MATMUL_RS:
                    outs.append(fp * chunk)
                else:  # APPLY: param tail minus the scaled reduced chunk
                    outs.append(
                        rows[r][size * n:(size + 1) * n] - fp * chunk
                    )
        for r, c in enumerate(calls):
            if c.res is not None and not c.res.is_dummy:
                _write_host_result(c.res, outs[r], n, self.interactions)
        return ErrorCode.OK

    def _route_p2p_channel(self, comm: Communicator, rank: int,
                           call: CallOptions, req: Request) -> None:
        """Post one gang-assembled SEND/RECV onto the shared tag-matched
        channel (the unbatched path's machinery, minus streams — stream
        p2p is never gang-eligible)."""
        ic = self.interactions
        me_world = comm.ranks[rank].session
        if call.op == Operation.SEND:
            cfg = call.arithcfg
            if isinstance(call.op0, DeviceBuffer):
                payload = _trim_program(call.count, call.op0.device)(
                    call.op0.device_array()
                )
                ic.bump()  # the payload-copy program
                if call.compression & CompressionFlags.ETH_COMPRESSED:
                    # compress lane on the sending chip (the unbatched
                    # path's wire-cast discipline, _start_send)
                    payload = _cast_program(
                        dtype_to_numpy(cfg.compressed), call.op0.device
                    )(payload)
                    ic.bump()
            else:
                payload = np.asarray(
                    call.op0.device_view()[: call.count]
                ).copy()
                if call.compression & CompressionFlags.ETH_COMPRESSED:
                    payload = payload.astype(
                        dtype_to_numpy(cfg.compressed)
                    )
            dst_world = comm.ranks[call.root_dst].session
            key = (comm.id, call.tag, me_world, dst_world)
            self.p2p.post_send(key, payload, req,
                               timeout_s=self.timeout_s)
            return
        src_world = comm.ranks[call.root_src].session
        key = (comm.id, call.tag, src_world, me_world)

        def sink(payload, call=call, ic=ic):
            if isinstance(payload, jax.Array) and isinstance(
                call.res, DeviceBuffer
            ):
                _p2p_device_deliver(payload, call.res, call.count, ic)
                return
            if isinstance(payload, jax.Array):
                payload = np.asarray(payload)
            _write_host_result(call.res, payload, call.count, ic)

        self.p2p.post_recv(key, sink, req, timeout_s=self.timeout_s)

    # -- batched execution ---------------------------------------------------
    _BATCH_TUNING_KEYS = (
        "allreduce_algorithm", "reduce_algorithm", "bcast_algorithm",
        "scatter_algorithm", "gather_algorithm",
    )

    def _execute_batch(self, comm: Communicator, entries: List[tuple]) -> None:
        """Execute a fully matched batch slot: ``entries[r]`` is rank r's
        ``(options_list, requests_list)``.  The whole batch runs as ONE
        fused jitted program when every position qualifies for the
        zero-host-copy device path; otherwise each position executes in
        order through the ordinary per-call machinery (still correct,
        just not single-interaction)."""
        lens = {len(e[0]) for e in entries}
        if lens != {len(entries[0][0])}:
            for _, batch_reqs in entries:
                for req in batch_reqs:
                    req.complete(ErrorCode.INVALID_OPERATION)
            return
        npos = len(entries[0][0])
        try:
            # command-ring fast path first: a warm window of eligible
            # collectives becomes slot refills + ONE sequencer dispatch
            # (planning is side-effect-free; True means the ring owns
            # request completion).  Ineligible batches fall through to
            # the fused program, then the sequential path.
            handled = self.cmdring.run_batch(comm, entries, npos)
        except Exception:
            import traceback

            traceback.print_exc()
            handled = False
        if handled:
            return
        try:
            # planning is side-effect-free: a False return means "not
            # fusable", safe to fall back; once dispatch has begun,
            # _run_batch_fused owns request completion (True) so the
            # sequential path can never double-execute a position
            handled = self._run_batch_fused(comm, entries, npos)
        except Exception:
            import traceback

            traceback.print_exc()
            handled = False
        if handled:
            return
        for i in range(npos):
            self._execute_calls(
                comm,
                [e[0][i] for e in entries],
                [e[1][i] for e in entries],
            )

    def _run_batch_fused(
        self, comm: Communicator, entries: List[tuple], npos: int
    ) -> bool:
        """Try to run the whole batch as one fused device program (one
        device interaction for N collectives).  Returns False — having
        dispatched nothing — when any position disqualifies: non-default
        tuning algorithms (the fused program composes the plain XLA
        lowerings), host/mixed operands, streams, or a gang signature
        mismatch at any position (that position must surface its error
        through the sequential path)."""
        mesh = self.submesh(comm)
        if npos == 0:
            return False
        if any(
            self.tuning.get(k, "xla") != "xla" for k in self._BATCH_TUNING_KEYS
        ):
            return False
        # per-call TuningPlan overlays selecting a non-XLA lowering also
        # disqualify fusion (the fused program composes plain XLA bodies)
        for options_list, _ in entries:
            for c in options_list:
                if c.tuning and any(
                    c.tuning.get(k, "xla") != "xla"
                    for k in self._BATCH_TUNING_KEYS
                ):
                    return False
        plans = []
        written: set = set()  # result-buffer roots of earlier positions
        for i in range(npos):
            calls = [e[0][i] for e in entries]
            lead = calls[0]
            if any(self._sig(c) != self._sig(lead) for c in calls[1:]):
                return False
            if lead.fuse:
                # fused positions never run the plain lowerings (the
                # packed operand layout differs) — the sequential path
                # decomposes them with the host reference
                return False
            # (_plan_device_call also enforces the BCAST op0-is-res form)
            plan = self._plan_device_call(comm, calls, lead, mesh)
            if plan is None:
                return False
            # data-dependency guard: all positions' operands are
            # assembled BEFORE the single fused dispatch, so a position
            # reading a buffer an earlier position writes would see the
            # PRE-batch bytes — only the sequential path orders such
            # chains; reject fusion (the in-place op0-is-res form of one
            # position is fine: its own read/write is inside one op)
            for call in calls:
                buf = call.op0
                if (
                    buf is not None
                    and not buf.is_dummy
                    and id(buf._root()) in written
                ):
                    return False
            for r in plan["writers"]:
                res = calls[r].res
                if res is not None and not res.is_dummy:
                    written.add(id(res._root()))
            plans.append((calls, lead, plan))

        t0 = time.perf_counter_ns()
        try:
            return self._dispatch_batch_fused(comm, entries, plans, mesh, t0)
        except Exception:
            # dispatch/adoption failed mid-batch: requests already
            # completed stay completed; the rest fail — NEVER fall back
            # to sequential re-execution (a collective must not run twice)
            import traceback

            traceback.print_exc()
            dt = time.perf_counter_ns() - t0
            for _, batch_reqs in entries:
                for req in batch_reqs:
                    if not req.done():  # side-effect-free engine probe
                        req.complete(ErrorCode.INVALID_OPERATION, dt)
            return True

    def _dispatch_batch_fused(
        self, comm: Communicator, entries, plans, mesh, t0
    ) -> bool:
        globals_ = []
        specs = []
        for calls, lead, plan in plans:
            global_arr, prep, _ = self._assemble_flat(calls, plan, mesh)
            globals_.append(global_arr)
            op = plan["op"]
            fn = lead.reduce_function
            wire_name = (
                np.dtype(plan["wire_npdt"]).name
                if plan["wire_npdt"] is not None
                else None
            )
            if op == Operation.ALLREDUCE:
                if wire_name is not None:
                    specs.append(
                        ("compressed_allreduce", fn, wire_name, prep, True)
                    )
                else:
                    specs.append(("allreduce", fn, None, prep, True))
            elif op == Operation.REDUCE:
                specs.append(("reduce", fn, lead.root_dst, prep, True))
            elif op == Operation.BCAST:
                # non-donating inside a batch: the operand may back other
                # positions' shards of the same fused program
                specs.append(("bcast", fn, lead.root_src, prep, True))
            elif op == Operation.SCATTER:
                specs.append(("scatter", fn, lead.root_src, prep, True))
            elif op == Operation.GATHER:
                specs.append(("gather", fn, lead.root_src, prep, True))
            elif op == Operation.ALLGATHER:
                specs.append(("allgather", fn, None, prep, True))
            elif op == Operation.REDUCE_SCATTER:
                specs.append(("reduce_scatter", fn, None, prep, True))
            elif op == Operation.ALLTOALL:
                specs.append(("alltoall", fn, None, prep, True))
            else:  # pragma: no cover - _plan_device_call gates on IN_W
                return False

        self.interactions.bump()  # ONE dispatch for the whole batch
        with annotate(f"accl::batch[{len(plans)}]"):
            outs = opdriver.run_batch(globals_, mesh, specs)
        all_reqs: List[Request] = []
        for i, (calls, lead, plan) in enumerate(plans):
            reqs = [e[1][i] for e in entries]
            self._adopt_out_shards(outs[i], calls, plan, reqs)
            all_reqs.extend(reqs)
        # the fused batch rides the in-flight window as ONE entry: all
        # positions came out of one program, so they become ready (and
        # complete) together, from the drainer's done-probe
        self._park_inflight(comm, outs, all_reqs, t0)
        return True

    def _run_op(
        self,
        comm: Communicator,
        calls: List[CallOptions],
        lead: CallOptions,
        reqs: Optional[List[Request]] = None,
        t0: Optional[int] = None,
    ) -> ErrorCode:
        if lead.op == Operation.BARRIER:
            # gang assembly IS the barrier on this tier: reaching here means
            # every rank of the communicator posted the call in this process.
            # A multi-process gang must NOT reuse this (see backends/dist for
            # the cross-process barrier over the device mesh).  The barrier
            # is also an overlap drain point: no rank may observe it pass
            # while an earlier collective of ITS communicator is still in
            # flight — and a wedged one fails the barrier within the
            # engine deadline instead of hanging it.  Per-key, matching
            # the window's keys-drain-independently contract: a wedged
            # UNRELATED communicator must not fail this barrier.
            if not self.window.drain_key(
                comm.id, drain_deadline_s(self.timeout_s)
            ):
                return ErrorCode.RECEIVE_TIMEOUT
            return ErrorCode.OK
        mesh = self.submesh(comm)
        code = self._run_op_device(comm, calls, lead, mesh, reqs, t0)
        if code is not None:
            return code
        return self._run_op_host(comm, calls, lead, mesh)

    # -- overlap plane --------------------------------------------------------
    def _park_inflight(self, comm, out, reqs, t0):
        """Hand a dispatched device call's completion to the in-flight
        window: the launch path returns immediately (result adoption has
        already been wired — pointer swaps done, writebacks deferred)
        and the drainer completes the requests when the device future
        resolves.  Falls back to inline completion when there are no
        requests to decouple."""
        if reqs is None:
            jax.block_until_ready(out)
            return ErrorCode.OK
        if t0 is None:
            t0 = time.perf_counter_ns()

        def waiter(out=out):
            jax.block_until_ready(out)

        def on_ready(overlap_ns, depth, ready_ns, reqs=reqs, t0=t0):
            dt = max(ready_ns - t0, 1)
            for req in reqs:
                # overlap_ns is 0 when nothing overlapped this call (a
                # lone sync call riding the window hid no device time) —
                # record None so telemetry never over-credits the window
                req.overlap_ns = overlap_ns or None
                req.inflight_depth = depth
                req.complete(ErrorCode.OK, dt)

        def on_error(exc, reqs=reqs, t0=t0, comm_id=comm.id):
            # a device-side failure surfaces on every request of the
            # launch, with the failure context the flight recorder and
            # ACCLError.details carry
            dt = max(time.perf_counter_ns() - t0, 1)
            ctx = {
                "comm": comm_id,
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }
            for req in reqs:
                if not req.done():  # side-effect-free engine probe
                    req.complete(
                        ErrorCode.INVALID_OPERATION, dt,
                        context=dict(ctx, op=req.op_name),
                    )

        self.window.park(comm.id, waiter, on_ready, on_error)
        return IN_FLIGHT

    # -- zero-host-copy device path ------------------------------------------
    def _plan_device_call(
        self,
        comm: Communicator,
        calls: List[CallOptions],
        lead: CallOptions,
        mesh,
    ) -> Optional[dict]:
        """Validate a gang call for the zero-host-copy path BEFORE any
        device work; returns the call plan, or None to fall back to the
        host-staged path (mixed/host operands, exotic dtypes)."""
        op = lead.op
        if op not in IN_W:
            return None
        size = comm.size
        n = lead.count
        if n <= 0:
            return None
        in_w = n * (size if IN_W[op] == "P" else 1)
        out_w = n * (size if OUT_W[op] == "P" else 1)
        devs = list(mesh.devices.flat)
        npdt = dtype_to_numpy(lead.arithcfg.uncompressed)
        compressed = bool(lead.compression & CompressionFlags.ETH_COMPRESSED)
        wire_npdt = (
            dtype_to_numpy(lead.arithcfg.compressed) if compressed else None
        )

        # which ranks' results get written
        if op in (Operation.REDUCE, Operation.GATHER):
            writers = {lead.root_dst if op == Operation.REDUCE else lead.root_src}
        else:
            writers = set(range(size))

        # validate operands + results device-resident before any work
        any_device = False
        for r, call in enumerate(calls):
            buf = call.op0
            if buf is not None and not buf.is_dummy:
                if not (
                    isinstance(buf, DeviceBuffer)
                    and buf.device == devs[r]
                    and buf.count >= in_w
                    and dtype_to_numpy(buf.dtype) == npdt
                ):
                    return None
                any_device = True
            if r in writers:
                res = call.res
                if res is None or res.is_dummy:
                    continue
                if not (
                    isinstance(res, DeviceBuffer)
                    and res.device == devs[r]
                    and res.count >= out_w
                    and dtype_to_numpy(res.dtype) == npdt
                ):
                    return None
        if not any_device:
            return None
        if op == Operation.BCAST and any(
            c.op0 is not c.res for c in calls
        ):
            # the device bcast program runs in-place (facade contract:
            # op0 IS res on every rank); other shapes stage via the host
            return None
        return {
            "op": op, "size": size, "n": n, "in_w": in_w, "out_w": out_w,
            "devs": devs, "npdt": npdt, "compressed": compressed,
            "wire_npdt": wire_npdt, "writers": writers,
        }

    def _assemble_flat(self, calls, plan, mesh) -> tuple:
        """Assemble the flat 1-D global for a planned device call with as
        few device interactions as possible.

        Preferred mode (single-interaction dispatch): every rank's shard
        is its RAW committed HBM array — zero-copy, zero dispatch — at
        the operands' uniform width ``w >= in_w``; the slice down to the
        call width and the wire-dtype rounding lane are FUSED into the
        collective program itself (``prep``), so operand staging never
        costs a separate device interaction.  Falls back to per-rank prep
        programs (one dispatch each) only for mixed widths.

        Returns ``(global_arr, prep, raw_bufs)`` where ``prep`` is the
        (take_w, wire_name) spec for the fused program and ``raw_bufs``
        is the cache-key buffer list (None when not cacheable).
        """
        from jax.sharding import NamedSharding, PartitionSpec

        ic = self.interactions
        op, size, in_w = plan["op"], plan["size"], plan["in_w"]
        devs, npdt = plan["devs"], plan["npdt"]
        wire_name = (
            np.dtype(plan["wire_npdt"]).name
            if plan["wire_npdt"] is not None and op != Operation.ALLREDUCE
            else None
        )

        arrs = []
        for call in calls:
            buf = call.op0
            if buf is None or buf.is_dummy:
                arrs.append(None)
            else:
                if buf._parent is not None:
                    ic.bump()  # child view: slice program dispatch
                arrs.append(buf.device_array())
        widths = {a.shape[0] for a in arrs if a is not None}
        uniform_w = widths.pop() if len(widths) == 1 else None

        shards = []
        raw_bufs: Optional[list] = []  # root buffers whose _dev went in raw
        if uniform_w is not None and uniform_w >= in_w:
            w = uniform_w
            prep = (
                (in_w, wire_name)
                if (w != in_w or wire_name is not None)
                else None
            )
            for r, (call, arr) in enumerate(zip(calls, arrs)):
                if arr is None:
                    ic.bump()  # on-device zeros for the dummy operand
                    shards.append(_dev_zeros((w,), npdt, devs[r]))
                    raw_bufs = None
                    continue
                shards.append(arr)
                buf = call.op0
                if raw_bufs is not None and buf._parent is None:
                    raw_bufs.append(buf)
                elif buf._parent is not None:
                    raw_bufs = None
        else:
            # mixed widths: per-rank prep programs to the exact call
            # width (one dispatch each — the legacy staging cost)
            w = in_w
            prep = None
            raw_bufs = None
            for r, (call, arr) in enumerate(zip(calls, arrs)):
                if arr is None:
                    ic.bump()
                    shards.append(_dev_zeros((in_w,), npdt, devs[r]))
                else:
                    ic.bump()
                    shards.append(
                        _prep_program(in_w, wire_name, devs[r], True)(arr)
                    )

        # assembled-global reuse: keyed by the BUFFER identities (stable
        # across in-place loops, unlike shard ids), re-validated against
        # each buffer's current _dev; a stale entry is REPLACED under its
        # key, so repeated in-place calls can't accumulate dead entries.
        # Buffers are held by WEAKREF with eviction callbacks — the cached
        # global (which pins every shard's HBM) dies with its buffers, so
        # the cache never outlives what the application released.
        # Donating ops (bcast) bypass the cache entirely.
        cacheable = raw_bufs is not None and op != Operation.BCAST
        global_arr = None
        key = None
        if cacheable:
            key = (tuple(map(id, raw_bufs)), w)
            global_arr = self._asm_lookup(key, raw_bufs)
        if global_arr is None:
            global_arr = jax.make_array_from_single_device_arrays(
                (size * w,),
                NamedSharding(mesh, PartitionSpec(opdriver.AXIS)),
                shards,
            )
            if cacheable:
                self._asm_store(key, global_arr, shards, raw_bufs)
        return global_arr, prep, raw_bufs

    def _asm_lookup(self, key, raw_bufs):
        """Assembled-global cache hit, re-validated against the buffers'
        live identity AND their current committed arrays (see the cache
        notes in _assemble_flat); None on miss/stale."""
        hit = self._asm_cache.get(key)
        if hit is None:
            return None
        hit_bufs = [ref() for ref in hit[2]]
        if all(b is hb for b, hb in zip(raw_bufs, hit_bufs)) and all(
            s is b._dev for s, b in zip(hit[1], raw_bufs)
        ):
            return hit[0]
        return None

    def _asm_store(self, key, global_arr, shards, raw_bufs) -> None:
        if len(self._asm_cache) >= 64 and key not in self._asm_cache:
            self._asm_cache.clear()

        def _evict(_ref, cache=self._asm_cache, key=key):
            cache.pop(key, None)

        self._asm_cache[key] = (
            global_arr,
            shards,
            [weakref.ref(b, _evict) for b in raw_bufs],
        )

    def _run_op_device_prepared(
        self,
        calls: List[CallOptions],
        lead: CallOptions,
        state: dict,
        reqs: Optional[List[Request]] = None,
        t0: Optional[int] = None,
    ) -> Optional[ErrorCode]:
        """The warm path of a planned gang collective: the template,
        sharding, adoption map and jitted program handle all come out of
        the CollectivePlan's prepared state — per call only the operand
        buffers are validated, the global assembled, and the ONE program
        dispatched.  Returns None to fall back to the full path (operand
        shape drift, dummy/view operands, host buffers)."""
        comm_id = lead.comm.id
        with annotate("accl.gang::assemble", comm=comm_id):
            prepared = self._assemble_prepared(calls, lead, state)
        if prepared is None:
            return None
        prog, global_arr = prepared
        self.interactions.bump()  # THE dispatch: one prepared program
        with annotate("accl.gang::dispatch", comm=comm_id):
            out = prog(global_arr)
        with annotate("accl.gang::adopt", comm=comm_id):
            self._adopt_out_shards(
                out, calls, state["tmpl"], reqs, state["dev_to_rank"]
            )
        with annotate("accl.gang::park", comm=comm_id):
            return self._park_inflight(lead.comm, out, reqs, t0)

    def _assemble_prepared(self, calls: List[CallOptions],
                           lead: CallOptions, state: dict):
        """Validate the operands against the prepared template, assemble
        the global and find the program: ``(prog, global_arr)``, or None
        where the call must take the full path."""
        tmpl = state["tmpl"]
        devs, npdt, in_w = tmpl["devs"], tmpl["npdt"], tmpl["in_w"]
        shards = []
        raw_bufs = []
        w = None
        for r, call in enumerate(calls):
            buf = call.op0
            if (
                buf is None
                or not isinstance(buf, DeviceBuffer)
                or buf.is_dummy
                or buf._parent is not None
                or buf.device != devs[r]
                or dtype_to_numpy(buf.dtype) != npdt
            ):
                return None
            arr = buf.device_array()
            aw = arr.shape[0]
            if w is None:
                w = aw
            elif aw != w:
                return None
            shards.append(arr)
            raw_bufs.append(buf)
        if w < in_w:
            return None
        out_w = tmpl["out_w"]
        for r in tmpl["writers"]:
            res = calls[r].res
            if res is None or res.is_dummy:
                continue
            if not (
                isinstance(res, DeviceBuffer)
                and res.device == devs[r]
                and res.count >= out_w
                and dtype_to_numpy(res.dtype) == npdt
            ):
                return None

        key = (tuple(map(id, raw_bufs)), w)
        global_arr = self._asm_lookup(key, raw_bufs)
        if global_arr is None:
            global_arr = jax.make_array_from_single_device_arrays(
                (tmpl["size"] * w,), state["sharding"], shards
            )
            self._asm_store(key, global_arr, shards, raw_bufs)

        prog = state["programs"].get(w)
        if prog is None:
            wire_name = (
                np.dtype(tmpl["wire_npdt"]).name
                if tmpl["wire_npdt"] is not None
                and tmpl["op"] != Operation.ALLREDUCE
                else None
            )
            prep = (
                (in_w, wire_name)
                if (w != in_w or wire_name is not None)
                else None
            )
            name, extra = resolve_lowering(
                tmpl["op"], lead,
                effective_tuning(self.tuning, lead),
                tmpl["wire_npdt"] if tmpl["compressed"] else None,
            )
            prog = opdriver.prepare(
                name, state["mesh"], lead.reduce_function, extra, prep
            )
            state["programs"][w] = prog

        return prog, global_arr

    def _adopt_out_shards(self, out, calls, plan, reqs,
                          dev_to_rank=None) -> None:
        """Place output shards into result buffers.  Exact-width root
        buffers adopt by pointer swap (free); anything needing a
        writeback/trim program is parked as a LAZY store — the request
        materializes it on wait()/test(), and any direct buffer access
        resolves it first — so fire-and-forget chains never pay the
        result-side device interaction at dispatch time."""
        devs, writers, out_w = plan["devs"], plan["writers"], plan["out_w"]
        if dev_to_rank is None:
            dev_to_rank = {d: r for r, d in enumerate(devs)}
        for shard in out.addressable_shards:
            r = dev_to_rank.get(shard.device)
            if r is None or r not in writers:
                continue
            res = calls[r].res
            if res is None or res.is_dummy:
                continue
            sd = shard.data
            if res._parent is None and res.count == out_w:
                res.store(sd, out_w)  # pointer swap — no device program
                continue

            def adopt(sd=sd, res=res, out_w=out_w, ic=self.interactions):
                if res.store(sd, out_w):
                    ic.bump()  # the deferred writeback program

            res.defer_store(adopt)
            if reqs is not None:
                reqs[r].defer_result(res.resolve_pending, handle=sd)

    def _run_op_device(
        self,
        comm: Communicator,
        calls: List[CallOptions],
        lead: CallOptions,
        mesh,
        reqs: Optional[List[Request]] = None,
        t0: Optional[int] = None,
    ) -> Optional[ErrorCode]:
        """Run the collective entirely on device-resident operands.

        Every rank's operand must be a :class:`DeviceBuffer` committed to
        that rank's mesh device (dummies become on-device zeros); the
        per-rank arrays are assembled into ONE sharded global array with
        ``jax.make_array_from_single_device_arrays`` — zero copy — the
        jitted shard_map program (with operand staging FUSED in, see
        ``_assemble_flat``) runs over the mesh, and the output shards are
        adopted back into the result buffers lazily.  The host never
        touches payload bytes, matching the reference's device-to-device
        hot path (``accl.cpp:780-826``), and the whole call is ONE device
        interaction — the reference's one-hostctrl-command-per-collective
        discipline.  Returns None to fall back to the host-staged path.
        """
        # command-ring eager mode (ACCL_CMDRING=eager): a single warm
        # eligible call rides a one-slot refill window — the `ring` fast
        # path beside the prepared-plan path.  Default mode keeps single
        # calls on the prepared path (a one-slot window amortizes
        # nothing) and reserves the ring for batched windows.
        if (
            self.cmdring.eager
            and reqs is not None
            and self.cmdring.supports(lead.op)
        ):
            entries = [
                ([calls[r]], [reqs[r]]) for r in range(len(calls))
            ]
            if self.cmdring.run_batch(comm, entries, 1, t0=t0):
                return IN_FLIGHT
        fp = lead.plan
        fast_eligible = fp is not None and lead.op in _FAST_OPS
        if fast_eligible:
            # prepared state is keyed by the exact COUNT: the owning
            # plan is bucket-keyed, and alternating counts within one
            # bucket must each keep their own template instead of
            # thrashing a single slot
            states = fp.engine.get("gang")
            state = states.get(lead.count) if states else None
            if (
                state is not None
                and state["mesh"] is mesh
                and state["tuning_epoch"] == self.tuning_epoch
            ):
                code = self._run_op_device_prepared(
                    calls, lead, state, reqs, t0
                )
                if code is not None:
                    return code
        with annotate("accl.gang::assemble", comm=comm.id):
            plan = self._plan_device_call(comm, calls, lead, mesh)
            if plan is None:
                return None
            if fast_eligible:
                # park the prepared state on the facade's CollectivePlan: the
                # next warm call on this plan skips re-validation, sharding
                # construction and program-cache hashing entirely
                from jax.sharding import NamedSharding, PartitionSpec

                states = fp.engine.setdefault("gang", {})
                if len(states) > 8 and lead.count not in states:
                    states.clear()  # pathological count churn within a bucket
                states[lead.count] = {
                    "tmpl": plan,
                    "mesh": mesh,
                    "tuning_epoch": self.tuning_epoch,
                    "sharding": NamedSharding(
                        mesh, PartitionSpec(opdriver.AXIS)
                    ),
                    "dev_to_rank": {
                        d: r for r, d in enumerate(plan["devs"])
                    },
                    "programs": {},
                }
            op = plan["op"]
            global_arr, prep, raw_bufs = self._assemble_flat(calls, plan, mesh)
        fn = lead.reduce_function
        self.interactions.bump()  # THE dispatch: one fused program
        with annotate("accl.gang::dispatch", comm=comm.id):
            if op == Operation.ALLREDUCE:
                wire = lead.arithcfg.compressed if plan["compressed"] else None
                # allreduce keeps its wire lane inside its own program (a
                # single rounding); prep carries only the width slice here
                # (_assemble_flat never sets a prep wire for allreduce)
                out = self._allreduce(
                    global_arr, mesh, fn, wire, prep=prep,
                    tuning=effective_tuning(self.tuning, lead),
                )
            elif op in (
                Operation.REDUCE, Operation.BCAST, Operation.SCATTER,
                Operation.GATHER,
            ):
                donate = op == Operation.BCAST and prep is None
                if donate:
                    # The donating bcast consumes shard arrays that may also
                    # back cached assembled globals from earlier ops on the
                    # same buffers.  JAX copy-on-donate keeps those entries
                    # readable, but evict them anyway so no cache hit can ever
                    # observe a donated (possibly aliased) array.
                    donors = {
                        id(c.op0) for c in calls
                        if c.op0 is not None and not c.op0.is_dummy
                    }
                    stale = [
                        k for k, v in self._asm_cache.items()
                        if any(id(ref()) in donors for ref in v[2])
                    ]
                    for k in stale:
                        self._asm_cache.pop(k, None)
                out = self._run_rooted(
                    op, global_arr, mesh, lead, donate=donate, prep=prep
                )
            elif op == Operation.ALLGATHER:
                out = opdriver.run_allgather(global_arr, mesh, prep=prep)
            elif op == Operation.REDUCE_SCATTER:
                out = opdriver.run_reduce_scatter(
                    global_arr, mesh, fn, prep=prep
                )
            elif op == Operation.ALLTOALL:
                out = opdriver.run_alltoall(global_arr, mesh, prep=prep)
            else:  # pragma: no cover - guarded by IN_W
                return None
        with annotate("accl.gang::adopt", comm=comm.id):
            self._adopt_out_shards(out, calls, plan, reqs)
        with annotate("accl.gang::park", comm=comm.id):
            return self._park_inflight(comm, out, reqs, t0)

    def _run_rooted(self, op, global_arr, mesh, lead, donate=False,
                    prep=None):
        return run_rooted_with_tuning(
            op, global_arr, mesh, lead, effective_tuning(self.tuning, lead),
            donate=donate, prep=prep,
        )

    # -- host-staged fallback path -------------------------------------------
    def _run_op_host(
        self,
        comm: Communicator,
        calls: List[CallOptions],
        lead: CallOptions,
        mesh,
    ) -> ErrorCode:
        op = lead.op
        size = comm.size
        fn = lead.reduce_function
        n = lead.count
        compressed = bool(lead.compression & CompressionFlags.ETH_COMPRESSED)
        wire_npdt = (
            dtype_to_numpy(lead.arithcfg.compressed) if compressed else None
        )

        def wire_cast(arr: np.ndarray) -> np.ndarray:
            if wire_npdt is None:
                return arr
            # the shared host codec, per contribution row with each
            # rank's mixed seed (rows ARE the per-rank contributions
            # on this host-staged path, so the rounding matches what
            # the fabric tiers — and the facade's EF residual
            # accounting — compute for the same call)
            from ... import wire as wirecodec

            base_seed = getattr(lead, "wire_seed", 0)
            return np.stack([
                wirecodec.roundtrip(
                    row, lead.arithcfg.compressed,
                    wirecodec.rank_seed(base_seed, r),
                ).astype(arr.dtype)
                for r, row in enumerate(arr)
            ])

        ic = self.interactions
        if op == Operation.ALLREDUCE:
            # no host-side pre-cast here: the compressed program casts to the
            # requested wire dtype itself (single rounding, on device)
            stacked = _np_stack_op0(calls, [n] * size, ic)
            wire = lead.arithcfg.compressed if compressed else None
            out = self._allreduce(
                stacked, mesh, fn, wire,
                tuning=effective_tuning(self.tuning, lead),
            )
            out = np.asarray(out)
            for r, call in enumerate(calls):
                _write_host_result(call.res, out[r], n, ic)
            return ErrorCode.OK

        if op == Operation.REDUCE:
            stacked = wire_cast(_np_stack_op0(calls, [n] * size, ic))
            out = np.asarray(self._run_rooted(op, stacked, mesh, lead))
            root = lead.root_dst
            res = calls[root].res
            if res is not None and not res.is_dummy:
                _write_host_result(res, out[root], n, ic)
            return ErrorCode.OK

        if op == Operation.BCAST:
            stacked = wire_cast(_np_stack_op0(calls, [n] * size, ic))
            out = np.asarray(self._run_rooted(op, stacked, mesh, lead))
            for r, call in enumerate(calls):
                _write_host_result(call.res, out[r], n, ic)
            return ErrorCode.OK

        if op == Operation.ALLGATHER:
            stacked = wire_cast(_np_stack_op0(calls, [n] * size, ic))
            out = np.asarray(opdriver.run_allgather(stacked, mesh))
            for r, call in enumerate(calls):
                _write_host_result(call.res, out[r], size * n, ic)
            return ErrorCode.OK

        if op == Operation.REDUCE_SCATTER:
            stacked = wire_cast(_np_stack_op0(calls, [size * n] * size, ic))
            out = np.asarray(opdriver.run_reduce_scatter(stacked, mesh, fn))
            for r, call in enumerate(calls):
                _write_host_result(call.res, out[r][:n], n, ic)
            return ErrorCode.OK

        if op == Operation.SCATTER:
            root = lead.root_src
            stacked = wire_cast(_np_stack_op0(calls, [size * n] * size, ic))
            out = np.asarray(self._run_rooted(op, stacked, mesh, lead))
            for r, call in enumerate(calls):
                _write_host_result(call.res, out[r], n, ic)
            return ErrorCode.OK

        if op == Operation.GATHER:
            root = lead.root_src
            stacked = wire_cast(_np_stack_op0(calls, [n] * size, ic))
            out = np.asarray(self._run_rooted(op, stacked, mesh, lead))
            res = calls[root].res
            if res is not None and not res.is_dummy:
                _write_host_result(res, out[root], size * n, ic)
            return ErrorCode.OK

        if op == Operation.ALLTOALL:
            stacked = wire_cast(_np_stack_op0(calls, [size * n] * size, ic))
            out = np.asarray(opdriver.run_alltoall(stacked, mesh))
            for r, call in enumerate(calls):
                _write_host_result(call.res, out[r], size * n, ic)
            return ErrorCode.OK

        return ErrorCode.COLLECTIVE_NOT_IMPLEMENTED

    def _allreduce(self, stacked, mesh, fn, wire_dtype, prep=None,
                   tuning=None):
        return run_allreduce_with_tuning(
            stacked, mesh, fn, wire_dtype,
            self.tuning if tuning is None else tuning, prep=prep,
        )


# p2p pairing: send/recv matched by (comm, tag, src, dst) independent of the
# collective gang sequence.  Receivers register a *sink* callable so the same
# channel serves buffer receives and recv-to-stream.  Unmatched posts carry a
# deadline honoring the engine timeout (the firmware's per-call deadline),
# armed at the gang's one deadline keeper; delivery — which may jit the
# fabric-hop program — runs OUTSIDE the channel lock so unrelated pairs
# never serialize behind a compile.
class _ParkedPost:
    """One unmatched send (``item`` is its payload) or recv (its sink)."""

    __slots__ = ("channel", "table", "key", "item", "request", "t0",
                 "__weakref__")

    def __init__(self, channel, table, key, item, request, t0):
        self.channel = channel
        self.table = table
        self.key = key
        self.item = item
        self.request = request
        self.t0 = t0

    def expire(self) -> bool:
        return self.channel._expire(self)


class _P2PChannel:
    """Tag-matched send/recv rendezvous between rank engines.

    Durations are MEASURED, not sentinels: each post is stamped at entry
    and each request completes with post->delivery wall-clock ns — the
    analog of the reference's per-call device-cycle reads that its
    sendrecv bench is built on (ref xrtdevice.cpp:242-249 get_duration,
    bench.cpp:25-31).  A parked side therefore reports its true wait
    (including the partner's late arrival); the late-arriving side
    reports roughly the delivery/copy cost alone."""

    def __init__(self, deadlines: _DeadlineKeeper):
        self._lock = threading.Lock()
        self._sends: Dict[tuple, list] = {}
        self._recvs: Dict[tuple, list] = {}
        self._deadlines = deadlines  # the gang's keeper

    def dump_parked(self) -> list:
        """Unmatched-post lines for the debug dump (a parked send holds
        its payload alive — the closest analog of an occupied rx buffer
        on this tier)."""
        lines = []
        with self._lock:
            for kind, table in (("SEND", self._sends), ("RECV", self._recvs)):
                for key, entries in table.items():
                    for _ in entries:
                        comm_id, tag, src, dst = key
                        lines.append(
                            f"rxbuf p2p-{kind} comm={comm_id} tag={tag} "
                            f"src={src} dst={dst} PARKED"
                        )
        return lines

    def post_send(self, key, payload, request, timeout_s=None):
        t0 = time.perf_counter_ns()
        with self._lock:
            match = self._match(self._recvs, key)
            if match is None:
                parked = self._park(self._sends, key, payload, request, t0)
        if match is not None:
            self._deliver(match.item, match.request, payload, request,
                          match.t0, t0)
        elif timeout_s:
            self._deadlines.arm(time.monotonic() + timeout_s, parked)

    def post_recv(self, key, sink, request, timeout_s=None):
        t0 = time.perf_counter_ns()
        with self._lock:
            match = self._match(self._sends, key)
            if match is None:
                parked = self._park(self._recvs, key, sink, request, t0)
        if match is not None:
            self._deliver(sink, request, match.item, match.request,
                          t0, match.t0)
        elif timeout_s:
            self._deadlines.arm(time.monotonic() + timeout_s, parked)

    @staticmethod
    def _match(table, key) -> Optional[_ParkedPost]:
        """Pop the oldest parked partner (caller holds the lock).  It is
        not cancelled at the keeper: it leaves the table, and its
        deadline finds it gone."""
        posts = table.get(key)
        return posts.pop(0) if posts else None

    def _park(self, table, key, item, request, t0) -> _ParkedPost:
        """Append an unmatched post (caller holds the lock, and arms its
        deadline after releasing it: the keeper's lock nests in none)."""
        post = _ParkedPost(self, table, key, item, request, t0)
        table.setdefault(key, []).append(post)
        return post

    def _expire(self, post: _ParkedPost) -> bool:
        key = post.key
        with self._lock:
            posts = post.table.get(key, ())
            if post not in posts:  # by identity: a post defines no ==
                return False  # matched in the meantime: nothing to do
            posts.remove(post)
        code = (
            ErrorCode.SEND_TIMEOUT
            if post.table is self._sends
            else ErrorCode.RECEIVE_TIMEOUT
        )
        dt = time.perf_counter_ns() - post.t0
        comm_id, _tag, src, dst = key
        post.request.complete(code, dt, context={
            "op": post.request.op_name,
            "comm": comm_id,
            # the absent partner: the sender for a starved recv, the
            # receiver for a starved send (global rank identities)
            "peer": src if code == ErrorCode.RECEIVE_TIMEOUT else dst,
            "elapsed_s": round(dt / 1e9, 3),
        })
        return True

    @staticmethod
    def _deliver(sink, rreq: Request, payload: np.ndarray, sreq,
                 recv_t0: int, send_t0: int):
        try:
            sink(payload)
        except Exception:
            t1 = time.perf_counter_ns()
            rreq.complete(ErrorCode.INVALID_OPERATION, max(t1 - recv_t0, 1))
            sreq.complete(ErrorCode.INVALID_OPERATION, max(t1 - send_t0, 1))
            return
        t1 = time.perf_counter_ns()
        rreq.complete(ErrorCode.OK, max(t1 - recv_t0, 1))
        sreq.complete(ErrorCode.OK, max(t1 - send_t0, 1))


class XLAEngine(StreamPortMixin, BaseEngine):
    """One rank handle's engine over a shared gang context.

    Local ops (copy/combine) execute immediately with jax.numpy on the
    default device; collectives rendezvous at the gang; p2p pairs match in
    the channel (the ICI transfer being a collective-permute is an XLA
    scheduling detail once both sides have arrived)."""

    def __init__(
        self,
        gang: XLAGangContext,
        p2p: Optional[_P2PChannel] = None,
        peers: Optional[Dict[int, "XLAEngine"]] = None,
        device=None,
    ):
        self.gang = gang
        self.p2p = p2p or _P2PChannel(gang.deadlines)
        if gang.p2p is None:
            gang.p2p = self.p2p
        self.peers = peers if peers is not None else {}
        self.device = device  # this rank's chip; buffers commit to its HBM
        self.timeout_s = DEFAULT_TIMEOUT_S
        self.max_eager_size = 32 * 1024
        self.max_rendezvous_size = MAX_EAGER_SIZE_LIMIT
        self.retry_limit = 0
        self.retry_backoff_s = 0.05
        # QoS arbiter plane: engine-side mirror of SET_TENANT_* writes
        # (comm id -> {class, weight, window_share, ring_slots, rate})
        self.tenants: Dict[int, dict] = {}
        self._init_streams()

    def start(self, options: CallOptions) -> Request:
        req = Request(op_name=options.op.name)
        req.mark_executing()
        self._start_with(options, req)
        return req

    def start_batch(self, items) -> None:
        """Dispatch a flushed command-queue batch.  Maximal runs of gang
        collectives sharing a communicator submit as ONE gang batch event
        (executed as one fused program when every position qualifies —
        see ``XLAGangContext._run_batch_fused``); local ops / p2p / config
        calls break the run and dispatch individually, preserving issue
        order."""
        run: list = []
        run_comm = None

        def flush_run():
            nonlocal run, run_comm
            if run:
                self.gang.submit_batch(
                    run_comm, [o for o, _ in run], [r for _, r in run]
                )
            run, run_comm = [], None

        for options, req in items:
            req.mark_executing()
            gang_eligible = (
                (options.op in IN_W or options.op == Operation.BARRIER)
                and options.stream == StreamFlags.NO_STREAM
            )
            # command-ring p2p: a batched SEND/RECV on a world-2 gang
            # joins the collective run so a matched pair can ride one
            # ring slot (root=src, peer=dst).  Eligibility is
            # pair-symmetric by construction (cmdring.p2p_eligible) so
            # both ends classify identically; unpaired positions fall
            # back to _execute_p2p_pair / the channel below.
            if (
                options.op in (Operation.SEND, Operation.RECV)
                and options.stream == StreamFlags.NO_STREAM
                and self.gang.cmdring.p2p_eligible(options)
            ):
                gang_eligible = True
            if gang_eligible:
                if run_comm is not None and options.comm is not run_comm:
                    flush_run()
                run_comm = options.comm
                run.append((options, req))
            else:
                flush_run()
                self._start_with(options, req)
        flush_run()

    def device_interactions(self) -> int:
        return self.gang.interactions.read()

    # -- contract plane (accl_tpu.contract) ----------------------------------
    def contract_anchor(self):
        """The gang context: every rank handle of this mesh shares it,
        so their verifiers exchange digests on one in-process board (the
        single-process analog of the multi-slice device-side digest
        reduce — ROADMAP item 2)."""
        return self.gang

    def set_contract_verifier(self, verifier) -> None:
        """A divergence verdict must fail the gang's PARKED slots too:
        the detecting rank raises pre-dispatch, which means its peers'
        already-submitted calls would otherwise starve their slot until
        the watchdog — the exact hang the verifier exists to remove."""
        self.contract_verifier = verifier
        if verifier is not None:
            verifier.add_verdict_listener(self.gang.contract_fail)

    def drain_inflight(self, timeout=None) -> bool:
        """Overlap drain point: block until the gang's in-flight window
        is empty (every launched collective completed).  Bounded by
        default — flush()/config callers must not hang forever on a
        wedged device call (the per-request wait()/check() path is
        where its failure surfaces)."""
        return self.gang.window.drain(
            timeout if timeout is not None
            else drain_deadline_s(self.gang.timeout_s)
        )

    # -- membership plane (accl_tpu.membership) ------------------------------
    def set_membership(self, view) -> None:
        """Arm (or with ``None`` disarm) the membership plane: the
        gang's slot-watchdog health transitions forward to the facade
        hook (the board does the agreement exchange — every gang rank
        handle shares the anchor).  Disarm removes the forwarder from
        the shared gang — it must not keep firing (or pin this engine)
        for the gang's lifetime across handle churn."""
        self.membership = view
        fwd = getattr(self, "_mbr_fwd", None)
        if view is None:
            if fwd is not None:
                self.gang.remove_health_listener(fwd)
                self._mbr_fwd = None
            return
        if fwd is None:

            def fwd(session, old, new, eng=self):
                hook = eng.on_health_transition
                if hook is not None:
                    hook(session, old, new)

            self._mbr_fwd = fwd
            self.gang.add_health_listener(fwd)

    def on_membership_cutover(self, plan: dict, addresses: tuple = (),
                              comm_ids: tuple = ()) -> None:
        """Post-cutover session re-arm (shrink AND grow): halt the
        command ring's persistent runs and abandon its per-comm
        sessions (they re-arm lazily over the new membership at the
        next warm window — the documented tear-down/re-arm), drop the
        evicted sessions' watchdog entries — and, on a JOIN, the
        admitted sessions' too: the candidate's previous life may have
        left a ``dead`` verdict that would fail-fast its first
        post-join window — and clear the suspect strikes the failure
        cascade accrued against survivors."""
        for s in tuple(plan.get("evict", ())) + tuple(
            plan.get("admit", ())
        ):
            self.gang.health.pop(s, None)
        # snapshot before iterating: the deadline keeper's thread inserts
        # concurrently, and a bare .values() walk can raise mid-cutover
        for h in list(self.gang.health.values()):
            if h["state"] == "suspect":
                h["state"] = "ok"
                h["timeouts"] = 0
        self.gang.cmdring.reset()

    def telemetry_report(self) -> dict:
        """Gang-tier counters for the telemetry snapshot: pending
        rendezvous slots, parked p2p posts, undrained stream ports, and
        the shared interaction counter."""
        with self.gang._lock:
            pending_slots = len(self.gang._slots)
        with self._stream_cv:
            stream_depths = {
                sid: len(chunks)
                for sid, chunks in sorted(self._streams.items())
                if chunks
            }
        return {
            "device_interactions": self.gang.interactions.read(),
            "gang_pending_slots": pending_slots,
            # the deadline keeper: armed == multi-rank slots + parked
            # p2p posts; a healthy run has fired 0, threads_started 1
            "gang_deadlines": self.gang.deadlines.stats(),
            "gang_tuning_epoch": self.gang.tuning_epoch,
            "p2p_parked": len(self.p2p.dump_parked()),
            "stream_depths": stream_depths,
            # overlap plane: the in-flight window's live depth + lifetime
            # counters (launched/completed/failed/max depth/overlap ns)
            "inflight": self.gang.window.stats(),
            # command-ring plane: refill/doorbell counters, occupancy,
            # park state and per-reason fallback counts
            "cmdring": self.gang.cmdring.stats(),
            # QoS arbiter plane: the engine-side tenant quota mirror
            "tenants": {str(k): dict(v) for k, v in
                        sorted(self.tenants.items())},
            "faults": None,
            # monitor plane: rank handles share the gang context, so
            # straggler windows meet on one in-process judge (the
            # contract board's anchor discipline reused)
            "skew_exchange": "board",
        }

    def trace_events(self) -> list:
        """Ring-resident spans (one per slot, nested under its refill
        window, flow-linked to the issuing call) — the gang tier's
        engine-owned rows in the facade's Perfetto export.  Every rank
        handle shares the gang, so every rank file embeds the same
        rows; merge_traces dedups them to one copy (cat ``cmdring``)."""
        return self.gang.cmdring.trace_events()

    def health_report(self, comm: Communicator) -> Dict[int, dict]:
        """Per-peer health from the gang watchdog accounting, keyed by
        comm-relative rank (capabilities()["health"] on the gang tier)."""
        report: Dict[int, dict] = {}
        for i, r in enumerate(comm.ranks):
            if i == comm.local_rank:
                continue
            h = self.gang.health.get(r.session)
            report[i] = dict(h) if h else {
                "state": "ok", "timeouts": 0, "failures": 0, "last_event": ""
            }
        return report

    def _start_with(self, options: CallOptions, req: Request) -> None:
        op = options.op
        if op == Operation.CONFIG:
            req.complete(self._apply_config(options))
        elif op == Operation.NOP:
            req.complete(ErrorCode.OK)
        elif op in (Operation.COPY, Operation.COMBINE):
            if options.stream & StreamFlags.OP0_STREAM:
                # streaming operand arrives asynchronously from a device
                # kernel: wait for it off the caller's thread
                self._spawn_completing(
                    lambda: req.complete(self._local_op(options)), req
                )
            else:
                req.complete(self._local_op(options))
        elif op == Operation.REDUCE and options.stream != StreamFlags.NO_STREAM:
            # stream-operand reduce (ref accl.hpp:514-590): bridge the
            # stream ports onto the gang off-thread
            self._spawn_completing(
                lambda: self._gang_with_streams(options, req), req
            )
        elif op == Operation.SEND:
            self._start_send(options, req)
        elif op == Operation.RECV:
            comm = options.comm
            # p2p keys use *global* rank identities (Rank.session) so that
            # subcommunicator traffic reaches the right engine
            src_world = comm.ranks[options.root_src].session
            me_world = comm.ranks[comm.local_rank].session
            key = (comm.id, options.tag, src_world, me_world)
            if options.stream & StreamFlags.RES_STREAM:
                sink = lambda payload: self.stream_push(
                    options.stream_id, np.asarray(payload).tobytes()
                )
            else:

                def sink(payload, call=options, req=req):
                    if isinstance(payload, jax.Array) and isinstance(
                        call.res, DeviceBuffer
                    ):
                        # both ends device-resident: ride the fabric —
                        # LAZILY.  The hop/trim programs (each a device
                        # interaction) are parked on the result buffer and
                        # run at the receiver's wait()/first data access,
                        # so a fire-and-forget recv chain never pays the
                        # result RTT at match time.  Shape validation
                        # stays EAGER so a mismatched pair still fails at
                        # the channel (INVALID_OPERATION on both sides),
                        # not at a later wait.
                        if payload.ndim != 1 or payload.shape[0] < call.count:
                            raise ValueError(
                                f"p2p payload of shape {payload.shape} "
                                f"into count {call.count}"
                            )
                        ic = self.gang.interactions

                        def deliver(payload=payload, call=call, ic=ic):
                            _p2p_device_deliver(
                                payload, call.res, call.count, ic
                            )

                        call.res.defer_store(deliver)
                        req.defer_result(
                            call.res.resolve_pending, handle=payload
                        )
                        return
                    if isinstance(payload, jax.Array):
                        payload = np.asarray(payload)  # host-side receiver
                    _write_host_result(
                        call.res, payload, call.count, self.gang.interactions
                    )

            self.p2p.post_recv(key, sink, req, timeout_s=self.timeout_s)
        else:
            self.gang.submit(options.comm, options, req)

    def _start_send(self, options: CallOptions, req: Request) -> None:
        """SEND with all four operand routings: buffer/local-stream source x
        tag-matched/remote-stream destination (emulator parity:
        algorithms.op_send)."""
        comm = options.comm

        def resolve_and_route():
            t0 = time.perf_counter_ns()
            cfg = options.arithcfg
            if options.stream & StreamFlags.OP0_STREAM:
                payload = self._pop_stream_payload(options)
                if payload is None:
                    req.complete(ErrorCode.DMA_TIMEOUT)
                    return
            elif isinstance(options.op0, DeviceBuffer) and not (
                options.stream & StreamFlags.RES_STREAM
            ):
                # device-resident send: post the payload as a committed
                # jax.Array (a fresh device copy, so the sender may free or
                # overwrite its buffer immediately); the matched receiver
                # moves it over the fabric with a collective-permute
                src_dev = options.op0.device
                payload = _trim_program(options.count, src_dev)(
                    options.op0.device_array()
                )
                self.gang.interactions.bump()  # the payload-copy program
                if options.compression & CompressionFlags.ETH_COMPRESSED:
                    # compress lane on the sending chip: the wire (and the
                    # ICI hop) carries the narrow dtype
                    payload = _cast_program(
                        dtype_to_numpy(cfg.compressed), src_dev
                    )(payload)
                    self.gang.interactions.bump()
            else:
                payload = np.asarray(
                    options.op0.device_view()[: options.count]
                ).copy()
            if isinstance(payload, np.ndarray) and (
                options.compression & CompressionFlags.ETH_COMPRESSED
            ):
                payload = payload.astype(dtype_to_numpy(cfg.compressed))
            dst_world = comm.ranks[options.root_dst].session
            me_world = comm.ranks[comm.local_rank].session
            if options.stream & StreamFlags.RES_STREAM:
                peer = self.peers.get(dst_world)
                if peer is None:
                    req.complete(ErrorCode.TRANSPORT_ERROR)
                else:
                    peer.stream_push(options.stream_id, payload.tobytes())
                    req.complete(
                        ErrorCode.OK, max(time.perf_counter_ns() - t0, 1)
                    )
                return
            key = (comm.id, options.tag, me_world, dst_world)
            self.p2p.post_send(key, payload, req, timeout_s=self.timeout_s)

        if options.stream & StreamFlags.OP0_STREAM:
            # operand arrives asynchronously from a device kernel: wait for
            # it off the caller's thread (the emulator parks in its scheduler)
            self._spawn_completing(resolve_and_route, req)
        else:
            resolve_and_route()

    def _spawn_completing(self, fn, req: Request) -> None:
        """Run ``fn`` on a daemon thread; an escaping exception completes
        the request with an error instead of leaving the caller waiting
        forever (the scheduler-level guard the emulator tier has)."""

        def run():
            try:
                fn()
            except Exception:
                import traceback

                traceback.print_exc()
                if not req.done():  # side-effect-free engine probe
                    req.complete(ErrorCode.INVALID_OPERATION)

        threading.Thread(
            target=run, name="accl-xla-op", daemon=True
        ).start()

    def _gang_with_streams(self, options: CallOptions, req: Request) -> None:
        """Stream-operand collective: pull OP0 from the stream port, run
        the gang collective on a host-staged temp, deliver the root result
        back to the stream port."""
        import dataclasses

        opts = options
        if opts.stream & StreamFlags.OP0_STREAM:
            payload = self._pop_stream_payload(opts)
            if payload is None:
                req.complete(ErrorCode.DMA_TIMEOUT)
                return
            acc_npdt = dtype_to_numpy(opts.arithcfg.uncompressed)
            tmp = EmuBuffer.from_array(payload.astype(acc_npdt))
            tmp.sync_to_device()
            opts = dataclasses.replace(
                opts, op0=tmp, stream=opts.stream & ~StreamFlags.OP0_STREAM
            )
        res_to_stream = bool(opts.stream & StreamFlags.RES_STREAM)
        tmp_res = None
        if res_to_stream:
            is_root = opts.comm.local_rank == opts.root_dst
            tmp_res = (
                EmuBuffer(opts.count, opts.arithcfg.uncompressed)
                if is_root
                else DummyBuffer(0, opts.arithcfg.uncompressed)
            )
            opts = dataclasses.replace(
                opts, res=tmp_res,
                stream=opts.stream & ~StreamFlags.RES_STREAM,
            )
        inner = Request(op_name=opts.op.name)
        inner.mark_executing()
        self.gang.submit(opts.comm, opts, inner)
        # acclint: allow[unbounded-wait] the gang slot watchdog completes
        # `inner` with RECEIVE_TIMEOUT when the gang never assembles, so
        # this wait is bounded by the engine timeout machinery, not ours
        inner.wait()
        code = inner.get_retcode()
        if (
            code == ErrorCode.OK
            and res_to_stream
            and not tmp_res.is_dummy
        ):
            self._push_stream_result(options, tmp_res.device_view())
        req.complete(code, inner.get_duration_ns())

    def _local_op(self, options: CallOptions) -> ErrorCode:
        n = options.count
        if options.stream & StreamFlags.OP0_STREAM:
            payload = self._pop_stream_payload(options)
            if payload is None:
                return ErrorCode.DMA_TIMEOUT
            acc = payload.astype(
                dtype_to_numpy(options.arithcfg.uncompressed)
            )
            if options.op == Operation.COMBINE:
                other = np.asarray(options.op1.device_view()[:n])
                if options.reduce_function == ReduceFunction.SUM:
                    acc = acc + other
                elif options.reduce_function == ReduceFunction.MAX:
                    acc = np.maximum(acc, other)
                else:
                    return ErrorCode.ARITH_ERROR
            if options.stream & StreamFlags.RES_STREAM:
                self._push_stream_result(options, acc)
            else:
                _write_host_result(
                    options.res, acc, n, self.gang.interactions
                )
            return ErrorCode.OK
        if options.stream & StreamFlags.RES_STREAM:
            src = np.asarray(options.op0.device_view()[:n])
            if options.op == Operation.COMBINE:
                other = np.asarray(options.op1.device_view()[:n])
                if options.reduce_function == ReduceFunction.SUM:
                    src = src + other
                elif options.reduce_function == ReduceFunction.MAX:
                    src = np.maximum(src, other)
                else:
                    return ErrorCode.ARITH_ERROR
            self._push_stream_result(options, src)
            return ErrorCode.OK
        bufs = [options.op0, options.res]
        if options.op == Operation.COMBINE:
            bufs.insert(1, options.op1)
        if all(isinstance(b, DeviceBuffer) for b in bufs) and len(
            {b.device for b in bufs}
        ) == 1:
            # all-device fast path: compute on the owning chip, adopt the
            # result — the reference's DMA-loopback copy/combine with no
            # host in the loop
            src = options.op0.device_array()[:n]
            if options.op == Operation.COMBINE:
                other = options.op1.device_array()[:n]
                if options.reduce_function == ReduceFunction.SUM:
                    out = src + other
                elif options.reduce_function == ReduceFunction.MAX:
                    out = jnp.maximum(src, other)
                else:
                    return ErrorCode.ARITH_ERROR
            else:
                # force a distinct array: a full-count slice returns the
                # IDENTICAL jax.Array, and sharing storage would make a later
                # free_buffer() on either buffer delete the other's data
                out = jnp.copy(src)
            res_npdt = dtype_to_numpy(options.res.dtype)
            if out.dtype != res_npdt:
                out = out.astype(res_npdt)  # cross-dtype copy/combine
            self.gang.interactions.bump()  # the eager device compute
            if options.res.store(out, n):
                self.gang.interactions.bump()
            return ErrorCode.OK
        src = jnp.asarray(options.op0.device_view()[:n])
        if options.op == Operation.COMBINE:
            other = jnp.asarray(options.op1.device_view()[:n])
            if options.reduce_function == ReduceFunction.SUM:
                out = src + other
            elif options.reduce_function == ReduceFunction.MAX:
                out = jnp.maximum(src, other)
            else:
                return ErrorCode.ARITH_ERROR
        else:
            out = src
        _write_host_result(
            options.res, np.asarray(out), n, self.gang.interactions
        )
        return ErrorCode.OK

    def _apply_config(self, options: CallOptions) -> ErrorCode:
        fn = ConfigFunction(options.cfg_function)
        val = options.cfg_value
        if fn == ConfigFunction.RESET:
            self.gang.soft_reset()
        elif fn == ConfigFunction.SET_TIMEOUT:
            if val <= 0:
                return ErrorCode.CONFIG_ERROR
            self.timeout_s = float(val)
            self.gang.timeout_s = float(val)
        elif fn == ConfigFunction.SET_MAX_EAGER_SIZE:
            if not 0 < val <= MAX_EAGER_SIZE_LIMIT:
                return ErrorCode.CONFIG_ERROR
            self.max_eager_size = int(val)
        elif fn == ConfigFunction.SET_MAX_RENDEZVOUS_SIZE:
            if val <= 0:
                return ErrorCode.CONFIG_ERROR
            self.max_rendezvous_size = int(val)
        elif fn == ConfigFunction.SET_RETRY_LIMIT:
            # no wire retransmit on this tier (XLA owns the fabric); the
            # knobs are accepted + stored so set_retry_policy is portable
            if val < 0:
                return ErrorCode.CONFIG_ERROR
            self.retry_limit = int(val)
        elif fn == ConfigFunction.SET_RETRY_BACKOFF:
            if val <= 0:
                return ErrorCode.CONFIG_ERROR
            self.retry_backoff_s = float(val)
        elif fn == ConfigFunction.SET_INFLIGHT_WINDOW:
            from ...constants import MAX_INFLIGHT_WINDOW

            if not 1 <= val <= MAX_INFLIGHT_WINDOW:
                return ErrorCode.CONFIG_ERROR
            # a depth change is itself a drain point: no launch made
            # under the old bound may still be in flight when the new
            # bound starts admitting (bounded — a wedged call fails the
            # config within the engine deadline instead of hanging it)
            if not self.gang.window.drain(
                drain_deadline_s(self.gang.timeout_s)
            ):
                return ErrorCode.RECEIVE_TIMEOUT
            self.gang.window.set_depth(int(val))
        elif fn in (
            ConfigFunction.SET_TENANT_CLASS,
            ConfigFunction.SET_TENANT_WEIGHT,
            ConfigFunction.SET_TENANT_WINDOW_SHARE,
            ConfigFunction.SET_TENANT_RING_SLOTS,
            ConfigFunction.SET_TENANT_RATE,
        ):
            # QoS arbiter plane, validated by the ONE shared validator
            # (arbiter.tenant_config_valid — the same ranges on every
            # tier).  This tier additionally ENFORCES the two device-
            # side quotas: WINDOW_SHARE becomes a per-key depth
            # override on the in-flight window (a drain point like
            # SET_INFLIGHT_WINDOW — nothing launched under the old
            # bound survives it) and RING_SLOTS the command ring's
            # refill-window slot budget.  Class/weight/rate stay
            # arbiter-side state, mirrored for introspection.
            from ...arbiter import tenant_config_field, tenant_config_valid

            if not tenant_config_valid(fn, val):
                return ErrorCode.CONFIG_ERROR
            if fn == ConfigFunction.SET_TENANT_WINDOW_SHARE:
                if not self.gang.window.drain(
                    drain_deadline_s(self.gang.timeout_s)
                ):
                    return ErrorCode.RECEIVE_TIMEOUT
                self.gang.window.set_key_depth(
                    int(options.cfg_key), int(val)
                )
            elif fn == ConfigFunction.SET_TENANT_RING_SLOTS:
                self.gang.cmdring.set_slot_budget(
                    int(options.cfg_key), int(val)
                )
            self.tenants.setdefault(
                int(options.cfg_key), {}
            )[tenant_config_field(fn)] = val
        elif fn == ConfigFunction.SET_TUNING:
            return self._apply_tuning(options)
        return ErrorCode.OK

    def _apply_tuning(self, options: CallOptions) -> ErrorCode:
        code = apply_tuning(self.gang.tuning, options)
        if code == ErrorCode.OK:
            self.gang.tuning_epoch += 1
        return code

    def create_buffer(self, count: int, dtype, host_only: bool = False,
                      data=None):
        """HBM-resident DeviceBuffer on this rank's chip; host-only
        buffers (and device-less fallback ranks) stay host pairs."""
        return make_buffer(
            self.device, count, dtype, host_only=host_only, data=data
        )

    def dump_rx_buffers(self) -> str:
        """Rx-accounting dump for the gang tier (the role of the
        reference's rx-buffer spare-queue dump, accl.cpp dump_rx_buffers):
        the live slot state here is parked gang rendezvous slots,
        unmatched p2p posts, and undrained stream-port chunks.  Lines for
        occupied state carry the ``rxbuf`` token WITHOUT ``IDLE`` so the
        soak/stress leak filters (tests/test_soak.py) read this tier's
        dump exactly like the emulator pool's — a clean engine emits no ``rxbuf`` line at all."""
        lines = [
            "XLA gang rx state "
            f"(device={self.device}, "
            f"device_interactions={self.gang.interactions.read()}):"
        ]
        lines += self.gang.dump_state()
        lines += self.p2p.dump_parked()
        with self._stream_cv:
            for sid, chunks in sorted(self._streams.items()):
                if chunks:
                    lines.append(
                        f"rxbuf stream-port {sid} depth={len(chunks)} "
                        "UNDRAINED"
                    )
        if len(lines) == 1:
            lines.append("all slots IDLE")
        return "\n".join(lines)

    def shutdown(self) -> None:
        # overlap plane: drain and stop the shared window's drainer (the
        # first rank handle's deinit does the work; later ones find it
        # already stopped — parks then degrade to inline completion)
        self.gang.window.stop()
        self.gang.deadlines.stop()
