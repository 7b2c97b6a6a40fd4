"""The multi-process (jax.distributed) collective engine.

One :class:`DistEngine` per OS process; the process owns the rank equal to
``jax.process_index()`` and that rank's device HBM.  Collectives are SPMD:
every member process calls the facade op in the same order (exactly the
contract mpirun imposes on the reference's per-rank hosts), each
contributes its local shard via ``jax.make_array_from_single_device_arrays``
(zero host copies for device-resident buffers), and all run the identical
jitted program over the global mesh.  Matched send/recv pairs run a
two-device collective-permute program in just the two owning processes.

Differences from the single-process gang (backends/xla):
* no rendezvous slot machinery — program order IS the match (SPMD);
* the barrier is a real cross-process device collective, not gang
  assembly;
* remote stream ports ride the distributed runtime's key-value service
  (one-sided, sequence-ordered — see the "remote stream ports" section
  below): a control-plane hop sized for kernel handoffs, not bulk data.
"""

from __future__ import annotations

import functools
import time
import traceback
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...analysis.markers import spmd_uniform
from ...buffer import DeviceBuffer, dev_zeros as _dev_zeros, make_buffer
from ...communicator import Communicator, Rank
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
from ...ops import driver as opdriver
from ...request import Request
from ..base import BaseEngine, CallOptions, InteractionCounter, StreamPortMixin
from ..xla.engine import (
    IN_W,
    OUT_W,
    apply_tuning,
    _cast_program,
    _p2p_hop_program,
    _write_host_result,
    run_allreduce_with_tuning,
    run_rooted_with_tuning,
)


#: collectives whose completion advances the contract verifier's digest
#: (the facade's _CONTRACT_OPS) — only these can complete a verification
#: window, so only they trigger the KV digest-piggyback exchange
_KV_VERIFIED_OPS = frozenset((
    Operation.BCAST, Operation.SCATTER, Operation.GATHER,
    Operation.ALLGATHER, Operation.REDUCE, Operation.ALLREDUCE,
    Operation.REDUCE_SCATTER, Operation.ALLTOALL, Operation.BARRIER,
))


def _bucket_width(n: int) -> int:
    """Power-of-two wire bucket (floor 8) for a per-chunk element count.

    Every XLA program this engine dispatches is specialized on its
    operand shapes: without bucketing, a workload sweeping arbitrary
    counts compiles a FRESH collective program per distinct size (the
    round-4 soak measured ~3 ops/s on the dist tier for exactly this
    reason — nearly every op was a cold compile).  Padding each chunk
    to the next power of two caps the program population at ~log2(max
    count) per collective and turns the steady state into cached-
    dispatch latency — the same static-shapes discipline XLA demands of
    TPU programs generally.  Zero-padding is neutral for every op here:
    reductions trim the pad before any result is read, and data-movement
    ops move the pad alongside and trim it at the edge."""
    if n <= 8:
        return 8
    return 1 << (n - 1).bit_length()


@functools.lru_cache(maxsize=1024)
def _pad_chunks_program(chunks: int, n: int, nb: int, wire_name, device):
    """Device-side re-layout of a (>= chunks*n,) operand into the
    (1, chunks*nb) padded wire row (big device-resident operands; small
    ones pad on the host, see _operand_shard)."""
    from jax.sharding import SingleDeviceSharding

    def f(a):
        m = a[: chunks * n].reshape(chunks, n)
        if wire_name is not None:
            # the shared in-program wire lane (cast lanes + the scaled
            # int8 lane), mirroring the gang tier's decode-loop helper
            from ...ops import wire as devwire

            m = devwire.wire_lane_roundtrip(m, jnp.dtype(wire_name))
        if nb != n:
            m = jnp.pad(m, ((0, 0), (0, nb - n)))
        return m.reshape(1, chunks * nb)

    return jax.jit(f, out_shardings=SingleDeviceSharding(device))


@functools.lru_cache(maxsize=1024)
def _unpad_chunks_program(chunks: int, n: int, nb: int, device,
                          npdt=None):
    """Inverse edge: (1, chunks*nb) padded wire row -> (chunks*n,).
    ``npdt`` fuses the decompress/cast lane into the SAME program (one
    result-side device interaction instead of the old unpad+cast pair —
    the single-interaction dispatch discipline applied to this tier's
    result leg)."""
    from jax.sharding import SingleDeviceSharding

    def f(a):
        a = a.reshape(chunks, nb)[:, :n].reshape(-1)
        if npdt is not None and a.dtype != npdt:
            a = a.astype(npdt)
        return a

    return jax.jit(f, out_shardings=SingleDeviceSharding(device))


class DistEngine(StreamPortMixin, BaseEngine):
    """This process's rank engine over the multi-controller runtime."""

    def __init__(self):
        if jax.process_count() < 2:
            raise RuntimeError(
                "DistEngine needs an initialized jax.distributed runtime "
                "with >= 2 processes (call dist_group_member)"
            )
        self.process_id = jax.process_index()
        locals_ = jax.local_devices()
        # one rank per process: the facade rank maps to this process's
        # first device (multi-device hosts shard within the process via
        # the model-parallel mesh APIs, not the MPI-like facade)
        self.device = locals_[0]
        self.timeout_s = DEFAULT_TIMEOUT_S
        self.max_eager_size = 32 * 1024
        self.max_rendezvous_size = MAX_EAGER_SIZE_LIMIT
        self.retry_limit = 0
        self.retry_backoff_s = 0.05
        self.tuning = {"allreduce_algorithm": "xla", "ring_segments": 1}
        self.interactions = InteractionCounter()
        # overlap plane: this tier's in-flight unit is the serialized
        # executor's backlog — start() applies backpressure once more
        # than `inflight_window` calls are queued ahead of the executor
        # (SET_INFLIGHT_WINDOW / ACCL_INFLIGHT_WINDOW), and
        # drain_inflight() rides a NOP through the queue as the barrier.
        from ...overlap import default_window_depth

        self.inflight_window = default_window_depth()
        # QoS arbiter plane: engine-side mirror of SET_TENANT_* writes
        self.tenants: Dict[int, dict] = {}
        self._init_streams()
        # per-port consumed counter for remotely-posted stream chunks
        import threading as _threading

        self._stream_seq: Dict[int, int] = {}
        self._stream_seq_lock = _threading.Lock()
        # learned not-found signature for KV try-get (see _is_notfound)
        self._nf_sig: Optional[tuple] = None
        self._nf_probed = False
        self._nf_probe_tries = 0
        # contract plane: per-comm KV digest-piggyback cursors +
        # lifetime counters (see _kv_contract_exchange)
        self._vfy_kv_state: Dict[int, dict] = {}
        self._vfy_kv_counters: Dict[str, int] = {
            "posted": 0, "claims": 0, "errors": 0,
        }
        self._meshes: Dict[tuple, object] = {}
        # one serialized executor thread (the FPGAQueue role): calls run
        # in submission order — the property SPMD needs — while start()
        # returns immediately so facade timeouts can fire even if a
        # mismatched cross-process program wedges the executor (the
        # reference's wedged-CCLO failure mode, recovered by re-init)
        from ...request import CommandQueue

        self._queue = CommandQueue()
        self._shut = False
        import threading

        self._executor = threading.Thread(
            target=self._run, name="accl-dist-engine", daemon=True
        )
        self._executor.start()
        # global rank -> that process's first device (a process may hold
        # several local devices, e.g. a forced multi-device CPU host or a
        # TPU host with 4 chips; the MPI-like facade rank uses the first)
        self._rank_device: Dict[int, object] = {}
        for d in jax.devices():
            self._rank_device.setdefault(d.process_index, d)

    def _device_of(self, session: int):
        dev = self._rank_device.get(session)
        if dev is None:
            raise ValueError(f"no device for process {session}")
        return dev

    # -- buffers -------------------------------------------------------------
    def create_buffer(self, count: int, dtype, host_only: bool = False,
                      data=None):
        return make_buffer(
            self.device, count, dtype, host_only=host_only, data=data
        )

    # -- mesh plumbing -------------------------------------------------------
    def _comm_mesh(self, comm: Communicator):
        """Mesh over the communicator members' devices (global rank ->
        process -> that process's device), cached per membership."""
        sessions = tuple(r.session for r in comm.ranks)
        if sessions in self._meshes:
            return self._meshes[sessions]
        from jax.sharding import Mesh

        mesh = Mesh(
            [self._device_of(s) for s in sessions], (opdriver.AXIS,)
        )
        self._meshes[sessions] = mesh
        return mesh

    # -- call entry ----------------------------------------------------------
    def start(self, options: CallOptions) -> Request:
        req = Request(op_name=options.op.name)
        if options.stream & StreamFlags.OP0_STREAM:
            # ANY streaming-operand op must not occupy the serialized
            # executor while waiting for the local kernel push (which may
            # come from the submitting thread after run_async — head-of-
            # line blocking would wedge the rank).  It runs on its own
            # thread; the caller must keep the cross-process op ORDER
            # consistent, the contract MPI nonblocking collectives impose.
            import threading

            threading.Thread(
                target=self._execute, args=(options, req),
                name="accl-dist-op", daemon=True,
            ).start()
        else:
            # overlap backpressure: an async caller more than
            # `inflight_window` calls ahead of the executor waits here —
            # BOUNDED by the engine timeout so a wedged executor can
            # never also wedge the submitting thread (facade deadlines
            # must still fire, the design note on the executor above)
            self._queue.wait_depth_below(
                self.inflight_window, timeout=self.timeout_s
            )
            try:
                self._queue.push((options, req))
            except RuntimeError:  # engine shut down
                req.mark_executing()
                req.complete(ErrorCode.INVALID_OPERATION)
        return req

    @spmd_uniform
    def start_batch(self, items) -> None:
        """A flushed facade batch becomes ONE queue item, so the executor
        sees the identical batch boundary in every member process (the
        SPMD contract extended to batches).  Unlike the single-process
        gang — which sees EVERY rank's buffers centrally and can make one
        fusion decision for the whole slot — this tier cannot decide
        fusion SPMD-consistently: the decision would hinge on process-
        LOCAL buffer aliasing (e.g. a non-root rank legitimately passes a
        DummyBuffer where the root passes a real one), and divergent
        fused-vs-sequential choices desynchronize the processes' program
        streams and wedge the mesh.  So a dist batch executes its items
        strictly in order; the win here is the facade-side contract
        (deferred dispatch + one flush point), not program fusion."""
        try:
            self._queue.push((
                [o for o, _ in items], [r for _, r in items]
            ))
        except RuntimeError:  # engine shut down
            for _, req in items:
                req.mark_executing()
                req.complete(ErrorCode.INVALID_OPERATION)

    def device_interactions(self) -> int:
        return self.interactions.read()

    # -- contract plane (accl_tpu.contract) ----------------------------------
    # One process per rank: there is no shared in-process board to meet
    # on (contract_anchor() stays the BaseEngine default, None), so
    # this tier verifies via the facade intake screen plus the executor
    # screen in _execute — AND the rolling-digest piggyback on the
    # distributed KV plane below (the PR 7 deferral, landed): after
    # each executed collective the verifier's latest completed window
    # digest is posted under accl/vfy/<comm>/<gen>/<window>/<rank> and
    # peers' posted digests are compared via observe_claim, so
    # cross-host divergence fails fast exactly like in-process.

    def _kv_contract_exchange(self, comm) -> None:
        """Post/compare the verifier's rolling digest over the KV plane
        (executor thread; bounded — try-get, never blocking-get).
        Failures are counted, never raised: an unreachable KV degrades
        verification to the intake screen, not the collective."""
        v = self.contract_verifier
        if v is None or comm is None:
            return
        from ...contract import kv_digest_exchange

        state = self._vfy_kv_state.setdefault(comm.id, {})
        try:
            kv = self._kv()
        except Exception:
            self._vfy_kv_counters["errors"] += 1
            return
        out = kv_digest_exchange(
            kv, v, comm.id, comm.local_rank, comm.size,
            state=state, is_notfound=self._is_notfound,
        )
        for k, n in out.items():
            self._vfy_kv_counters[k] = self._vfy_kv_counters.get(k, 0) + n

    def telemetry_report(self) -> dict:
        """Dist-tier counters for the telemetry snapshot: executor queue
        backlog, remote stream-port sequence positions, cached meshes."""
        with self._stream_seq_lock:
            stream_seq = dict(self._stream_seq)
        return {
            "device_interactions": self.interactions.read(),
            "executor_queue_depth": len(self._queue),
            "inflight_window": self.inflight_window,
            "remote_stream_seq": stream_seq,
            "cached_meshes": len(self._meshes),
            "faults": None,
            # contract plane: the KV digest-piggyback exchange counters
            # (windows posted / peer claims compared / KV errors)
            "contract_kv": dict(self._vfy_kv_counters),
            # monitor plane: per-rank baselines only — the cross-
            # process skew exchange rides ROADMAP item 2's topology
            # work
            "skew_exchange": "local",
        }

    def drain_inflight(self, timeout=None) -> bool:
        """Overlap drain point: a NOP barrier through the serialized
        executor — when it completes, every call queued before it has
        executed (the SPMD program stream is empty)."""
        from ...overlap import drain_deadline_s

        req = Request(op_name="NOP")
        try:
            self._queue.push((CallOptions(op=Operation.NOP), req))
        except RuntimeError:  # engine shut down: nothing left to drain
            return True
        # the shared drain policy: queued calls get their own engine
        # deadlines first — a tighter bound here would make flush()
        # spuriously report deadlock over a healthy backlog
        return req.wait(
            timeout if timeout is not None
            else drain_deadline_s(self.timeout_s)
        )

    def _run(self) -> None:
        while not self._shut:
            item = self._queue.pop(timeout=0.5)
            if item is None:
                continue  # timeout/spurious wake; re-check shutdown
            if isinstance(item[0], list):
                self._execute_batch(*item)
            else:
                self._execute(*item)
        # drain: abandoned queued requests complete with an error instead
        # of leaving waiters blocked forever
        while True:
            item = self._queue.pop(timeout=0)
            if item is None:
                return
            reqs = item[1] if isinstance(item[1], list) else [item[1]]
            for req in reqs:
                req.mark_executing()
                req.complete(ErrorCode.INVALID_OPERATION)

    def _execute(self, options: CallOptions, req: Request) -> None:
        req.mark_executing()
        cv = self.contract_verifier
        if (
            cv is not None and cv.has_verdict and options.comm is not None
        ):
            verdict = cv.check(options.comm.id)
            if verdict is not None:
                # contract plane: the verifier proved this process's call
                # sequence diverged from its peers — calls already queued
                # behind the detection point fail fast instead of wedging
                # the serialized executor on a cross-process program that
                # can never assemble
                from ...contract import verdict_context

                req.complete(
                    ErrorCode.CONTRACT_VIOLATION, 0,
                    context=verdict_context(verdict, options.op.name),
                )
                return
        t0 = time.perf_counter_ns()
        try:
            code = self._dispatch(options, req)
        except Exception:
            traceback.print_exc()
            code = ErrorCode.INVALID_OPERATION
        req.complete(code, time.perf_counter_ns() - t0)
        if (
            cv is not None and code == ErrorCode.OK
            and options.comm is not None
            and options.op in _KV_VERIFIED_OPS
        ):
            # digest piggyback on the KV plane: post/compare the latest
            # completed verification window (cheap cursor check when
            # nothing new completed)
            self._kv_contract_exchange(options.comm)

    # -- batched execution ---------------------------------------------------
    def _execute_batch(self, options_list, reqs) -> None:
        """Execute one flushed batch strictly in order (see start_batch:
        cross-process fusion decisions cannot be made SPMD-uniformly on
        this tier, so the batch boundary is preserved but items run
        through the ordinary per-call path)."""
        for options, req in zip(options_list, reqs):
            self._execute(options, req)

    def _dispatch(self, options: CallOptions,
                  req: Optional[Request] = None) -> ErrorCode:
        op = options.op
        if op == Operation.CONFIG:
            return self._apply_config(options)
        if op == Operation.NOP:
            return ErrorCode.OK
        if op in (Operation.COPY, Operation.COMBINE):
            return self._local_op(options)
        if op == Operation.SEND:
            return self._send(options)
        if op == Operation.RECV:
            return self._recv(options)
        if op == Operation.BARRIER:
            # a REAL cross-process barrier: a tiny psum over the
            # communicator mesh — my output shard cannot materialize until
            # every member process has contributed, so blocking on it IS
            # the barrier
            mesh = self._comm_mesh(options.comm)
            shard = _dev_zeros((1, 8), np.float32, self.device)
            self.interactions.bump(2)  # the zeros shard + barrier psum
            out = opdriver.run_allreduce(
                self._assemble(options.comm, mesh, shard, 8), mesh
            )
            self._local_shard(out).block_until_ready()
            return ErrorCode.OK
        if op in IN_W:
            return self._collective(options, req)
        return ErrorCode.COLLECTIVE_NOT_IMPLEMENTED

    # -- collectives -----------------------------------------------------------
    def _assemble(self, comm: Communicator, mesh, local_shard, width: int):
        """Global (size, width) array from this process's shard; peers
        contribute theirs in their own processes."""
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.make_array_from_single_device_arrays(
            (comm.size, width),
            NamedSharding(mesh, PartitionSpec(opdriver.AXIS)),
            [local_shard],
        )

    def _local_shard(self, global_arr):
        (shard,) = [
            s for s in global_arr.addressable_shards
            if s.device == self.device
        ]
        return shard.data

    def _operand_shard(self, options: CallOptions, chunks: int, n: int,
                       nb: int):
        """This rank's (1, chunks*nb) committed wire shard from op0:
        ``chunks`` chunks of ``n`` elements, each padded to the ``nb``
        bucket (see :func:`_bucket_width`).  Small operands stage on the
        host (free numpy pad, no per-count program); big device-resident
        operands re-layout on device."""
        buf = options.op0
        npdt = dtype_to_numpy(options.arithcfg.uncompressed)
        compressed = bool(
            options.compression & CompressionFlags.ETH_COMPRESSED
        )
        wire_name = (
            np.dtype(dtype_to_numpy(options.arithcfg.compressed)).name
            if compressed and options.op != Operation.ALLREDUCE
            else None
        )
        in_w = chunks * n
        if options.stream & StreamFlags.OP0_STREAM:
            row = self._pop_stream_payload(options, in_w)
            if row is None:
                return None
            row = np.asarray(row).astype(npdt)[:in_w]
        elif buf is None or buf.is_dummy:
            self.interactions.bump()
            return _dev_zeros((1, chunks * nb), npdt, self.device)
        elif isinstance(buf, DeviceBuffer) and buf.device == self.device:
            # eager/rendezvous is decided per CHUNK — the wire message
            # unit, matching the reference's per-message eager rule (a
            # scatter of world eager-sized chunks is eager protocol).
            # A TuningPlan's per-size-bucket eager threshold overlays the
            # global register (every member process loads the same plan,
            # so the choice stays SPMD-uniform).
            if n * np.dtype(npdt).itemsize > options.eager_limit(
                self.max_eager_size
            ):
                # RENDEZVOUS domain: zero-host-copy (transfer-guard-
                # tested) — re-layout on device.  The pad program
                # retraces per exact count, but the expensive collective
                # program compiles per BUCKET only.
                self.interactions.bump()
                return _pad_chunks_program(
                    chunks, n, nb, wire_name, self.device
                )(buf.device_array())
            # EAGER domain: stage through the host, the reference's own
            # protocol for small payloads (eager sends land in rx bounce
            # buffers and are memcpy'd out — zero-copy is a rendezvous-
            # path property, ref rxbuf_offload).  Numpy pad/trim costs
            # microseconds and compiles NOTHING per count — the property
            # that lets a soak sweep arbitrary sizes at cached-dispatch
            # speed.
            self.interactions.bump()  # eager D2H read
            row = np.asarray(buf.device_view()[:in_w]).astype(npdt)
        else:
            if isinstance(buf, DeviceBuffer):
                self.interactions.bump()
            row = np.asarray(buf.device_view()[:in_w]).astype(npdt)
        # already host-side: chunk, wire-round, pad in numpy (free), one
        # committed put of the bucket-shaped row
        m = row.reshape(chunks, n)
        if wire_name is not None:
            # the shared host codec (scaled int8 lane + SR seeds
            # included), per chunk — mirrors the emulator's chunk lanes
            from ... import wire as wirecodec

            seed = wirecodec.options_rank_seed(options)
            m = np.stack([
                wirecodec.roundtrip(
                    c, options.arithcfg.compressed, seed
                ).astype(npdt)
                for c in m
            ])
        if nb != n:
            m = np.concatenate(
                [m, np.zeros((chunks, nb - n), npdt)], axis=1
            )
        self.interactions.bump()  # the committed put
        return jax.device_put(m.reshape(1, chunks * nb), self.device)

    def _collective(self, options: CallOptions,
                    req: Optional[Request] = None) -> ErrorCode:
        comm = options.comm
        op = options.op
        size = comm.size
        n = options.count
        if n <= 0:
            return ErrorCode.INVALID_COUNT
        nb = _bucket_width(n)
        in_chunks = size if IN_W[op] == "P" else 1
        out_chunks = size if OUT_W[op] == "P" else 1
        mesh = self._comm_mesh(comm)
        fn = options.reduce_function
        if op in (
            Operation.REDUCE, Operation.ALLREDUCE, Operation.REDUCE_SCATTER
        ) and not options.arithcfg.supports(fn):
            return ErrorCode.ARITH_ERROR
        shard = self._operand_shard(options, in_chunks, n, nb)
        if shard is None:
            return ErrorCode.DMA_TIMEOUT
        global_arr = self._assemble(comm, mesh, shard, in_chunks * nb)
        compressed = bool(
            options.compression & CompressionFlags.ETH_COMPRESSED
        )

        # per-size-bucket TuningPlan overlay (CallOptions.tuning) over the
        # global registers — identical in every member process when all
        # load the same plan, so the SPMD program streams stay uniform
        tuning = options.effective_tuning(self.tuning)

        self.interactions.bump()  # the collective program dispatch
        if op == Operation.ALLREDUCE:
            wire = options.arithcfg.compressed if compressed else None
            out = run_allreduce_with_tuning(
                global_arr, mesh, fn, wire, tuning
            )
        elif op in (Operation.REDUCE, Operation.BCAST, Operation.SCATTER,
                    Operation.GATHER):
            out = run_rooted_with_tuning(
                op, global_arr, mesh, options, tuning
            )
        elif op == Operation.ALLGATHER:
            out = opdriver.run_allgather(global_arr, mesh)
        elif op == Operation.REDUCE_SCATTER:
            out = opdriver.run_reduce_scatter(global_arr, mesh, fn)
        elif op == Operation.ALLTOALL:
            out = opdriver.run_alltoall(global_arr, mesh)
        else:  # pragma: no cover - guarded by IN_W
            return ErrorCode.COLLECTIVE_NOT_IMPLEMENTED

        return self._place_result(options, out, n, nb, out_chunks, req)

    def _place_result(self, options: CallOptions, out, n: int, nb: int,
                      out_chunks: int, req: Optional[Request]) -> ErrorCode:
        """Adopt this process's output shard into the result buffer.
        The rendezvous-domain unpad+cast (one FUSED device program, see
        ``_unpad_chunks_program``) is parked LAZILY on the buffer/request
        — materialized at wait()/first data access — so a fire-and-forget
        chain pays no result-side device interaction at dispatch time."""
        comm = options.comm
        op = options.op
        out_w = n * out_chunks
        # result placement: only ranks the op addresses read their shard
        writes = True
        if op == Operation.REDUCE:
            writes = comm.local_rank == options.root_dst
        elif op == Operation.GATHER:
            writes = comm.local_rank == options.root_src
        arr = self._local_shard(out)  # (1, out_chunks*nb) padded wire row
        if not writes:
            return ErrorCode.OK
        res = options.res
        if options.stream & StreamFlags.RES_STREAM:
            host = np.asarray(arr).reshape(out_chunks, nb)[:, :n]
            self._push_stream_result(options, host.reshape(-1))
            return ErrorCode.OK
        if res is None or res.is_dummy:
            return ErrorCode.OK
        if (
            isinstance(res, DeviceBuffer) and res.device == self.device
            and n * np.dtype(arr.dtype).itemsize
            > options.eager_limit(self.max_eager_size)
        ):
            # rendezvous domain: chunk-trim + decompress ON DEVICE
            # (zero-host-copy), one fused program, deferred to the reader
            npdt = dtype_to_numpy(res.dtype)

            def adopt(arr=arr, res=res, npdt=npdt, out_w=out_w,
                      out_chunks=out_chunks, n=n, nb=nb,
                      ic=self.interactions):
                trimmed = _unpad_chunks_program(
                    out_chunks, n, nb, self.device, npdt
                )(arr)
                ic.bump()
                if res.store(trimmed, out_w):
                    ic.bump()

            res.defer_store(adopt)
            if req is not None:
                req.defer_result(res.resolve_pending, handle=arr)
        elif isinstance(res, DeviceBuffer) and res.device == self.device:
            # eager domain: host trim, one committed put (see
            # _operand_shard's eager note)
            host = np.asarray(arr).reshape(out_chunks, nb)[:, :n]
            npdt = dtype_to_numpy(res.dtype)
            self.interactions.bump()  # D2H read + H2D put of a tiny row
            if res.store(
                jax.device_put(
                    host.reshape(-1).astype(npdt), self.device
                ),
                out_w,
            ):
                self.interactions.bump()
        else:
            host = np.asarray(arr).reshape(out_chunks, nb)[:, :n]
            _write_host_result(
                res, host.reshape(-1), out_w, self.interactions
            )
        return ErrorCode.OK

    # -- p2p -------------------------------------------------------------------
    def _p2p_devices(self, options: CallOptions, remote_is_dst: bool):
        comm = options.comm
        peer = options.root_dst if remote_is_dst else options.root_src
        return self._device_of(comm.ranks[peer].session)

    def _send(self, options: CallOptions) -> ErrorCode:
        if options.stream & StreamFlags.RES_STREAM:
            return self._remote_stream_put(options)
        n = options.count
        nb = _bucket_width(n)
        shard = self._operand_shard(options, 1, n, nb)
        if shard is None:
            return ErrorCode.DMA_TIMEOUT
        if options.compression & CompressionFlags.ETH_COMPRESSED:
            # compress lane on the sending chip: the wire carries the
            # narrow dtype (the receiver's zeros shard matches it)
            self.interactions.bump()
            shard = _cast_program(
                dtype_to_numpy(options.arithcfg.compressed), self.device
            )(shard)
        dst_dev = self._p2p_devices(options, remote_is_dst=True)
        if dst_dev == self.device:
            return ErrorCode.INVALID_RANK  # self-send needs no processes
        return self._p2p_run(shard, self.device, dst_dev, n, nb)

    def _recv(self, options: CallOptions) -> ErrorCode:
        n = options.count
        nb = _bucket_width(n)
        npdt = dtype_to_numpy(
            options.arithcfg.compressed
            if options.compression & CompressionFlags.ETH_COMPRESSED
            else options.arithcfg.uncompressed
        )
        src_dev = self._p2p_devices(options, remote_is_dst=False)
        if src_dev == self.device:
            return ErrorCode.INVALID_RANK
        self.interactions.bump()
        shard = _dev_zeros((1, nb), npdt, self.device)
        code = self._p2p_run(
            shard, src_dev, self.device, n, nb, recv_into=options
        )
        return code

    def _p2p_run(self, local_shard, src_dev, dst_dev, n, nb,
                 recv_into: Optional[CallOptions] = None) -> ErrorCode:
        """Both owning processes execute the same 2-device ppermute
        program over the (2, nb) BUCKETED wire row (so the hop program
        compiles per bucket, not per exact count); the receiver adopts
        its shard and trims the pad."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh, prog = _p2p_hop_program(src_dev, dst_dev)
        global_in = jax.make_array_from_single_device_arrays(
            (2, nb),
            NamedSharding(mesh, PartitionSpec("p2p")),
            [local_shard],
        )
        self.interactions.bump()  # the hop program
        out = prog(global_in)
        arr = self._local_shard(out)
        if recv_into is None:
            return ErrorCode.OK
        options = recv_into
        if options.stream & StreamFlags.RES_STREAM:
            self._push_stream_result(
                options, np.asarray(arr).reshape(-1)[:n]
            )
            return ErrorCode.OK
        res = options.res
        if res is None or res.is_dummy:
            return ErrorCode.OK
        if (
            isinstance(res, DeviceBuffer) and res.device == self.device
            and n * np.dtype(arr.dtype).itemsize
            > options.eager_limit(self.max_eager_size)
        ):
            # fused unpad + decompress: ONE result-side program
            npdt = dtype_to_numpy(res.dtype)
            self.interactions.bump()
            arr = _unpad_chunks_program(1, n, nb, self.device, npdt)(arr)
            if res.store(arr, n):
                self.interactions.bump()
        elif isinstance(res, DeviceBuffer) and res.device == self.device:
            npdt = dtype_to_numpy(res.dtype)
            host = np.asarray(arr).reshape(-1)[:n].astype(npdt)
            self.interactions.bump()
            if res.store(jax.device_put(host, self.device), n):
                self.interactions.bump()
        else:
            _write_host_result(
                res, np.asarray(arr).reshape(-1)[:n], n, self.interactions
            )
        return ErrorCode.OK

    # -- remote stream ports over the distributed KV service -------------------
    # stream_put to another process's port is ONE-SIDED in the reference
    # (data lands on the remote CCLO's ext-kernel stream with no receiver
    # call, tag<247 routing accl.cpp:181-183).  SPMD device programs can't
    # express that (the receiver would have to run a matched program), so
    # the dist tier rides the distributed runtime's key-value service —
    # the same control plane that bootstrapped the gang: the sender
    # atomically takes the destination port's next sequence number and
    # posts the wire bytes under it; the receiver's stream_pop drains in
    # sequence order.  A control-plane hop sized for kernel handoffs (the
    # reference's stream port is a FIFO of 512-bit words, not a bulk
    # path); bulk data belongs to the collectives.

    @staticmethod
    def _stream_key(dst: int, sid: int, seq: int) -> str:
        return f"accl/strm/{dst}/{sid}/{seq}"

    @staticmethod
    def _stream_ctr(dst: int, sid: int) -> str:
        return f"accl/strmctr/{dst}/{sid}"

    def _kv(self):
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:  # pragma: no cover - initialize() guarantees it
            raise RuntimeError("distributed KV service unavailable")
        return client

    def arbiter_kv(self):
        """The KV plane handed to the QoS arbiter's cross-process tenant
        ledger (same adapter the contract-digest ledger rides); raises
        when the distributed KV service is unavailable."""
        return self._kv()

    def _remote_stream_put(self, options: CallOptions) -> ErrorCode:
        n = options.count
        cfg = options.arithcfg
        if options.stream & StreamFlags.OP0_STREAM:
            payload = self._pop_stream_payload(options, n)
            if payload is None:
                return ErrorCode.DMA_TIMEOUT
            data = np.asarray(payload)
        else:
            buf = options.op0
            if buf is None or buf.is_dummy:
                return ErrorCode.INVALID_OPERATION
            data = np.asarray(buf.device_view()[:n])
        data = data.astype(dtype_to_numpy(cfg.uncompressed))
        if options.compression & CompressionFlags.ETH_COMPRESSED:
            # wire carries the narrow dtype, same as the gang tier
            data = data.astype(dtype_to_numpy(cfg.compressed))
        dst_proc = options.comm.ranks[options.root_dst].session
        if dst_proc == self.process_id:
            self.stream_push(options.stream_id, data.tobytes())
            return ErrorCode.OK
        try:
            kv = self._kv()
            seq = kv.key_value_increment(
                self._stream_ctr(dst_proc, options.stream_id), 1
            )
            kv.key_value_set_bytes(
                self._stream_key(dst_proc, options.stream_id, seq),
                data.tobytes(),
            )
        except Exception:
            traceback.print_exc()
            return ErrorCode.TRANSPORT_ERROR
        return ErrorCode.OK

    def _is_notfound(self, e: Exception) -> bool:
        """Is this try-get exception 'key absent' (normal while polling)
        rather than a real KV/transport failure?

        jaxlib renders XlaRuntimeError as a flat string, so the only
        portable discrimination is the message — but a hardcoded
        "NOT_FOUND" substring breaks silently if a jaxlib upgrade changes
        the rendering (every empty poll would then raise out of the
        polling loop).  So the signature is LEARNED once per engine: ask
        the KV for a key that cannot exist and record (type, message
        fragments around the key); a later exception matches if it is the
        same type and carries the same fragments.  The substring check
        stays as a belt-and-braces fallback for KV services that render
        differently between the probe and real keys."""
        if not self._nf_probed:
            probe_key = (
                f"accl/__nf_probe__/{self.process_id}/{id(self)}"
            )
            try:
                self._kv().key_value_try_get_bytes(probe_key)
                # this KV returns (not raises) on missing keys: nothing
                # to learn, and nothing the fallback can add
                self._nf_sig = None
                self._nf_probed = True
            except Exception as probe_e:
                msg = str(probe_e)
                parts = tuple(p for p in msg.split(probe_key) if p)
                # only trust a signature that can actually DISCRIMINATE:
                # it must name the key and carry non-trivial text around
                # it — a bare-key rendering ("'<key>'") would make every
                # same-typed exception match vacuously
                trivial = (
                    sum(len(p.strip("'\"` :.,()[]{}")) for p in parts) < 4
                )
                # ...and it must READ like not-found: a transport error
                # raised while fetching the probe key also names the key
                # ("UNAVAILABLE: failed to fetch <key>: connection
                # refused"), and learning THAT shape would silently fold
                # every later persistent KV failure into 'nothing
                # posted'.  Every known coordination-service rendering
                # of key-absent carries one of these words; a probe
                # without any is treated as a transport blip.
                looks_notfound = any(
                    mk in msg.lower()
                    for mk in (
                        "not_found", "not found", "notfound", "no such",
                        "missing", "does not exist", "absent",
                    )
                )
                if probe_key in msg and not trivial and looks_notfound:
                    self._nf_sig = (type(probe_e), parts)
                    self._nf_probed = True
                elif probe_key in msg and trivial:
                    # rendering is bare-key: cannot discriminate, and
                    # re-probing would never improve — substring
                    # fallback only
                    self._nf_sig = None
                    self._nf_probed = True
                else:
                    # the KV itself was unreachable or errored (init
                    # blip): re-arm so a later healthy poll can still
                    # learn, but cap the retries — each one is an extra
                    # KV roundtrip on the ~20 Hz polling path
                    self._nf_sig = None
                    self._nf_probe_tries += 1
                    self._nf_probed = self._nf_probe_tries >= 8
        if self._nf_sig is not None:
            typ, parts = self._nf_sig
            msg = str(e)
            if isinstance(e, typ) and all(p in msg for p in parts):
                return True
        return "NOT_FOUND" in str(e)

    def _drain_remote_stream(self, stream_id: int) -> bool:
        """Pull this port's next remotely-posted chunk (if any) into the
        local port; returns True when one landed.  The sequence counter
        is advanced under its lock so concurrent poppers of one port
        cannot both fetch (and double-deliver) the same chunk."""
        with self._stream_seq_lock:
            nxt = self._stream_seq.get(stream_id, 0) + 1
            key = self._stream_key(self.process_id, stream_id, nxt)
            try:
                data = self._kv().key_value_try_get_bytes(key)
            except Exception as e:
                if self._is_notfound(e):
                    return False  # nothing posted yet
                # a persistent KV/transport failure must not be silently
                # folded into "nothing posted" — the caller would only
                # see a generic stream TimeoutError with no cause
                traceback.print_exc()
                raise
            self._stream_seq[stream_id] = nxt
            # delete before releasing the seq lock: a crash between get
            # and delete cannot leak the KV entry to a concurrent popper
            try:
                self._kv().key_value_delete(key)
            except Exception:  # pragma: no cover - cleanup only
                pass
        self.stream_push(stream_id, data)
        return True

    def stream_pop(self, stream_id: int, timeout: Optional[float] = None) -> bytes:
        """Local port first (condition-variable fast path, woken
        immediately by a local push); while empty, poll the KV service
        non-blockingly for chunks another process stream_put into this
        port (sequence order, ~20 probes/s)."""
        budget = self.timeout_s if timeout is None else timeout
        deadline = time.monotonic() + budget
        while True:
            with self._stream_cv:
                q = self._streams.get(stream_id)
                if not q:
                    # a local push lands here instantly; the short wait
                    # only bounds the remote-probe cadence
                    self._stream_cv.wait(0.05)
                    q = self._streams.get(stream_id)
                if q:
                    return q.pop(0)
            if self._drain_remote_stream(stream_id):
                continue
            if time.monotonic() > deadline:
                raise TimeoutError(f"stream {stream_id} empty")

    # -- local ops / streams ---------------------------------------------------
    def _local_op(self, options: CallOptions) -> ErrorCode:
        n = options.count
        if options.stream & StreamFlags.OP0_STREAM:
            payload = self._pop_stream_payload(options, n)
            if payload is None:
                return ErrorCode.DMA_TIMEOUT
            acc = payload.astype(
                dtype_to_numpy(options.arithcfg.uncompressed)
            )
        else:
            acc = np.asarray(options.op0.device_view()[:n])
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
            return ErrorCode.OK
        _write_host_result(options.res, acc, n)
        return ErrorCode.OK

    # -- config ----------------------------------------------------------------
    def _apply_config(self, options: CallOptions) -> ErrorCode:
        fn = ConfigFunction(options.cfg_function)
        val = options.cfg_value
        if fn == ConfigFunction.RESET:
            pass
        elif fn == ConfigFunction.ENABLE_TRANSPORT:
            pass
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
        elif fn == ConfigFunction.SET_RETRY_LIMIT:
            # SPMD fabric: no host retransmit exists, but the knob is
            # accepted so set_retry_policy stays portable across tiers
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
            # the config itself rode the queue, so everything launched
            # under the old bound has already executed (ordered drain)
            self.inflight_window = int(val)
        elif fn in (
            ConfigFunction.SET_TENANT_CLASS,
            ConfigFunction.SET_TENANT_WEIGHT,
            ConfigFunction.SET_TENANT_WINDOW_SHARE,
            ConfigFunction.SET_TENANT_RING_SLOTS,
            ConfigFunction.SET_TENANT_RATE,
        ):
            # QoS arbiter plane: this tier serializes everything through
            # one executor — enforcement lives in the per-process facade
            # arbiter; the ONE shared validator keeps the write
            # portable across tiers
            from ...arbiter import tenant_config_field, tenant_config_valid

            if not tenant_config_valid(fn, val):
                return ErrorCode.CONFIG_ERROR
            self.tenants.setdefault(
                int(options.cfg_key), {}
            )[tenant_config_field(fn)] = val
        elif fn == ConfigFunction.SET_TUNING:
            return self._apply_tuning(options)
        else:
            return ErrorCode.CONFIG_ERROR
        return ErrorCode.OK

    def _apply_tuning(self, options: CallOptions) -> ErrorCode:
        return apply_tuning(self.tuning, options)

    def shutdown(self) -> None:
        # close FIRST so a racing start() either lands before (drained) or
        # gets the closed-queue error — never a forever-queued request
        self._queue.close()
        self._shut = True
        # executor exits at its next 0.5s poll and drains the queue; a
        # wedged in-flight program (mismatched cross-process call) cannot
        # be interrupted — the daemon thread dies with the process, the
        # reference's wedged-CCLO failure mode
        self._executor.join(timeout=2.0)


def dist_group_member(
    rank: int,
    world: int,
    coordinator: str = "127.0.0.1:47600",
    **accl_kwargs,
):
    """Initialize this process as rank ``rank`` of a ``world``-process
    distributed group and return its ACCL handle (the mpirun-per-rank
    bring-up of ref fixture.hpp:124-132 over jax.distributed).

    On CPU hosts the cross-process collectives ride gloo (the test tier);
    on TPU pods jax.distributed wires ICI/DCN natively.
    """
    import os

    # (no backend probe here: it would initialize jax before
    # jax.distributed does)
    if "tpu" not in os.environ.get("JAX_PLATFORMS", "").lower():
        # CPU backend needs an explicit cross-process collectives impl
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator, num_processes=world, process_id=rank
    )
    from ...core import ACCL

    ranks = [Rank(address=f"dist:{i}", session=i) for i in range(world)]
    return ACCL(DistEngine(), ranks, rank, **accl_kwargs)
