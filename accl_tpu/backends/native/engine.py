"""ctypes binding for the native C++ collective engine (libaccl_engine.so).

Role split (mirrors the reference): Python is the host driver facade; the
C++ library owns scheduling, protocol state machines (eager segmentation with
per-peer sequence numbers, rendezvous address handshake), RX buffer matching,
reductions/casts, and both transports.  See ``native/src/engine/`` for the
firmware-role citations.
"""

from __future__ import annotations

import ctypes
import itertools
import threading
from typing import List, Optional, Sequence

from ...buffer import BaseBuffer
from ...communicator import Communicator, Rank
from ...constants import (
    DEFAULT_RX_BUFFER_COUNT,
    DEFAULT_RX_BUFFER_SIZE,
    ErrorCode,
)
from ...request import Request
from ..base import BaseEngine, CallOptions
from ... import native as _native_dataplane

_group_ids = itertools.count(0)

_LIB = None
_LOAD_ATTEMPTED = False


class _CallArgs(ctypes.Structure):
    """Field-for-field mirror of accl::CallArgs (native/src/engine/accl_engine.h)."""

    _fields_ = [
        ("op", ctypes.c_int32),
        ("comm_id", ctypes.c_uint32),
        ("count", ctypes.c_int64),
        ("root_src", ctypes.c_int32),
        ("root_dst", ctypes.c_int32),
        ("tag", ctypes.c_uint32),
        ("rfunc", ctypes.c_int32),
        ("acc_dtype", ctypes.c_int32),
        ("cmp_dtype", ctypes.c_int32),
        ("supports_rfunc", ctypes.c_int32),
        ("compression", ctypes.c_uint32),
        ("stream_flags", ctypes.c_uint32),
        ("stream_id", ctypes.c_int32),
        ("cfg_function", ctypes.c_int32),
        ("cfg_value", ctypes.c_double),
        ("op0", ctypes.c_void_p),
        ("op1", ctypes.c_void_p),
        ("res", ctypes.c_void_p),
        ("op0_dtype", ctypes.c_int32),
        ("op1_dtype", ctypes.c_int32),
        ("res_dtype", ctypes.c_int32),
        ("cfg_key", ctypes.c_int32),
    ]


def _bind(lib) -> None:
    c = ctypes
    lib.accl_ng_engine_new.restype = c.c_int
    lib.accl_ng_engine_new.argtypes = [c.c_char_p, c.c_int, c.c_int, c.c_int]
    lib.accl_ng_engine_shutdown.restype = None
    lib.accl_ng_engine_shutdown.argtypes = [c.c_int]
    lib.accl_ng_add_comm.restype = c.c_int
    lib.accl_ng_add_comm.argtypes = [
        c.c_int, c.c_uint32, c.c_int, c.c_int,
        c.POINTER(c.c_char_p), c.POINTER(c.c_uint32),
    ]
    lib.accl_ng_start.restype = c.c_uint64
    lib.accl_ng_start.argtypes = [c.c_int, c.POINTER(_CallArgs)]
    lib.accl_ng_wait.restype = c.c_int
    lib.accl_ng_wait.argtypes = [c.c_int, c.c_uint64, c.c_double]
    lib.accl_ng_test.restype = c.c_int
    lib.accl_ng_test.argtypes = [c.c_int, c.c_uint64]
    lib.accl_ng_retcode.restype = c.c_uint32
    lib.accl_ng_retcode.argtypes = [c.c_int, c.c_uint64]
    lib.accl_ng_duration_ns.restype = c.c_int64
    lib.accl_ng_duration_ns.argtypes = [c.c_int, c.c_uint64]
    lib.accl_ng_free_request.restype = None
    lib.accl_ng_free_request.argtypes = [c.c_int, c.c_uint64]
    lib.accl_ng_stream_push.restype = None
    lib.accl_ng_stream_push.argtypes = [c.c_int, c.c_int, c.c_void_p, c.c_int64]
    lib.accl_ng_stream_pop.restype = c.c_int64
    lib.accl_ng_stream_pop.argtypes = [
        c.c_int, c.c_int, c.c_void_p, c.c_int64, c.c_double,
    ]
    lib.accl_ng_rx_occupancy.restype = c.c_int
    lib.accl_ng_rx_occupancy.argtypes = [c.c_int]
    lib.accl_ng_rx_capacity.restype = c.c_int
    lib.accl_ng_rx_capacity.argtypes = [c.c_int]


def _load():
    global _LIB, _LOAD_ATTEMPTED
    if _LOAD_ATTEMPTED:
        return _LIB
    _LOAD_ATTEMPTED = True
    if not _native_dataplane.build():
        return None
    lib = ctypes.CDLL(str(_native_dataplane._ENGINE_SO_PATH))
    _bind(lib)
    _LIB = lib
    return _LIB


def engine_library_available() -> bool:
    return _load() is not None


class NativeRequest(Request):
    """Request completed inside the C++ engine; wait/test bridge the C ABI."""

    def __init__(self, engine: "NativeEngine", native_id: int, op_name: str,
                 keepalive):
        super().__init__(op_name=op_name)
        self._engine = engine
        self._native_id = native_id
        self._keepalive = keepalive  # numpy views the engine writes into
        self._fin_lock = threading.Lock()

    def _finalize(self) -> None:
        with self._fin_lock:
            if self._done.is_set():
                return
            lib, h = self._engine._lib, self._engine._handle
            ret = ErrorCode(lib.accl_ng_retcode(h, self._native_id))
            dur = lib.accl_ng_duration_ns(h, self._native_id)
            lib.accl_ng_free_request(h, self._native_id)
            self._keepalive = None
            self.complete(ret, dur)

    def test(self) -> bool:
        if self._done.is_set():
            return True
        if self._engine._lib.accl_ng_test(
            self._engine._handle, self._native_id
        ):
            self._finalize()
            return True
        return False

    def wait(self, timeout: Optional[float] = None) -> bool:
        if self._done.is_set():
            return True
        t = -1.0 if timeout is None else float(timeout)
        if self._engine._lib.accl_ng_wait(
            self._engine._handle, self._native_id, t
        ):
            self._finalize()
            return True
        return False


class NativeEngine(BaseEngine):
    """One rank's handle onto the C++ engine."""

    TRANSPORT_INPROC = 0
    TRANSPORT_SOCKET = 1

    def __init__(
        self,
        address: str,
        transport: int = TRANSPORT_INPROC,
        rx_buffer_count: int = DEFAULT_RX_BUFFER_COUNT,
        rx_buffer_size: int = DEFAULT_RX_BUFFER_SIZE,
    ):
        lib = _load()
        if lib is None:
            raise RuntimeError(
                "libaccl_engine.so unavailable (native toolchain missing?)"
            )
        self._lib = lib
        self.address = address
        self._handle = lib.accl_ng_engine_new(
            address.encode(), transport, rx_buffer_count, rx_buffer_size
        )
        if self._handle < 0:
            raise RuntimeError(f"native engine failed to open {address!r}")
        self._registered_comms: set = set()
        self._shut = False
        from ...overlap import default_window_depth

        self.inflight_window = default_window_depth()
        # QoS arbiter plane: host-side mirror of SET_TENANT_* writes
        # (the C ABI predates the tenant vocabulary)
        self.tenants: dict = {}
        # host-side mirror of the C engine's register table, seeded from
        # the shared defaults: every SET_TUNING write that rides the ABI
        # is mirrored here (write-through), registers the ABI predates
        # (pipeline_threshold) live here outright — the facade's
        # _engine_tuning and register-visibility tests read this dict
        from ...constants import TUNING_DEFAULTS

        self.tuning: dict = dict(TUNING_DEFAULTS)

    # -- plumbing ------------------------------------------------------------
    def _ensure_comm(self, comm: Communicator) -> None:
        if comm.id in self._registered_comms:
            return
        n = comm.size
        addrs = (ctypes.c_char_p * n)(
            *[r.address.encode() for r in comm.ranks]
        )
        segs = (ctypes.c_uint32 * n)(
            *[r.max_segment_size for r in comm.ranks]
        )
        rc = self._lib.accl_ng_add_comm(
            self._handle, comm.id, comm.local_rank, n, addrs, segs
        )
        if rc != 0:
            raise RuntimeError("add_comm failed")
        self._registered_comms.add(comm.id)

    @staticmethod
    def _operand(buf: Optional[BaseBuffer]):
        """(pointer, dtype code, keepalive view) for one operand."""
        if buf is None or buf.is_dummy:
            return 0, 0, None
        view = buf.device_view()
        return view.ctypes.data, int(buf.dtype), view

    def start(self, options: CallOptions) -> Request:
        from ...constants import (
            ConfigFunction,
            MAX_INFLIGHT_WINDOW,
            Operation,
            TuningKey,
        )

        if (
            options.op == Operation.CONFIG
            and int(options.cfg_function)
            == int(ConfigFunction.SET_INFLIGHT_WINDOW)
        ):
            # overlap-plane parity knob, handled host-side: the C engine
            # predates the window vocabulary and its scheduler already
            # completes requests asynchronously (no launch-path blocking
            # to decouple) — accept + store so set_inflight_window is
            # portable across all four tiers
            req = Request(op_name=options.op.name)
            req.mark_executing()
            if 1 <= options.cfg_value <= MAX_INFLIGHT_WINDOW:
                self.inflight_window = int(options.cfg_value)
                req.complete(ErrorCode.OK)
            else:
                req.complete(ErrorCode.CONFIG_ERROR)
            return req
        if options.op == Operation.CONFIG and int(
            options.cfg_function
        ) in (
            int(ConfigFunction.SET_TENANT_CLASS),
            int(ConfigFunction.SET_TENANT_WEIGHT),
            int(ConfigFunction.SET_TENANT_WINDOW_SHARE),
            int(ConfigFunction.SET_TENANT_RING_SLOTS),
            int(ConfigFunction.SET_TENANT_RATE),
        ):
            # QoS arbiter plane, handled host-side: the C ABI predates
            # the tenant vocabulary and enforcement lives in the facade
            # arbiter anyway — accept + mirror, through the ONE shared
            # validator, so set_tenant_class/quota stay portable across
            # all four tiers
            from ...arbiter import tenant_config_field, tenant_config_valid

            fn = ConfigFunction(int(options.cfg_function))
            val = options.cfg_value
            req = Request(op_name=options.op.name)
            req.mark_executing()
            if tenant_config_valid(fn, val):
                self.tenants.setdefault(
                    int(options.cfg_key), {}
                )[tenant_config_field(fn)] = val
                req.complete(ErrorCode.OK)
            else:
                req.complete(ErrorCode.CONFIG_ERROR)
            return req
        if (
            options.op == Operation.CONFIG
            and int(options.cfg_function) == int(ConfigFunction.SET_TUNING)
            and int(options.cfg_key)
            == int(TuningKey.PIPELINE_THRESHOLD)
        ):
            # overlap-plane register, handled host-side: the C ABI's
            # register table predates it, and the facade-level segmented
            # split reads it from this host dict anyway
            req = Request(op_name=options.op.name)
            req.mark_executing()
            if options.cfg_value >= 0:
                self.tuning["pipeline_threshold"] = int(options.cfg_value)
                req.complete(ErrorCode.OK)
            else:
                req.complete(ErrorCode.CONFIG_ERROR)
            return req
        if (
            options.op == Operation.CONFIG
            and int(options.cfg_function) == int(ConfigFunction.SET_TUNING)
            and int(options.cfg_key) in (
                int(TuningKey.WIRE_DTYPE),
                int(TuningKey.WIRE_DTYPE_ICI),
                int(TuningKey.WIRE_DTYPE_DCN),
            )
        ):
            # quantized-wire verdict registers (generic + per link
            # class), handled host-side like pipeline_threshold: the
            # ABI predates them and the facade's _plan_for reads this
            # host mirror anyway — same validation as every other tier
            # (0 or a registered wire lane)
            from ... import wire as wirecodec
            from ...constants import TUNING_KEY_NAMES

            req = Request(op_name=options.op.name)
            req.mark_executing()
            val = int(options.cfg_value)
            if val == 0 or wirecodec.is_wire_dtype(val):
                name = TUNING_KEY_NAMES[TuningKey(int(options.cfg_key))]
                self.tuning[name] = val
                req.complete(ErrorCode.OK)
            else:
                req.complete(ErrorCode.CONFIG_ERROR)
            return req
        if (
            options.op == Operation.CONFIG
            and int(options.cfg_function) == int(ConfigFunction.SET_TUNING)
            and int(options.cfg_key) == int(TuningKey.HIERARCHICAL)
        ):
            # topology-plane register, handled host-side: the facade's
            # hierarchical dispatch reads the host mirror; the C
            # dataplane only ever sees the decomposed sub-collectives
            req = Request(op_name=options.op.name)
            req.mark_executing()
            if int(options.cfg_value) in (0, 1):
                self.tuning["hierarchical"] = int(options.cfg_value)
                req.complete(ErrorCode.OK)
            else:
                req.complete(ErrorCode.CONFIG_ERROR)
            return req
        mv = self.membership
        if (
            mv is not None and mv.self_evicted
            and options.comm is not None
            and options.op not in (
                Operation.CONFIG, Operation.NOP, Operation.COPY,
                Operation.COMBINE,
            )
        ):
            # membership plane: a rank voted out of the group fails its
            # comm ops fast at intake with the agreement evidence — the
            # C dataplane cannot consult the Python view mid-call, so
            # the screen sits here, like the facade's intake screen
            req = Request(op_name=options.op.name)
            req.mark_executing()
            req.complete(ErrorCode.RANK_EVICTED, 0, context={
                "op": options.op.name,
                "comm": options.comm.id,
                "membership": mv.evidence(),
                "elapsed_s": 0.0,
            })
            return req
        # quantized wire plane, host-side mirror: the C ABI's cast
        # lanes (hp_compression role) cover the f16/bf16/fp8 wire
        # dtypes; the SCALED int8 lane (per-segment absmax + SR) is
        # mirrored here through the shared host codec — the operand is
        # pre-rounded through the wire exactly as the other tiers
        # round it, and the C engine runs the call uncompressed, so
        # every tier computes the same quantized sum.  (Wire BYTES on
        # this tier stay full-width — the honest-bytes lane needs ABI
        # growth; the numeric protocol is what must agree.)
        options = self._mirror_scaled_wire(options)
        args = _CallArgs()
        args.op = int(options.op)
        args.cfg_function = int(options.cfg_function)
        args.cfg_value = float(options.cfg_value)
        args.cfg_key = int(options.cfg_key)
        args.count = int(options.count)
        args.root_src = int(options.root_src)
        args.root_dst = int(options.root_dst)
        args.tag = int(options.tag) & 0xFFFFFFFF
        args.rfunc = int(options.reduce_function)
        args.compression = int(options.compression)
        args.stream_flags = int(options.stream)
        args.stream_id = int(options.stream_id)
        if options.comm is not None:
            self._ensure_comm(options.comm)
            args.comm_id = options.comm.id
        cfg = options.arithcfg
        if cfg is not None:
            args.acc_dtype = int(cfg.uncompressed)
            args.cmp_dtype = int(cfg.compressed)
            args.supports_rfunc = int(cfg.supports(options.reduce_function))
        else:
            args.acc_dtype = args.cmp_dtype = 2  # FLOAT32
            args.supports_rfunc = 1
        keep = []
        args.op0, args.op0_dtype, k0 = self._operand(options.op0)
        args.op1, args.op1_dtype, k1 = self._operand(options.op1)
        args.res, args.res_dtype, k2 = self._operand(options.res)
        keep = [k for k in (k0, k1, k2) if k is not None]
        native_id = self._lib.accl_ng_start(self._handle, ctypes.byref(args))
        req = NativeRequest(self, native_id, options.op.name, keep)
        req.mark_executing()
        if (
            options.op == Operation.CONFIG
            and int(options.cfg_function) == int(ConfigFunction.SET_TUNING)
        ):
            # write-through mirror: keep the host-readable register dict
            # in step with the C engine — but only once the engine
            # ACCEPTED the write (a rejected value must never leak into
            # the mirror the facade's pipelining verdict reads).  The
            # algorithm registers are skipped: every other tier's table
            # holds their NAME strings, and mirroring the wire's int
            # would flip-flop the dict's value type across tiers.
            from ...constants import ALGORITHM_TUNING_KEYS, TUNING_KEY_NAMES

            try:
                tkey = TuningKey(int(options.cfg_key))
                name = (
                    None if tkey in ALGORITHM_TUNING_KEYS
                    else TUNING_KEY_NAMES.get(tkey)
                )
            except ValueError:
                name = None
            if name is not None:
                val = int(options.cfg_value)

                def _mirror(name=name, val=val, req=req):
                    if req.get_retcode() == ErrorCode.OK:
                        self.tuning[name] = val

                req.add_done_callback(_mirror)
        return req

    def _mirror_scaled_wire(self, options: CallOptions) -> CallOptions:
        """Scaled-wire (int8) calls re-shaped for the C ABI: round the
        operand through the shared host codec (blockwise absmax + this
        call's rank-mixed SR seed — the identical arithmetic every
        other tier runs) into a staging buffer, then dispatch the call
        UNCOMPRESSED.  Cast-lane and uncompressed calls pass through
        untouched."""
        from ...constants import CompressionFlags, Operation
        from ... import wire as wirecodec

        cfg = options.arithcfg
        if (
            cfg is None
            or not options.compression & CompressionFlags.ETH_COMPRESSED
            or not wirecodec.is_scaled(cfg.compressed)
            or options.op == Operation.CONFIG
            or options.op0 is None
            or options.op0.is_dummy
        ):
            return options
        import dataclasses

        import numpy as np

        from ...arithconfig import ArithConfig
        from ...buffer import EmuBuffer

        seed = wirecodec.options_rank_seed(options)
        # operand WIDTH follows the op: the P-wide ops' op0 spans
        # size*count elements (staging only `count` would hand the C
        # engine a truncated buffer it reads past)
        in_w = options.count
        if options.comm is not None and options.op in (
            Operation.REDUCE_SCATTER, Operation.ALLTOALL,
            Operation.SCATTER,
        ):
            in_w *= options.comm.size
        x = np.asarray(options.op0.device_view()[:in_w])
        rounded = wirecodec.roundtrip(
            x, cfg.compressed, seed
        ).astype(x.dtype)
        staged = EmuBuffer.from_array(np.ascontiguousarray(rounded))
        staged.sync_to_device()
        return dataclasses.replace(
            options,
            op0=staged,
            arithcfg=ArithConfig(
                cfg.uncompressed, cfg.uncompressed, cfg.reduce_functions
            ),
            compression=options.compression
            & ~CompressionFlags.ETH_COMPRESSED,
        )

    def shutdown(self) -> None:
        if not self._shut:
            self._shut = True
            self._lib.accl_ng_engine_shutdown(self._handle)

    # -- device stream ports -------------------------------------------------
    def stream_push(self, stream_id: int, data: bytes) -> None:
        self._lib.accl_ng_stream_push(
            self._handle, stream_id, data, len(data)
        )

    def stream_pop(self, stream_id: int, timeout: Optional[float] = None) -> bytes:
        t = 30.0 if timeout is None else float(timeout)
        cap = 1 << 16
        while True:
            out = ctypes.create_string_buffer(cap)
            n = self._lib.accl_ng_stream_pop(
                self._handle, stream_id, out, cap, t
            )
            if n < 0:
                raise TimeoutError(f"stream {stream_id} pop timed out")
            if n <= cap:
                return out.raw[:n]
            cap = int(n)  # chunk bigger than buffer: retry with exact size

    # -- contract plane (accl_tpu.contract) ----------------------------------
    def contract_anchor(self):
        """None (no board): in-proc native groups share one
        process-wide CDLL, but anchoring the digest board there would
        let two *sequential* groups cross-compare stale windows under
        colliding comm ids.  The native tier verifies via the facade
        intake screen; its C dataplane cannot consult a Python verifier
        mid-call (set_contract_verifier keeps the BaseEngine store-only
        behavior)."""
        return None

    # -- debug (ref ACCL::dump_eager_rx_buffers) -----------------------------
    def dump_rx_buffers(self) -> str:
        used = self._lib.accl_ng_rx_occupancy(self._handle)
        total = self._lib.accl_ng_rx_capacity(self._handle)
        return "\n".join(
            f"rxbuf[{i}] {'FILLED' if i < used else 'IDLE'}"
            for i in range(total)
        )

    def telemetry_report(self) -> dict:
        """Native-tier counters for the telemetry snapshot: the C++
        engine's rx-pool occupancy over the C ABI (per-call facts ride
        the shared Request flight-recorder hook like every tier)."""
        return {
            "device_interactions": None,
            "rx_pool": {
                "used": int(self._lib.accl_ng_rx_occupancy(self._handle)),
                "total": int(self._lib.accl_ng_rx_capacity(self._handle)),
            },
            "faults": None,
            # monitor plane: per-rank baselines only (no board — the
            # contract_anchor rationale above applies to the skew judge
            # identically: sequential groups would cross-compare)
            "skew_exchange": "local",
        }


# ---------------------------------------------------------------------------
# group constructors (mirror core.emulated_group / socket_group_member)
# ---------------------------------------------------------------------------


def native_group(
    n: int,
    rx_buffer_count: int = DEFAULT_RX_BUFFER_COUNT,
    rx_buffer_size: int = DEFAULT_RX_BUFFER_SIZE,
    **accl_kwargs,
) -> List:
    """N ranks in one process over the C++ in-proc transport."""
    from ...core import ACCL

    # unique address namespace per group so groups never collide in the
    # process-wide native registry
    gid = next(_group_ids)
    ranks = [
        Rank(
            address=f"native:{gid}:{i}",
            session=i,
            max_segment_size=rx_buffer_size,
        )
        for i in range(n)
    ]
    engines = [
        NativeEngine(
            f"native:{gid}:{i}",
            NativeEngine.TRANSPORT_INPROC,
            rx_buffer_count=rx_buffer_count,
            rx_buffer_size=rx_buffer_size,
        )
        for i in range(n)
    ]
    return [ACCL(engines[i], ranks, i, **accl_kwargs) for i in range(n)]


def native_socket_member(
    rank: int,
    addresses: Sequence[str],
    rx_buffer_count: int = DEFAULT_RX_BUFFER_COUNT,
    rx_buffer_size: int = DEFAULT_RX_BUFFER_SIZE,
    **accl_kwargs,
):
    """This process's member of a multi-process native group over TCP (one
    process per rank, the reference's per-rank emulator-process layout)."""
    from ...core import ACCL

    ranks = [
        Rank(address=a, session=i, max_segment_size=rx_buffer_size)
        for i, a in enumerate(addresses)
    ]
    engine = NativeEngine(
        addresses[rank],
        NativeEngine.TRANSPORT_SOCKET,
        rx_buffer_count=rx_buffer_count,
        rx_buffer_size=rx_buffer_size,
    )
    return ACCL(engine, ranks, rank, **accl_kwargs)
