"""Core vocabulary of the framework: operations, flags, error codes, dtypes.

This mirrors the *semantic surface* of the reference's constant tables
(``driver/xrt/include/accl/constants.hpp`` in bo3z/ACCL: op enum at :191-210,
cfg functions :179-185, reduce functions :218-221, dataType :256-264,
stream/host/compression flags :279-326, networkProtocol :334-338, errorCode
bitmask :355-384) re-expressed for a TPU-native engine.  Values are our own;
what matters for parity is the set of names and their meaning, which the test
suite exercises.
"""

from __future__ import annotations

import enum

# NOTE: no numpy/ml_dtypes at module scope.  This module anchors the
# jax-free import closure (overlap/telemetry/faults/plans all pull it),
# so socket-fabric rank processes, the telemetry merge CLI, and the
# analysis tooling can import it without the heavy numeric stack; the
# dtype tables below build lazily on first use (acclint:
# jax-free-module enforces this stays true).

# ---------------------------------------------------------------------------
# Operations understood by the collective engine (the "CCLO" role).
# ---------------------------------------------------------------------------


class Operation(enum.IntEnum):
    """Every callable scenario of the engine (ref constants.hpp:191-210)."""

    CONFIG = 0
    COPY = 1
    COMBINE = 2
    SEND = 3
    RECV = 4
    BCAST = 5
    SCATTER = 6
    GATHER = 7
    REDUCE = 8
    ALLGATHER = 9
    ALLREDUCE = 10
    REDUCE_SCATTER = 11
    ALLTOALL = 12
    BARRIER = 13
    NOP = 14


class ConfigFunction(enum.IntEnum):
    """Sub-functions of Operation.CONFIG (ref constants.hpp:179-185).

    ``RESET`` with value 0 is the light init-time reset; value >= 1 is the
    FULL flush used by soft-reset recovery (rx pool, inbox, retransmit
    window, dedup ledger, health map are all abandoned).

    ``SET_RETRY_LIMIT`` / ``SET_RETRY_BACKOFF`` configure the emulated
    tiers' eager retransmit protocol (``ACCL.set_retry_policy``): limit 0
    disables it (fire-and-forget, the classic wire); limit N arms
    per-segment ACKs with up to N retransmits at exponentially backed-off
    intervals starting from the configured backoff seconds.

    ``SET_INFLIGHT_WINDOW`` sizes the overlap plane's per-communicator
    in-flight window (``ACCL.set_inflight_window`` / the
    ``ACCL_INFLIGHT_WINDOW`` env): up to N collectives may be launched
    before the first completes — the TPU analog of the reference's
    host-side command FIFO, which keeps queuing work while the CCLO
    executes (the "no host in the data path" contract).  Value 1 keeps
    the window but serializes (at most one launch in flight); the
    engines still complete requests from the device done-probe.

    ``SET_TENANT_*`` configure the QoS arbiter plane
    (``accl_tpu.arbiter``; ``ACCL.set_tenant_class`` /
    ``ACCL.set_tenant_quota``), keyed by communicator id in
    ``cfg_key``: CLASS is the :class:`~accl_tpu.arbiter.TenantClass`
    int, WEIGHT the DRR weight, WINDOW_SHARE the tenant's per-rank
    share of the in-flight window depth, RING_SLOTS its slot budget
    per command-ring refill window, RATE a token-bucket bytes/s cap
    (0 clears).  Every tier accepts + stores them; the device tier
    additionally wires WINDOW_SHARE into the overlap window's per-key
    depth and RING_SLOTS into the gang command ring.
    """

    RESET = 0
    ENABLE_TRANSPORT = 1
    SET_TIMEOUT = 2
    SET_MAX_EAGER_SIZE = 3
    SET_MAX_RENDEZVOUS_SIZE = 4
    SET_TUNING = 5
    SET_RETRY_LIMIT = 6
    SET_RETRY_BACKOFF = 7
    SET_INFLIGHT_WINDOW = 8
    SET_TENANT_CLASS = 9
    SET_TENANT_WEIGHT = 10
    SET_TENANT_WINDOW_SHARE = 11
    SET_TENANT_RING_SLOTS = 12
    SET_TENANT_RATE = 13


class TuningKey(enum.IntEnum):
    """Runtime tuning registers (ref ``ccl_offload_control.h:86-90``,
    written by the host at ``accl.cpp:1198-1208``).  The first five mirror
    the firmware's flat-vs-tree threshold registers; the last two select
    the device tier's allreduce lowering (the TPU analog of picking the
    firmware algorithm variant)."""

    GATHER_FLAT_TREE_MAX_FANIN = 0
    GATHER_FLAT_TREE_MAX_COUNT = 1
    BCAST_FLAT_TREE_MAX_RANKS = 2
    REDUCE_FLAT_TREE_MAX_RANKS = 3
    REDUCE_FLAT_TREE_MAX_COUNT = 4
    ALLREDUCE_ALGORITHM = 5
    RING_SEGMENTS = 6
    # rooted-collective lowering on the device tier (XLA vs the rooted
    # Pallas ring-relay kernels); values from AllreduceAlgorithm
    # (XLA / PALLAS_RING)
    BCAST_ALGORITHM = 7
    REDUCE_ALGORITHM = 8
    SCATTER_ALGORITHM = 9
    GATHER_ALGORITHM = 10
    # overlap plane: payloads whose byte size exceeds this threshold are
    # split into RING_SEGMENTS pipelined sub-launches (host staging of
    # chunk k overlaps device execution of chunk k-1).  0 disables the
    # host-level split (the conservative default; the autotuner races it)
    PIPELINE_THRESHOLD = 11
    # quantized wire plane: the per-bucket compression verdict — the
    # DataType value (from WIRE_LANE_DTYPES) a call's payload rides the
    # wire in when the caller requested no explicit compress_dtype.
    # 0 (DataType.NONE) = off, the conservative default; typically set
    # per size bucket by an autotuned TuningPlan overlay (the reference
    # hard-wires its hp_compression lane per ArithConfig — this makes
    # the lane a measured, per-bucket register like any algorithm)
    WIRE_DTYPE = 12
    # 13 and 14 are unassigned (they steered a command-ring form that is
    # gone): a SET_TUNING of either is refused like any unknown key, and
    # the registers after them keep their numbers
    # topology plane: 1 = decompose eligible collectives hierarchically
    # (intra-slice / cross-slice stages over derived subcomms) when the
    # communicator carries a multi-slice Topology; 0 = flat (the
    # conservative default; the autotuner races hierarchical-vs-flat per
    # (op x bucket x topology) like any other register)
    HIERARCHICAL = 15
    # per-link-class wire verdicts: the WIRE_DTYPE ladder split by the
    # comm's uniform link class (fp8 on slow DCN, full width on fast
    # ICI as the first ladder).  0 = defer to the generic WIRE_DTYPE
    # register; a comm whose link classes mix always uses the generic
    WIRE_DTYPE_ICI = 16
    WIRE_DTYPE_DCN = 17


class AllreduceAlgorithm(enum.IntEnum):
    """Values for TuningKey.ALLREDUCE_ALGORITHM on the device tier."""

    XLA = 0          # let XLA's collective scheduler pick
    RING = 1         # explicit segmented ppermute ring pipeline
    PALLAS_RING = 2  # the Pallas remote-DMA ring kernel
    PALLAS_RING_BIDIR = 3  # bidirectional ring: both ICI links per pair


#: TuningKey -> engine tuning-table name (the emulator/native engines index
#: their registers by these names; see TUNING_DEFAULTS below)
TUNING_KEY_NAMES = {
    TuningKey.GATHER_FLAT_TREE_MAX_FANIN: "gather_flat_tree_max_fanin",
    TuningKey.GATHER_FLAT_TREE_MAX_COUNT: "gather_flat_tree_max_count",
    TuningKey.BCAST_FLAT_TREE_MAX_RANKS: "bcast_flat_tree_max_ranks",
    TuningKey.REDUCE_FLAT_TREE_MAX_RANKS: "reduce_flat_tree_max_ranks",
    TuningKey.REDUCE_FLAT_TREE_MAX_COUNT: "reduce_flat_tree_max_count",
    TuningKey.ALLREDUCE_ALGORITHM: "allreduce_algorithm",
    TuningKey.RING_SEGMENTS: "ring_segments",
    TuningKey.BCAST_ALGORITHM: "bcast_algorithm",
    TuningKey.REDUCE_ALGORITHM: "reduce_algorithm",
    TuningKey.SCATTER_ALGORITHM: "scatter_algorithm",
    TuningKey.GATHER_ALGORITHM: "gather_algorithm",
    TuningKey.PIPELINE_THRESHOLD: "pipeline_threshold",
    TuningKey.WIRE_DTYPE: "wire_dtype",
    TuningKey.HIERARCHICAL: "hierarchical",
    TuningKey.WIRE_DTYPE_ICI: "wire_dtype_ici",
    TuningKey.WIRE_DTYPE_DCN: "wire_dtype_dcn",
}

#: lowerings valid for the ROOTED algorithm registers (no ppermute-ring /
#: bidirectional form exists for rooted ops)
ROOTED_ALGORITHMS = (AllreduceAlgorithm.XLA, AllreduceAlgorithm.PALLAS_RING)

#: tuning keys that select a collective lowering (value: AllreduceAlgorithm)
ALGORITHM_TUNING_KEYS = (
    TuningKey.ALLREDUCE_ALGORITHM,
    TuningKey.BCAST_ALGORITHM,
    TuningKey.REDUCE_ALGORITHM,
    TuningKey.SCATTER_ALGORITHM,
    TuningKey.GATHER_ALGORITHM,
)


class ReduceFunction(enum.IntEnum):
    """Reduction arithmetic selector (ref constants.hpp:218-221)."""

    SUM = 0
    MAX = 1


# ---------------------------------------------------------------------------
# Data types.  The reference supports f16/f32/f64/i32/i64 (constants.hpp:256-264)
# plus an f32->f16 compression pair; on TPU we add bfloat16 as a first-class
# citizen since it is the native MXU dtype.
# ---------------------------------------------------------------------------


class DataType(enum.IntEnum):
    NONE = 0
    FLOAT16 = 1
    FLOAT32 = 2
    FLOAT64 = 3
    INT32 = 4
    INT64 = 5
    BFLOAT16 = 6
    INT8 = 7
    # fp8 wire formats (beyond the reference's f16-only lane): the TPU
    # generation this targets computes and transports fp8 natively
    FLOAT8_E4M3 = 8
    FLOAT8_E5M2 = 9


#: Registered WIRE LANES: DataType member name -> numpy dtype name, the
#: ONE vocabulary of reduced-precision wire formats the whole stack
#: speaks (facade verdicts, the shared host codec in accl_tpu.wire, the
#: slot ``wire`` field of the command ring, and the ring's decode
#: loop).  A LITERAL dict on purpose: the acclint
#: ``cmdring-slot-layout`` cross-check parses it from the AST and fails
#: the tree when a registered lane is not handled by the decode loop —
#: growing this table without wiring a lane is a finding, not a
#: workload fallback.
WIRE_LANE_DTYPES = {
    "FLOAT16": "float16",
    "BFLOAT16": "bfloat16",
    "FLOAT8_E4M3": "float8_e4m3fn",
    "FLOAT8_E5M2": "float8_e5m2",
    "INT8": "int8",
}

#: wire lanes that ride a per-segment absmax scale sidecar (blockwise
#: quantization) instead of a plain dtype cast
SCALED_WIRE_DTYPES = ("INT8",)

#: elements per int8 scale block — one fp32 scale (absmax/127) per
#: WIRE_SEGMENT_ELEMS elements of payload.  256 keeps the scale sidecar
#: at ~1.6% of the int8 payload while bounding the absmax blast radius
#: of one outlier to 1 KiB of fp32 source data.
WIRE_SEGMENT_ELEMS = 256

#: wire lanes rounded STOCHASTICALLY by default (fp8/int8: at 2-3
#: mantissa bits / 8 quantization levels per scale block, deterministic
#: round-to-nearest biases repeated compressed reductions hard enough
#: to stall convergence — the error-feedback plane assumes unbiased
#: rounding).  f16/bf16 keep deterministic round-to-nearest-even, the
#: reference hp_compression behavior.
STOCHASTIC_WIRE_DTYPES = (
    "FLOAT8_E4M3", "FLOAT8_E5M2", "INT8",
)


#: itemsize per DataType, table-driven so ``dtype_size`` needs no numpy
#: (the jax-free planes size wire payloads with it constantly)
_DTYPE_ITEMSIZE = {
    DataType.FLOAT16: 2,
    DataType.FLOAT32: 4,
    DataType.FLOAT64: 8,
    DataType.INT32: 4,
    DataType.INT64: 8,
    DataType.BFLOAT16: 2,
    DataType.INT8: 1,
    DataType.FLOAT8_E4M3: 1,
    DataType.FLOAT8_E5M2: 1,
}

# lazily-built numpy dtype tables (populated on first dtype_to_numpy /
# numpy_to_dtype call; importing this module must stay numpy-free)
_DTYPE_TO_NUMPY = None
_NUMPY_TO_DTYPE = None


def _dtype_tables():
    global _DTYPE_TO_NUMPY, _NUMPY_TO_DTYPE
    # racy-read safe: _DTYPE_TO_NUMPY is the guard and is assigned LAST,
    # so a concurrent reader that sees it non-None also sees the inverse
    # map (worst case two threads build the identical tables once each)
    table, inv = _DTYPE_TO_NUMPY, _NUMPY_TO_DTYPE
    if table is not None:
        return table, inv
    import numpy as np

    try:  # ml_dtypes ships with jax; bfloat16/fp8 dtypes live there
        import ml_dtypes

        bf16 = np.dtype(ml_dtypes.bfloat16)
        f8_e4m3 = np.dtype(ml_dtypes.float8_e4m3fn)
        f8_e5m2 = np.dtype(ml_dtypes.float8_e5m2)
    except ImportError:  # pragma: no cover - bundled with jax
        # no ml_dtypes, no bf16/fp8 numpy dtypes: OMIT them rather than
        # alias another dtype — an alias would lie about the wire
        # itemsize (_DTYPE_ITEMSIZE says 2 for bf16) and corrupt the
        # inverted map, skewing eager/pipeline byte accounting
        bf16 = None
        f8_e4m3 = None
        f8_e5m2 = None

    table = {
        DataType.FLOAT16: np.dtype(np.float16),
        DataType.FLOAT32: np.dtype(np.float32),
        DataType.FLOAT64: np.dtype(np.float64),
        DataType.INT32: np.dtype(np.int32),
        DataType.INT64: np.dtype(np.int64),
        DataType.INT8: np.dtype(np.int8),
    }
    if bf16 is not None:
        table[DataType.BFLOAT16] = bf16
        table[DataType.FLOAT8_E4M3] = f8_e4m3
        table[DataType.FLOAT8_E5M2] = f8_e5m2
    inv = {v: k for k, v in table.items()}
    _NUMPY_TO_DTYPE = inv
    _DTYPE_TO_NUMPY = table  # guard last (see note above)
    return table, inv


def dtype_to_numpy(dt: DataType):
    return _dtype_tables()[0][dt]


def numpy_to_dtype(dt) -> DataType:
    import numpy as np

    dt = np.dtype(dt)
    try:
        return _dtype_tables()[1][dt]
    except KeyError:
        raise ValueError(f"unsupported dtype {dt}") from None


def dtype_size(dt: DataType) -> int:
    return _DTYPE_ITEMSIZE[DataType(dt)]


# ---------------------------------------------------------------------------
# Operand flags (ref constants.hpp:279-326).  streamFlags select whether an
# operand comes from / goes to a device stream rather than a buffer;
# compressionFlags select which operands are in the compressed dtype;
# hostFlags mark operands living in host memory.
# ---------------------------------------------------------------------------


class StreamFlags(enum.IntFlag):
    NO_STREAM = 0
    OP0_STREAM = 1
    RES_STREAM = 2


class CompressionFlags(enum.IntFlag):
    NO_COMPRESSION = 0
    OP0_COMPRESSED = 1
    OP1_COMPRESSED = 2
    RES_COMPRESSED = 4
    ETH_COMPRESSED = 8


class HostFlags(enum.IntFlag):
    NO_HOST = 0
    OP0_HOST = 1
    OP1_HOST = 2
    RES_HOST = 4


# ---------------------------------------------------------------------------
# Transports.  The reference speaks UDP / TCP / RDMA over 100G Ethernet
# (constants.hpp:334-338).  The TPU-native equivalents:
#   INPROC  - in-process queues between rank engines (emulator CI tier)
#   SOCKET  - TCP sockets between per-rank processes (emulator, multi-process)
#   ICI     - XLA collectives over the TPU inter-chip interconnect
#   DCN     - XLA collectives across slice boundaries (multi-slice)
# ---------------------------------------------------------------------------


class Transport(enum.IntEnum):
    INPROC = 0
    SOCKET = 1
    ICI = 2
    DCN = 3


# ---------------------------------------------------------------------------
# Error codes: a bitmask so multiple failures can be reported per call
# (ref constants.hpp:355-384 defines 27 codes; we keep the ones meaningful
# for a TPU engine and reserve the rest of the bit space).
# ---------------------------------------------------------------------------


class ErrorCode(enum.IntFlag):
    OK = 0
    DMA_MISMATCH = 1 << 0
    DMA_TRANSACTION_ERROR = 1 << 1
    DMA_TIMEOUT = 1 << 2
    RECEIVE_TIMEOUT = 1 << 3
    SEND_TIMEOUT = 1 << 4
    COLLECTIVE_NOT_IMPLEMENTED = 1 << 5
    RECEIVE_OFFCHIP_UNSUPPORTED = 1 << 6
    INVALID_COMM = 1 << 7
    INVALID_RANK = 1 << 8
    INVALID_COUNT = 1 << 9
    INVALID_TAG = 1 << 10
    INVALID_OPERATION = 1 << 11
    INVALID_DTYPE = 1 << 12
    ARITH_ERROR = 1 << 13
    COMPRESSION_ERROR = 1 << 14
    SEGMENT_TOO_LARGE = 1 << 15
    RX_BUFFER_EXHAUSTED = 1 << 16
    RENDEZVOUS_TIMEOUT = 1 << 17
    TRANSPORT_ERROR = 1 << 18
    NOT_READY = 1 << 19  # internal: call must be retried (never surfaced)
    DEADLOCK_SUSPECTED = 1 << 20
    CONFIG_ERROR = 1 << 21
    # contract plane (accl_tpu.contract): the cross-rank runtime
    # verifier proved this communicator's ranks issued diverging
    # collective sequences — fail fast instead of letting the mismatch
    # surface as a timeout N calls later
    CONTRACT_VIOLATION = 1 << 22
    # membership plane (accl_tpu.membership): the call addressed (or
    # belongs to) a rank the surviving majority agreed to evict — the
    # structured terminal code for in-flight work against a dead
    # member, carrying the agreement evidence in ACCLError.details
    RANK_EVICTED = 1 << 23

    @staticmethod
    def describe(code: "ErrorCode") -> str:
        if code == ErrorCode.OK:
            return "no error"
        names = [f.name for f in ErrorCode if f and (code & f)]
        return " | ".join(names)


class ACCLError(RuntimeError):
    """Raised by check_return_value when a call completes with errors.

    Mirrors the exception surface of the reference host driver
    (``driver/xrt/src/accl.cpp:1210-1234`` check_return_value).

    ``details`` carries structured failure context when the engine
    recorded it — typically ``op`` (operation name), ``comm``
    (communicator id), ``peer`` (the peer address/rank implicated),
    ``attempts`` (retry/failure count) and ``elapsed_s`` — so chaos-plane
    failures are diagnosable without log spelunking.
    """

    def __init__(self, code: ErrorCode, context: str = "", details=None):
        self.code = ErrorCode(code)
        self.details = dict(details) if details else {}
        msg = f"ACCL call failed [{ErrorCode.describe(self.code)}]"
        if context:
            msg += f" during {context}"
        if self.details:
            # bulky structured payloads (the telemetry plane's
            # flight-recorder tail) are summarized by length in the
            # message; the full records stay in .details for callers
            msg += " (" + ", ".join(
                f"{k}=<{len(v)} records>"
                if k == "flight_recorder" and isinstance(v, (list, tuple))
                else f"{k}={v}"
                for k, v in sorted(self.details.items())
            ) + ")"
        super().__init__(msg)


# ---------------------------------------------------------------------------
# Engine defaults (ref accl.hpp:102-104 and ccl_offload_control.c:27-28).
# ---------------------------------------------------------------------------

TAG_ANY = 0xFFFFFFFF
EAGER_THRESHOLD_DEFAULT = 32 * 1024  # bytes; above this, rendezvous
MAX_EAGER_SIZE_LIMIT = 16 * 1024 * 1024
DEFAULT_RX_BUFFER_COUNT = 16
DEFAULT_RX_BUFFER_SIZE = 4 * 1024  # bytes per eager RX buffer / segment
DEFAULT_TIMEOUT_S = 30.0
DEFAULT_RETRY_BACKOFF_S = 0.05  # first retransmit delay (doubles per try)
MAX_RETRY_LIMIT = 64  # sanity ceiling for SET_RETRY_LIMIT

# Tuning-parameter surface (ref ccl_offload_control.h:86-90, accl.cpp:1198-1208):
# thresholds steering flat-tree vs binary-tree vs ring algorithm selection.
TUNING_DEFAULTS = {
    "gather_flat_tree_max_fanin": 2,
    "gather_flat_tree_max_count": 32 * 1024,
    "bcast_flat_tree_max_ranks": 3,
    "reduce_flat_tree_max_ranks": 4,
    "reduce_flat_tree_max_count": 8 * 1024,
    # overlap plane: 0 = host-level segmented pipelining disabled (the
    # conservative default; RING_SEGMENTS > 1 + a positive threshold arm
    # it, typically via an autotuned TuningPlan)
    "pipeline_threshold": 0,
    # quantized wire plane: 0 = no automatic wire compression (explicit
    # compress_dtype= keeps working); a DataType value from
    # WIRE_LANE_DTYPES makes eligible calls ride that lane — typically
    # set per size bucket by an autotuned TuningPlan overlay
    "wire_dtype": 0,
    # topology plane: 0 = flat dispatch (hierarchical decomposition off
    # until a TuningPlan or explicit set_tuning arms it on a comm that
    # actually carries a multi-slice Topology)
    "hierarchical": 0,
    # per-link-class wire verdicts: 0 = defer to the generic wire_dtype
    "wire_dtype_ici": 0,
    "wire_dtype_dcn": 0,
}

# Overlap plane (async in-flight window) defaults: how many collectives
# per communicator may be launched before the first completes.  Small
# and conservative by default — each in-flight launch pins its output
# shards in HBM until the done-probe fires.  Override per group with
# ACCL.set_inflight_window / the ACCL_INFLIGHT_WINDOW env var.
DEFAULT_INFLIGHT_WINDOW = 4
MAX_INFLIGHT_WINDOW = 64


# ---------------------------------------------------------------------------
# Device-resident command ring (the TPU CCLO analog).  The host encodes
# warm collectives into fixed-width int32 slots of a device-memory ring;
# ONE sequencer program per refill decodes the slots ON DEVICE and
# executes the whole window, writing a (seqn, retcode) status word per
# slot that the drainer polls.  This table is the single source of truth
# for the slot layout: the host-side encoder (accl_tpu/cmdring.py) and
# the device-side decode loop (ops/cmdring.py) read THE SAME indices
# from it, and the
# acclint ``cmdring-slot-layout`` check fails any module that re-derives
# them locally.  Everything here is plain ints — the jax-free closure.
# ---------------------------------------------------------------------------


class CmdOpcode(enum.IntEnum):
    """Opcode space of a command-ring slot — the sequencer's full
    dispatch vocabulary (the reference CCLO's run-loop opcode set).
    Every non-NOP opcode is implemented by the decode loop (enforced
    by the acclint ``cmdring-slot-layout`` cross-file presence check);
    anything outside this enum falls back to host
    dispatch with a counted reason."""

    NOP = 0        # padding slot: decoded, skipped, status OK
    ALLREDUCE = 1
    BCAST = 2
    HALT = 3       # teardown marker: parks the sequencer (soft_reset)
    REDUCE_SCATTER = 4
    ALLGATHER = 5
    ALLTOALL = 6
    BARRIER = 7    # the gather IS the sync; orders the slots around it
    SEND = 8       # matched p2p pair as one slot (root=src, peer=dst)
    RECV = 9       # the complementary spelling of the same pair slot
    # Fused compute slots (the reference accl_hls/vadd_put discipline):
    # a compute epilogue runs inside the slot's relay instead of a host
    # round-trip between the kernel and the collective that consumes it.
    FUSED_MATMUL_RS = 10   # scaled GEMM-partial epilogue feeding a
                           # reduce-scatter relay (alpha in fparam)
    FUSED_APPLY = 11       # optimizer apply-on-arrival: own param chunk
                           # rides the operand tail; the reduced grad
                           # chunk is applied (p - lr*g) during the
                           # gather, not after it (lr in fparam)
    FUSED_ATTN_HOP = 12    # ring-attention hop: q rides the operand
                           # tail, kv relays one hop; the epilogue emits
                           # the scaled partial score block (scale in
                           # fparam, hop offset in peer)


class FusedCompute(enum.IntEnum):
    """Fuse hint of a call (``CallOptions.fuse``): which compute
    epilogue rides the collective's command-ring slot.  NONE is the
    plain collective; every other member maps to a fused CmdOpcode via
    ``CMDRING_FUSED_OPCODES``.  Fused calls that miss the ring cannot
    run the plain base op (the packed operand layout differs) — the
    engine decomposes them on host with a counted fallback instead."""

    NONE = 0
    MATMUL_RS = 1
    APPLY = 2
    ATTN_HOP = 3


#: Operation -> CmdOpcode: the ONE definition of the sequencer's
#: warm-path subset (engine eligibility, slot encoding and the bench's
#: per-opcode residency evidence all read this table).  COPY/COMBINE/
#: SCATTER/GATHER/REDUCE stay host-dispatch: rooted trees and local ops
#: are not floor-bound the way the warm window stream is.  Fused
#: opcodes are keyed by their fuse-hint name (they share a base
#: Operation with a plain entry, so the Operation key is taken): the
#: planner resolves them through CMDRING_FUSED_OPCODES below, and the
#: string keys keep this table the exhaustive executable-opcode
#: coverage map that acclint checks values-first.
CMDRING_OPCODES = {
    Operation.ALLREDUCE: CmdOpcode.ALLREDUCE,
    Operation.BCAST: CmdOpcode.BCAST,
    Operation.REDUCE_SCATTER: CmdOpcode.REDUCE_SCATTER,
    Operation.ALLGATHER: CmdOpcode.ALLGATHER,
    Operation.ALLTOALL: CmdOpcode.ALLTOALL,
    Operation.BARRIER: CmdOpcode.BARRIER,
    Operation.SEND: CmdOpcode.SEND,
    Operation.RECV: CmdOpcode.RECV,
    "fused_matmul_rs": CmdOpcode.FUSED_MATMUL_RS,
    "fused_apply": CmdOpcode.FUSED_APPLY,
    "fused_attn_hop": CmdOpcode.FUSED_ATTN_HOP,
}

#: FusedCompute -> CmdOpcode: the slot opcode a fuse hint encodes as.
#: Also pins each fused opcode's BASE operation semantics: MATMUL_RS
#: rides a REDUCE_SCATTER call, APPLY and ATTN_HOP ride ALLREDUCE
#: calls (their operand carries the fused tail — see ring_widths).
CMDRING_FUSED_OPCODES = {
    FusedCompute.MATMUL_RS: CmdOpcode.FUSED_MATMUL_RS,
    FusedCompute.APPLY: CmdOpcode.FUSED_APPLY,
    FusedCompute.ATTN_HOP: CmdOpcode.FUSED_ATTN_HOP,
}

#: Q16.16 fixed-point unit of the fparam slot word: fused epilogues
#: carry their scalar (alpha / lr / scale) as round(x * FPARAM_ONE)
#: in an int32 word — exact for the power-of-two scales that dominate
#: training.
CMDRING_FPARAM_ONE = 65536

#: int32 words per slot (fields below + reserved headroom)
CMDRING_SLOT_WORDS = 11

#: field name -> word index within a slot.  Indices must stay dense,
#: unique and < CMDRING_SLOT_WORDS (enforced by acclint).
CMDRING_FIELDS = {
    "seqn": 0,      # monotone completion sequence number (mod 2^31)
    "opcode": 1,    # CmdOpcode
    "count": 2,     # element count of the collective
    "dtype": 3,     # DataType of the operand
    "function": 4,  # ReduceFunction (ALLREDUCE/REDUCE_SCATTER slots)
    "root": 5,      # comm-relative root rank (BCAST; src for SEND/RECV)
    "flags": 6,     # stochastic-rounding seed of the wire lane (0 =
                    # deterministic; rank-mixed on device — wire.rank_seed)
    "nseg": 7,      # ring segmentation register snapshot
    "peer": 8,      # comm-relative destination rank (SEND/RECV slots);
                    # hop OFFSET for FUSED_ATTN_HOP (slots are encoded
                    # once globally, so the word must be SPMD-uniform —
                    # each rank derives its source as (me - peer) % size)
    "wire": 9,      # DataType of the compressed wire lane (0 = none)
    "fparam": 10,   # Q16.16 fixed-point scalar of a fused epilogue
                    # (alpha / lr / scale; 0 for plain slots)
}

#: per-slot status-word retcodes the window program writes back
CMDRING_ST_OK = 1
CMDRING_ST_BAD_OP = 2

#: ring geometry + knobs (ACCL_CMDRING=0 disables; =eager also routes
#: single warm calls through one-slot windows; ACCL_CMDRING_DEPTH sizes
#: the ring; payloads above ACCL_CMDRING_MAX_BYTES fall back to host
#: dispatch — big transfers are bandwidth-bound, not floor-bound)
CMDRING_ENV = "ACCL_CMDRING"
CMDRING_DEPTH_ENV = "ACCL_CMDRING_DEPTH"
CMDRING_MAX_BYTES_ENV = "ACCL_CMDRING_MAX_BYTES"
CMDRING_DEPTH_DEFAULT = 8
CMDRING_MAX_DEPTH = 64
CMDRING_MAX_PAYLOAD_BYTES = 4 * 1024 * 1024

# Segmented-pipelining wire tags (overlap plane): concurrent segment
# sub-collectives of ONE pipelined call execute as concurrent engine
# tasks on the fabric tiers, and eager matching there is strictly
# seqn-ordered per (comm, peer, tag) with no per-task discrimination —
# same-tag siblings can steal each other's chunks under scheduler
# stalls.  Each segment therefore rides a reserved tag derived from a
# per-comm pipelined-call counter (SPMD-uniform: the split decision is
# register-driven, so every rank assigns the same tags in the same
# order).  The base sits below the barrier-reserved space (0x7FFFFFF0)
# and far above plausible user tags.
PIPELINE_SEG_TAG_BASE = 0x7E000000


def pipeline_segment_tag(call_index: int, segment: int) -> int:
    """Reserved tag for segment ``segment`` of the ``call_index``-th
    pipelined collective on a communicator.  The call counter wraps at
    2^15 (collision would need 32768 pipelined calls concurrently in
    flight — orders beyond any window bound); segments cap at 64
    (``MAX_INFLIGHT_WINDOW``-scale, far above practical ring_segments)."""
    return PIPELINE_SEG_TAG_BASE | ((call_index & 0x7FFF) << 6) | (segment & 0x3F)
