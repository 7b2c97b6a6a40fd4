"""Command-ring host half: the slot codec and the window's shape.

Role model: the reference's hostctrl path — the host writes fixed-width
commands into a hardware FIFO and reads completions from a status FIFO
(``ccl_offload_control.c``).  This module is everything the host owns
of that protocol, importable without jax (numpy only — the CI ring
smoke exercises it standalone):

* the **slot codec**: ``encode_slot``/``decode_slot``/``encode_window``
  pack a collective into ``CMDRING_SLOT_WORDS`` int32 words through the
  ONE layout table (:data:`accl_tpu.constants.CMDRING_FIELDS` — the
  acclint ``cmdring-slot-layout`` check keeps every reader honest);
* the **window shape**: :class:`WindowShape` is the static signature of
  a refill window, the only thing that keys the window program's
  compile cache;
* the pair and fused-slot **eligibility** predicates the ring planner
  and the engine's fallbacks share.

The device half — the window program that decodes these slots — lives
in ``ops/cmdring.py``; the gang engine's session/refill management in
``backends/xla/cmdring.py``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from .constants import (
    CMDRING_FIELDS,
    CMDRING_FPARAM_ONE,
    CMDRING_SLOT_WORDS,
    CmdOpcode,
    FusedCompute,
    Operation,
    ReduceFunction,
)

__all__ = [
    "WindowShape",
    "complementary_pair",
    "decode_fparam",
    "decode_slot",
    "encode_fparam",
    "encode_slot",
    "encode_window",
    "fused_slot_eligible",
    "ring_widths",
]

_F = CMDRING_FIELDS  # the one layout table (constants.py)


# ---------------------------------------------------------------------------
# slot codec
# ---------------------------------------------------------------------------


def encode_slot(
    seqn: int,
    opcode: CmdOpcode,
    count: int,
    dtype: int = 0,
    function: ReduceFunction = ReduceFunction.SUM,
    root: int = 0,
    flags: int = 0,
    nseg: int = 1,
    peer: int = 0,
    wire: int = 0,
    fparam: int = 0,
) -> np.ndarray:
    """One command slot as ``(CMDRING_SLOT_WORDS,)`` int32 — every field
    written through :data:`CMDRING_FIELDS`, never a literal index.
    ``root`` doubles as the SEND/RECV source rank with ``peer`` the
    destination; ``wire`` is the compressed wire DataType (0 = none);
    ``fparam`` a fused epilogue's Q16.16 scalar (see ``encode_fparam``)."""
    words = np.zeros(CMDRING_SLOT_WORDS, np.int32)
    words[_F["seqn"]] = int(seqn) & 0x7FFFFFFF
    words[_F["opcode"]] = int(opcode)
    words[_F["count"]] = int(count)
    words[_F["dtype"]] = int(dtype)
    words[_F["function"]] = int(function)
    words[_F["root"]] = int(root)
    words[_F["flags"]] = int(flags)
    words[_F["nseg"]] = max(1, int(nseg))
    words[_F["peer"]] = int(peer)
    words[_F["wire"]] = int(wire)
    words[_F["fparam"]] = int(fparam)
    return words


def encode_fparam(x: float) -> int:
    """A fused epilogue's scalar as the Q16.16 fparam word: exact for
    the power-of-two alphas/lrs/scales that dominate training, and
    decoded on the device by an int-to-float divide (no float
    bit-pattern punning through the int32 slot plane)."""
    q = int(round(float(x) * CMDRING_FPARAM_ONE))
    return max(-(2 ** 31), min(2 ** 31 - 1, q))


def decode_fparam(word: int) -> float:
    """The host-side inverse of :func:`encode_fparam`."""
    return float(int(word)) / CMDRING_FPARAM_ONE


def decode_slot(words) -> dict:
    """The encoder's inverse (tests / debug dumps / ring introspection)."""
    w = np.asarray(words).reshape(-1)
    if w.size != CMDRING_SLOT_WORDS:
        raise ValueError(
            f"slot has {w.size} words, layout says {CMDRING_SLOT_WORDS}"
        )
    out = {name: int(w[idx]) for name, idx in _F.items()}
    out["opcode"] = CmdOpcode(out["opcode"])
    return out


def encode_window(slots: Sequence[np.ndarray], depth: int) -> np.ndarray:
    """Stack encoded slots into a ``(depth, CMDRING_SLOT_WORDS)`` window,
    NOP-padding the tail (padding slots decode to retcode OK and move no
    payload — the sequencer's idle slots)."""
    if len(slots) > depth:
        raise ValueError(f"{len(slots)} slots into a depth-{depth} window")
    rows = [np.asarray(s, np.int32).reshape(-1) for s in slots]
    while len(rows) < depth:
        rows.append(encode_slot(0, CmdOpcode.NOP, 0))
    return np.stack(rows).astype(np.int32)


def complementary_pair(calls) -> Optional[Tuple[int, int]]:
    """(src, dst) when a world-2 batch position holds a matched
    SEND/RECV pair — THE one pair definition the ring planner's slot
    eligibility and the engine's direct-delivery fallback both use (a
    divergence between them would let one path deliver what the other
    rejects).  A matched pair agrees on roles, count, tag and operand
    dtype, and carries no wire compression (compressed p2p keeps the
    channel's cast lanes).  None otherwise."""
    if len(calls) != 2:
        return None
    ops = [c.op for c in calls]
    if sorted(ops) != sorted((Operation.SEND, Operation.RECV)):
        return None
    src = ops.index(Operation.SEND)
    dst = ops.index(Operation.RECV)
    snd, rcv = calls[src], calls[dst]
    from .constants import CompressionFlags

    if (
        snd.root_dst != dst or rcv.root_src != src
        or snd.count != rcv.count or snd.tag != rcv.tag
        or snd.arithcfg.uncompressed != rcv.arithcfg.uncompressed
        or (snd.compression | rcv.compression)
        & CompressionFlags.ETH_COMPRESSED
    ):
        return None
    return src, dst


def ring_widths(
    op: Operation, count: int, size: int, fuse: int = 0
) -> Tuple[int, int]:
    """(operand width, result width) in elements for one ring slot —
    the sequencer analog of the engine's IN_W/OUT_W tables.  BARRIER
    rides a one-element token; SEND/RECV move ``count`` point-to-point.

    Fused slots pack their compute operands into the SAME operand row
    (one pull per slot — the fused epilogue never re-enters the host):

    * ``MATMUL_RS``: GEMM partials in reduce-scatter layout — the plain
      RS geometry, ``(n*size, n)``; the epilogue only scales.
    * ``APPLY``: gradients in allreduce layout plus this rank's param
      chunk riding the tail — ``(n*(size+1), n)``; the result is the
      applied param chunk, not the reduced gradient.
    * ``ATTN_HOP``: the kv block to relay plus the resident q block —
      ``(2n, n)``; the result is the scaled partial score block.

    The width RELATIONS fully determine the fused geometry: operand
    width ``out*(size+1)`` only arises for APPLY, ``2*out`` (size>2)
    only for ATTN_HOP — the window program classifies slots by these
    relations with the opcode word selecting within a class."""
    n = int(count)
    fuse = FusedCompute(int(fuse))
    if fuse == FusedCompute.APPLY:
        return n * (size + 1), n
    if fuse == FusedCompute.ATTN_HOP:
        return 2 * n, n
    if op in (Operation.REDUCE_SCATTER, Operation.ALLTOALL):
        in_w = n * size
    elif op == Operation.BARRIER:
        in_w = 1
    else:
        in_w = n
    if op in (Operation.ALLGATHER, Operation.ALLTOALL):
        out_w = n * size
    elif op == Operation.BARRIER:
        out_w = 1
    else:
        out_w = n
    return in_w, out_w


#: FusedCompute -> the base Operation its call rides (the engine plans
#: the collective half with this op; the fuse hint selects the epilogue)
FUSED_BASE_OPS = {
    FusedCompute.MATMUL_RS: Operation.REDUCE_SCATTER,
    FusedCompute.APPLY: Operation.ALLREDUCE,
    FusedCompute.ATTN_HOP: Operation.ALLREDUCE,
}


def fused_slot_eligible(
    fuse: int,
    op: Operation,
    size: int,
    count: int,
    operand_count: int,
    npdt,
    compressed: bool = False,
) -> Optional[str]:
    """Why a fused call CANNOT ride a ring slot (None = eligible) — the
    ONE fused-eligibility predicate, numpy-only so the CI ring smoke
    gates it without jax and the engine planner counts the same reasons.

    Fused epilogues are float arithmetic fused into the relay: they need
    a real ring (size >= 2), a float operand, the fuse's base operation,
    an operand row packed to exactly the fused width, and no wire
    compression (the epilogue would otherwise run on lossy-cast chunks
    the plain path never produces)."""
    try:
        fuse = FusedCompute(int(fuse))
    except ValueError:
        return "unknown_fuse"
    if fuse == FusedCompute.NONE:
        return None
    base = FUSED_BASE_OPS.get(fuse)
    if base is None or op != base:
        return "fused_base_op"
    if int(size) < 2:
        return "fused_world_too_small"
    if np.dtype(npdt).kind != "f":
        return "fused_dtype"
    in_w, _ = ring_widths(base, count, size, fuse=fuse)
    if int(operand_count) != in_w:
        return "fused_operand_width"
    if compressed:
        return "fused_compressed"
    return None


class WindowShape:
    """Static shape signature of a refill window — everything that keys
    the sequencer program's compile cache.  Slot CONTENT (opcode, reduce
    function, root, peer, seqn) stays data; only the payload geometry
    and the per-slot wire-cast dtypes are shape."""

    __slots__ = ("depth", "in_ws", "out_ws", "wires", "npdt")

    def __init__(self, depth: int, in_ws, out_ws, wires, npdt):
        self.depth = int(depth)
        self.in_ws = tuple(int(w) for w in in_ws)
        self.out_ws = tuple(int(w) for w in out_ws)
        self.wires = tuple(wires)  # numpy dtype name or None, per slot
        self.npdt = np.dtype(npdt)

    def key(self) -> tuple:
        return (self.depth, self.in_ws, self.out_ws, self.wires,
                self.npdt.name)

    def __eq__(self, other) -> bool:
        return isinstance(other, WindowShape) and self.key() == other.key()

    def __hash__(self) -> int:
        return hash(self.key())
