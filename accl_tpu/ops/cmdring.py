"""The command ring's device half: a batch becomes one program.

Role model: the reference's CCLO firmware run loop — the host enqueues
fixed-width commands into a hardware FIFO and the offload kernel decodes
and executes whole collectives with no host in the data path
(``ccl_offload_control.c`` run loop + ``dma_mover``).  The TPU analog is
the **window program**: one jitted ``shard_map`` program takes a refill
window's slot words and its per-slot operand globals, executes every
slot (``lax.all_gather`` wire move + :func:`slot_epilogue`) and returns
the per-slot ``(seqn, retcode)`` status words next to the results.  One
window, one program launch, on every platform.

A window's control words cost no round trip of their own
(:func:`run_windows`).  Down: slot words the chips already hold are not
put again — :class:`KeptSlots` keeps the device array of a window's
words by the mesh and the words' CONTENT, every field but ``seqn``,
which rides window-relative (0 … n-1; the device only echoes it into
the status, the host adds the window's base back).  Back: the copy to
the host of the ONE status shard the drainer reads is asked for as the
program's results arrive, so :func:`status_view` finds the literal
there or on its way.  The program is the same either way, and so are the
words it runs on.

Split of responsibilities:

* host half (slot codec + window shape): ``accl_tpu/cmdring.py``
  (numpy-only — re-exported here for the established import surface);
* device half (this module): the decode loop and the window program;
* engine half (sessions, refills, fallbacks): ``backends/xla/cmdring.py``.

The decode loop reads the :data:`accl_tpu.constants.CMDRING_FIELDS` slot
words and runs the data-driven per-slot epilogue, which covers the FULL
opcode space: ALLREDUCE, BCAST, REDUCE_SCATTER, ALLGATHER, ALLTOALL,
BARRIER, SEND/RECV pair slots and the three fused-compute slots.
Opcode, reduce function, root and peer are decoded ON DEVICE from the
slot words — a warm window never recompiles on op/function/root churn;
only the window's payload *shape signature* (per-slot widths + wire-cast
dtypes) keys the program cache, because output geometry is a
compile-time fact.  Per-slot wire casts run as rounding lanes inside the
decode loop (``ops.wire`` — the ONE lane helper, acclint cross-checks
this module for it).

Oversized payloads never get here — the engine falls back to host
dispatch above ``CMDRING_MAX_PAYLOAD_BYTES``.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from functools import lru_cache
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# the host half re-exported: tests/tools import the codec from here
from ..cmdring import (  # noqa: F401  (re-export surface)
    WindowShape,
    decode_fparam,
    decode_slot,
    encode_fparam,
    encode_slot,
    encode_window,
    fused_slot_eligible,
    ring_widths,
)
from ..constants import (
    CMDRING_FIELDS,
    CMDRING_FPARAM_ONE,
    CMDRING_ST_BAD_OP,
    CMDRING_ST_OK,
    CmdOpcode,
)
from .. import wire as wirecodec
from ..utils.profiling import annotate
from . import wire as devwire
from .pallas.attention import attn_hop_partial
from .pallas.ring import hop_source

__all__ = [
    "decode_fparam",
    "decode_slot",
    "encode_fparam",
    "encode_slot",
    "encode_window",
    "fused_slot_eligible",
    "KeptSlots",
    "run_windows",
    "slot_epilogue",
    "status_view",
    "status_words",
]

_F = CMDRING_FIELDS  # the one layout table (constants.py)


# ---------------------------------------------------------------------------
# the decode loop
# ---------------------------------------------------------------------------


def _reduce_chain(blocks, fn):
    """Data-driven fold over the gathered per-rank blocks: SUM and MAX
    both computed as static chains, the ReduceFunction scalar (read
    from the slot words ON DEVICE) selects.  Chain order is rank order
    on every rank — the determinism the replay test pins."""
    acc_sum = blocks[0]
    acc_max = blocks[0]
    for b in blocks[1:]:
        acc_sum = acc_sum + b
        acc_max = jnp.maximum(acc_max, b)
    from ..constants import ReduceFunction

    return jnp.where(fn == int(ReduceFunction.MAX), acc_max, acc_sum)


def _root_select(blocks, root):
    """Static-indexed select chain of the ``root``-th block (no dynamic
    gather: both the VPU and the CPU tier lower where-chains)."""
    out = blocks[0]
    for r in range(1, len(blocks)):
        out = jnp.where(root == r, blocks[r], out)
    return out


def _fparam_scale(fparam, dtype):
    """The fused epilogue's scalar, decoded ON DEVICE from the slot's
    Q16.16 ``fparam`` word (int-to-float multiply by the exact
    power-of-two reciprocal — no float bit-pattern punning through the
    int32 slot plane)."""
    if fparam is None:
        fparam = 0
    fp = jnp.asarray(fparam, jnp.int32).astype(jnp.float32)
    return (fp * (1.0 / CMDRING_FPARAM_ONE)).astype(dtype)


def _attn_hop_result(blocks, own, me, peer, out_lead, fp):
    """The FUSED_ATTN_HOP candidate: the slot's ``peer`` word is the hop
    OFFSET (SPMD-uniform), each rank derives its source rank on device
    and folds the visiting kv block against the resident q block riding
    the operand tail."""
    size = len(blocks)
    src = hop_source(me, peer, size)
    visiting = _root_select(blocks, src)
    return attn_hop_partial(
        own[out_lead:2 * out_lead], visiting[:out_lead], fp
    )


def slot_epilogue(blocks, own, me, op, fn, root, peer, out_lead,
                  chunk: Optional[int] = None, fparam=None):
    """ONE per-slot decode epilogue for the full opcode space.
    ``blocks`` is the gathered per-rank block list
    (static length = world size), ``own`` this rank's (pass-through)
    operand, and ``op``/``fn``/``root``/``peer``/``fparam`` int32
    scalars read from the slot words ON DEVICE.  ``out_lead`` is the
    slot's static result height along the leading axis; ``chunk`` the
    per-rank sub-block height for the P-wide ops (``in_lead // size``,
    in elements).

    Output GEOMETRY is compile-time (it shapes the program), so the
    width class picks the candidate set and the opcode selects within
    the class as data:

    * ``out == in * size``      → ALLGATHER (the gathered stack);
    * ``in == out * (size+1)``  → FUSED_APPLY (optimizer apply-on-
      arrival: the param chunk riding the operand tail minus
      ``fparam`` times this rank's reduced gradient chunk — the apply
      happens during the gather, not after it);
    * ``in == out * size``      → REDUCE_SCATTER / FUSED_MATMUL_RS
      (fold, take my chunk; the fused form scales by ``fparam`` — the
      vadd_put discipline) / FUSED_ATTN_HOP at size 2 (where the hop
      class coincides);
    * ``in == out * 2``, size>2 → FUSED_ATTN_HOP (kv block relays one
      hop, the epilogue emits the scaled partial against the resident
      q block on the operand tail);
    * ``out == in``             → ALLREDUCE / BCAST / ALLTOALL /
      BARRIER / SEND / RECV / NOP selected by the opcode word: the
      fold, the root block, the transpose-of-chunks, the pass-through
      token, the pair move (``me == peer`` adopts the src block), or
      ``own``.
    """
    size = len(blocks)
    in_lead = own.shape[0]
    if size == 1:
        return own[:out_lead] if out_lead <= in_lead else own
    if out_lead == in_lead * size:
        # ALLGATHER class: the gathered stack is the result — opcode
        # still guards as data, so a mis-encoded slot yields its own
        # operand tiled instead of silently gathering
        cat = jnp.concatenate(blocks, axis=0)
        return jnp.where(
            op == int(CmdOpcode.ALLGATHER),
            cat,
            jnp.concatenate([own] * size, axis=0),
        )
    reduced = _reduce_chain(blocks, fn)
    fp = _fparam_scale(fparam, own.dtype)
    if in_lead == out_lead * (size + 1):
        # FUSED_APPLY class: gradients in allreduce layout with this
        # rank's param chunk riding the operand tail.  Fold the
        # gathered gradients, take my chunk, apply p - lr*g — the
        # optimizer step runs per received chunk during the gather.
        # Opcode guards as data: a mis-encoded slot passes its own
        # leading chunk through untouched.
        grad = lax.dynamic_slice_in_dim(reduced, me * out_lead, out_lead)
        mine = own[size * out_lead:(size + 1) * out_lead]
        return jnp.where(
            op == int(CmdOpcode.FUSED_APPLY),
            mine - fp * grad,
            own[:out_lead],
        )
    if in_lead == out_lead * size:
        # REDUCE_SCATTER class: fold everything, keep my chunk (opcode
        # guard as above — a mis-encoded slot keeps its own chunk).
        # FUSED_MATMUL_RS shares the geometry and scales the chunk by
        # fparam (the GEMM-partial epilogue feeding the relay); at
        # size 2 the attn-hop class coincides (2*out == size*out) and
        # the opcode word selects it here.
        mine = lax.dynamic_slice_in_dim(reduced, me * out_lead, out_lead)
        res = jnp.where(
            op == int(CmdOpcode.REDUCE_SCATTER),
            mine,
            lax.dynamic_slice_in_dim(own, me * out_lead, out_lead),
        )
        res = jnp.where(
            op == int(CmdOpcode.FUSED_MATMUL_RS), fp * mine, res
        )
        if size == 2:
            res = jnp.where(
                op == int(CmdOpcode.FUSED_ATTN_HOP),
                _attn_hop_result(blocks, own, me, peer, out_lead, fp),
                res,
            )
        return res
    if in_lead == out_lead * 2:
        # FUSED_ATTN_HOP class (size > 2): kv ‖ q operand rows — the
        # relay moves the kv block one hop, the epilogue contracts it
        # against the resident q block
        return jnp.where(
            op == int(CmdOpcode.FUSED_ATTN_HOP),
            _attn_hop_result(blocks, own, me, peer, out_lead, fp),
            own[:out_lead],
        )
    rooted = _root_select(blocks, root)
    res = jnp.where(op == int(CmdOpcode.ALLREDUCE), reduced, own)
    res = jnp.where(op == int(CmdOpcode.BCAST), rooted, res)
    # BARRIER: the gather that fed `blocks` IS the sync; the result is
    # the pass-through token
    res = jnp.where(op == int(CmdOpcode.BARRIER), own, res)
    # SEND/RECV pair slot: root=src, peer=dst — the destination adopts
    # the source block, everyone else passes through (their result is
    # never written back; writers = {dst} at adoption)
    pair = jnp.where(me == peer, rooted, own)
    res = jnp.where(
        (op == int(CmdOpcode.SEND)) | (op == int(CmdOpcode.RECV)),
        pair, res,
    )
    if chunk is not None and chunk * size == in_lead and chunk > 0:
        a2a = jnp.concatenate(
            [
                lax.dynamic_slice_in_dim(blocks[j], me * chunk, chunk)
                for j in range(size)
            ],
            axis=0,
        )
        res = jnp.where(op == int(CmdOpcode.ALLTOALL), a2a, res)
    return res


#: the opcode range the status check accepts — derived from the enum,
#: never a hardcoded member, so growing CmdOpcode (with the acclint
#: cross-file check enforcing the wiring) never stamps BAD_OP on a
#: fully implemented opcode
_MAX_OPCODE = max(int(o) for o in CmdOpcode)


def status_words(slots):
    """Per-slot ``(seqn, retcode)`` status words, computed ON DEVICE
    from the slot data by the same program that executes the window —
    the completion words the host drainer reads from the status FIFO.
    Every CmdOpcode is implemented; out-of-range opcodes stamp
    ``CMDRING_ST_BAD_OP``."""
    op = slots[:, _F["opcode"]]
    ok = (op >= 0) & (op <= _MAX_OPCODE)
    ret = jnp.where(ok, CMDRING_ST_OK, CMDRING_ST_BAD_OP).astype(jnp.int32)
    return jnp.stack([slots[:, _F["seqn"]], ret], axis=1)


def _decode_slot_xla(slots, i, own, me, size, shape: WindowShape):
    """One slot of the flat (element-granular) XLA decode loop: the
    wire-cast rounding lane, the ``lax.all_gather`` wire move (int8
    lanes additionally gather their per-segment scale sidecar — the
    honest wire-byte accounting), and the shared epilogue.  The SR seed
    rides the slot's ``flags`` word as DATA (rank-mixed on device), so
    seed churn never recompiles a warm window."""
    wire = shape.wires[i]
    x = own
    if wire is not None:
        # the compressed lane lowered into the decode loop: every
        # contribution rounds through the wire dtype exactly like the
        # compressed_allreduce program (single rounding, on device).
        # ONE lane helper covers every registered wire dtype (acclint
        # cross-checks this module for it).
        from ..constants import numpy_to_dtype

        seed = devwire.rank_seed(
            slots[i, _F["flags"]].astype(jnp.uint32), me
        )
        if wirecodec.is_scaled(numpy_to_dtype(np.dtype(wire))):
            # scaled lane: the wire moves int8 values + fp32 scales;
            # contributions dequantize per source rank before the fold
            q, scales = devwire.quantize_int8(x, seed)
            gq = lax.all_gather(q, _axis_name())
            gs = lax.all_gather(scales, _axis_name())
            in_w = shape.in_ws[i]
            blocks = [
                devwire.dequantize_int8(
                    gq[r], gs[r], in_w, out_dtype=own.dtype
                )
                for r in range(size)
            ]
            chunk = in_w // size if size and in_w % size == 0 else None
            return slot_epilogue(
                blocks, own, me,
                slots[i, _F["opcode"]],
                slots[i, _F["function"]],
                slots[i, _F["root"]],
                slots[i, _F["peer"]],
                shape.out_ws[i],
                chunk=chunk,
                fparam=slots[i, _F["fparam"]],
            )
        x = devwire._cast_lane(x, jnp.dtype(wire), seed)
    g = lax.all_gather(x, _axis_name())
    blocks = [g[r].astype(own.dtype) for r in range(size)]
    in_w = shape.in_ws[i]
    chunk = in_w // size if size and in_w % size == 0 else None
    return slot_epilogue(
        blocks, own, me,
        slots[i, _F["opcode"]],
        slots[i, _F["function"]],
        slots[i, _F["root"]],
        slots[i, _F["peer"]],
        shape.out_ws[i],
        chunk=chunk,
        fparam=slots[i, _F["fparam"]],
    )


def _axis_name():
    from .driver import AXIS

    return AXIS


# ---------------------------------------------------------------------------
# the window program: a batch becomes one program
# ---------------------------------------------------------------------------


@lru_cache(maxsize=128)
def _windows_program(mesh_id: int, shape_key: tuple, nwin: int):
    """The jitted window program: ``(slots_global, *slot_globals) ->
    (status_global, *result_globals)``.  Slot CONTENT is data — only the
    shape signature and the backlog length key the cache."""
    from .driver import _MESHES, AXIS, _smap

    mesh = _MESHES[mesh_id]
    size = mesh.devices.size
    depth, in_ws, out_ws, wires, npdt_name = shape_key
    shape = WindowShape(depth, in_ws, out_ws, wires, npdt_name)
    nslots = nwin * depth
    spec_in = (jax.sharding.PartitionSpec(AXIS),) * (1 + nslots)
    spec_out = (jax.sharding.PartitionSpec(AXIS),) * (1 + nslots)

    def body(slots, *flat_xs):
        me = lax.axis_index(AXIS)
        # the operand width slice FUSED into the program (the engine's
        # prep discipline): raw committed shards may be wider than the
        # slot's in_w — slice, never re-stage on the host
        sliced = [
            x[: shape.in_ws[i % depth]]
            if x.shape[0] > shape.in_ws[i % depth] else x
            for i, x in enumerate(flat_xs)
        ]
        xs = [
            list(sliced[w * depth:(w + 1) * depth]) for w in range(nwin)
        ]
        outs = [
            [
                _decode_slot_xla(
                    slots[w * depth:(w + 1) * depth],
                    i, xs[w][i], me, size, shape,
                )
                for i in range(depth)
            ]
            for w in range(nwin)
        ]
        status = jnp.concatenate(
            [
                status_words(slots[w * depth:(w + 1) * depth])
                for w in range(nwin)
            ],
            axis=0,
        )
        flat = [o for per in outs for o in per]
        return (status, *flat)

    return _smap(mesh, body, spec_in, spec_out)


#: windows of slot words a ring keeps on its chips (least recently sent
#: goes first; a window of eight is 352 bytes a chip)
KEPT_WINDOWS = 32


class KeptSlots:
    """The slot words a ring's chips already hold: one device array a
    window, by the mesh and the words' content — every field but
    ``seqn``, which the kept words carry window-relative (0 … n-1).
    A job's window is the same slots every step, so a warm window sends
    nothing down; a window whose words differ in ANY other field (the
    count, the function, a root, a peer, an fparam, a compressed lane's
    seed, the chaos plane's poisoned opcode) is another key and is put.
    Owned by the engine's ring, which empties it where it drops its
    sessions; ``hits`` and ``puts`` count the windows served each way."""

    def __init__(self):
        self._lock = threading.Lock()
        self._kept: "OrderedDict[tuple, jax.Array]" = OrderedDict()
        self.hits = 0
        self.puts = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._kept)

    def clear(self) -> None:
        with self._lock:
            self._kept.clear()

    def on_device(self, rows: np.ndarray, mesh):
        """The ``(size * n, words)`` slot global of ``rows`` (``(n,
        words)`` int32, ``seqn`` as the host encoded it) on ``mesh``,
        with ``seqn`` window-relative: kept if these words were sent
        before, else put now and kept."""
        from jax.sharding import NamedSharding, PartitionSpec

        from .driver import AXIS, _mesh_key

        words = np.array(rows, np.int32)
        words[:, _F["seqn"]] = np.arange(len(words), dtype=np.int32)
        key = (_mesh_key(mesh), words.tobytes())
        with self._lock:
            dev = self._kept.get(key)
            if dev is not None:
                self._kept.move_to_end(key)
                self.hits += 1
                return dev
        dev = jax.device_put(
            np.tile(words, (mesh.devices.size, 1)),
            NamedSharding(mesh, PartitionSpec(AXIS)),
        )
        with self._lock:
            self._kept[key] = dev
            while len(self._kept) > KEPT_WINDOWS:
                self._kept.popitem(last=False)
            self.puts += 1
        return dev


def run_windows(windows, mesh, shape: WindowShape, kept: KeptSlots):
    """Dispatch a backlog of refill windows as ONE program (the engine
    sends one window a call).  ``windows`` is a list of
    ``(slots_np, slot_globals)`` where ``slot_globals`` are assembled
    flat per-slot globals (the zero-copy assembly of the gang engine);
    ``kept`` is the ring's :class:`KeptSlots`.
    Returns ``(status_global, status_shard, results)`` with
    ``results[w][i]`` the slot's result global.  The caller blocks on
    the status global — THE device status words — at its drain points
    and then reads ``status_shard`` through :func:`status_view`: the one
    addressable shard whose copy to the host was asked for here, as the
    results arrived.  Its ``seqn`` column is relative to the call's
    first slot (what the kept words carry)."""
    from .driver import _mesh_key

    nwin = len(windows)
    with annotate("accl.ring::slots"):
        slots_dev = kept.on_device(
            np.concatenate([w[0] for w in windows], axis=0), mesh
        )
    with annotate("accl.ring::program"):
        prog = _windows_program(_mesh_key(mesh), shape.key(), nwin)
        flat = [g for _, gs in windows for g in gs]
        out = prog(slots_dev, *flat)
    status, results = out[0], list(out[1:])
    # every rank's copy is identical by construction: the drainer reads
    # one.  `.data` makes a new array each time and a new array has no
    # copy under way, so THIS object is the one handed on.
    status_shard = status.addressable_shards[0].data
    status_shard.copy_to_host_async()
    depth = shape.depth
    return status, status_shard, [
        results[w * depth:(w + 1) * depth] for w in range(nwin)
    ]


def status_view(status_shard) -> np.ndarray:
    """The drainer's read of the device status words: the shard
    :func:`run_windows` returned, whose copy to the host is under way or
    done, as a host ``(nwin * depth, 2)`` int32 array of
    ``(seqn, retcode)``."""
    return np.asarray(status_shard).reshape(-1, 2)
