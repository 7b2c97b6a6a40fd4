"""The device-resident command ring: the persistent sequencer lowerings.

Role model: the reference's CCLO firmware run loop — the host enqueues
fixed-width commands into a hardware FIFO and the offload kernel's own
infinite loop decodes and executes whole collectives with no host in
the data path (``ccl_offload_control.c`` run loop + ``dma_mover``).
The TPU analog built here is genuinely *multi-window persistent*: one
sequencer **run** is ONE long-running device program that drains up to
``run_windows`` refill windows from the host-visible mailbox
(:mod:`accl_tpu.cmdring`) before returning — consecutive warm windows
execute with ZERO program re-dispatches, and the doorbell is a mailbox
write, not a launch.

Split of responsibilities:

* host half (slot codec + mailbox protocol): ``accl_tpu/cmdring.py``
  (numpy-only — re-exported here for the established import surface);
* device half (this module): the decode loop, twice lowered;
* engine half (sessions, refills, fallbacks): ``backends/xla/cmdring.py``.

ONE decode loop, two lowerings — both read the same
:data:`accl_tpu.constants.CMDRING_FIELDS` slot words and share the
data-driven per-slot epilogue (:func:`slot_epilogue`), which covers the
FULL opcode space: ALLREDUCE, BCAST, REDUCE_SCATTER, ALLGATHER,
ALLTOALL, BARRIER and SEND/RECV pair slots.  Opcode, reduce function,
root and peer are decoded ON DEVICE from the slot words — a warm run
never recompiles on op/function/root churn; only the window's payload
*shape signature* (per-slot widths + wire-cast dtypes) keys the
program cache, because output geometry is a compile-time fact.

* ``"xla"`` — the persistent session program: a ``scan``-bounded run
  loop whose every step pulls the next window from the mailbox (ordered
  ``io_callback``), executes every slot (``lax.all_gather`` wire move +
  the shared epilogue) and pushes the per-slot ``(seqn, retcode)``
  status words and results back.  This is the emulator/CI tier —
  provable on the virtual CPU mesh, with the mailbox decision protocol
  guaranteeing every rank sees the identical window schedule.
* ``"pallas"`` — the mega-window kernel: one Mosaic program whose
  ``fori``-shaped window×slot loop drains a backlog of refill windows
  staged into the slot mailbox region at the doorbell; per slot the
  gather hops are Mosaic remote DMAs over ICI driven by the ring
  kernels' store-and-relay machine (``ring.relay_allgather_hops``; the
  two-rank form composes ``put.remote_block_put``), with a neighbor
  barrier between slots gating comm-slot reuse.  f16 windows ride a
  f32 compute view installed around the kernel (Mosaic has no f16);
  per-slot wire casts run as rounding lanes inside the decode loop.

Payloads ride the gather at the window's uniform tile-aligned height;
results are trimmed by host-side adoption (pads are never observed).
Oversized payloads never get here — the engine falls back to host
dispatch above ``CMDRING_MAX_PAYLOAD_BYTES``.
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import io_callback
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the host half re-exported: tests/tools import the codec from here
from ...cmdring import (  # noqa: F401  (re-export surface)
    SequencerMailbox,
    WindowShape,
    decode_fparam,
    decode_slot,
    encode_fparam,
    encode_slot,
    encode_window,
    fused_slot_eligible,
    mailbox_for,
    register_mailbox,
    ring_widths,
    unregister_mailbox,
)
from ...constants import (
    CMDRING_FIELDS,
    CMDRING_FPARAM_ONE,
    CMDRING_SLOT_WORDS,
    CMDRING_ST_BAD_OP,
    CMDRING_ST_OK,
    CmdOpcode,
)
from ._common import (
    LANES,
    InterpretArg,
    default_interpret,
    require_mosaic_dtypes,
    sublanes_for,
)
from .attention import attn_hop_partial
from .put import remote_block_put
from .ring import _neighbors, _ring_barrier, hop_source, relay_allgather_hops
from ... import wire as wirecodec
from .. import wire as devwire

__all__ = [
    "decode_fparam",
    "decode_slot",
    "encode_fparam",
    "encode_slot",
    "encode_window",
    "fused_slot_eligible",
    "run_session",
    "run_windows",
    "session_program",
    "slot_epilogue",
    "status_words",
]

_F = CMDRING_FIELDS  # the one layout table (constants.py)


# ---------------------------------------------------------------------------
# the shared decode loop pieces (both lowerings)
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
    from ...constants import ReduceFunction

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
    int32 slot plane; both lowerings decode identically)."""
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
    """ONE per-slot decode epilogue for the full opcode space, shared by
    both lowerings.  ``blocks`` is the gathered per-rank block list
    (static length = world size), ``own`` this rank's (pass-through)
    operand, and ``op``/``fn``/``root``/``peer``/``fparam`` int32
    scalars read from the slot words ON DEVICE.  ``out_lead`` is the
    slot's static result height along the leading axis; ``chunk`` the
    per-rank sub-block height for the P-wide ops (``in_lead // size`` —
    element-granular on the flat XLA form, row-granular on the packed
    Pallas form).

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
        # ONE lane helper covers every registered wire dtype on both
        # lowerings (acclint cross-checks this module for it).
        from ...constants import numpy_to_dtype

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
    from ..driver import AXIS

    return AXIS


# ---------------------------------------------------------------------------
# the persistent session program (xla lowering): one dispatch, N windows
# ---------------------------------------------------------------------------


def _pull_host_fn(shape: WindowShape, size: int):
    """Host target of the run loop's pull callback.  Resolves the
    mailbox through the registry BY ID (an operand, not a closure) so
    the compiled program is reusable across runs; a missing mailbox —
    a torn-down session whose run is still draining — degrades to HALT
    payloads instead of wedging the program."""

    def pull(mid, rank):
        mbox = mailbox_for(int(mid))
        if mbox is None:
            return (
                np.int32(0),
                np.zeros((shape.depth, CMDRING_SLOT_WORDS), np.int32),
                *[np.zeros((w,), shape.npdt) for w in shape.in_ws],
            )
        try:
            live, slots, payload = mbox.pull(int(rank))
        except Exception:  # never wedge the device program
            import traceback

            traceback.print_exc()
            return (
                np.int32(0),
                np.zeros((shape.depth, CMDRING_SLOT_WORDS), np.int32),
                *[np.zeros((w,), shape.npdt) for w in shape.in_ws],
            )
        return (live, slots, *payload)

    return pull


def _push_host_fn():
    def push(mid, rank, live, status, *outs):
        mbox = mailbox_for(int(mid))
        if mbox is not None:
            try:
                mbox.push(int(rank), int(live), status, list(outs))
            except Exception:
                import traceback

                traceback.print_exc()
        return np.int32(0)

    return push


@lru_cache(maxsize=64)
def _session_program(mesh_id: int, shape_key: tuple, nwin: int):
    """The compiled persistent run: ``(anchor) -> anchor`` where the
    anchor's per-rank shard carries the mailbox id.  The run loop is a
    genuine ``while_loop`` — pull the next window, and while it is
    live: decode/execute every slot, push status + results, pull
    again.  A HALT decision exits the loop IMMEDIATELY (no tail steps,
    no zero-payload gathers — the parked sequencer costs nothing), so
    a run's lifetime is exactly its windows plus one cheap halt pull;
    ``nwin`` bounds the loop as a belt on top of the mailbox's window
    budget.  Only the window SHAPE and the bound key this cache —
    mailbox identity is data, so every run of a shape reuses one
    executable."""
    from ..driver import _MESHES, AXIS, _smap

    mesh = _MESHES[mesh_id]
    size = mesh.devices.size
    depth, in_ws, out_ws, wires, npdt_name = shape_key
    shape = WindowShape(depth, in_ws, out_ws, wires, npdt_name)
    npdt = shape.npdt
    pull = _pull_host_fn(shape, size)
    push = _push_host_fn()
    pull_shapes = (
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((depth, CMDRING_SLOT_WORDS), jnp.int32),
        *[jax.ShapeDtypeStruct((w,), npdt) for w in in_ws],
    )

    def body(anchor):
        mid = anchor[0]
        me = lax.axis_index(AXIS)

        def do_pull():
            return io_callback(pull, pull_shapes, mid, me, ordered=True)

        def cond(carry):
            return (carry[0] > 0) & (carry[1] < nwin)

        def step(carry):
            _live, n, slots, *payload = carry
            status = status_words(slots)
            outs = [
                _decode_slot_xla(slots, i, payload[i], me, size, shape)
                for i in range(depth)
            ]
            io_callback(
                push, jax.ShapeDtypeStruct((), jnp.int32),
                mid, me, jnp.int32(1), status, *outs, ordered=True,
            )
            nlive, nslots, *npayload = do_pull()
            return (nlive, n + 1, nslots, *npayload)

        live0, slots0, *payload0 = do_pull()
        lax.while_loop(
            cond, step, (live0, jnp.int32(0), slots0, *payload0)
        )
        return anchor

    spec = jax.sharding.PartitionSpec(AXIS)
    return _smap(mesh, body, (spec,), spec)


def session_program(mesh, shape: WindowShape, nwin: int):
    """Prepared persistent-run handle (the engine dispatches it once per
    run; every refill after that is a mailbox post)."""
    from ..driver import _mesh_key

    return _session_program(_mesh_key(mesh), shape.key(), int(nwin))


def run_session(mesh, shape: WindowShape, mbox_id: int, nwin: int):
    """Dispatch one persistent sequencer run: launches the run-loop
    program armed with ``mbox_id`` and returns the output handle (held
    by the engine's run record; completion flows through the mailbox's
    push path, never through blocking on this handle)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..driver import AXIS

    prog = session_program(mesh, shape, nwin)
    size = mesh.devices.size
    anchor = jax.device_put(
        np.full((size,), int(mbox_id), np.int32),
        NamedSharding(mesh, PartitionSpec(AXIS)),
    )
    return prog(anchor)


# ---------------------------------------------------------------------------
# the Pallas mega-window kernel (chip tier): one Mosaic program, a
# backlog of windows
# ---------------------------------------------------------------------------


def _sequencer_kernel(axis_name: str, size: int, nwin: int, depth: int,
                      rows: int, out_rows: Sequence[int],
                      chunk_rows: Optional[int]):
    """The mega-window sequencer as ONE Mosaic program: the kernel's
    window × slot loop — not host dispatch — sequences ``nwin * depth``
    collectives.  ``rows`` is the uniform tile-aligned per-slot payload
    height; slot ``(w, i)`` owns ``x_ref[(w*depth+i)*rows : ...]``.  Per
    slot: ring-allgather the block via the store-and-relay remote-DMA
    machine (the two-rank ring degenerates to one
    ``put.remote_block_put`` exchange), then run the shared data-driven
    epilogue on the VPU.  A neighbor barrier separates slots so slot
    ``k+1``'s first hop can never overwrite a comm slot its consumer is
    still folding.  ``chunk_rows`` (= ``rows // size``) gives the
    P-wide ops their row-aligned per-rank sub-blocks."""

    def kernel(slots_ref, x_ref, o_ref, gathered, carry, comm, send_sem,
               recv_sem, ack_sem):
        me, nxt, prv = _neighbors(axis_name, size)
        out_off = 0
        for w in range(nwin):
            for i in range(depth):
                k = w * depth + i
                _ring_barrier(nxt, prv)  # doorbell + slot-reuse gate
                block = x_ref[pl.ds(k * rows, rows), :]
                gathered[pl.ds(me * rows, rows), :] = block
                if size == 2:
                    # two-rank gather IS one neighbor put (the put.py
                    # primitive): my block lands in the peer's comm slot
                    carry[0] = block
                    remote_block_put(
                        carry.at[0],
                        comm.at[0, 0],
                        send_sem.at[0, 0],
                        recv_sem.at[0, 0],
                        nxt,
                    )
                    gathered[pl.ds(prv * rows, rows), :] = comm[0, 0]
                elif size > 2:
                    carry[0] = block

                    def place(origin, _j, data):
                        gathered[pl.ds(origin * rows, rows), :] = data

                    relay_allgather_hops(
                        place, carry, comm, send_sem, recv_sem, ack_sem,
                        me, nxt, prv, size,
                    )
                # decode the slot words from SMEM (scalar reads) and run
                # the SAME epilogue the xla lowering uses
                op = slots_ref[k, _F["opcode"]]
                fn = slots_ref[k, _F["function"]]
                root = slots_ref[k, _F["root"]]
                peer = slots_ref[k, _F["peer"]]
                fparam = slots_ref[k, _F["fparam"]]
                blocks = [
                    gathered[pl.ds(r * rows, rows), :] for r in range(size)
                ]
                o_rows = out_rows[i]
                res = slot_epilogue(
                    blocks, block, me, op, fn, root, peer, o_rows,
                    chunk=chunk_rows,
                    fparam=fparam,
                )
                o_ref[pl.ds(out_off, o_rows), :] = res
                out_off += o_rows

    return kernel


def _pack_rows(x, rows: int, chunks: int, dtype):
    """Pack a flat operand into ``(rows, LANES)``: flat for the 1-wide
    ops, per-rank-chunk row-aligned for the P-wide ops so the epilogue's
    row slicing lands on chunk boundaries."""
    if chunks <= 1:
        w = x.shape[0]
        pad = rows * LANES - w
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), dtype)])
        return x.reshape(rows, LANES)
    crows = rows // chunks
    n = x.shape[0] // chunks
    parts = []
    for c in range(chunks):
        seg = x[c * n:(c + 1) * n]
        pad = crows * LANES - n
        if pad:
            seg = jnp.concatenate([seg, jnp.zeros((pad,), dtype)])
        parts.append(seg.reshape(crows, LANES))
    return jnp.concatenate(parts, axis=0)


def _unpack_rows(y, w: int, chunks: int):
    """Inverse of :func:`_pack_rows` for a slot's result region."""
    if chunks <= 1:
        return y.reshape(-1)[:w]
    crows = y.shape[0] // chunks
    n = w // chunks
    return jnp.concatenate(
        [
            y[c * crows:(c + 1) * crows].reshape(-1)[:n]
            for c in range(chunks)
        ]
    )


def _pallas_windows(slots, xs, axis_name, size, nwin, depth,
                    shape: WindowShape, me=None,
                    interpret: InterpretArg = None):
    """Trace a backlog of ``nwin`` windows through one ``pallas_call``.
    Per-slot operands are packed to one uniform tile-aligned height
    inside the traced body (zero extra dispatch — this all runs in the
    SAME program); f16 windows ride a f32 compute view around the
    kernel (Mosaic has no f16) and per-slot wire casts run as rounding
    lanes before packing (the SAME shared lane helper the xla lowering
    decodes with — fp8/int8 included, seeds from the slot ``flags``
    words) — both 'inside the decode loop' at the program level, with
    no extra host interaction."""
    npdt = shape.npdt
    f16_view = np.dtype(npdt) == np.float16
    compute = jnp.float32 if f16_view else npdt
    interp = default_interpret(interpret)
    require_mosaic_dtypes(interp, "command-ring sequencer", compute)
    sub = sublanes_for(compute)
    # per-slot chunking decided ONCE and used by pack, kernel slicing
    # AND unpack — a pack/unpack mismatch would read padding as payload.
    # Fused slots are classified by their width RELATIONS first (the
    # same relations the epilogue branches on): an APPLY operand packs
    # as size+1 chunks (grads ‖ param tail), an attn-hop operand as 2
    # (kv ‖ q); everything else keeps the plain rule.
    def _chunks_of(in_w: int, ow: int) -> int:
        if size > 1 and in_w == ow * (size + 1):
            return size + 1
        if size > 1 and in_w == ow * size:
            return size
        if size > 1 and in_w == 2 * ow and ow < in_w:
            return 2
        return size if in_w % size == 0 and in_w >= size else 1

    slot_chunks = [
        _chunks_of(shape.in_ws[i], shape.out_ws[i]) for i in range(depth)
    ]
    # uniform slot height: rows = pc * L with pc the sublane-rounded
    # max per-chunk height and L the lcm of every chunk divisor in the
    # window (plus size, so plain P-wide slicing lands on row
    # boundaries and rows // c stays sublane-aligned for every class)
    pc = max(
        -(-max(
            shape.in_ws[i] // max(slot_chunks[i], 1)
            if slot_chunks[i] > 1 else shape.in_ws[i]
            for i in range(depth)
        ) // LANES), 1)
    pc = -(-pc // sub) * sub
    lcm = size
    for c in set(slot_chunks):
        if c > 1:
            lcm = lcm * c // math.gcd(lcm, c)
    rows = pc * lcm
    chunk_rows = rows // size  # the P-wide per-rank sub-block height
    out_rows = []
    for i in range(depth):
        ow = shape.out_ws[i]
        in_w = shape.in_ws[i]
        if ow >= in_w * size and size > 1:
            out_rows.append(rows * size)          # allgather class
        elif in_w == ow * (size + 1) and size > 1:
            out_rows.append(rows // (size + 1))   # fused-apply class
        elif in_w == ow * size and size > 1:
            out_rows.append(chunk_rows)           # reduce-scatter class
        elif in_w == 2 * ow and ow < in_w and size > 1:
            out_rows.append(rows // 2)            # attn-hop class
        else:
            out_rows.append(rows)
    packed = []
    for w_idx in range(nwin):
        for i in range(depth):
            x = xs[w_idx][i].astype(compute)
            wire = shape.wires[i]
            if wire is not None and np.dtype(wire) != np.dtype(npdt):
                # wire rounding lane inside the decode loop (the shared
                # per-lane helper: cast lanes + the scaled int8 lane,
                # SR seed from the slot flags word); Mosaic dtypes only
                # INSIDE the kernel — the rounding happens in jnp
                # before packing, so fp8/int8 lanes ride fine while the
                # engine routes f16 wires to the xla lowering
                k = w_idx * depth + i
                seed = devwire.rank_seed(
                    slots[k, _F["flags"]].astype(jnp.uint32),
                    me if me is not None else jnp.uint32(0),
                )
                x = devwire.wire_lane_roundtrip(
                    x, jnp.dtype(wire), seed
                )
            packed.append(_pack_rows(x, rows, slot_chunks[i], compute))
    xp = jnp.concatenate(packed, axis=0)
    total_out = sum(out_rows) * nwin
    scratch = [
        pltpu.VMEM((size * rows, LANES), compute),  # gathered blocks
        pltpu.VMEM((1, rows, LANES), compute),      # relay carry
        pltpu.VMEM((2, 1, rows, LANES), compute),   # comm slots
        pltpu.SemaphoreType.DMA((2, 1)),            # send
        pltpu.SemaphoreType.DMA((2, 1)),            # recv
        pltpu.SemaphoreType.REGULAR((2, 1)),        # slot acks
    ]
    out = pl.pallas_call(
        _sequencer_kernel(
            axis_name, size, nwin, depth, rows, out_rows, chunk_rows
        ),
        out_shape=jax.ShapeDtypeStruct((total_out, LANES), compute),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interp,
    )(slots, xp)
    outs = []
    off = 0
    for w_idx in range(nwin):
        per = []
        for i in range(depth):
            region = out[off:off + out_rows[i]]
            off += out_rows[i]
            ow = shape.out_ws[i]
            if out_rows[i] == rows * size:
                # allgather class: size blocks, each laid out exactly
                # like the (possibly chunk-packed) input block
                in_w = shape.in_ws[i]
                got = jnp.concatenate([
                    _unpack_rows(
                        region[b * rows:(b + 1) * rows], in_w,
                        slot_chunks[i],
                    )
                    for b in range(size)
                ]).astype(npdt)
            elif out_rows[i] < rows and size > 1:
                # chunk-result classes (reduce-scatter, fused apply,
                # attn hop): the result is ONE flat chunk
                got = _unpack_rows(region, ow, 1).astype(npdt)
            else:
                # same-width class: the result keeps the input layout
                got = _unpack_rows(region, ow, slot_chunks[i]).astype(
                    npdt
                )
            per.append(got)
        outs.append(per)
    return outs


def _compiler_params():
    """Collective id 5: the module namespace holds 0=ring, 1=put,
    2=attention, 3=alltoall, 4=int8 scale leg, 5=this sequencer."""
    return pltpu.CompilerParams(has_side_effects=True, collective_id=5)


@lru_cache(maxsize=128)
def _windows_program(mesh_id: int, shape_key: tuple, nwin: int,
                     lowering: str):
    """The jitted backlog program (pallas form): ``(slots_global,
    *slot_globals) -> (status_global, *result_globals)``.  Slot CONTENT
    is data — only the shape signature, backlog length and lowering key
    the cache."""
    from ..driver import _MESHES, AXIS, _smap

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
        if lowering == "pallas":
            outs = _pallas_windows(
                slots, xs, AXIS, size, nwin, depth, shape, me=me
            )
        else:
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


def run_windows(windows, mesh, shape: WindowShape, lowering: str = "pallas"):
    """Dispatch a BACKLOG of refill windows as one mega-window program
    (the chip-tier persistence form: every window queued at doorbell
    time rides the same launch).  ``windows`` is a list of
    ``(slots_np, slot_globals)`` where ``slot_globals`` are assembled
    flat per-slot globals (the zero-copy assembly of the gang engine).
    Returns ``(status_global, results)`` with ``results[w][i]`` the
    slot's result global; the caller blocks on the status global — THE
    device status words — at its drain points."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..driver import AXIS, _mesh_key

    nwin = len(windows)
    size = mesh.devices.size
    prog = _windows_program(
        _mesh_key(mesh), shape.key(), nwin, str(lowering)
    )
    tiled = np.concatenate(
        [np.asarray(w[0], np.int32) for w in windows], axis=0
    )
    slots_dev = jax.device_put(
        np.tile(tiled, (size, 1)),
        NamedSharding(mesh, PartitionSpec(AXIS)),
    )
    flat = [g for _, gs in windows for g in gs]
    out = prog(slots_dev, *flat)
    status, results = out[0], list(out[1:])
    depth = shape.depth
    return status, [
        results[w * depth:(w + 1) * depth] for w in range(nwin)
    ]


def status_view(status_global) -> np.ndarray:
    """The drainer's read of the device status words: one addressable
    shard (every rank's copy is identical by construction) as a host
    ``(nwin * depth, 2)`` int32 array of ``(seqn, retcode)``."""
    shard = status_global.addressable_shards[0].data
    return np.asarray(shard).reshape(-1, 2)
