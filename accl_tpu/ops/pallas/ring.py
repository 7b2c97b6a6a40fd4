"""Ring collectives as Pallas TPU kernels — the dataplane hot loop on ICI.

The reference's headline allreduce is a *segmented ring reduce-scatter +
ring allgather* the firmware drives through the DMA-mover: per hop it
issues a strided read, an RX-buffer seek for the incoming fragment, a fused
reduce, and a packetizer command to the next rank, releasing RX buffers on
ack (/root/reference/kernels/cclo/fw/sw_apps/ccl_offload_control/src/
ccl_offload_control.c:1888-2071; dma_mover.cpp:433-703).  This module is
that machine re-built for TPU hardware: one Pallas kernel per collective in
which every hop is a Mosaic **remote DMA** to the ring neighbor over ICI,
segments pipeline the wire against the VPU reduce, and a slot-ack protocol
(regular semaphores signalled back to the sender) plays the role of the
eager RX-buffer release path.

All entry points run *inside* ``shard_map`` over a 1-D mesh axis whose
order matches the devices' ICI ring.  ``num_segments`` is the reference's
segmentation tuning knob: each ring hop is split into that many
independently-DMA'd segments so hop ``s``'s wire time overlaps hop
``s``'s reduce time.  On non-TPU backends the same kernels execute under
the Pallas TPU interpreter (see ``_common``), which is also how the test
tier runs them — optionally with the interpreter's vector-clock race
detector enabled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...constants import ReduceFunction
from ._common import (
    LANES,
    InterpretArg,
    ack_gate,
    ack_release,
    default_interpret,
    require_mosaic_dtypes,
    neighbor_barrier,
    pack_lanes,
    sublanes_for,
)

_OPS = {
    ReduceFunction.SUM: jnp.add,
    ReduceFunction.MAX: jnp.maximum,
}


def _pack_ring(x: jax.Array, size: int, num_segments: int,
               wire_dtype=None):
    """Flatten + pad to (size * num_segments * sublane-aligned segB, LANES).

    When a narrower wire dtype rides the comm buffers, segment tiles must
    satisfy BOTH dtypes' sublane minimums (bf16 needs 16 where f32 needs
    8) or the compiled wire buffers violate Mosaic tile alignment."""
    sub = sublanes_for(x.dtype)
    if wire_dtype is not None:
        sub = max(sub, sublanes_for(wire_dtype))
    return pack_lanes(x, min_rows=size * num_segments * sub)


def _neighbors(axis_name: str, size: int):
    me = lax.axis_index(axis_name)
    nxt = jnp.where(me + 1 == size, 0, me + 1)
    prv = jnp.where(me == 0, size - 1, me - 1)
    return me, nxt, prv


def hop_source(me, hop, size):
    """Rank whose block rank ``me`` holds after ``hop`` ring hops (the
    FUSED_ATTN_HOP peer word carries the hop OFFSET, not an absolute
    rank — slots are encoded once globally, so the word is SPMD-uniform
    and each rank derives its source here, on device or host).  Works
    for python ints and traced values alike."""
    return (me - hop + size) % size


def _ring_barrier(nxt, prv):
    neighbor_barrier(nxt, prv)


def _hop(dst_ref, src_ref, send_ref, recv_ref, ack_ref, dst_dev, hop):
    """One segment of one ring hop: ack-gated remote DMA of ``src_ref``
    into ``dst_ref`` on device ``dst_dev`` (a comm slot there).  All refs
    arrive fully indexed.  Returns the descriptor to wait on.  Ack
    protocol = the reference's RX-buffer release: a slot is rewritten two
    hops later only after its consumer signalled it free."""
    ack_gate(ack_ref, hop)
    rdma = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_ref,
        recv_sem=recv_ref,
        device_id=dst_dev,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    return rdma


def _release(ack_ref, ups, hop, total_hops):
    """Tell the sender (upstream rank) its slot is consumed — unless no
    future hop will reuse it (semaphores drain to zero by kernel end)."""
    ack_release(ack_ref, hop, total_hops, ups)


def _scratch(size, num_segments, seg_rows, dtype):
    return [
        pltpu.VMEM((2, num_segments, seg_rows, LANES), dtype),  # comm slots
        pltpu.SemaphoreType.DMA((2, num_segments)),  # send
        pltpu.SemaphoreType.DMA((2, num_segments)),  # recv
        pltpu.SemaphoreType.REGULAR((2, num_segments)),  # slot acks
    ]


def _allreduce_kernel(axis_name, size, num_segments, op, ndirs=1,
                      wire_dtype=None):
    """Segmented ring allreduce over 1 or 2 direction lanes.

    ``ndirs=2`` is the bidirectional ring (pallas_guide 'Bi-directional
    Ring'): the operand's two halves travel in opposite directions around
    the ring simultaneously, using both ICI links of each neighbor pair —
    2x the usable ring bandwidth.  Each direction lane is a complete,
    independent instance of the slot-ack protocol (own comm slots,
    semaphores, accumulator); the hop loop interleaves them so both wires
    are in flight before either fold begins."""
    total_hops = 2 * (size - 1)
    compressed = wire_dtype is not None

    def kernel(x_ref, o_ref, acc, comm, *rest):
        # rest = (stage, send_sem, recv_sem, ack_sem) when compressed,
        #        (send_sem, recv_sem, ack_sem) otherwise
        if compressed:
            stage, send_sem, recv_sem, ack_sem = rest
        else:
            stage = acc  # send directly from the accumulator
            send_sem, recv_sem, ack_sem = rest
        me, nxt, prv = _neighbors(axis_name, size)
        S = num_segments
        segB = comm.shape[3]
        B = S * segB
        H = size * B  # rows per direction half

        def up(v):
            # wire -> accumulate dtype (the hp_compression decompress lane)
            return v.astype(acc.dtype) if compressed else v

        # (destination, upstream, ring orientation sign) per lane
        dirs = [(nxt, prv, 1)]
        if ndirs == 2:
            dirs.append((prv, nxt, -1))

        def xseg(d, blk, j):
            start = d * H + jnp.mod(blk, size) * B + j * segB
            return x_ref[pl.ds(start, segB), :]

        _ring_barrier(nxt, prv)

        # --- ring reduce-scatter: hops 1 .. P-1 --------------------------
        for d, (_, _, sg) in enumerate(dirs):
            for j in range(S):
                acc[d, j] = xseg(d, me - sg, j)
        for s in range(1, size):
            slot = s % 2
            rdmas = {}
            for d, (dst, ups, _) in enumerate(dirs):
                for j in range(S):
                    if compressed:  # narrow onto the wire (compress lane)
                        stage[d, j] = acc[d, j].astype(stage.dtype)
                    rdmas[d, j] = _hop(
                        comm.at[d, slot, j], stage.at[d, j],
                        send_sem.at[d, slot, j], recv_sem.at[d, slot, j],
                        ack_sem.at[d, slot, j], dst, s,
                    )
            for d, (_, ups, sg) in enumerate(dirs):
                for j in range(S):
                    rdmas[d, j].wait_recv()  # upstream partial landed
                    rdmas[d, j].wait_send()  # our stage is free to rewrite
                    acc[d, j] = op(
                        up(comm[d, slot, j]), xseg(d, me - sg * (1 + s), j)
                    )
                    _release(ack_sem.at[d, slot, j], ups, s, total_hops)

        # acc now holds the fully-reduced block ``me`` of each half
        for d in range(len(dirs)):
            for j in range(S):
                o_ref[pl.ds(d * H + me * B + j * segB, segB), :] = acc[d, j]

        # --- ring allgather: hops P .. 2P-2 ------------------------------
        for t in range(1, size):
            h = size - 1 + t
            slot = h % 2
            rdmas = {}
            for d, (dst, ups, _) in enumerate(dirs):
                for j in range(S):
                    if compressed:
                        stage[d, j] = acc[d, j].astype(stage.dtype)
                    rdmas[d, j] = _hop(
                        comm.at[d, slot, j], stage.at[d, j],
                        send_sem.at[d, slot, j], recv_sem.at[d, slot, j],
                        ack_sem.at[d, slot, j], dst, h,
                    )
            for d, (_, ups, sg) in enumerate(dirs):
                origin = jnp.mod(me - sg * t, size)
                for j in range(S):
                    rdmas[d, j].wait_recv()
                    rdmas[d, j].wait_send()
                    o_ref[pl.ds(d * H + origin * B + j * segB, segB), :] = (
                        up(comm[d, slot, j]).astype(o_ref.dtype)
                    )
                    acc[d, j] = up(comm[d, slot, j])  # relay on the next hop
                    _release(ack_sem.at[d, slot, j], ups, h, total_hops)

    return kernel


def _reduce_scatter_kernel(axis_name, size, num_segments, op):
    total_hops = size - 1

    def kernel(x_ref, o_ref, comm, send_sem, recv_sem, ack_sem):
        me, nxt, prv = _neighbors(axis_name, size)
        S = num_segments
        segB = comm.shape[2]
        B = S * segB

        def xseg(blk, j):
            start = jnp.mod(blk, size) * B + j * segB
            return x_ref[pl.ds(start, segB), :]

        _ring_barrier(nxt, prv)
        for j in range(S):
            o_ref[pl.ds(j * segB, segB), :] = xseg(me - 1, j)
        for s in range(1, size):
            slot = s % 2
            rdmas = [
                _hop(comm.at[slot, j], o_ref.at[pl.ds(j * segB, segB), :],
                     send_sem.at[slot, j], recv_sem.at[slot, j],
                     ack_sem.at[slot, j], nxt, s)
                for j in range(S)
            ]
            for j in range(S):
                rdmas[j].wait_recv()
                rdmas[j].wait_send()
                o_ref[pl.ds(j * segB, segB), :] = op(
                    comm[slot, j], xseg(me - 1 - s, j)
                )
                _release(ack_sem.at[slot, j], prv, s, total_hops)

    return kernel


def relay_allgather_hops(dst_write, carry, comm, send_sem, recv_sem,
                         ack_sem, me, nxt, prv, size):
    """The store-and-relay ring allgather hop loop (ref
    ccl_offload_control.c:1402-1500) of the allgather kernel:
    ``carry[j]`` must be pre-seeded with this rank's own block
    segments; ``dst_write(origin, j, data)`` places each arriving
    block's segment ``j`` (``origin`` = the block's home rank, traced).
    Segment count derives from ``carry``'s leading dim; semaphores drain
    to zero by loop end (the slot-ack release discipline)."""
    S = carry.shape[0]
    total_hops = size - 1
    for t in range(1, size):
        slot = t % 2
        rdmas = [
            _hop(comm.at[slot, j], carry.at[j],
                 send_sem.at[slot, j], recv_sem.at[slot, j],
                 ack_sem.at[slot, j], nxt, t)
            for j in range(S)
        ]
        origin = jnp.mod(me - t, size)
        for j in range(S):
            rdmas[j].wait_recv()
            rdmas[j].wait_send()
            dst_write(origin, j, comm[slot, j])
            carry[j] = comm[slot, j]
            _release(ack_sem.at[slot, j], prv, t, total_hops)


def _allgather_kernel(axis_name, size, num_segments):
    def kernel(x_ref, o_ref, carry, comm, send_sem, recv_sem, ack_sem):
        me, nxt, prv = _neighbors(axis_name, size)
        S = num_segments
        segB = comm.shape[2]
        B = S * segB

        _ring_barrier(nxt, prv)
        for j in range(S):
            carry[j] = x_ref[pl.ds(j * segB, segB), :]
            o_ref[pl.ds(me * B + j * segB, segB), :] = carry[j]

        def place(origin, j, data):
            o_ref[pl.ds(origin * B + j * segB, segB), :] = data

        relay_allgather_hops(
            place, carry, comm, send_sem, recv_sem, ack_sem, me, nxt, prv,
            size,
        )

    return kernel


def _call(kernel, x, out_rows, scratch, collective_id, interpret):
    interp = default_interpret(interpret)
    # no XLA reroute here: these are remote-DMA kernels, not math — the
    # compiler's f16 rejection becomes a usable error up front
    require_mosaic_dtypes(interp, "ring collective", x.dtype)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=interp,
    )(x)


def ring_allreduce(
    x: jax.Array,
    axis_name: str,
    function: ReduceFunction = ReduceFunction.SUM,
    num_segments: int = 1,
    *,
    bidirectional: bool = False,
    wire_dtype=None,
    collective_id: int = 0,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Segmented-ring allreduce (reduce-scatter + allgather) as one Pallas
    kernel: 2(P-1) neighbor remote-DMA hops on ICI (ref allreduce,
    ccl_offload_control.c:1888-2071).

    ``bidirectional=True`` splits the operand in half and runs the two
    halves around the ring in opposite directions simultaneously — both
    ICI links per neighbor pair carry payload, doubling usable ring
    bandwidth (beyond the reference, whose eager ring is one-directional).

    ``wire_dtype`` (e.g. ``jnp.bfloat16``) narrows every hop's payload on
    the wire while accumulating in the operand dtype — the ETH_COMPRESSED
    / hp_compression composition executed inside the kernel: compress lane
    before the DMA, decompress after, half the ICI bytes.
    """
    size = lax.axis_size(axis_name)
    if size == 1:
        return x
    op = _OPS[function]
    ndirs = 2 if bidirectional else 1
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else None
    if wire is not None and wire == x.dtype:
        wire = None  # no-op compression
    require_mosaic_dtypes(
        default_interpret(interpret), "ring allreduce (wire_dtype)", wire
    )
    xp, n = _pack_ring(x, ndirs * size, num_segments, wire)
    rows = xp.shape[0]
    seg_rows = rows // (ndirs * size * num_segments)
    S = num_segments
    comm_dtype = wire if wire is not None else x.dtype
    scratch = [
        pltpu.VMEM((ndirs, S, seg_rows, LANES), x.dtype),  # accumulators
        pltpu.VMEM((ndirs, 2, S, seg_rows, LANES), comm_dtype),  # comm slots
    ]
    if wire is not None:
        scratch.append(
            pltpu.VMEM((ndirs, S, seg_rows, LANES), wire)  # send staging
        )
    scratch += [
        pltpu.SemaphoreType.DMA((ndirs, 2, S)),  # send
        pltpu.SemaphoreType.DMA((ndirs, 2, S)),  # recv
        pltpu.SemaphoreType.REGULAR((ndirs, 2, S)),  # slot acks
    ]
    out = _call(
        _allreduce_kernel(
            axis_name, size, num_segments, op, ndirs, wire
        ),
        xp, rows, scratch, collective_id, interpret,
    )
    return out.reshape(-1)[:n].reshape(x.shape)


def ring_reduce_scatter(
    x: jax.Array,
    axis_name: str,
    function: ReduceFunction = ReduceFunction.SUM,
    num_segments: int = 1,
    *,
    collective_id: int = 0,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Ring reduce-scatter: P-1 fused recv-reduce-send hops (ref
    ccl_offload_control.c:1782-1851).  Returns rank ``i``'s reduced block
    of the (padded) operand, flattened to (block_rows, 128)."""
    size = lax.axis_size(axis_name)
    op = _OPS[function]
    xp, _ = _pack_ring(x, size, num_segments)
    rows = xp.shape[0]
    if size == 1:
        return xp
    seg_rows = rows // (size * num_segments)
    scratch = _scratch(size, num_segments, seg_rows, x.dtype)
    return _call(
        _reduce_scatter_kernel(axis_name, size, num_segments, op),
        xp, rows // size, scratch, collective_id, interpret,
    )


def ring_allgather(
    x: jax.Array,
    axis_name: str,
    num_segments: int = 1,
    *,
    collective_id: int = 0,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Ring allgather: store-and-relay around the ring (ref
    ccl_offload_control.c:1402-1500).  ``x`` is this rank's block; returns
    all blocks concatenated along the leading axis."""
    size = lax.axis_size(axis_name)
    if size == 1:
        return x
    xp, n = _pack_ring(x, 1, num_segments)
    rows = xp.shape[0]
    seg_rows = rows // num_segments
    scratch = [pltpu.VMEM((num_segments, seg_rows, LANES), x.dtype)]
    scratch += _scratch(size, num_segments, seg_rows, x.dtype)
    out = _call(
        _allgather_kernel(axis_name, size, num_segments),
        xp, rows * size, scratch, collective_id, interpret,
    )
    blocks = out.reshape(size, -1)[:, :n]
    return blocks.reshape((size * x.shape[0],) + x.shape[1:])


def int8_allreduce(
    x: jax.Array,
    axis_name: str,
    num_segments: int = 1,
    *,
    collective_id: int = 0,
    scale_collective_id: int = 4,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Allreduce with blockwise-int8 wire compression on the Pallas ring
    tier — the ``hp_compression`` role at its narrowest lane.

    A plain dtype cast (the ``wire_dtype`` path of :func:`ring_allreduce`)
    cannot express int8: blockwise quantization needs a per-tile scale
    riding with the payload.  So the composition is quantize-once /
    gather / dequantize-reduce: each rank quantizes its full operand with
    the Pallas quant kernel (one fp32 scale per ~32 KiB tile), the int8
    payload AND the scale vector ride the Pallas ring allgather
    (store-and-relay remote DMAs), and every rank dequantizes each peer
    block with the Pallas dequant kernel and reduces locally.

    Wire cost: ``(P-1) * n`` int8 bytes per rank (plus ~n/8192 scale
    bytes) versus the f32 ring's ``2(P-1)/P * 4n`` — ~2x fewer wire
    bytes at P=4 and, unlike a reduce-scatter ring in int8, the payload
    is quantized exactly ONCE, so the error bound is the sum of each
    rank's own tile scales (asserted in the e2e test), not a per-hop
    requantization cascade.

    CONSUMES TWO collective ids: ``collective_id`` for the payload ring
    and ``scale_collective_id`` for the scale ring (the module
    namespace holds 0=ring, 1=put, 2=attention, 3=alltoall, 4=this
    scale leg) — compose with other collective kernels accordingly.
    """
    from .compression import dequantize_int8, quantize_int8

    size = lax.axis_size(axis_name)
    if size == 1:
        return x
    values, scales, n = quantize_int8(x, interpret=interpret)
    rows = values.shape[0]
    nblk = scales.shape[0]
    # two ring kernels in one program get DISTINCT collective ids so
    # their barrier semaphores can never alias (id-namespace hygiene;
    # note the size=8 interpreter slowness investigated alongside this
    # turned out to be the single-core busy-spin convoy below, not id
    # aliasing — distinct ids are kept as correct composition anyway)
    all_v = ring_allgather(
        values.reshape(-1), axis_name, num_segments,
        collective_id=collective_id, interpret=interpret,
    ).reshape(size, rows, LANES)
    all_s = ring_allgather(
        scales.reshape(-1), axis_name,
        collective_id=scale_collective_id, interpret=interpret,
    ).reshape(size, nblk, 1)
    # ONE batched dequant kernel over all ranks' blocks (the per-tile
    # scale arithmetic is position-independent), then trim each rank's
    # lane padding and reduce — P kernel launches would otherwise stack
    # up on the collective hot path
    flat = dequantize_int8(
        all_v.reshape(size * rows, LANES),
        all_s.reshape(size * nblk, 1),
        size * rows * LANES, (size, rows * LANES), jnp.float32,
        interpret=interpret,
    )
    acc = flat[:, :n].sum(axis=0)
    return acc.reshape(x.shape).astype(x.dtype)
