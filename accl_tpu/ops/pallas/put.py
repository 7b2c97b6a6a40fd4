"""Fused compute + remote put: device-initiated communication in one
kernel.

The reference lets FPGA compute kernels command the collective engine with
no host in the data path: ``vadd_put`` reads fp32, adds a constant, streams
the result into the CCLO and issues ``stream_put`` to a remote rank
(/root/reference/kernels/plugins/vadd_put/vadd_put.cpp:25-100, via the HLS
bindings driver/hls/accl_hls.h:277-298).  The TPU-native form of "the
kernel owns the wire" is a Pallas kernel that computes in VMEM and then
issues the Mosaic remote DMA itself — compute and communication fused in
one Mosaic program, no separate collective op, no host round-trip.

``fused_shift`` is the SPMD shape of that flow: every rank computes
``compute(x)`` and puts the result into the output buffer of the rank
``distance`` away on the ring (the reference's tag-matched ``stream_put``
to a chosen peer, arranged symmetrically so SPMD semaphore accounting is
static).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (
    LANES,
    InterpretArg,
    default_interpret,
    require_mosaic_dtypes,
    neighbor_barrier,
    pack_lanes,
)


def remote_block_put(src_ref, dst_ref, send_sem, recv_sem, dst_dev):
    """One device-initiated block put: remote-DMA ``src_ref`` into
    ``dst_ref`` on ``dst_dev`` and block until both sides drained — the
    ``stream_put`` primitive of :func:`fused_shift`.  The caller owns the pre-put barrier (the remote ref
    must exist before data lands in it)."""
    rdma = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=dst_dev,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    # acclint: allow[unbounded-wait] Mosaic-traced DMA semaphore wait
    # inside the kernel: Pallas remote copies have no timeout form;
    # the host-side gang watchdog bounds the whole program instead
    rdma.wait()


def _kernel(axis_name: str, size: int, distance: int, compute):
    def kernel(x_ref, o_ref, y, send_sem, recv_sem):
        me = lax.axis_index(axis_name)
        dst = jnp.mod(me + distance, size)
        src = jnp.mod(me - distance, size)

        # compute phase: the "vadd" half, any VMEM->VMEM function
        y[:] = compute(x_ref[:])

        # put phase: the "stream_put" half — this kernel, not the host and
        # not a collective op, initiates the wire transfer
        neighbor_barrier(dst, src)
        remote_block_put(y, o_ref, send_sem, recv_sem, dst)

    return kernel


def fused_shift(
    x: jax.Array,
    axis_name: str,
    distance: int = 1,
    compute: Optional[Callable[[jax.Array], jax.Array]] = None,
    *,
    collective_id: int = 1,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Compute ``compute(x)`` on-chip and put the result into the output of
    rank ``(me + distance) % size``; returns what rank ``(me - distance)``
    put here.  Runs inside ``shard_map`` over a 1-D mesh axis.

    This is ``vadd_put`` in one Mosaic program: compute result never
    returns to the host or to XLA before crossing ICI.
    """
    size = lax.axis_size(axis_name)
    compute = compute if compute is not None else (lambda v: v)
    if size == 1:
        xp, n = pack_lanes(x)
        return compute(xp).reshape(-1)[:n].reshape(x.shape)
    interp = default_interpret(interpret)
    require_mosaic_dtypes(interp, "fused-put", x.dtype)
    xp, n = pack_lanes(x)
    rows = xp.shape[0]
    out = pl.pallas_call(
        _kernel(axis_name, size, distance, compute),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), x.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=interp,
    )(xp)
    return out.reshape(-1)[:n].reshape(x.shape)
