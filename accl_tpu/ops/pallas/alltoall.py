"""All-to-all as a Pallas kernel: direct one-sided writes, no ring.

The reference's ``all_to_all`` is a fused flat tree: every rank copies its
local block, sends buffer addresses to all peers, and serves incoming
address requests out of order
(/root/reference/kernels/cclo/fw/sw_apps/ccl_offload_control/src/
ccl_offload_control.c:2123-2218 — the rendezvous path's one-sided writes).
On TPU the address handshake is unnecessary — SPMD symmetry means every
rank already knows where its block lands — so the kernel is pure payload:
P-1 remote DMAs, each writing block ``p`` of my operand straight into slot
``me`` of rank ``p``'s output, all in flight simultaneously.  This is the
transpose primitive under all-to-all sequence parallelism (Ulysses-style
attention, ``models.ulysses_attention``).
"""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (
    LANES,
    InterpretArg,
    default_interpret,
    require_mosaic_dtypes,
    sublanes_for,
)


def _kernel(axis_name: str, size: int):
    def kernel(x_ref, o_ref, send_sem, recv_sem):
        me = lax.axis_index(axis_name)
        B = x_ref.shape[0] // size

        # ALL peers' output buffers must exist before one-sided writes
        # land — unlike the ring kernels (which only touch neighbors) this
        # writes to every rank, so the barrier is global: signal every
        # peer, wait for every peer
        bar = pltpu.get_barrier_semaphore()
        for d in range(1, size):
            pltpu.semaphore_signal(
                bar, inc=1, device_id=jnp.mod(me + d, size),
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
        pltpu.semaphore_wait(bar, size - 1)

        # local block moves locally
        o_ref[pl.ds(me * B, B), :] = x_ref[pl.ds(me * B, B), :]

        # launch every remote write before waiting any (the flat tree's
        # out-of-order serves: all transfers in flight at once)
        rdmas = []
        for d in range(1, size):
            dst = jnp.mod(me + d, size)
            rdma = pltpu.make_async_remote_copy(
                src_ref=x_ref.at[pl.ds(dst * B, B), :],
                dst_ref=o_ref.at[pl.ds(me * B, B), :],
                send_sem=send_sem.at[d - 1],
                recv_sem=recv_sem.at[d - 1],
                device_id=dst,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            rdmas.append(rdma)
        for rdma in rdmas:
            # acclint: allow[unbounded-wait] Mosaic-traced DMA semaphore
            # wait: Pallas remote copies have no timeout form; the host
            # watchdog bounds the whole program
            rdma.wait()

    return kernel


def alltoall(
    x: jax.Array,
    axis_name: str,
    *,
    collective_id: int = 3,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Block transpose across the axis: rank r's output block p is rank
    p's input block r (ref ``ACCL::alltoall``).  ``x``'s leading dim must
    be divisible by the axis size; blocks are padded to lane tiles
    internally per block.

    Note the destination-slot symmetry: my block ``dst`` lands in slot
    ``me`` on ``dst`` — every rank runs the identical program, so each of
    my P-1 slots is written by exactly one peer (recv semaphores indexed
    by ring distance make the accounting static).
    """
    n = x.shape[0]
    size = lax.axis_size(axis_name)
    if n % size:
        raise ValueError(f"leading dim {n} not divisible by axis size {size}")
    if size == 1:
        return x
    interp = default_interpret(interpret)
    require_mosaic_dtypes(interp, "alltoall", x.dtype)
    per_block = n // size
    rest = int(np.prod(x.shape[1:])) if x.ndim > 1 else 1

    # pack each block to (rows, LANES) so per-block DMAs are tile-aligned
    flat = x.reshape(size, per_block * rest)
    m = flat.shape[1]
    sub = sublanes_for(x.dtype)
    rows = max(-(-m // LANES), 1)
    rows = -(-rows // sub) * sub
    pad = rows * LANES - m
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((size, pad), x.dtype)], axis=1
        )
    packed = flat.reshape(size * rows, LANES)

    out = pl.pallas_call(
        _kernel(axis_name, size),
        out_shape=jax.ShapeDtypeStruct((size * rows, LANES), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((size - 1,)),
            pltpu.SemaphoreType.DMA((size - 1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=interp,
    )(packed)
    return (
        out.reshape(size, rows * LANES)[:, :m].reshape(x.shape)
    )
