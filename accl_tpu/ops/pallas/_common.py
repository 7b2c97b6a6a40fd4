"""Shared plumbing for the Pallas kernel tier.

The kernels in this package are the TPU-native re-design of the reference's
HLS dataplane plugins (reduce_ops, hp_compression — /root/reference
kernels/plugins/) and of the segmented-ring hot loop the firmware drives
through the dma_mover (ccl_offload_control.c:1888-2071): instead of AXIS
streams through a 512-bit switch, data moves HBM->VMEM->VPU in (rows, 128)
lane tiles, and inter-chip hops are Mosaic remote DMAs over ICI.

Every public kernel takes ``interpret=None``: on a real TPU it compiles via
Mosaic; elsewhere it runs under the Pallas TPU interpreter
(``pltpu.InterpretParams``), which is how the CI tier (virtual CPU mesh)
executes the very same kernels — the role the reference's x86-compiled HLS
emulator plays for its hardware dataplane.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# TPU vector lane width: last dim of every tile is 128 lanes.
LANES = 128
# Sublane padding that satisfies every dtype's minimum tile (f32 needs 8,
# bf16/f16 16, int8 32 — pad rows to the worst case).
SUBLANES = 32

InterpretArg = Union[None, bool, "pltpu.InterpretParams"]


def sublanes_for(dtype) -> int:
    """Minimum sublane multiple for a dtype's VMEM tile (second-to-last
    dim): f32 8, bf16/f16 16, int8/fp8 32."""
    import jax.numpy as jnp

    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def default_interpret(interpret: InterpretArg = None):
    """Resolve the ``interpret`` argument: explicit values pass through;
    ``None`` selects compiled Mosaic on TPU and the TPU interpreter on any
    other backend (the CI tier)."""
    if interpret is not None:
        return interpret
    if jax.default_backend() == "tpu":
        return False
    return pltpu.InterpretParams()


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A ``pallas_call`` ``out_shape`` that varies over the union of the
    operands' varying mesh axes: inside a ``check_vma`` shard_map (the
    sharded train steps) ``pallas_call`` refuses an output without."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def mosaic_rejects(interpret_resolved, *dtypes) -> bool:
    """True when ``interpret_resolved`` (the output of
    :func:`default_interpret`) selects compiled Mosaic and any of
    ``dtypes`` is float16.  The TPU mosaic dialect has no ``f16``
    (measured on v5e: the compile rejects the kernel with "Unsupported
    type in mosaic dialect: 'f16'") — so every kernel entry point
    reroutes to XLA or raises a usable error BEFORE ``pallas_call``.  ``None``
    entries are ignored; the interpreter tier handles f16 fine."""
    if interpret_resolved:
        return False
    f16 = jnp.dtype(jnp.float16)
    return any(d is not None and jnp.dtype(d) == f16 for d in dtypes)


def require_mosaic_dtypes(interpret_resolved, which: str, *dtypes) -> None:
    """Raise the shared f16 rejection for kernels with no XLA reroute
    (remote-DMA / fused-compute programs): one message, one rule, every
    entry point."""
    if mosaic_rejects(interpret_resolved, *dtypes):
        raise ValueError(
            f"float16 operands are not supported by the compiled {which} "
            "kernel (the TPU mosaic dialect has no f16); use bfloat16"
        )


def pack_lanes(x: jax.Array, min_rows: int = SUBLANES):
    """Flatten ``x`` and pad it into a (rows, LANES) tile-aligned 2-D array.

    Returns ``(packed, n)`` where ``n`` is the original element count;
    ``unpack_lanes`` inverts it.  Zero padding is benign for every wire/
    arith op in this package (pads are sliced off before results are used).
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // LANES)
    rows = max(-(-rows // min_rows), 1) * min_rows  # >=1 tile even for n=0
    pad = rows * LANES - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    return flat.reshape(rows, LANES), n


def unpack_lanes(packed: jax.Array, n: int, shape, dtype=None) -> jax.Array:
    out = packed.reshape(-1)[:n].reshape(shape)
    return out.astype(dtype) if dtype is not None else out


def block_rows(total_rows: int, want: int = 512) -> int:
    """Pick a grid block height: a divisor of ``total_rows`` close to
    ``want`` that keeps tiles sublane-aligned."""
    if total_rows <= want:
        return total_rows
    for cand in range(want, SUBLANES - 1, -SUBLANES):
        if total_rows % cand == 0:
            return cand
    return total_rows


def neighbor_barrier(peer_a, peer_b):
    """Barrier with two (possibly equal) peers before the first remote
    write: signal each peer's global barrier semaphore, wait for both of
    ours — the precondition that the remote comm scratch exists before
    data lands in it.  Requires ``collective_id`` in the kernel's
    CompilerParams."""
    sem = pltpu.get_barrier_semaphore()
    for peer in (peer_a, peer_b):
        pltpu.semaphore_signal(
            sem, inc=1, device_id=peer,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(sem, 2)


def ack_gate(ack_sem_ref, hop: int, value: int = 1):
    """Slot-reuse gate of the RX-release protocol: before writing a
    double-buffered comm slot at ring hop ``hop`` (1-based), wait for the
    consumer's ack.  Hops 1 and 2 write fresh slots and pass ungated;
    hop h >= 3 reuses hop h-2's slot and must absorb ``value`` signals
    (one per DMA the consumer drained)."""
    if hop > 2:
        pltpu.semaphore_wait(ack_sem_ref, value)


def ack_release(ack_sem_ref, hop: int, total_hops: int, upstream, value: int = 1):
    """Release half of the protocol: after hop ``hop``'s slot is fully
    consumed — folded/copied *and* any forwarding DMA reading it has
    drained — signal the upstream sender that the slot is free.  Only
    emitted while a future hop (hop+2 <= total_hops) will absorb it, so
    all semaphores drain to zero by kernel end."""
    if hop + 2 <= total_hops:
        pltpu.semaphore_signal(
            ack_sem_ref, inc=value, device_id=upstream,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
