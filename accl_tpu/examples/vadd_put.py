"""vadd_put: a device kernel commanding the collective engine directly.

Role model: ``kernels/plugins/vadd_put/vadd_put.cpp:25-100`` + the HLS
bindings (``driver/hls/accl_hls.h``) — an FPGA compute kernel reads fp32,
adds a constant, streams the result into the CCLO and issues ``stream_put``
to a remote rank with NO host in the data path.

TPU-natively the "device kernel" is a jitted function and the stream port
is the engine's kernel-facing FIFO: compute happens under jit (on the
accelerator), the result is pushed into the local stream port, and the
engine forwards it to the destination's port — the host never touches the
payload between compute and wire."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..backends.base import CallOptions
from ..constants import DataType, Operation, StreamFlags


@jax.jit
def _vadd(x: jax.Array, increment: float) -> jax.Array:
    return x + increment


def vadd_put(
    accl,
    data: np.ndarray,
    dst: int,
    stream_id: int = 0,
    increment: float = 1.0,
) -> None:
    """Compute x+increment on device, push into the local stream port, then
    send from the port to ``dst``'s tag-matched receive (OP0_STREAM path)."""
    out = np.asarray(_vadd(jnp.asarray(data, jnp.float32), increment))
    accl.stream_push(out, stream_id=stream_id)
    accl.send(
        None, out.size, dst=dst, tag=stream_id, from_stream=True,
        stream_id=stream_id,
    )


def vadd_put_streamed(
    accl,
    data: np.ndarray,
    dst: int,
    stream_id: int = 0,
    increment: float = 1.0,
) -> None:
    """Full device-to-device variant: operand from the local stream port AND
    delivery into the remote stream port (OP0_STREAM | RES_STREAM) — no
    tag-matched buffer anywhere, the exact vadd_put flow."""
    out = np.asarray(_vadd(jnp.asarray(data, jnp.float32), increment))
    accl.stream_push(out, stream_id=stream_id)
    cfg, flags = accl._resolve_arithcfg(DataType.FLOAT32, None)
    opts = CallOptions(
        op=Operation.SEND,
        comm=accl.comm,
        count=out.size,
        root_dst=dst,
        tag=stream_id,
        arithcfg=cfg,
        compression=flags,
        stream=StreamFlags.OP0_STREAM | StreamFlags.RES_STREAM,
        stream_id=stream_id,
    )
    accl._launch(opts, False, "vadd_put_streamed")


def vadd_put_pallas(stacked, mesh, increment: float = 1.0, distance: int = 1):
    """The fully-fused variant: compute AND wire in ONE Mosaic kernel.

    Where :func:`vadd_put` computes under jit and hands the result to the
    engine's stream port, this form is the exact analog of the FPGA flow —
    a single device kernel (``ops.pallas.fused_shift``) computes
    ``x + increment`` in VMEM and itself issues the remote DMA to the
    neighbor ``distance`` away, host and XLA collective scheduler both out
    of the data path.  ``stacked[r]`` is rank r's operand; returns stacked
    results (row r = what rank r received)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.driver import AXIS
    from ..ops.pallas import fused_shift

    fn = jax.jit(
        shard_map(
            lambda x: fused_shift(
                x[0], AXIS, distance, lambda v: v + increment
            )[None],
            mesh=mesh,
            in_specs=P(AXIS),
            out_specs=P(AXIS),
            check_vma=False,
        )
    )
    return fn(jnp.asarray(stacked, jnp.float32))
