"""Pallas TPU kernel tier: the reference's hardware dataplane as real
TPU kernels.

* ``combine`` — the reduce_ops arithmetic plugin (fused elementwise
  SUM/MAX with optional result-lane cast).
* ``compression`` — the hp_compression plugin (dtype casts incl.
  stochastic rounding, plus blockwise int8 wire quantization).
* ``ring`` — the firmware's segmented ring collectives as single Pallas
  kernels whose hops are Mosaic remote DMAs over ICI, with slot-ack flow
  control (the RX-buffer release protocol).
* ``attention`` — flash attention (forward and backward) and ring
  attention whose K/V blocks rotate over ICI inside the kernel.
* ``grouped_matmul`` — rows grouped by expert against one matrix a group
  (the dropless MoE experts): three tiled kernels after jax's megablox
  (forward, the input's and the weights' gradient) behind one
  ``custom_vjp``, tiles chosen from the shapes for each form.
* ``place_rows`` — the held experts' buffer rows placed on their tokens,
  weighted (the held MoE path's combine and its dispatch gather's
  cotangent): one kernel that reads the buffer's rows once, in the
  contiguous runs a stable sort by expert leaves a token tile.
* ``kda`` — the KDA core (the chunked gated delta rule of ``ops/kda.py``)
  as a forward and a backward kernel that keep what a chunk makes in
  VMEM, the scan over the chunk states fused into them.
* ``kda_mixer`` — the KDA mixer's float32 chains round that core (the
  convolutions, SiLU and L2 norms of q, k, v; the decay's gate; the output
  norm and gate) as one pass over HBM each, forward and backward, the move
  to head-major and back a block's index map.
* ``ssd`` — the Mamba-2 core (the chunked selective state-space recurrence
  of ``ops/ssd.py``) as a forward and a backward kernel that keep a chunk's
  decay squares and the running state in VMEM, token-major in and out.
* ``mamba_mixer`` — the Mamba-2 mixer's two float32 chains round that core
  (the convolution, bias and SiLU of x, B and C: ``mamba_in_fwd`` /
  ``mamba_in_bwd``; the gate and the grouped norm: ``mamba_out_fwd`` /
  ``mamba_out_bwd``) as one pass over HBM each, token-major as the core
  takes and gives its rows.

On non-TPU backends every kernel runs under the Pallas TPU interpreter so
the CI tier exercises the identical kernel code (see
``_common.default_interpret``).
"""

from . import (  # noqa: F401
    alltoall,
    attention,
    compression,
    put,
    ring,
    rooted,
)
from ._common import default_interpret, pack_lanes, unpack_lanes  # noqa: F401
from .attention import flash_attention, flash_tile_pairs  # noqa: F401
from .alltoall import alltoall as alltoall_kernel  # noqa: F401
from .combine import combine  # noqa: F401
from .compression import cast, dequantize_int8, quantize_int8  # noqa: F401
from .grouped_matmul import grouped_matmul  # noqa: F401
from .put import fused_shift  # noqa: F401
from .ring import (  # noqa: F401
    int8_allreduce,
    ring_allgather,
    ring_allreduce,
    ring_reduce_scatter,
)
from .rooted import (  # noqa: F401
    ring_bcast,
    ring_gather,
    ring_reduce,
    ring_scatter,
)
