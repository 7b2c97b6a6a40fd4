"""accl_tpu.ops: the idiomatic TPU collective layer.

Pure-functional JAX collectives in two flavors:

* ``collectives`` — XLA's native collectives (psum / all_gather /
  psum_scatter / all_to_all / ppermute) wrapped with the reference op
  vocabulary, for use inside ``shard_map``/``pjit`` over a Mesh.  This is
  the fast path: XLA schedules the ICI transfers.
* ``ring`` — explicit, segment-controlled ring pipelines built from
  ``lax.ppermute`` (algorithm-faithful mode, mirroring the reference
  firmware's ring reduce-scatter + allgather allreduce,
  ccl_offload_control.c:1888-2071), for when you need the reference's
  tuning surface (segment sizes, overlap) rather than XLA's choices.
* ``pallas`` — hand-written TPU kernels for the dataplane hot ops: the
  reduce_ops/hp_compression plugins as VMEM-tiled VPU passes, and the
  segmented ring collectives as single Pallas kernels whose hops are
  Mosaic remote DMAs over ICI with slot-ack flow control (the RX-buffer
  release protocol).  Off-TPU they execute under the Pallas TPU
  interpreter, optionally with its vector-clock race detector.

* ``cmdring`` — the command ring's device half: a batched window of
  collectives decoded from slot words and executed as ONE program.

The ``driver`` module wraps both in host-level helpers that take global
arrays and a Mesh and run the jitted SPMD program.
"""

from . import collectives, overlap, pallas, ring  # noqa: F401
from .driver import (  # noqa: F401
    make_mesh,
    run_allgather,
    run_allreduce,
    run_alltoall,
    run_bcast,
    run_gather,
    run_reduce,
    run_reduce_scatter,
    run_ring_allreduce,
    run_scatter,
)
