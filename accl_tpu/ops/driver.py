"""Host-level drivers: global arrays in, jitted SPMD collectives out.

The convention mirrors the test harness of the reference (per-rank operand
buffers): operands are *stacked* along a leading rank axis — ``stacked[r]``
is rank r's contribution — and results come back stacked the same way.
Under the hood each call builds (and caches) one jitted ``shard_map``
program over the mesh; on TPU the transfers ride ICI.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..constants import ReduceFunction
from . import collectives, pallas, ring

AXIS = "ranks"


def make_mesh(n: Optional[int] = None, axis: str = AXIS) -> Mesh:
    devs = jax.devices()
    n = n or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(devs[:n], (axis,))


def _smap(mesh: Mesh, fn, in_spec, out_spec, donate: bool = False):
    return jax.jit(
        shard_map(
            fn,
            mesh=mesh,
            in_specs=in_spec,
            out_specs=out_spec,
            check_vma=False,
        ),
        # donation: in-place collectives (bcast writes its own operand) hand
        # their operand's HBM to XLA, the jax analog of the reference's
        # in-place device BOs
        donate_argnums=(0,) if donate else (),
    )


def _shard_fn(op: str, fn: ReduceFunction, extra=None):
    """Per-shard collective body for ``op`` — the building block both the
    single-op programs and the fused batch programs are traced from."""
    if op == "allreduce":
        sfn = lambda x: collectives.allreduce(x, AXIS, fn)
    elif op == "ring_allreduce":
        nseg = extra or 1
        sfn = lambda x: ring.ring_allreduce(x, AXIS, fn, nseg)
    elif op == "pallas_allreduce":
        nseg, wire, bidir = extra  # (num_segments, wire_dtype_name, bidir)
        nseg = nseg or 1
        sfn = lambda x: pallas.ring_allreduce(
            x, AXIS, fn, nseg,
            bidirectional=bidir,
            wire_dtype=wire and jnp.dtype(wire),
        )
    elif op == "compressed_allreduce":
        wire = jnp.dtype(extra or "bfloat16")
        sfn = lambda x: collectives.compressed_allreduce(x, AXIS, wire, fn)
    elif op == "reduce":
        sfn = lambda x: collectives.reduce(x, AXIS, extra, fn)
    elif op == "pallas_reduce":
        root, nseg = extra
        sfn = lambda x: pallas.ring_reduce(x, AXIS, root, fn, nseg or 1)
    elif op == "pallas_bcast":
        root, nseg = extra
        sfn = lambda x: pallas.ring_bcast(x, AXIS, root, nseg or 1)
    elif op == "pallas_scatter":
        root, nseg = extra
        sfn = lambda x: pallas.ring_scatter(x, AXIS, root, nseg or 1)
    elif op == "pallas_gather":
        root, nseg = extra
        sfn = lambda x: pallas.ring_gather(x, AXIS, root, nseg or 1)
    elif op == "reduce_scatter":
        sfn = lambda x: collectives.reduce_scatter(x, AXIS, fn, tiled=True)
    elif op == "allgather":
        sfn = lambda x: collectives.allgather(x, AXIS, tiled=True)
    elif op in ("bcast", "bcast_inplace"):
        # bcast_inplace: donating variant for the engine's device-resident
        # in-place bcast (op0 IS res on every rank); the public run_bcast
        # never donates — callers may hold the input array
        sfn = lambda x: collectives.bcast(x, AXIS, extra)
    elif op == "scatter":
        sfn = lambda x: collectives.scatter(x, AXIS, extra)
    elif op == "gather":
        sfn = lambda x: collectives.gather(x, AXIS, extra)
    elif op == "alltoall":
        sfn = lambda x: collectives.alltoall(x, AXIS)
    else:
        raise ValueError(op)
    return sfn


def _with_prep(sfn, prep):
    """Fuse operand staging INTO the collective body (single-interaction
    dispatch): ``prep = (take_w, wire_name)`` slices a rank's raw (w,)
    HBM shard down to the call width and applies the wire-dtype rounding
    lane inside the SAME program, so a width-slack or compressed operand
    costs no separate staging dispatch (the old ``_prep_program`` hop)."""
    if prep is None:
        return sfn
    take_w, wire_name = prep

    def fused(x):
        if take_w is not None and take_w != x.shape[0]:
            x = x[:take_w]
        if wire_name is not None:
            # the shared wire lane helper: covers the scaled int8 lane
            # (blockwise quantize round-trip) beside the plain cast
            # lanes; deterministic here — the prep spec is part of the
            # program-cache key and carries no per-call seed
            from . import wire as devwire

            x = devwire.wire_lane_roundtrip(x, jnp.dtype(wire_name))
        return sfn(x)

    return fused


@lru_cache(maxsize=256)
def _program(op: str, mesh_id: int, fn: ReduceFunction, extra=None,
             flat: bool = False, prep=None):
    """``flat=False``: operands/results are (size, w) stacked arrays (the
    host/test convention).  ``flat=True``: 1-D (size*w,) globals whose
    per-rank shards ARE raw (w,) device arrays — the engine's zero-dispatch
    path (a rank's HBM buffer plugs in as a shard with no reshape program,
    and result shards adopt straight into buffers).  ``prep`` (flat only)
    fuses per-shard staging into the program — see :func:`_with_prep`."""
    mesh = _MESHES[mesh_id]
    spec = P(AXIS)
    sfn = _shard_fn(op, fn, extra)
    if flat:
        body = _with_prep(sfn, prep)
    else:
        body = lambda x: sfn(x[0])[None]
    return _smap(mesh, body, (spec,), spec, donate=op == "bcast_inplace")


@lru_cache(maxsize=128)
def _batch_program(mesh_id: int, specs: tuple):
    """ONE jitted shard_map over a whole flushed command-queue batch:
    ``specs`` is a tuple of per-slot ``(op, fn, extra, prep, flat)``
    records; the program takes one global per slot and returns one output
    per slot.  N queued collectives therefore dispatch as a single device
    interaction — the batched analog of the reference's one-command-per-
    collective hostctrl discipline, amortized N:1."""
    mesh = _MESHES[mesh_id]
    spec = P(AXIS)
    bodies = []
    for op, fn, extra, prep, flat in specs:
        sfn = _shard_fn(op, fn, extra)
        if flat:
            bodies.append(_with_prep(sfn, prep))
        else:
            bodies.append(lambda x, sfn=sfn: sfn(x[0])[None])

    def body(*xs):
        return tuple(b(x) for b, x in zip(bodies, xs))

    n = len(specs)
    return _smap(mesh, body, (spec,) * n, (spec,) * n)


def run_batch(globals_, mesh: Mesh, specs) -> tuple:
    """Run a flushed batch: one global array per spec, one fused program,
    one dispatch.  ``specs`` as in :func:`_batch_program`."""
    return _batch_program(_mesh_key(mesh), tuple(specs))(
        *[_put(g, mesh) for g in globals_]
    )


_MESHES = {}


def _mesh_key(mesh: Mesh) -> int:
    key = id(mesh)
    _MESHES[key] = mesh
    return key


def _put(stacked, mesh: Mesh):
    sharding = NamedSharding(mesh, P(AXIS))
    if isinstance(stacked, jax.Array) and stacked.sharding == sharding:
        return stacked  # already assembled on the mesh: zero-copy passthrough
    stacked = jnp.asarray(stacked)
    return jax.device_put(stacked, sharding)


def _is_flat(stacked) -> bool:
    return getattr(stacked, "ndim", 2) == 1


def prepare(op: str, mesh: Mesh, function=ReduceFunction.SUM, extra=None,
            prep=None):
    """Prepared-program handle for an engine's plan cache: the jitted
    flat-layout program, to be invoked directly on an already-assembled
    global array (the caller owns the sharding guarantee).  Resolving it
    once per plan skips the per-call ``_put`` sharding construction/
    comparison and the lru key hashing the ``run_*`` entry points pay.

    The ``extra``-omitted call form matches the ``run_*`` entry points'
    convention exactly: lru_cache keys distinguish positional from
    keyword args, and a mismatched form would alias the SAME program
    under a second jit wrapper — a full recompile on the warm path."""
    if extra is None:
        return _program(op, _mesh_key(mesh), function, flat=True, prep=prep)
    return _program(op, _mesh_key(mesh), function, extra, flat=True,
                    prep=prep)


def run_allreduce(stacked, mesh: Mesh, function=ReduceFunction.SUM,
                  prep=None):
    """stacked[r] = rank r's operand; returns stacked results (identical
    rows).  One XLA all-reduce over the mesh axis.  A 1-D operand selects
    the flat layout (shards are raw per-rank arrays; see _program);
    ``prep`` fuses per-shard staging into the program (_with_prep)."""
    return _program(
        "allreduce", _mesh_key(mesh), function, flat=_is_flat(stacked),
        prep=prep,
    )(_put(stacked, mesh))


def run_ring_allreduce(
    stacked, mesh: Mesh, function=ReduceFunction.SUM, num_segments: int = 1,
    prep=None,
):
    """The explicit segmented-ring pipeline (algorithm-faithful mode)."""
    return _program(
        "ring_allreduce", _mesh_key(mesh), function, num_segments,
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_pallas_allreduce(
    stacked,
    mesh: Mesh,
    function=ReduceFunction.SUM,
    num_segments: int = 1,
    wire_dtype: str = None,
    bidirectional: bool = False,
    prep=None,
):
    """The segmented ring as a single Pallas kernel: remote-DMA hops over
    ICI with slot-ack flow control (interpreted off-TPU).  ``wire_dtype``
    (a dtype name string, to key the program cache) narrows the payload on
    the wire with in-kernel compress/decompress lanes; ``bidirectional``
    runs the operand's halves around the ring in opposite directions,
    using both ICI links of every neighbor pair."""
    return _program(
        "pallas_allreduce", _mesh_key(mesh), function,
        (num_segments, wire_dtype, bool(bidirectional)),
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_compressed_allreduce(
    stacked, mesh: Mesh, function=ReduceFunction.SUM,
    wire_dtype: str = "bfloat16", prep=None,
):
    """Allreduce with operands narrowed to ``wire_dtype`` on the wire (the
    ETH_COMPRESSED analog); ``wire_dtype`` is a dtype name string so it can
    key the program cache."""
    return _program(
        "compressed_allreduce", _mesh_key(mesh), function, str(wire_dtype),
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_reduce(stacked, mesh: Mesh, root=0, function=ReduceFunction.SUM,
               prep=None):
    return _program(
        "reduce", _mesh_key(mesh), function, root, flat=_is_flat(stacked),
        prep=prep,
    )(_put(stacked, mesh))


def run_pallas_reduce(
    stacked, mesh: Mesh, root=0, function=ReduceFunction.SUM,
    num_segments: int = 1, prep=None,
):
    """Reduce-to-root as the rooted Pallas ring pipeline (algorithm-
    faithful mode; only the root row of the result is meaningful)."""
    return _program(
        "pallas_reduce", _mesh_key(mesh), function, (root, num_segments),
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_pallas_bcast(stacked, mesh: Mesh, root=0, num_segments: int = 1,
                     prep=None):
    return _program(
        "pallas_bcast", _mesh_key(mesh), ReduceFunction.SUM,
        (root, num_segments), flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_pallas_scatter(stacked, mesh: Mesh, root=0, num_segments: int = 1,
                       prep=None):
    return _program(
        "pallas_scatter", _mesh_key(mesh), ReduceFunction.SUM,
        (root, num_segments), flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_pallas_gather(stacked, mesh: Mesh, root=0, num_segments: int = 1,
                      prep=None):
    """Gather via the ring relay (every row holds the full gather; the
    root's row is the result)."""
    return _program(
        "pallas_gather", _mesh_key(mesh), ReduceFunction.SUM,
        (root, num_segments), flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_reduce_scatter(stacked, mesh: Mesh, function=ReduceFunction.SUM,
                       prep=None):
    return _program(
        "reduce_scatter", _mesh_key(mesh), function, flat=_is_flat(stacked),
        prep=prep,
    )(_put(stacked, mesh))


def run_allgather(stacked, mesh: Mesh, prep=None):
    return _program(
        "allgather", _mesh_key(mesh), ReduceFunction.SUM,
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_bcast(stacked, mesh: Mesh, root=0, donate: bool = False,
              prep=None):
    """``donate=True`` hands the input's HBM to XLA (in-place bcast); only
    safe when the caller no longer needs the input array — never combined
    with ``prep`` width slack (the donated operand outlives the sliced
    result, so callers pass donate=False when prep is active)."""
    op = "bcast_inplace" if donate and prep is None else "bcast"
    return _program(
        op, _mesh_key(mesh), ReduceFunction.SUM, root,
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_scatter(stacked, mesh: Mesh, root=0, prep=None):
    return _program(
        "scatter", _mesh_key(mesh), ReduceFunction.SUM, root,
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_gather(stacked, mesh: Mesh, root=0, prep=None):
    return _program(
        "gather", _mesh_key(mesh), ReduceFunction.SUM, root,
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))


def run_alltoall(stacked, mesh: Mesh, prep=None):
    return _program(
        "alltoall", _mesh_key(mesh), ReduceFunction.SUM,
        flat=_is_flat(stacked), prep=prep,
    )(_put(stacked, mesh))
