"""Ulysses-style sequence parallelism: all-to-all context parallelism.

The second of the two long-context strategies (next to
``models.ring_attention``): instead of rotating K/V blocks around a ring,
two all-to-alls re-shard the tensors between *sequence*-parallel and
*head*-parallel layouts:

1. q/k/v arrive sequence-sharded: each device holds T/P timesteps of all
   H heads.
2. **all-to-all #1** transposes to head-sharded: each device holds H/P
   heads over the FULL sequence.
3. local attention runs per head — dense, no masking games, full MXU
   utilization.
4. **all-to-all #2** transposes the output back to sequence-sharded.

Communication volume is 2 all-to-alls of the activations vs the ring's
P-1 K/V rotations; the trade is the classic DeepSpeed-Ulysses vs
ring-attention one — alltoall wins when H >= P and sequences are long.
Built on the framework's collective layer: ``lax.all_to_all`` on the fast
path (one XLA all-to-all on ICI), or the Pallas direct-write kernel
(``ops.pallas.alltoall``) in algorithm-faithful mode — the fused flat-tree
one-sided-write pattern of the reference's ``all_to_all``
(ccl_offload_control.c:2123-2218).

Requires ``H % P == 0`` (heads divide across devices).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .ring_attention import reference_attention


def _a2a(x: jax.Array, axis_name: str, split: int, concat: int) -> jax.Array:
    """XLA all-to-all: split ``split`` across the axis, concat ``concat``."""
    return lax.all_to_all(
        x, axis_name, split_axis=split, concat_axis=concat, tiled=True
    )


def _a2a_pallas(x, axis_name, split, concat, interpret):
    """Same re-shard via the Pallas direct-write kernel: move the split
    axis to the front, block-transpose, then re-assemble."""
    from ..ops.pallas.alltoall import alltoall

    size = lax.axis_size(axis_name)
    moved = jnp.moveaxis(x, split, 0)  # (split_dim, ...)
    flat = moved.reshape(moved.shape[0], -1)
    out = alltoall(flat, axis_name, interpret=interpret)
    out = out.reshape(moved.shape)
    # out block p (along dim 0) = peer p's block me; stitching them along
    # the concat axis reproduces lax.all_to_all(tiled) semantics
    out = jnp.moveaxis(out, 0, split)
    blocks = jnp.split(out, size, axis=split)
    return jnp.concatenate(blocks, axis=concat)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    *,
    use_pallas_alltoall: bool = False,
    interpret=None,
) -> jax.Array:
    """Attention over the full sequence with q/k/v sequence-sharded.

    q, k, v: ``(B, H, T_local, D)`` per device inside ``shard_map`` over a
    1-D mesh axis; returns the same shape.  ``H`` must be divisible by the
    axis size."""
    size = lax.axis_size(axis_name)
    B, H, T, D = q.shape
    if H % size:
        raise ValueError(f"heads {H} not divisible by axis size {size}")
    if size == 1:
        return reference_attention(q, k, v, causal=causal)

    a2a = (
        (lambda x, s, c: _a2a_pallas(x, axis_name, s, c, interpret))
        if use_pallas_alltoall
        else (lambda x, s, c: _a2a(x, axis_name, s, c))
    )

    # seq-sharded (H, T/P) -> head-sharded (H/P, T): split heads, gather seq
    qh, kh, vh = (a2a(t, 1, 2) for t in (q, k, v))
    # dense local attention over the full sequence for our head subset
    oh = reference_attention(qh, kh, vh, causal=causal)
    # head-sharded -> seq-sharded: split seq, gather heads
    return a2a(oh, 2, 1)
