"""Ring attention as one Pallas kernel: K/V blocks rotate over ICI while
the MXU folds the visiting block — wire/compute overlap *inside* the
kernel.

The model-level ``models.ring_attention`` expresses the rotation as
``lax.ppermute`` hops and leaves overlap to XLA's scheduler.  This kernel
owns the schedule the way the reference firmware owns its segmented ring
hot loop (ccl_offload_control.c:1888-2071 — recv/reduce/send of hop ``s``
overlapped explicitly): at every hop the *next* remote DMA is launched
first, then the just-arrived K/V block is folded into the online-softmax
state while the wire runs.  Slot reuse is ack-gated exactly like
``ops.pallas.ring`` (the RX-buffer release protocol).

Layout: per device q/k/v are ``(BH, T, D)`` — batch x heads folded into
the leading dim, D padded to the 128-lane width by the wrapper.  The
online-softmax state (running numerator, max, denominator) lives in VMEM
scratch in float32 regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (
    LANES,
    InterpretArg,
    ack_gate,
    ack_release,
    default_interpret,
    out_struct,
    require_mosaic_dtypes,
    neighbor_barrier,
)
from ...utils.remat import kept_under_remat



_NEG = -1e30


def _mxu_precision(dtype):
    """Dot precision for the attention kernels, from the operand dtype.

    The MXU's DEFAULT precision multiplies f32 operands in ONE bf16 pass
    (measured on v5e: 1.4e-1 max error on a 128x128 f32 matmul vs 6e-6
    under HIGHEST) — fine for bf16 training, but it silently downgrades
    an f32 kernel contract, and the interpreter tier (exact f32) would
    never show it.  f32 operands therefore request the multi-pass mode;
    bf16/int8 keep DEFAULT (single pass, already exact for their
    inputs)."""
    return (
        lax.Precision.HIGHEST
        if jnp.dtype(dtype) == jnp.float32 else None
    )


def attn_hop_partial(q, kv, scale):
    """One FUSED_ATTN_HOP epilogue: the scaled elementwise partial of the
    resident q block against the kv block that just arrived on the relay
    (the sequencer's flat-row form of a hop's score contribution — the
    blocked kernel above folds full (T, D) tiles; a fused slot streams
    the same hop product per lane row).  Shared by the command ring's
    decode loop and the engine's host-decomposition reference so the slot
    semantics have exactly one definition.  Works on jnp and numpy
    operands alike."""
    return (q * kv) * scale


def _fold(bh, q_ref, k_blk_ref, v_blk_ref, o_acc, m_ref, l_ref, mask, scale):
    """Fold one visiting K/V block into (o, m, l) for batch-head ``bh``.

    Matmul operands stay in the input dtype (bf16 keeps the MXU on its
    fast path; an f32 upcast quarters throughput on v5e) with f32
    accumulation via preferred_element_type; only the softmax state is
    f32."""
    q = q_ref[bh]
    k_blk = k_blk_ref[bh]
    v_blk = v_blk_ref[bh]
    scores = jax.lax.dot_general(
        q, k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_mxu_precision(q.dtype),
    ) * scale
    scores = jnp.where(mask, scores, _NEG)
    m_old = m_ref[bh][:, :1]
    m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_old - m_new)
    o_acc[bh] = o_acc[bh] * alpha + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_mxu_precision(v_blk.dtype),
    )
    l_ref[bh] = jnp.broadcast_to(
        l_ref[bh][:, :1] * alpha + p.sum(axis=-1, keepdims=True),
        l_ref[bh].shape,
    )
    m_ref[bh] = jnp.broadcast_to(m_new, m_ref[bh].shape)


def _attention_kernel(axis_name, size, causal, scale, striped=False):
    total_hops = size - 1

    def kernel(q_ref, k_ref, v_ref, o_ref,
               o_acc, m_ref, l_ref, comm, send_sem, recv_sem, ack_sem):
        BH, T, D = q_ref.shape
        me = lax.axis_index(axis_name)
        nxt = jnp.where(me + 1 == size, 0, me + 1)
        prv = jnp.where(me == 0, size - 1, me - 1)

        rows = lax.broadcasted_iota(jnp.int32, (T, T), 0)
        cols = lax.broadcasted_iota(jnp.int32, (T, T), 1)

        def mask_for(origin):
            """``rows + shift > cols`` with a SCALAR shift chosen per
            (rank, origin): 1 is the triangle with its diagonal, 0 the
            strict triangle, T everything, -T nothing.  Choosing between
            two boolean tiles instead does not compile: Mosaic on v5e
            has no ``arith.select`` on ``vector<8x128xi1>`` ("failed to
            legalize operation 'arith.select'", chip run, PR 21)."""
            if not causal:
                return jnp.ones((T, T), jnp.bool_)
            if striped:
                # round-robin token layout (models.stripe_sequence):
                # global q pos = tq*P + me, k pos = tk*P + origin, so the
                # mask is triangular for EVERY (rank, origin) pair — the
                # causal work balances across the ring
                shift = jnp.where(me >= origin, 1, 0)
            else:
                shift = jnp.where(
                    origin == me, 1, jnp.where(origin < me, T, -T)
                )
            return rows + shift > cols

        # init state + fold the local block
        for bh in range(BH):
            o_acc[bh] = jnp.zeros((T, D), jnp.float32)
            m_ref[bh] = jnp.full((T, LANES), _NEG, jnp.float32)
            l_ref[bh] = jnp.zeros((T, LANES), jnp.float32)

        if size > 1:
            neighbor_barrier(nxt, prv)

            # hop 1 in flight before any compute: send local K/V to next
            def start_hop(hop, src_k, src_v):
                slot = hop % 2
                ack_gate(ack_sem.at[slot], hop, value=2)  # 2 DMAs (K+V)
                for which, src in ((0, src_k), (1, src_v)):
                    pltpu.make_async_remote_copy(
                        src_ref=src,
                        dst_ref=comm.at[slot, which],
                        send_sem=send_sem.at[slot, which],
                        recv_sem=recv_sem.at[slot, which],
                        device_id=nxt,
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                    ).start()

            def wait_hop(hop):
                slot = hop % 2
                for which in (0, 1):
                    pltpu.make_async_remote_copy(
                        src_ref=comm.at[slot, which],
                        dst_ref=comm.at[slot, which],
                        send_sem=send_sem.at[slot, which],
                        recv_sem=recv_sem.at[slot, which],
                        device_id=nxt,
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                        # acclint: allow[unbounded-wait] Mosaic-traced DMA
                        # semaphore wait: no timeout form exists in Pallas;
                        # the host watchdog bounds the whole program
                    ).wait()

            start_hop(1, k_ref, v_ref)

        for bh in range(BH):
            _fold(bh, q_ref, k_ref, v_ref, o_acc, m_ref, l_ref,
                  mask_for(me), scale)

        for s in range(1, size):
            slot = s % 2
            wait_hop(s)  # K/V block s landed; send side of hop s drained
            # hop s's send read comm[(s-1)%2]; that drain (just waited) is
            # what frees the *previous* slot for the upstream neighbor —
            # acking any earlier would let prv overwrite a slot the
            # forwarding DMA is still reading (real race, caught by the
            # interpreter's detector).  Signal only while a future hop
            # (s+1 <= P-1 at prv) will consume the ack.
            if s >= 2:  # hop 1 sent from the input refs, not a comm slot
                ack_release(
                    ack_sem.at[(s - 1) % 2], s - 1, total_hops, prv, value=2
                )
            if s + 1 < size:
                # launch the next rotation *before* folding: the wire moves
                # hop s+1 while the MXU folds hop s (the overlap the
                # firmware gets from its segmented move pipeline)
                start_hop(s + 1, comm.at[slot, 0], comm.at[slot, 1])
            origin = jnp.mod(me - s, size)
            for bh in range(BH):
                _fold(bh, q_ref, comm.at[slot, 0], comm.at[slot, 1],
                      o_acc, m_ref, l_ref, mask_for(origin), scale)

        for bh in range(BH):
            o_ref[bh] = (
                o_acc[bh] / jnp.maximum(l_ref[bh][:, :1], 1e-30)
            ).astype(o_ref.dtype)

    return kernel


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    *,
    striped: bool = False,
    collective_id: int = 2,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Sequence-parallel attention in one Pallas kernel.

    q, k, v: ``(B, H, T_local, D)`` per device inside ``shard_map`` over a
    1-D mesh axis (sequence axis sharded).  Returns ``(B, H, T_local, D)``.
    D is padded to 128 lanes internally; T_local must be a multiple of 8.

    ``striped=True`` expects round-robin (striped) sequence shards
    (``models.stripe_sequence``): every hop's causal mask is then
    triangular, balancing the causal work across the ring instead of
    idling early ranks (Striped Attention) — same wire, same fold.
    """
    B, H, T, D = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"q/k/v shapes must match, got {q.shape}/{k.shape}/{v.shape}"
        )
    if k.dtype != q.dtype or v.dtype != q.dtype:
        raise ValueError(
            f"q/k/v dtypes must match (comm slots and DMAs are typed from "
            f"q), got {q.dtype}/{k.dtype}/{v.dtype}"
        )
    if T % 8:
        raise ValueError("T_local must be a multiple of 8")
    require_mosaic_dtypes(default_interpret(interpret), "ring attention",
                          q.dtype)
    size = lax.axis_size(axis_name)
    scale = 1.0 / (D ** 0.5)  # scale by the *logical* head dim, not padded

    pad = (-D) % LANES
    if pad:
        padding = [(0, 0)] * 3 + [(0, pad)]
        q, k, v = (jnp.pad(a, padding) for a in (q, k, v))
    Dp = D + pad

    qf = q.reshape(B * H, T, Dp)
    kf = k.reshape(B * H, T, Dp)
    vf = v.reshape(B * H, T, Dp)

    out = pl.pallas_call(
        _attention_kernel(axis_name, size, causal, scale, striped),
        out_shape=jax.ShapeDtypeStruct((B * H, T, Dp), q.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((B * H, T, Dp), jnp.float32),   # o accumulator
            pltpu.VMEM((B * H, T, LANES), jnp.float32),  # running max
            pltpu.VMEM((B * H, T, LANES), jnp.float32),  # running denom
            pltpu.VMEM((2, 2, B * H, T, Dp), q.dtype),   # K/V comm slots
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=default_interpret(interpret),
    )(qf, kf, vf)
    out = out.reshape(B, H, T, Dp)
    return out[..., :D] if pad else out


# ---------------------------------------------------------------------------
# single-chip flash attention (no ring): fused forward + custom backward
# ---------------------------------------------------------------------------


def _window_k_tiles(iq, b: int, nkb: int, window):
    """The k tiles ``[lo, hi)`` a causal q tile ``iq`` visits (tiles of
    ``b`` rows both ways): up to its own, and with a ``window`` from the
    tile that holds key ``iq * b - (window - 1)``, the oldest key its
    FIRST row sees.  ``window=None`` keeps ``lo`` the literal 0 it was."""
    hi = jnp.minimum(iq + 1, nkb)
    if window is None:
        return 0, hi
    return jnp.maximum(iq * b - (window - 1), 0) // b, hi


def _window_q_tiles(jk, b: int, nq: int, window):
    """The q tiles ``[lo, hi)`` that attend to causal k tile ``jk``: from
    its own, and with a ``window`` up to the tile that holds query
    ``(jk + 1) * b - 1 + (window - 1)``, the last one to see the tile's
    LAST key."""
    lo = jnp.minimum(jk, nq)
    if window is None:
        return lo, nq
    return lo, jnp.minimum(((jk + 1) * b + window - 2) // b + 1, nq)


#: what a visited (q tile, k tile) pair has to compare, known from the
#: tiles' distance ``d = iq - jk`` alone, in the order the counters list them
TILE_CLASSES = ("interior", "diagonal", "edge", "padded")
#: classes that are exactly one tile of a grid step where T is not padded
_ONE_TILE = ("diagonal", "block", "strict", "lower")


#: the classes under a block-diffusion layout (:func:`_layout_ranges`)
LAYOUT_TILE_CLASSES = ("interior", "block", "strict", "lower", "padded")


def _layout_ranges(y, n: int, b: int, block: int, noisy: bool, padded: bool,
                   forward: bool):
    """:func:`_tile_ranges` under a block-diffusion layout: the sequence
    is a NOISY half and a CLEAN half of ``n`` tiles each (tiles ``0..n``
    and ``n..2n``), both at positions ``0..L`` in blocks of ``block``.  A
    noisy query sees the noisy keys of its own block and the clean keys
    of the blocks before it; a clean query the clean keys of the blocks up
    to its own; nothing else is live.  ``y`` is the step's tile WITHIN its
    half (``noisy`` says which), the ranges are in whole-sequence tiles,
    ascending, and hold exactly the tiles with a live pair:

    * ``block < b`` (a tile holds whole blocks): the pairs of one tile
      index are the masked ones, ``block`` (noisy on noisy: same block),
      ``strict`` (noisy on clean: an earlier block) and ``lower`` (clean
      on clean: no later block); the clean tiles before are ``interior``.
      A noisy q tile folds its noisy tile, then the clean tiles up to its
      own: two ranges that do not touch.
    * ``block`` a multiple of ``b`` (a block holds ``m`` whole tiles):
      every live tile is all true, ``interior``.

    ``padded``: each half was padded up to the tiles and its last tile
    holds that many real rows (0: no padding).  A pair whose q or k tile
    is a half's last then compares everything, as the causal kernels'
    padded class does (only ``block < b`` can need padding: the halves
    are whole blocks); and where those rows are ONE block, the last noisy
    tile has no earlier block in the clean tile of its index, which is
    then not visited."""
    if block < b:
        if forward and noisy:
            ranges = [(y, y + 1, "block", False), (n, n + y, "interior", False),
                      (n + y, n + y + 1, "strict", False)]
        elif forward:
            ranges = [(n, n + y, "interior", False),
                      (n + y, n + y + 1, "lower", False)]
        elif noisy:
            ranges = [(y, y + 1, "block", False)]
        else:
            ranges = [(y, y + 1, "strict", False), (y + 1, n, "interior", True),
                      (n + y, n + y + 1, "lower", False),
                      (n + y + 1, 2 * n, "interior", True)]
    else:
        m = block // b
        g = y // m * m          # the first tile of y's block
        if forward and noisy:
            ranges = [(g, g + m, "interior", False),
                      (n, n + g, "interior", False)]
        elif forward:
            ranges = [(n, n + g + m, "interior", False)]
        elif noisy:
            ranges = [(g, g + m, "interior", False)]
        else:
            ranges = [(g + m, n, "interior", False),
                      (n + g, 2 * n, "interior", False)]
    if not padded:
        return [r[:3] for r in ranges]
    last = y == n - 1
    out = []
    for start, stop, cls, tail in ranges:
        if cls == "strict" and padded <= block:
            stop = jnp.where(last, start, stop)
        # what the step's own last tile pairs with, and (backward) a
        # range's last q tile where that is a half's last
        cut = stop - 1 if tail else stop
        cut = jnp.where(last, start, jnp.clip(cut, start, stop))
        out += [(start, cut, cls), (cut, stop, "padded")]
    return out


def _tile_ranges(y, n: int, b: int, causal: bool, window, padded: bool,
                 forward: bool, layout=None):
    """The tiles a grid step visits (all ``n`` when not causal, else
    :func:`_window_k_tiles` in the forward and :func:`_window_q_tiles` in
    the backward), split into consecutive ``(start, stop, class)`` ranges,
    ascending as the step folds them.  ``y`` is the step's own tile (a q
    tile in the forward, a k tile in the backward) and ``x`` the visited
    one, ``d = |y - x|`` tiles away, so ``q_pos - k_pos`` runs over
    ``d * b - (b - 1) .. d * b + (b - 1)``:

    * ``diagonal`` (``d == 0``): the only tile where ``q_pos >= k_pos`` can
      fail; under a window narrower than the tile it compares that too.
    * ``edge`` (``d * b + b - 1 >= window``, so ``d >= window // b``): the
      only tiles where ``q_pos - k_pos < window`` can fail.
    * ``interior``: every other tile of an unpadded T; its mask is all true.
    * ``padded``: T was padded up to the tiles and ``x`` or ``y`` is the
      last tile, the only ones that hold a position ``>= T``.

    The forward meets them in the order edge, interior, diagonal (``d``
    falls as k tiles ascend), the backward in the order diagonal, interior,
    edge; the padded range closes both.  Traced and plain integers alike
    (the kernels and :func:`flash_tile_classes` count by the same lines).

    ``layout=(noisy, block)`` is the block-diffusion geometry, which is
    no band: :func:`_layout_ranges` (``y`` within its half of ``n``)."""
    if layout is not None:
        return _layout_ranges(y, n, b, layout[1], layout[0], padded, forward)
    lo, hi = 0, n
    if causal:
        lo, hi = (_window_k_tiles if forward else _window_q_tiles)(
            y, b, n, window)
    end = hi
    if padded:
        end = jnp.where(y == n - 1, lo, jnp.minimum(hi, n - 1))
    if not causal:
        ranges = [(lo, end, "interior")]
    elif forward:
        diag = jnp.minimum(y, end)
        ranges = [(lo, diag, "interior"), (diag, end, "diagonal")]
        if window is not None:
            edge = jnp.clip(y - window // b + 1, lo, diag)
            ranges[:1] = [(lo, edge, "edge"), (edge, diag, "interior")]
    else:
        diag = jnp.minimum(y + 1, end)  # lo is y itself
        ranges = [(lo, diag, "diagonal"), (diag, end, "interior")]
        if window is not None:
            edge = jnp.clip(y + window // b, diag, end)
            ranges[1:] = [(diag, edge, "interior"), (edge, end, "edge")]
    if padded:
        ranges.append((end, hi, "padded"))
    return ranges


def _tile_mask(cls: str, rc, d, b: int, causal: bool, window, pad):
    """The ``(b, b)`` mask of one tile pair of class ``cls``, or ``None``
    where it is all true.  ``rc`` is ``row - col`` inside a tile, built
    once a grid step, so ``q_pos - k_pos`` of a pair ``d`` tiles apart is
    ``d * b + rc`` and each compare is ``rc`` against one scalar.  ``pad``
    are the ``t_real`` compares of a padded pair, which compares
    everything the call has besides (its ``d`` is any).  Where the class
    fixes the distance it is taken as a literal, so the compare is one
    the compiler folds: 0 on the diagonal, ``window // b`` on the edge of
    a window that ends within a key of a tile boundary (one edge tile a
    row then, as under Trinity's 2,048 keys in tiles of 512)."""
    if cls == "diagonal":
        d = 0
    elif cls == "edge" and window % b < 2:
        d = window // b
    terms = list(pad)
    if causal and cls in ("diagonal", "padded"):
        terms.append(rc >= -d * b)
    if window is not None and (
        cls in ("edge", "padded") or (cls == "diagonal" and window < b)
    ):
        terms.append(rc < window - d * b)
    return functools.reduce(jnp.logical_and, terms) if terms else None


def _block_index(pos, block: int):
    """``pos // block`` of non-negative int32 positions: a shift where
    ``block`` is a power of two (what Mosaic is sure to lower)."""
    if block & (block - 1) == 0:
        return lax.shift_right_logical(pos, block.bit_length() - 1)
    return lax.div(pos, block)


def _layout_mask(cls: str, bd, pad=(), bounds=None):
    """The ``(b, b)`` mask of one tile pair under a block-diffusion layout
    with whole blocks a tile, or ``None`` where it is all true.  ``bd`` is
    ``row // block - col // block`` inside a tile, built once a grid step:
    the q and k tiles of a masked pair hold the same positions, so the
    blocks' distance is ``bd`` itself and a class is one compare of it
    against 0.  A ``padded`` pair is of any kind: it compares ``bd`` with
    the ``bounds`` (two scalars, from the pair's halves and whether the
    tiles are the same) and the ``pad`` terms of the real length."""
    if cls == "interior":
        return None
    if cls == "block":
        return bd == 0
    if cls == "strict":
        return bd > 0
    if cls == "lower":
        return bd >= 0
    lo, hi = bounds
    return functools.reduce(
        jnp.logical_and, [*pad, bd >= lo, bd <= hi]
    )


_FAR = 1 << 30


def _layout_bounds(q_noisy, k_noisy, same):
    """The ``(lo, hi)`` a padded pair compares ``bd`` with
    (:func:`_layout_mask`): of the tiles at one index ``[0, 0]`` noisy on
    noisy, ``[1, far)`` noisy on clean, ``[0, far)`` clean on clean;
    everything where the k tile lies before.  Scalars, traced or not."""
    lo = jnp.where(same, jnp.where(k_noisy, 0, jnp.where(q_noisy, 1, 0)),
                   -_FAR)
    return lo, jnp.where(k_noisy, 0, _FAR)


def _fold_tiles(fold, carry, y, n: int, b: int, causal: bool, window,
                padded: bool, forward: bool, layout=None):
    """Fold the ranges of :func:`_tile_ranges` in their order, each by the
    body ``fold(class)`` traced for it.  Every range is a loop of a dynamic
    trip count (Mosaic lowers it to a while loop; the causal early exit and
    the window's bound are these trip counts) but the diagonal of an
    unpadded T, which is exactly the tile ``start``: folded in line, where
    its ``d`` is the literal 0 and its mask a constant the compiler folds
    into the vregs that straddle the diagonal; so are a layout's
    ``block``, ``strict`` and ``lower`` tiles, one tile each."""
    ranges = _tile_ranges(y, n, b, causal, window, padded, forward, layout)
    for start, stop, cls in ranges:
        if cls in _ONE_TILE and not padded:
            carry = fold(cls)(start, carry)
        else:
            carry = lax.fori_loop(start, stop, fold(cls), carry)
    return carry


def _layout_tile(L: int, block: int, dtype, tile: int) -> int:
    """The flash kernels' tile under a block-diffusion layout of halves
    of ``L`` in blocks of ``block``: :func:`_flash_block` of a HALF (the
    halves are padded to the tiles apart, so no tile straddles them).
    Refuses, by name, the layouts the kernels' ranges do not cover."""
    b = _flash_block(L, dtype, tile)
    if block < 1 or L % block:
        raise ValueError(
            f"block_diffusion: the block length ({block}) must divide the "
            f"half's length ({L})"
        )
    if not (block < b and b % block == 0) and block % b:
        raise ValueError(
            f"block_diffusion: the flash kernels take a block length that "
            f"divides their tile or is a multiple of it, got blocks of "
            f"{block} under tiles of {b} (the naive and blockwise forms "
            "take any)"
        )
    return b


def flash_tile_classes(T: int, block: int = 512, window=None,
                       dtype=jnp.bfloat16, block_diffusion=None,
                       forward: bool = True) -> dict:
    """How many of the (q tile, k tile) pairs the causal flash kernels
    visit for one head fall into each of :data:`TILE_CLASSES`, from the
    shapes and by the kernels' own ranges (:func:`_tile_ranges`; forward
    and backward agree): at T=8192 in tiles of 512, 120 interior and 16
    diagonal, under a window of 2048 42, 16 and 12 window-edge ones.

    ``block_diffusion=(L, B)`` (``T = 2 L``): the pairs under that layout
    by :data:`LAYOUT_TILE_CLASSES`; at L=4096, B=4 in tiles of 512, 56
    interior, 8 ``block``, 8 ``strict`` and 8 ``lower`` of 80, every one
    with a live pair and no other tile with one.  ``forward=False`` counts
    by the backward's lists (k tile major)."""
    if block_diffusion is not None:
        L, B = block_diffusion
        if T != 2 * L or window is not None:
            raise ValueError(
                f"block_diffusion=(L, B) lays out T = 2 L rows and has no "
                f"window, got T={T}, L={L}, window={window}"
            )
        b = _layout_tile(L, B, dtype, block)
        n = -(-L // b)
        counts = dict.fromkeys(LAYOUT_TILE_CLASSES, 0)
        for noisy in (True, False):
            for y in range(n):
                for start, stop, cls in _tile_ranges(
                    y, n, b, False, None, L % b, forward, (noisy, B)
                ):
                    counts[cls] += int(stop) - int(start)
        return counts
    b = _flash_block(T, dtype, block)
    n = -(-T // b)
    if window is not None and window >= T:
        window = None
    counts = dict.fromkeys(TILE_CLASSES, 0)
    for iq in range(n):
        for start, stop, cls in _tile_ranges(
            iq, n, b, True, window, T % b != 0, forward=forward
        ):
            counts[cls] += int(stop) - int(start)
    return counts


def flash_tile_pairs(T: int, block: int = 512, window=None,
                     dtype=jnp.bfloat16, block_diffusion=None) -> int:
    """How many (q tile, k tile) pairs the causal flash kernels visit for
    one head of a ``T``-long sequence, from the shapes and by the kernels'
    own bounds: 136 at T=8192 in tiles of 512, 70 of them under a window
    of 2048, 80 under ``block_diffusion=(4096, 4)``."""
    return sum(
        flash_tile_classes(T, block, window, dtype, block_diffusion).values()
    )


def _flash_kernel(causal, scale, bq, bk, nkb, t_real, with_lse=False,
                  window=None, rope=False, layout=None, sink=None):
    """One grid step computes one (bq, Dv) output block: fold the visiting
    k/v blocks with online softmax.  Outputs are written exactly once per
    grid step (blocked o spec): every grid axis of the FORWARD is
    independent, so none needs an "arbitrary" ordering.  (The backward
    kernel revisits its dq block across k tiles and orders that axis.)

    The fold body is chosen by the tile's class (:func:`_tile_ranges`),
    one traced body a class: an interior tile's scores go straight to the
    running max, no position, compare or select; the diagonal tile (in
    line, last) and the window-edge tiles (a loop of at most two, first)
    select by one compare of ``row - col`` against a scalar; the
    ``t_real`` compare is traced only when T was padded, into the body of
    the last tiles.  The same selects with the same truth values on the
    same tiles in the same order as one masked body on every tile gave.

    ``with_lse`` adds a per-row logsumexp output (the softmax normalizer,
    ``m + log l``) — the residual the backward kernel needs to rebuild
    the probabilities tile by tile without ever storing them.

    ``rope``: two more operands follow v, a second part of q and of k
    (``flash_attention``'s ``q_rope`` / ``k_rope``) whose product is
    added into the same score tile before the scale.

    ``layout``: the block length of a block-diffusion layout
    (:func:`_layout_ranges`; ``nkb`` tiles in two halves, ``t_real`` a
    half's real length).  The two halves' q tiles fold different lists, so
    the step branches once on its half and each branch is traced with its
    own ranges and its own in-line masked tiles.

    ``sink``: the number of heads ``H`` of one more operand, a learned
    scalar a head ``(H,)`` in SMEM that stands in every row's softmax as a
    key without a value (``flash_attention``'s ``sink``).  It is the fold's
    FIRST term: the carry starts at ``m = sink, l = 1, acc = 0`` in place of
    ``(-inf, 0, 0)`` and every body is what it was; the saved logsumexp then
    holds the sink, which is all the backward kernel needs of it."""

    halves = 1 if layout is None else 2
    nh = nkb // halves
    padded = t_real < nh * bk
    if layout is not None:
        padded = t_real % bk   # the real rows of a half's last tile

    def kernel(q_ref, k_ref, v_ref, *rest):
        if rope:
            qr_ref, kr_ref, *rest = rest
            qr = qr_ref[0]  # (bq, Dr)
        if sink:
            sink_ref, *rest = rest
        o_ref, *maybe_lse = rest
        iq = pl.program_id(1)
        # operands stay in the input dtype (bf16 MXU fast path); the
        # scale folds into the f32 scores, the softmax state is f32
        q = q_ref[0]  # (bq, D)
        # q_pos - k_pos of the pair (iq, j) is (iq - j) * bq + rc
        k_col = lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if layout is None:
            rc = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) - k_col
        else:
            # the blocks' distance of a pair of tiles at one index
            bd = _block_index(
                lax.broadcasted_iota(jnp.int32, (bq, bk), 0), layout
            ) - _block_index(k_col, layout)

        def causal_mask(cls, j):
            return _tile_mask(
                cls, rc, iq - j, bq, causal, window,
                [k_col < t_real - j * bk] if cls == "padded" else [],
            )

        def layout_mask(noisy, i):
            def mask_of(cls, j):
                if cls != "padded":
                    return _layout_mask(cls, bd)
                k_noisy = j < nh
                jh = jnp.where(k_noisy, j, j - nh)
                return _layout_mask(
                    cls, bd, [k_col < t_real - jh * bk],
                    _layout_bounds(noisy, k_noisy, jh == i),
                )

            return mask_of

        def fold(cls, mask_of=causal_mask):
            def body(j, carry):
                m, l, acc = carry
                kb = k_ref[0, pl.ds(j * bk, bk), :]
                vb = v_ref[0, pl.ds(j * bk, bk), :]
                s = jax.lax.dot_general(
                    q, kb,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(q.dtype),
                )
                if rope:
                    s = s + jax.lax.dot_general(
                        qr, kr_ref[0, pl.ds(j * bk, bk), :],
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=_mxu_precision(qr.dtype),
                    )
                s = s * scale
                mask = mask_of(cls, j)
                if mask is not None:
                    s = jnp.where(mask, s, _NEG)
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + p.sum(axis=-1, keepdims=True)
                acc_new = acc * alpha + jax.lax.dot_general(
                    p.astype(vb.dtype), vb,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(vb.dtype),
                )
                return m_new, l_new, acc_new

            return body

        init = (
            jnp.full((bq, 1), _NEG, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, v_ref.shape[-1]), jnp.float32),
        )
        if sink:
            init = (
                jnp.full((bq, 1), sink_ref[pl.program_id(0) % sink]),
                jnp.ones((bq, 1), jnp.float32), init[2],
            )
        # causal early exit: with bq == bk, q block iq only sees k blocks
        # 0..iq; under a window only those that reach into it.  A row whose
        # keys in the first visited tile are all outside the window folds
        # that tile at m = _NEG, and the next tile's alpha = exp(_NEG - m)
        # = 0 wipes it exactly; its own diagonal tile always comes.
        def write(m, l, acc):
            o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
            if with_lse:
                # (bq, 1) sublane vector -> (bq,) lane vector: an explicit
                # relayout Mosaic supports; rows beyond t_real carry ~-1e30
                # and are masked out by the backward kernel
                maybe_lse[0][0, 0, 0] = (
                    m + jnp.log(jnp.maximum(l, 1e-30))
                ).reshape(bq)

        if layout is None:
            write(*_fold_tiles(
                fold, init, iq, nkb, bq, causal, window, padded, forward=True
            ))
            return

        def half(noisy):
            i = iq if noisy else iq - nh
            masks = layout_mask(noisy, i)
            write(*_fold_tiles(
                lambda cls: fold(cls, masks), init, i, nh, bq, False, None,
                padded, forward=True, layout=(noisy, layout),
            ))

        pl.when(iq < nh)(functools.partial(half, True))
        pl.when(iq >= nh)(functools.partial(half, False))

    return kernel


def _flash_block(T: int, dtype, block: int) -> int:
    """Block height for the flash kernels: a sublane multiple (f32 8 /
    bf16 16 / int8 32 — Mosaic rejects smaller VMEM tiles); short
    sequences round T UP to the sublane grid and pad, they don't shrink
    the tile below it.  Forward and backward must agree on this."""
    from ._common import sublanes_for

    sub = sublanes_for(dtype)
    return min(max(block // sub * sub, sub), (T + sub - 1) // sub * sub)


def _flash_kv_map(H: int, Hkv: int, blocked: bool = False):
    """Grid index -> flattened K/V head.  For grouped-query attention
    (Hkv < H) q head ``h`` reads kv head ``h // G`` — sharing happens in
    the BlockSpec index map, so the smaller K/V never get materialized
    at H heads anywhere (the whole point of GQA's cache savings).
    ``blocked=True`` returns the (head, block-i, 0) form for specs whose
    second dim follows the grid's block index."""
    if H == Hkv:
        head = lambda bh: bh  # noqa: E731
    else:
        G = H // Hkv
        head = lambda bh: (bh // H) * Hkv + (bh % H) // G  # noqa: E731
    if blocked:
        return lambda bh, i: (head(bh), i, 0)
    return lambda bh, i: (head(bh), 0, 0)


def _default_scale(q, q_rope=None) -> float:
    """``1 / sqrt`` of the width the scores are taken over."""
    D = q.shape[-1] + (0 if q_rope is None else q_rope.shape[-1])
    return 1.0 / (D ** 0.5)


def _pad_rows(a, padT: int, halves: int = 1, value=0):
    """``a`` (B, H, T, ...) with ``padT`` more rows behind each of its
    ``halves`` (a block-diffusion layout's two are padded apart)."""
    if not padT:
        return a
    B, H, T = a.shape[:3]
    a = a.reshape(B, H, halves, T // halves, *a.shape[3:])
    a = jnp.pad(
        a, [(0, 0)] * 3 + [(0, padT)] + [(0, 0)] * (a.ndim - 4),
        constant_values=value,
    )
    return a.reshape(B, H, T + halves * padT, *a.shape[4:])


def _unpad_rows(a, T: int, halves: int = 1, axis: int = 2):
    """The first ``T / halves`` of each half of ``a``'s ``axis`` (of
    ``Tp`` padded rows)."""
    Tp = a.shape[axis]
    if Tp == T:
        return a
    lead, rest = a.shape[:axis], a.shape[axis + 1:]
    a = a.reshape(*lead, halves, Tp // halves, *rest)
    a = lax.slice_in_dim(a, 0, T // halves, axis=axis + 1)
    return a.reshape(*lead, T, *rest)


def _pad_rows_lanes(padT: int, *arrays, halves: int = 1):
    """``arrays`` (B, H, T, D) with ``padT`` more rows (behind each of
    ``halves``) and each last dim padded up to the lanes: an operand is as
    wide as what IT holds."""
    out = []
    for a in arrays:
        padD = (-a.shape[-1]) % LANES
        if padD:
            a = jnp.pad(a, [(0, 0), (0, 0), (0, 0), (0, padD)])
        out.append(_pad_rows(a, padT, halves))
    return out


def _tiling(T: int, dtype, block: int, layout):
    """``(tile, rows padded behind each half, halves, a half's length,
    the kernels' ``layout``)`` for a sequence of ``T``: under a
    block-diffusion ``layout=(L, B)`` the tile is a half's."""
    if layout is None:
        b = _flash_block(T, dtype, block)
        return b, (-T) % b, 1, T, None
    L, B = layout
    b = _layout_tile(L, B, dtype, block)
    return b, (-L) % b, 2, L, B


def _flat_heads(a):
    B, H, Tp, Dp = a.shape
    return a.reshape(B * H, Tp, Dp)


def _flash_fwd_impl(q, k, v, causal, block, interpret, with_lse,
                    window=None, scale=None, q_rope=None, k_rope=None,
                    layout=None, sink=None):
    B, H, T, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[-1]
    rope = q_rope is not None
    if scale is None:
        scale = _default_scale(q, q_rope)
    b, padT, halves, t_real, layout = _tiling(T, q.dtype, block, layout)
    q, k, v = _pad_rows_lanes(padT, q, k, v, halves=halves)
    Tp, Dp, Dvp = q.shape[2], q.shape[-1], v.shape[-1]
    nq = nkb = Tp // b

    qf, kf, vf = _flat_heads(q), _flat_heads(k), _flat_heads(v)
    kv_map = _flash_kv_map(H, Hkv)

    out_shape = [out_struct((B * H, Tp, Dvp), q.dtype, q, k, v)]
    out_specs = [
        pl.BlockSpec((1, b, Dvp), lambda bh, iq: (bh, iq, 0),
                     memory_space=pltpu.VMEM),
    ]
    if with_lse:
        # row-stat layout: (B*H, nq, 1, b) so the block (1, 1, 1, b) has
        # its last two dims EQUAL to the array's — the only tile shape
        # Mosaic accepts for a lane vector shorter than 128
        out_shape.append(
            out_struct((B * H, nq, 1, b), jnp.float32, q, k, v)
        )
        out_specs.append(
            pl.BlockSpec((1, 1, 1, b), lambda bh, iq: (bh, iq, 0, 0),
                         memory_space=pltpu.VMEM)
        )
    operands = [qf, kf, vf]
    in_specs = [
        pl.BlockSpec((1, b, Dp), lambda bh, iq: (bh, iq, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Tp, Dp), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Tp, Dvp), kv_map, memory_space=pltpu.VMEM),
    ]
    if rope:
        # the second part of q a head, and of k as few heads as it has
        # (one under MLA), each shared through the index map
        q_rope, k_rope = _pad_rows_lanes(padT, q_rope, k_rope, halves=halves)
        Drp = q_rope.shape[-1]
        operands += [_flat_heads(q_rope), _flat_heads(k_rope)]
        in_specs += [
            pl.BlockSpec((1, b, Drp), lambda bh, iq: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Tp, Drp), _flash_kv_map(H, k_rope.shape[1]),
                         memory_space=pltpu.VMEM),
        ]
    if sink is not None:
        # a scalar a head, whole in SMEM: the step reads its head's
        operands.append(sink.astype(jnp.float32))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    res = pl.pallas_call(
        _flash_kernel(causal, scale, b, b, nkb, t_real, with_lse=with_lse,
                      window=window, rope=rope, layout=layout,
                      sink=None if sink is None else H),
        grid=(B * H, nq),
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=default_interpret(interpret),
        name="flash_fwd",
    )(*operands)
    out = _unpad_rows(res[0].reshape(B, H, Tp, Dvp), T, halves)[..., :Dv]
    if not with_lse:
        return out, None
    # (B*H, nq, 1, b) -> rows
    return out, _unpad_rows(res[1].reshape(B, H, Tp), T, halves)


def _flash_bwd_kernel(causal, scale, bq, bk, nq, t_real, window=None,
                      rope=False, layout=None):
    """The whole backward of one (k tile, q tile) pair, once: grid step
    (bh, jk) owns one (bk, D) dk + (bk, Dv) dv block pair and folds the q
    blocks that attended to it (causal: q blocks jk..nq-1, a dynamic lower
    bound, the mirror of the forward's early exit; under a ``window`` an
    upper bound too, :func:`_window_q_tiles`).  The scores, the
    probabilities (rebuilt from the saved logsumexp, p = exp(s - lse),
    never stored), dp and ds of a pair feed dv, dk AND that q block's dq
    rows: five products a pair.  dq is an f32 accumulator of the whole
    (T, D) that stays in VMEM across the ``jk`` axis of one ``bh`` (so
    that axis is "arbitrary": zeroed at jk == 0, each q block's rows
    summed over k tiles in ascending order, cast into the revisited dq
    output block at the last jk).  The body of a pair is chosen by its
    class as in the forward (:func:`_tile_ranges`: the diagonal tile in
    line and first, the interior loop with ``p = exp(s - lse)`` and no
    mask, the window-edge loop); both ``t_real`` compares are traced only
    when T was padded, into the body of the pairs that hold the last q or
    k tile.

    ``rope``: the scores have a second part (``flash_attention``'s
    ``q_rope`` / ``k_rope``), so a pair has eight products: one more into
    the score tile, and ``ds`` into that part's dk block and dq
    accumulator, which follow the first part's as operands, outputs and
    scratch.

    ``layout``: as in :func:`_flash_kernel`; the step branches on its k
    tile's half and folds the q tiles of :func:`_layout_ranges`' backward
    lists (a clean k tile's in two ranges, the noisy and the clean q
    tiles that see it)."""

    halves = 1 if layout is None else 2
    nh = nq // halves
    padded = t_real < nh * bq
    if layout is not None:
        padded = t_real % bq   # the real rows of a half's last tile

    def kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dl_ref, *rest):
        if rope:
            kr_ref, qr_ref, *rest = rest
            dq_ref, dk_ref, dv_ref, dqr_ref, dkr_ref, dq_acc, dqr_acc = rest
        else:
            dq_ref, dk_ref, dv_ref, dq_acc = rest
        jk = pl.program_id(1)

        @pl.when(jk == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)
            if rope:
                dqr_acc[...] = jnp.zeros_like(dqr_acc)

        kb = k_ref[0]
        vb = v_ref[0]
        if rope:
            krb = kr_ref[0]
        # q_pos - k_pos of the pair (i, jk) is (i - jk) * bq + rc
        q_row = lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_col = lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if layout is None:
            rc = q_row - k_col
        else:
            bd = _block_index(q_row, layout) - _block_index(k_col, layout)

        def causal_mask(cls, i):
            return _tile_mask(
                cls, rc, i - jk, bq, causal, window,
                [k_col < t_real - jk * bk, q_row < t_real - i * bq]
                if cls == "padded" else [],
            )

        def layout_mask(noisy, j):
            def mask_of(cls, i):
                if cls != "padded":
                    return _layout_mask(cls, bd)
                q_noisy = i < nh
                ih = jnp.where(q_noisy, i, i - nh)
                return _layout_mask(
                    cls, bd,
                    [k_col < t_real - j * bk, q_row < t_real - ih * bq],
                    _layout_bounds(q_noisy, noisy, ih == j),
                )

            return mask_of

        def fold(cls, mask_of=causal_mask):
            def body(i, carry):
                dk, dv, *dkr = carry
                rows = pl.ds(i * bq, bq)
                qb = q_ref[0, rows, :]
                dob = do_ref[0, rows, :]
                # (bq,) lane vectors -> (bq, 1) sublane vectors for row
                # broadcast
                lse = lse_ref[0, i, 0].reshape(bq, 1)
                delta = dl_ref[0, i, 0].reshape(bq, 1)
                s = lax.dot_general(
                    qb, kb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(qb.dtype),
                )
                if rope:
                    qrb = qr_ref[0, rows, :]
                    s = s + lax.dot_general(
                        qrb, krb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=_mxu_precision(qrb.dtype),
                    )
                s = s * scale
                p = jnp.exp(s - lse)
                mask = mask_of(cls, i)
                if mask is not None:
                    # explicit where: padded q rows have lse ~ -1e30, where
                    # a bare exp(s - lse) would resurrect them as p = 1
                    p = jnp.where(mask, p, 0.0)
                dv = dv + lax.dot_general(
                    p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(dob.dtype),
                )
                dp = lax.dot_general(
                    dob, vb, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(dob.dtype),
                )
                ds = (p * (dp - delta) * scale).astype(qb.dtype)
                dk = dk + lax.dot_general(
                    ds, qb, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(qb.dtype),
                )
                dq_acc[rows, :] += lax.dot_general(
                    ds, kb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(kb.dtype),
                )
                if not rope:
                    return dk, dv
                dkr = dkr[0] + lax.dot_general(
                    ds, qrb, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(qrb.dtype),
                )
                dqr_acc[rows, :] += lax.dot_general(
                    ds, krb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=_mxu_precision(krb.dtype),
                )
                return dk, dv, dkr

            return body

        init = (jnp.zeros((bk, kb.shape[-1]), jnp.float32),
                jnp.zeros((bk, vb.shape[-1]), jnp.float32))
        if rope:
            init += (jnp.zeros((bk, krb.shape[-1]), jnp.float32),)
        def write(dk, dv, *dkr):
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)
            if rope:
                dkr_ref[0] = dkr[0].astype(dkr_ref.dtype)

        def half(noisy):
            j = jk if noisy else jk - nh
            masks = layout_mask(noisy, j)
            write(*_fold_tiles(
                lambda cls: fold(cls, masks), init, j, nh, bq, False, None,
                padded, forward=False, layout=(noisy, layout),
            ))

        # bq == bk
        if layout is None:
            write(*_fold_tiles(
                fold, init, jk, nq, bq, causal, window, padded, forward=False,
            ))
        else:
            pl.when(jk < nh)(functools.partial(half, True))
            pl.when(jk >= nh)(functools.partial(half, False))

        @pl.when(jk == pl.num_programs(1) - 1)
        def _():
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
            if rope:
                dqr_ref[0] = dqr_acc[...].astype(dqr_ref.dtype)

    return kernel


def _flash_bwd_vmem_bytes(Tp: int, Dp: int, b: int, itemsize: int,
                          Dvp: int | None = None, Drp: int = 0) -> int:
    """What one grid step of :func:`_flash_bwd_kernel` keeps in VMEM, from
    the shapes: q, dO and the dq output block whole (each double-buffered
    by the pipeline), the f32 dq accumulator, the k/v/dk/dv tiles, the two
    row statistics (a (1, b) f32 row fills an (8, b) tile), the (b, b)
    f32 temporaries of a pair (s, p, dp, ds and the two casts, counted as
    six) and the (b, b) int32 ``row - col`` the masked tiles compare.
    T=8192, D=128, bf16, b=512: 4+4+4 MiB, 4 MiB, 1 MiB, 1 MiB, 6 MiB,
    1 MiB = 25 MiB, past the compiler's 16 MiB scoped default — so the
    call passes this sum (and a quarter of it as room for what Mosaic
    spills) as its ``vmem_limit_bytes``.

    Each operand at its own padded width: q, k and their gradients
    ``Dp``, v, dO and dv ``Dvp`` (``Dp`` where not given), and with a
    second score part q_rope, k_rope and their gradients ``Drp`` (T=4096,
    192 | 128 as 128 + 64 -> 128: 23 MiB)."""
    Dvp = Dp if Dvp is None else Dvp
    whole = Tp * (2 * Dp + Dvp + 2 * Drp) * itemsize   # q, dq, dO, q_rope, dq_rope
    tiles = 2 * b * (Dp + Dvp + Drp) * itemsize        # k, v, k_rope and theirs
    stats = 2 * (Tp // b) * 8 * b * 4
    return (
        2 * (whole + tiles + stats) + Tp * (Dp + Drp) * 4 + 7 * b * b * 4
    )


def _flash_bwd_impl(q, k, v, o, lse, g, causal, block, interpret,
                    window=None, scale=None, q_rope=None, k_rope=None,
                    layout=None):
    B, H, T, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[-1]
    rope = q_rope is not None
    if scale is None:
        scale = _default_scale(q, q_rope)
    b, padT, halves, t_real, layout = _tiling(T, q.dtype, block, layout)
    # delta = rowsum(dO * O): the softmax-transpose correction, a cheap
    # fused elementwise+reduce XLA does well — no kernel needed
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    q, k, v, g = _pad_rows_lanes(padT, q, k, v, g, halves=halves)
    lse = _pad_rows(lse, padT, halves, value=_NEG)
    delta = _pad_rows(delta, padT, halves)
    Tp, Dp, Dvp = q.shape[2], q.shape[-1], v.shape[-1]
    nq = nkb = Tp // b

    qf, kf, vf, dof = (_flat_heads(a) for a in (q, k, v, g))
    # row-stat layout (see _flash_fwd_impl): block last-two dims == array
    lsef = lse.reshape(B * H, nq, 1, b)
    dlf = delta.reshape(B * H, nq, 1, b)
    vmem = pltpu.VMEM

    def tile(width, heads=H):
        """A (b, width) tile of the ``heads`` that the q heads share."""
        return pl.BlockSpec((1, b, width),
                            _flash_kv_map(H, heads, blocked=True),
                            memory_space=vmem)

    def whole(width):
        return pl.BlockSpec((1, Tp, width), lambda bh, j: (bh, 0, 0),
                            memory_space=vmem)

    rows_whole = pl.BlockSpec((1, nq, 1, b), lambda bh, j: (bh, 0, 0, 0),
                              memory_space=vmem)

    # dk/dv come out PER Q-HEAD (a kv head's blocks are written once by
    # each q head of its group); the group sum is one cheap XLA reduction
    # after the kernel
    def grad_struct(width):
        return out_struct((B * H, Tp, width), q.dtype, q, k, v, g)

    operands = [kf, vf, qf, dof, lsef, dlf]
    in_specs = [tile(Dp, Hkv), tile(Dvp, Hkv), whole(Dp), whole(Dvp),
                rows_whole, rows_whole]
    out_shape = [grad_struct(Dp), grad_struct(Dp), grad_struct(Dvp)]
    out_specs = [whole(Dp), tile(Dp), tile(Dvp)]
    scratch = [pltpu.VMEM((Tp, Dp), jnp.float32)]
    Drp = 0
    if rope:
        Dr, Hr = q_rope.shape[-1], k_rope.shape[1]
        q_rope, k_rope = _pad_rows_lanes(padT, q_rope, k_rope, halves=halves)
        Drp = q_rope.shape[-1]
        operands += [_flat_heads(k_rope), _flat_heads(q_rope)]
        in_specs += [tile(Drp, Hr), whole(Drp)]
        out_shape += [grad_struct(Drp), grad_struct(Drp)]
        out_specs += [whole(Drp), tile(Drp)]
        scratch.append(pltpu.VMEM((Tp, Drp), jnp.float32))
    resident = _flash_bwd_vmem_bytes(Tp, Dp, b, q.dtype.itemsize, Dvp, Drp)
    dq, dk, dv, *rope_grads = pl.pallas_call(
        _flash_bwd_kernel(causal, scale, b, b, nq, t_real, window, rope=rope,
                          layout=layout),
        grid=(B * H, nkb),
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=resident + resident // 4,
        ),
        interpret=default_interpret(interpret),
        name="flash_bwd",
    )(*operands)

    def group_sum(a, heads, width):
        """Per-q-head blocks summed onto the ``heads`` that share them,
        at their real ``width``."""
        a = a.reshape(B, heads, H // heads, Tp, a.shape[-1])
        a = _unpad_rows(a, T, halves, axis=3)[..., :width]
        if H == heads:
            return a[:, :, 0]
        return a.astype(jnp.float32).sum(2).astype(k.dtype)

    def rows_of(a, width):
        return _unpad_rows(a.reshape(B, H, Tp, a.shape[-1]), T, halves)[
            ..., :width
        ]

    grads = (rows_of(dq, D), group_sum(dk, Hkv, D), group_sum(dv, Hkv, Dv))
    if not rope:
        return grads + (None, None)
    dqr, dkr = rope_grads
    return grads + (rows_of(dqr, Dr), group_sum(dkr, Hr, Dr))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_vjp(q, k, v, q_rope, k_rope, sink, causal, block, interpret,
                    window, scale, layout):
    """``scale``, the scores' second part, the ``sink`` and the
    block-diffusion ``layout`` may each be ``None`` (a ``None`` among the
    differentiable arguments adds no leaf: a call without it traces what it
    always did)."""
    out, _ = _flash_fwd_impl(q, k, v, causal, block, interpret,
                             with_lse=False, window=window, scale=scale,
                             q_rope=q_rope, k_rope=k_rope, layout=layout,
                             sink=sink)
    return out


def _flash_vjp_fwd(q, k, v, q_rope, k_rope, sink, causal, block, interpret,
                        window, scale, layout):
    out, lse = _flash_fwd_impl(q, k, v, causal, block, interpret,
                               with_lse=True, window=window, scale=scale,
                               q_rope=q_rope, k_rope=k_rope, layout=layout,
                               sink=sink)
    # the kernel's two outputs are all that the backward reads of it: a
    # rematerialised block that keeps both never calls ``flash_fwd`` again
    out, lse = kept_under_remat(out), kept_under_remat(lse)
    return out, (q, k, v, q_rope, k_rope, sink, out, lse)


def _flash_vjp_bwd(causal, block, interpret, window, scale, layout, res, g):
    """With a ``sink`` the backward kernel is as it is: ``exp(s - lse)``
    with the sink inside ``lse`` IS the sink's softmax, and ``delta =
    rowsum(dO * O)`` needs no term of the sink, whose value is zero.  The
    sink's own gradient is ``-sum_rows p_sink * delta``, ``p_sink =
    exp(sink - lse)``: a reduce of two row statistics, XLA's."""
    q, k, v, q_rope, k_rope, sink, o, lse = res
    grads = _flash_bwd_impl(q, k, v, o, lse, g, causal, block, interpret,
                            window, scale, q_rope, k_rope, layout)
    if sink is None:
        return grads + (None,)
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    p_sink = jnp.exp(sink.astype(jnp.float32)[None, :, None] - lse)
    return grads + ((-(p_sink * delta).sum((0, 2))).astype(sink.dtype),)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    *,
    block: int = 512,
    window: int | None = None,
    scale: float | None = None,
    q_rope: jax.Array | None = None,
    k_rope: jax.Array | None = None,
    block_diffusion: tuple | None = None,
    sink: jax.Array | None = None,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Local (single-chip) fused attention: ``(B, H, T, D) -> same`` with
    the (T, T) score matrix never leaving VMEM — the kernel-owned form of
    ``ops.attention.blockwise_attention``, and like it fully trainable:
    a ``custom_vjp`` pairs the forward kernel ``flash_fwd`` (which saves
    only o + per-row logsumexp) with ONE backward kernel ``flash_bwd``
    that rebuilds each visited tile pair's probabilities once and feeds
    dq, dk and dv from them (five matrix products a pair).  dk/dv blocks
    are written once a grid step; the dq block of a batch-head is
    revisited across the k-tile axis, summed in f32 in VMEM.

    Grouped-query attention comes free: pass k/v with FEWER heads
    (``(B, Hkv, T, D)``, ``H % Hkv == 0``) and q head ``h`` reads kv head
    ``h // (H // Hkv)`` through the BlockSpec index map — the smaller K/V
    are never expanded to H heads anywhere (fwd or bwd).

    K/V (forward) and q/dO/dq (backward) live whole in VMEM per
    (batch*head) grid step — sized for serving/training sequence lengths
    (T <= ~8K at 128 lanes; the backward passes the sum of its residents
    as its VMEM limit, :func:`_flash_bwd_vmem_bytes`); the ring kernel
    covers longer sequences across chips.  The model's ``auto`` gates on
    the PADDED width of ``q`` (``models.transformer._auto_flash_fits``): a
    head of 192 as one operand pads to 256 lanes and falls back to
    ``blockwise`` past T = 4,096, while the same head with its rotating
    part passed apart (``q_rope`` / ``k_rope``: 128 + 64, the MiMo-V2.5
    cell's) is gated by each part's own padded width and stays here at
    8,192.

    ``window=W`` (causal only) is sliding-window attention: query ``i``
    sees keys ``j`` with ``0 <= i - j < W``, its own among them.  Forward
    and backward visit only the tile pairs that reach into the window
    (:func:`flash_tile_pairs`: 70 of 136 at T=8192, W=2048) and compare
    ``i - j < W`` only on the tiles the window's edge crosses; ``W >= T``
    is plain causal attention and runs as it.

    Both kernels fold a tile pair by the body of its class
    (:func:`flash_tile_classes`: interior, diagonal, window-edge, padded;
    120 / 16 / 0 / 0 of the 136 pairs at T=8192 in tiles of 512, 42 / 16 /
    12 / 0 of the 70 under a window of 2048): no mask work at all on an
    interior tile, one compare against a scalar on a diagonal or
    window-edge one, the ``T`` compares only where T was padded.  The
    class comes from the tiles' distance, the window and the padding,
    which the kernels see; nothing a caller sets.

    TWO WIDTHS: v ``(B, Hkv, T, Dv)`` and the output ``(B, H, T, Dv)``
    may be another width than q and k; every operand and gradient is
    padded to the lanes on its own, so no product runs over columns that
    are padding because another operand is wider.  ``scale`` multiplies
    the scores (``(D + Dr) ** -0.5`` where not given).  ``q_rope``
    ``(B, H, T, Dr)`` and ``k_rope`` ``(B, Hr, T, Dr)`` (``H % Hr == 0``)
    are a SECOND PART of q and k: ``q_rope . k_rope`` is added into the
    same f32 score tile (two products into one tile; three products a
    pair forward, eight backward), and k's part may have fewer heads than
    k itself, shared through the index map as a kv head is (DeepSeek-V2's
    latent attention: 128 + 64 columns scored, 128 of values, ONE rope
    key head for 128 query heads, never expanded in HBM).

    ``block_diffusion=(L, B)`` is the layout of block-diffusion training
    (BD3-LM's), in place of ``causal`` and a window: the ``T = 2 L`` rows
    are a NOISY copy of a sequence and then the CLEAN one, both at
    positions ``0..L`` in blocks of ``B``.  A noisy query sees the noisy
    keys of its own block (both ways) and the clean keys of the blocks
    strictly before; a clean query the clean keys of the blocks up to and
    including its own; no query sees a noisy key outside its block.  The
    kernels visit exactly the tile pairs that hold a live (q, k) pair
    (:func:`_layout_ranges`; 80 of 256 at L=4096, B=4 in tiles of 512, 56
    of them with no mask work), a noisy q tile in two ranges that do not
    touch, the backward by the transposed lists; the noisy-on-noisy tiles
    are a class of the kernel.  ``B`` divides the tile or is a multiple of
    it (anything else is refused by name; the naive and blockwise forms
    take any), an ``L`` off the tiles pads each half apart, and GQA, the
    two widths and the second score part work as without it.

    ``sink`` ``(H,)``: a learned scalar a QUERY head that stands in every
    row's softmax as one more key WITHOUT a value, after the scale: ``p_ij =
    exp(s_ij - m_i) / (sum_j' exp(s_ij' - m_i) + exp(sink_h - m_i))`` with
    ``m_i`` the larger of the row's largest score and the sink; it takes
    probability and adds nothing (an attention sink; causal, with or without
    a window, beside GQA, the two widths and the second score part; not under
    a block-diffusion layout).  The forward kernel only starts its fold from
    another carry and the backward kernel is untouched (``_flash_vjp_bwd``);
    ``d sink`` is a reduce of the saved row statistics outside it.

    The forward rule NAMES the kernel's two outputs, ``o`` and ``lse``
    (``utils.remat.kept_under_remat``): a block rematerialised under
    ``save_only_these_names`` of that name (``models.transformer``'s
    ``remat``) keeps them and its backward never calls ``flash_fwd`` a second
    time; anywhere else the name is the identity.

    ``block=512`` is the measured optimum on v5e at T=4096: vs 256 the
    forward runs 2.1x faster (40.7 vs 19.6 TFLOPs) and the full T=4096
    train step gains 6.9 MFU points (62.1% -> 69.0%, A/B on the bench's
    own step); 1024 regresses (VMEM pressure).  Short sequences clamp
    the block to T via ``_flash_block``."""
    B, H, T, D = q.shape
    Bk, Hkv, Tk, Dk = k.shape
    if (Bk, Tk, Dk) != (B, T, D) or Hkv <= 0 or H % Hkv:
        raise ValueError(
            f"q/k shapes must match outside the head dim and q heads must "
            f"be a multiple of kv heads, got {q.shape}/{k.shape}"
        )
    if k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"k/v shapes must match outside the width, got "
            f"{k.shape}/{v.shape}"
        )
    if k.dtype != q.dtype or v.dtype != q.dtype:
        raise ValueError(
            f"q/k/v dtypes must match (tiles and accumulators are typed "
            f"from q), got {q.dtype}/{k.dtype}/{v.dtype}"
        )
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together")
    if q_rope is not None:
        Hr = k_rope.shape[1]
        if (
            q_rope.shape[:3] != (B, H, T) or Hr <= 0 or H % Hr
            or k_rope.shape != (B, Hr, T, q_rope.shape[-1])
            or q_rope.dtype != q.dtype or k_rope.dtype != q.dtype
        ):
            raise ValueError(
                f"q_rope is q's second part (B, H, T, Dr) and k_rope k's, "
                f"on heads that divide H, in q's dtype; got {q_rope.shape} "
                f"{q_rope.dtype}/{k_rope.shape} {k_rope.dtype} for q "
                f"{q.shape} {q.dtype}"
            )
    require_mosaic_dtypes(default_interpret(interpret), "flash attention",
                          q.dtype)
    if sink is not None and (
        sink.shape != (H,) or not causal or block_diffusion is not None
    ):
        raise ValueError(
            f"sink is one scalar a query head, ({H},), of a causal softmax "
            f"(no block-diffusion layout); got {sink.shape}, causal={causal}, "
            f"block_diffusion={block_diffusion}"
        )
    if block_diffusion is not None:
        L, B_len = (int(n) for n in block_diffusion)
        if window is not None or T != 2 * L:
            raise ValueError(
                f"block_diffusion=(L, B) lays out T = 2 L rows and has no "
                f"window, got T={T}, L={L}, window={window}"
            )
        _layout_tile(L, B_len, q.dtype, block)   # refuses what it cannot
        return _flash_vjp(
            q, k, v, q_rope, k_rope, None, False, block, interpret, None,
            None if scale is None else float(scale), (L, B_len),
        )
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"a window ({window}) is causal and at least 1 key wide"
            )
        if window >= T:
            window = None  # every earlier key is inside it
    return _flash_vjp(
        q, k, v, q_rope, k_rope, sink, causal, block, interpret, window,
        None if scale is None else float(scale), None,
    )
