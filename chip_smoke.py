#!/usr/bin/env python3
"""Does the system still start on the chip?  One process, every local chip.

``python chip_smoke.py`` drives the main path once through the entry
points a user calls, on the accelerator jax finds, and exits 0 only if
every leg passed:

* facade   — ``accl_tpu.core.xla_group`` over all local chips, one thread
  per rank, six collectives at three sizes against numpy, the warm loop
  under ``jax.transfer_guard("disallow")`` with one device interaction a
  call, and a batched window on the command ring;
* kernels  — the Pallas kernels compiled by Mosaic at benchmark sizes
  (several chips: the remote-DMA kernels against their XLA natives) and
  the fp8/int8 wire lanes against the host codec;
* flagship — ``make_sharded_train_step`` at full width for three steps
  (``attention="auto"`` must resolve to flash) and
  ``make_sharded_generate`` on the decode configuration;
* zoo      — ``__graft_entry__.dryrun_multichip`` on every chip.

It refuses to run off the TPU (no CPU fallback) and on a ``device_kind``
with no row in the peak table.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``.  It reports compile and
steady seconds apart for each leg and claims no speed: a smoke run is not
a measurement.

The legs are plain functions of their sizes, so tests/test_bench_harness.py
runs each one tiny on the 8-device CPU mesh; only ``main`` insists on
the chip.
"""

from __future__ import annotations

import faulthandler
import json
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Sequence

import numpy as np

_RANK_JOIN_S = 600.0
# a hung device program cannot be interrupted from Python: past this many
# seconds main() dumps every thread's stack and exits nonzero on its own
_DEADLINE_S = 1100


class _Clock:
    """Seconds split into the first call of each program (compile and
    run) and the calls after it."""

    def __init__(self):
        self.compile_s = 0.0
        self.steady_s = 0.0

    def timed(self, fn: Callable, cold: bool):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        if cold:
            self.compile_s += dt
        else:
            self.steady_s += dt
        return out

    def report(self, **fields) -> dict:
        return dict(
            fields,
            compile_s=round(self.compile_s, 2),
            steady_s=round(self.steady_s, 3),
        )


def _run_ranks(group: Sequence, fn: Callable) -> List:
    """``fn(handle, rank)`` on one thread per rank; first error re-raised."""
    results = [None] * len(group)
    errors: List = [None] * len(group)

    def runner(i):
        try:
            results[i] = fn(group[i], i)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors[i] = e

    threads = [
        threading.Thread(target=runner, args=(i,), daemon=True)
        for i in range(len(group))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(_RANK_JOIN_S)
        if t.is_alive():
            raise TimeoutError("a rank did not finish (collective deadlock)")
    for e in errors:
        if e is not None:
            raise e
    return results


def _block(x):
    import jax

    return jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# leg 1: the facade on the gang tier
# ---------------------------------------------------------------------------


def _facade_size(g, devs, nbytes: int, warm_iters: int, clock: _Clock) -> dict:
    """Six collectives at ``nbytes`` per rank: cold once, checked, then
    ``warm_iters`` times under the transfer guard with the shared
    interaction counter read between calls, checked again."""
    import jax

    from accl_tpu.buffer import DeviceBuffer

    P = len(g)
    per = max(nbytes // 4 // P, 1)
    n = per * P
    root = P - 1
    data = [
        np.random.default_rng(1000 + r).integers(-8, 8, n).astype(np.float32)
        for r in range(P)
    ]
    total = np.sum(data, axis=0)
    bufs = []
    for r, a in enumerate(g):
        b = {
            "send": a.create_buffer_from(data[r].copy()),
            "ar": a.create_buffer(n, np.float32),
            "ag": a.create_buffer(n, np.float32),
            "rs": a.create_buffer(per, np.float32),
            "a2a": a.create_buffer(n, np.float32),
            "bc": a.create_buffer_from(data[r].copy()),
            "rx": a.create_buffer(n, np.float32),
        }
        for name, buf in b.items():
            if not isinstance(buf, DeviceBuffer) or buf.device != devs[r]:
                raise AssertionError(
                    f"rank {r} buffer {name!r} is not on device {devs[r]}: "
                    f"{type(buf).__name__} on {getattr(buf, 'device', None)}"
                )
            if buf.device_array().devices() != {devs[r]}:
                raise AssertionError(
                    f"rank {r} buffer {name!r} array lives on "
                    f"{buf.device_array().devices()}, not {devs[r]}"
                )
        bufs.append(b)

    def p2p(a, r):
        b = bufs[r]
        sreq = a.send(b["send"], n, dst=(r + 1) % P, tag=7, run_async=True)
        a.recv(b["rx"], n, src=(r - 1) % P, tag=7)
        if not sreq.wait(_RANK_JOIN_S):
            raise TimeoutError("send never completed")
        sreq.check()

    ops = [
        ("allreduce", lambda a, r: a.allreduce(
            bufs[r]["send"], bufs[r]["ar"], n)),
        ("allgather", lambda a, r: a.allgather(
            bufs[r]["send"], bufs[r]["ag"], per)),
        ("reduce_scatter", lambda a, r: a.reduce_scatter(
            bufs[r]["send"], bufs[r]["rs"], per)),
        ("alltoall", lambda a, r: a.alltoall(
            bufs[r]["send"], bufs[r]["a2a"], per)),
        ("bcast", lambda a, r: a.bcast(bufs[r]["bc"], n, root=root)),
        ("sendrecv", p2p),
    ]

    def check(stage: str):
        for r in range(P):
            b = bufs[r]
            want = {
                "ar": total,
                "ag": np.concatenate([data[p][:per] for p in range(P)]),
                "rs": total[r * per:(r + 1) * per],
                "a2a": np.concatenate(
                    [data[p][r * per:(r + 1) * per] for p in range(P)]
                ),
                "bc": data[root],
                "rx": data[(r - 1) % P],
            }
            for name, expect in want.items():
                b[name].sync_from_device()
                if not np.array_equal(b[name].data, expect):
                    raise AssertionError(
                        f"{stage}: rank {r} {name} differs from numpy at "
                        f"{nbytes} bytes"
                    )

    clock.timed(
        lambda: _run_ranks(g, lambda a, r: [op(a, r) for _, op in ops]),
        cold=True,
    )
    check("cold")

    # warm: every rank meets at a barrier round each call so rank 0 can
    # read the gang's one interaction counter with nothing in flight
    counter = g[0].engine.gang.interactions
    gate = threading.Barrier(P, timeout=_RANK_JOIN_S)
    deltas: Dict[str, List[int]] = {name: [] for name, _ in ops}

    def warm(a, r):
        for _ in range(warm_iters):
            for name, op in ops:
                gate.wait()
                before = counter.read()
                gate.wait()
                with jax.transfer_guard("disallow"):
                    op(a, r)
                gate.wait()
                if r == 0:
                    deltas[name].append(counter.read() - before)

    clock.timed(lambda: _run_ranks(g, warm), cold=False)
    check("warm")
    for name, got in deltas.items():
        if name != "sendrecv" and got != [1] * warm_iters:
            raise AssertionError(
                f"{name} at {nbytes} bytes took {got} device interactions "
                "a warm call, not 1: it left the zero-host-copy path"
            )
    return {"count": n, "interactions": {k: v[-1] for k, v in deltas.items()}}


def _facade_window(g, window_bytes: int, clock: _Clock) -> dict:
    """A ``with a.batch():`` window of three collectives, cold then warm,
    which must ride the command ring: slots enqueued, no fallback, no
    breaker strike."""
    from accl_tpu.constants import ReduceFunction

    P = len(g)
    n = max(window_bytes // 4, 8)
    root = P - 1
    data = [
        np.random.default_rng(2000 + r).integers(-8, 8, n).astype(np.float32)
        for r in range(P)
    ]
    send = [a.create_buffer_from(data[r].copy()) for r, a in enumerate(g)]
    out_sum = [a.create_buffer(n, np.float32) for a in g]
    out_max = [a.create_buffer(n, np.float32) for a in g]
    bc = [a.create_buffer_from(data[r].copy()) for r, a in enumerate(g)]

    def window(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], out_sum[r], n, run_async=True),
                a.allreduce(send[r], out_max[r], n,
                            function=ReduceFunction.MAX, run_async=True),
                a.bcast(bc[r], n, root=root, run_async=True),
            ]
        for req in reqs:
            if not req.wait(_RANK_JOIN_S):
                raise TimeoutError("a batched collective never completed")
            req.check()

    def check(stage: str):
        for r in range(P):
            for buf, expect in (
                (out_sum[r], np.sum(data, axis=0)),
                (out_max[r], np.max(data, axis=0)),
                (bc[r], data[root]),
            ):
                buf.sync_from_device()
                if not np.array_equal(buf.data, expect):
                    raise AssertionError(
                        f"{stage} window: rank {r} result differs from numpy"
                    )

    clock.timed(lambda: _run_ranks(g, window), cold=True)
    check("cold")
    counter = g[0].engine.gang.interactions
    before = counter.read()
    clock.timed(lambda: _run_ranks(g, window), cold=False)
    warm_interactions = counter.read() - before
    check("warm")
    ring = g[0].engine.telemetry_report()["cmdring"]
    strikes = {
        c: b["reasons"] for c, b in ring["breakers"].items() if b["reasons"]
    }
    report = {
        "slots": ring["slots"],
        "dispatches": ring["dispatches"],
        "fallbacks": ring["fallbacks"],
        "breaker_strikes": strikes,
        "warm_interactions": warm_interactions,
    }
    if ring["slots"] <= 0 or ring["fallbacks"] or strikes:
        raise AssertionError(f"batched window left the command ring: {report}")
    return report


def leg_facade(n_ranks: int,
               sizes: Sequence[int] = (1 << 10, 1 << 20, 64 << 20),
               warm_iters: int = 2, window_bytes: int = 1 << 20) -> dict:
    import jax

    from accl_tpu.core import xla_group

    devs = jax.devices()[:n_ranks]
    if len(set(devs)) != n_ranks:
        raise AssertionError(f"need {n_ranks} distinct devices, have {devs}")
    clock = _Clock()
    g = xla_group(n_ranks)
    try:
        per_size = {
            str(nbytes): _facade_size(g, devs, nbytes, warm_iters, clock)
            for nbytes in sizes
        }
        window = _facade_window(g, window_bytes, clock)
    finally:
        for a in g:
            a.deinit()
    return clock.report(
        ranks=n_ranks,
        devices=[str(d) for d in devs],
        sizes=per_size,
        window=window,
    )


# ---------------------------------------------------------------------------
# leg 2: kernels compiled by Mosaic
# ---------------------------------------------------------------------------


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _kernels_local(elems: int, clock: _Clock) -> dict:
    """combine, the bf16 and stochastic casts, int8 quantize/dequantize
    on one chip, each against jax.numpy."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.ops import pallas as pk

    out = {}
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (elems,), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (elems,), jnp.float32)

    def twice(fn):
        first = clock.timed(lambda: _block(fn()), cold=True)
        clock.timed(lambda: _block(fn()), cold=False)
        return first

    combine = jax.jit(lambda x, y: pk.combine(x, y))
    got = twice(lambda: combine(a, b))
    if not bool(jnp.array_equal(got, a + b)):
        raise AssertionError("pallas combine differs from a + b")
    out["combine"] = "exact"

    cast = jax.jit(lambda x: pk.cast(x, jnp.bfloat16))
    got = twice(lambda: cast(a))
    if not bool(jnp.array_equal(got, a.astype(jnp.bfloat16))):
        raise AssertionError("pallas bf16 cast differs from astype")
    out["cast_bf16"] = "exact"

    sr = jax.jit(
        lambda x: pk.cast(x, jnp.bfloat16, stochastic=True, seed=7)
    )
    got = twice(lambda: sr(a)).astype(jnp.float32)
    # a stochastic round lands on one of the two bf16 neighbours: never
    # further than one bf16 step (2^-8 relative, 2^-7 across a binade)
    step = jnp.abs(a) * 2.0 ** -7 + 1e-30
    if not bool(jnp.all(jnp.abs(got - a) <= step)):
        raise AssertionError("stochastic cast moved a value past a neighbour")
    out["cast_stochastic_bias"] = float(jnp.mean(got - a))

    def quant_round_trip(x):
        v, s, cnt = pk.quantize_int8(x)
        return pk.dequantize_int8(v, s, cnt, x.shape, x.dtype), s

    qrt = jax.jit(quant_round_trip)
    got, scales = twice(lambda: qrt(a))
    # each tile quantizes to its own absmax/127 step: half a step of error
    worst = float(jnp.max(jnp.abs(got - a)))
    bound = float(jnp.max(scales)) * 0.5 * 1.001
    if not worst <= bound:
        raise AssertionError(
            f"int8 round trip error {worst} past half a step {bound}"
        )
    out["int8_round_trip_err"] = worst
    return out


def _kernels_flash(T: int, clock: _Clock, heads: int = 2, D: int = 128) -> dict:
    """Flash attention forward and backward at sequence length ``T`` in
    bf16 against the materialized-softmax form in f32."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.ops.pallas.attention import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k, v, w = (
        jax.random.normal(kk, (1, heads, T, D), jnp.bfloat16) for kk in keys
    )

    def naive(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def loss(fn):
        return lambda q, k, v: (
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)
        ).sum()

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    bwd = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))
    got_o = clock.timed(lambda: _block(fwd(q, k, v)), cold=True)
    clock.timed(lambda: _block(fwd(q, k, v)), cold=False)
    got_g = clock.timed(lambda: _block(bwd(q, k, v)), cold=True)
    clock.timed(lambda: _block(bwd(q, k, v)), cold=False)
    with jax.default_matmul_precision("highest"):
        want_o = jax.jit(naive)(q, k, v)
        want_g = jax.jit(jax.grad(loss(naive), argnums=(0, 1, 2)))(q, k, v)
    errs = {"o": _rel_err(got_o, want_o)}
    for name, g_, w_ in zip("qkv", got_g, want_g):
        errs["d" + name] = _rel_err(g_, w_)
    # bf16 operands against an f32 reference: 2^-8 per rounding, a few
    # roundings deep; a wrong mask or block is O(1)
    for name, e in errs.items():
        if not np.isfinite(e) or e > 4e-2:
            raise AssertionError(f"flash T={T} {name} off by {e} of max")
    return errs


def _kernels_remote(n: int, elems: int, clock: _Clock) -> dict:
    """The remote-DMA kernels over ``n`` chips, each against its XLA
    native inside the same ``shard_map``."""
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from accl_tpu.models.ring_attention import reference_attention
    from accl_tpu.ops import pallas as pk

    mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
    x = jnp.asarray(
        np.random.default_rng(5).integers(-8, 8, (n, elems)), jnp.float32
    )

    def smap(body, in_specs=P("x"), out_specs=P("x")):
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    def ring_rs(v):
        # the kernel returns rank i's reduced block of the lane-padded
        # operand as (rows, 128): elems is chosen so no padding exists
        return pk.ring_reduce_scatter(v[0], "x").reshape(-1)[None]

    pairs = {
        "ring_allreduce": (
            lambda v: pk.ring_allreduce(v[0], "x", num_segments=2)[None],
            lambda v: lax.psum(v[0], "x")[None],
        ),
        "ring_reduce_scatter": (
            ring_rs,
            lambda v: lax.psum_scatter(v[0], "x", tiled=True)[None],
        ),
        "ring_allgather": (
            lambda v: pk.ring_allgather(v[0], "x")[None],
            lambda v: lax.all_gather(v[0], "x", tiled=True)[None],
        ),
        "alltoall": (
            lambda v: pk.alltoall_kernel(v[0], "x")[None],
            lambda v: lax.all_to_all(
                v[0].reshape(n, -1), "x", 0, 0, tiled=True
            ).reshape(-1)[None],
        ),
        "ring_bcast": (
            lambda v: pk.ring_bcast(v[0], "x", root=n - 1)[None],
            lambda v: lax.all_gather(v[0], "x")[n - 1][None],
        ),
        "fused_shift": (
            lambda v: pk.fused_shift(v[0], "x", 1, lambda t: t * 2.0)[None],
            lambda v: lax.ppermute(
                v[0] * 2.0, "x", [(i, (i + 1) % n) for i in range(n)]
            )[None],
        ),
    }
    out = {}
    for name, (kernel, native) in pairs.items():
        fn = smap(kernel)
        got = clock.timed(lambda: _block(fn(x)), cold=True)
        clock.timed(lambda: _block(fn(x)), cold=False)
        want = smap(native)(x)
        if not bool(jnp.array_equal(got, want)):
            raise AssertionError(f"pallas {name} differs from its XLA native")
        out[name] = "exact"

    # the ring-attention kernel: K/V blocks rotate by remote DMA
    B, H, D = 1, 2, 128
    T = 128 * n
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5 for kk in keys
    )
    seq = P(None, None, "x", None)
    ring = smap(
        lambda q, k, v: pk.attention.ring_attention(q, k, v, "x"),
        in_specs=(seq,) * 3, out_specs=seq,
    )
    got = clock.timed(lambda: _block(ring(q, k, v)), cold=True)
    clock.timed(lambda: _block(ring(q, k, v)), cold=False)
    with jax.default_matmul_precision("highest"):
        want = reference_attention(q, k, v, causal=True)
    err = _rel_err(got, want)
    if not err <= 2e-2:
        raise AssertionError(f"pallas ring attention off by {err} of max")
    out["ring_attention_err"] = err
    return out


def _wire_lanes(n_ranks: int, elems: int, clock: _Clock) -> dict:
    """PR 15's fp8 and scaled-int8 wire lanes: the device encoder's bytes
    against ``accl_tpu.wire.encode_bytes``, then one facade allreduce on
    each lane inside the lane's rounding bound."""
    import jax.numpy as jnp

    from accl_tpu import compat, wire as hostwire
    from accl_tpu.constants import DataType
    from accl_tpu.core import xla_group
    from accl_tpu.ops import wire as devwire

    out = {"faithful_fp8_cast": compat.has_faithful_fp8_cast()}
    x = (
        np.random.default_rng(9).standard_normal(elems) * 2.0
    ).astype(np.float32)
    seed = 4242
    host = np.frombuffer(
        hostwire.encode_bytes(x, DataType.FLOAT8_E4M3, seed), np.uint8
    )
    dev = np.asarray(devwire._cast_lane(
        jnp.asarray(x), jnp.dtype("float8_e4m3fn"), jnp.uint32(seed)
    )).view(np.uint8)
    normal = np.abs(x) >= hostwire.lane_tiny(DataType.FLOAT8_E4M3)
    out["fp8_bytes_differ_normal"] = int((host != dev)[normal].sum())
    out["fp8_bytes_differ_total"] = int((host != dev).sum())
    hq, hs = hostwire._scaled_lane_encode(x, seed)
    dq, ds = devwire.quantize_int8(jnp.asarray(x), jnp.uint32(seed))
    dq, ds = np.asarray(dq), np.asarray(ds)
    out["int8_bytes_differ"] = int((hq != dq).sum())
    out["int8_scales_differ"] = int((hs != ds).sum())
    # stochastically rounded normals are exactly representable, so the
    # two codecs must agree on them whatever the platform's own cast does;
    # the int8 lane may differ by one code where the chip's divide is
    # not the host's, never by more
    if out["fp8_bytes_differ_normal"]:
        raise AssertionError(f"fp8 lane bytes differ from the host's: {out}")
    if np.max(np.abs(hq.astype(np.int32) - dq.astype(np.int32))) > 1:
        raise AssertionError(f"int8 lane codes differ by more than one: {out}")

    n = elems
    data = [
        (np.random.default_rng(30 + r).standard_normal(n) * 2.0).astype(
            np.float32
        )
        for r in range(n_ranks)
    ]
    for lane, dt in (("float8_e4m3", DataType.FLOAT8_E4M3),
                     ("int8", DataType.INT8)):
        g = xla_group(n_ranks)
        try:
            send = [a.create_buffer_from(data[r].copy())
                    for r, a in enumerate(g)]
            recv = [a.create_buffer(n, np.float32) for a in g]

            def work(a, r):
                a.set_tuning("wire_dtype", lane)
                a.allreduce(send[r], recv[r], n)

            clock.timed(lambda: _run_ranks(g, work), cold=True)
            recv[0].sync_from_device()
            got = recv[0].data.copy()
        finally:
            for a in g:
                a.deinit()
        # one (stochastic) rounding a contribution: an fp8 step is 2^-3
        # of the value and never finer than 2^-3 of the lane's smallest
        # normal (the subnormals' fixed spacing), an int8 step absmax/127
        # of its segment
        if dt == DataType.INT8:
            bound = sum(np.max(np.abs(d)) / 127.0 for d in data)
        else:
            tiny = hostwire.lane_tiny(dt)
            bound = np.sum(
                [np.maximum(np.abs(d), tiny) for d in data], axis=0
            ) * 2.0 ** -3
        err = np.abs(got - np.sum(data, axis=0))
        if not np.all(err <= bound + 1e-6):
            raise AssertionError(
                f"{lane} allreduce error {err.max()} past the lane's bound"
            )
        if err.max() < 1e-4:
            # f32 summation noise only: the compiler saw through the
            # narrow -> wide pair and the wire lane rounded nothing
            raise AssertionError(
                f"{lane} allreduce is exact to {err.max()}: the lane did "
                "not round (an f32 allreduce under the lane's name)"
            )
        out[f"{lane}_max_err"] = float(err.max())
    return out


def leg_kernels(n_chips: int, elems: int = 64 << 20,
                flash_lengths: Sequence[int] = (1024, 8192),
                remote_elems: int = 64 << 10,
                wire_elems: int = 1 << 20) -> dict:
    clock = _Clock()
    out = {"local": _kernels_local(elems, clock)}
    out["flash"] = {
        str(T): _kernels_flash(T, clock) for T in flash_lengths
    }
    if n_chips >= 2:
        out["remote"] = _kernels_remote(n_chips, remote_elems, clock)
    else:
        out["remote"] = "not run: the remote-DMA kernels need two chips"
    out["wire"] = _wire_lanes(n_chips, wire_elems, clock)
    return clock.report(chips=n_chips, **out)


# ---------------------------------------------------------------------------
# leg 3: the flagship train step and decode
# ---------------------------------------------------------------------------


def leg_flagship(train_cfg, decode_cfg, dp: int, tp: int, seq: int,
                 batch: int, steps: int = 3, prompt_len: int = 128,
                 new_tokens: int = 8, expect_attention: str = "flash") -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from accl_tpu.models import (
        init_params,
        make_sharded_generate,
        make_sharded_train_step,
    )
    from accl_tpu.models.transformer import resolve_attention

    clock = _Clock()
    mesh = Mesh(
        np.array(jax.devices()[: dp * tp]).reshape(dp, tp), ("dp", "tp")
    )
    head_dim = train_cfg.d_model // train_cfg.n_heads
    q_local = jax.ShapeDtypeStruct(
        (batch // dp, train_cfg.n_heads // tp, seq, head_dim),
        jnp.dtype(train_cfg.dtype),
    )
    resolved = resolve_attention(train_cfg.attention, q_local)
    if resolved != expect_attention:
        raise AssertionError(
            f"attention={train_cfg.attention!r} resolved to {resolved!r} for "
            f"a per-device q of {q_local.shape}, not {expect_attention!r}"
        )

    step, shard = make_sharded_train_step(train_cfg, mesh, lr=0.01)
    params = shard(init_params(jax.random.PRNGKey(0), train_cfg))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, train_cfg.vocab, (batch, seq)), jnp.int32
    )
    # sixteen target classes: enough signal a class that three bf16 SGD
    # steps move the loss (one target in `vocab` would round away)
    targets = tokens % 16
    losses = []
    for i in range(steps):
        params, loss = clock.timed(
            lambda: _block(step(params, tokens, targets)), cold=i == 0
        )
        losses.append(float(loss))
    del params
    if not all(np.isfinite(losses)) or not all(
        b < a for a, b in zip(losses, losses[1:])
    ):
        raise AssertionError(f"train loss is not finite and falling: {losses}")

    gen, gshard = make_sharded_generate(decode_cfg, mesh, new_tokens)
    gparams = gshard(init_params(jax.random.PRNGKey(1), decode_cfg))
    # rows i and i + half carry the same prompt and, with dp > 1, sit on
    # different chips: greedy decode must give them the same tokens
    gbatch = 2 * dp * max(batch // (2 * dp), 1)
    half = rng.integers(0, decode_cfg.vocab, (gbatch // 2, prompt_len))
    prompt = jnp.asarray(np.concatenate([half, half]), jnp.int32)
    toks = np.asarray(
        clock.timed(lambda: _block(gen(gparams, prompt)), cold=True)
    )
    again = np.asarray(
        clock.timed(lambda: _block(gen(gparams, prompt)), cold=False)
    )
    if toks.shape != (gbatch, new_tokens):
        raise AssertionError(f"decode gave {toks.shape}")
    if toks.min() < 0 or toks.max() >= decode_cfg.vocab:
        raise AssertionError("decode gave a token outside the vocabulary")
    if not np.array_equal(toks, again):
        raise AssertionError("greedy decode is not repeatable")
    if not np.array_equal(toks[: gbatch // 2], toks[gbatch // 2:]):
        raise AssertionError("equal prompts on different chips decoded apart")
    return clock.report(
        mesh={"dp": dp, "tp": tp},
        attention=resolved,
        losses=[round(x, 4) for x in losses],
        decode_tokens=toks[0].tolist(),
    )


# ---------------------------------------------------------------------------
# leg 4: the zoo's other sharded paths
# ---------------------------------------------------------------------------


def leg_zoo(n_chips: int) -> dict:
    import __graft_entry__

    clock = _Clock()
    clock.timed(lambda: __graft_entry__.dryrun_multichip(n_chips), cold=True)
    return clock.report(chips=n_chips)


# ---------------------------------------------------------------------------


def _full_width_configs():
    """The flagship train step's and the decode leg's configurations
    at their full widths."""
    import jax.numpy as jnp

    from accl_tpu.models import TransformerConfig

    train = TransformerConfig(
        vocab=32768, d_model=4096, n_heads=32, n_layers=6, d_ff=16384,
        max_seq=1024, dtype=jnp.bfloat16, attention="auto",
    )
    decode = TransformerConfig(
        vocab=32768, d_model=2048, n_heads=16, n_layers=8, d_ff=8192,
        max_seq=1024, dtype=jnp.bfloat16,
    )
    return train, decode


def main() -> int:
    faulthandler.dump_traceback_later(
        _DEADLINE_S, exit=True, file=sys.__stderr__
    )
    try:
        return _main()
    finally:
        faulthandler.cancel_dump_traceback_later()


def _main() -> int:
    import jax

    from accl_tpu.utils import device_peaks, use_compile_cache

    cache_dir = use_compile_cache()
    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU, jax found {device}", file=sys.stderr)
        return 1
    device_peaks(device["kind"])  # an unknown kind raises: never a default
    print(f"chip_smoke: device {json.dumps(device)} compile cache {cache_dir}",
          flush=True)

    n = len(devs)
    dp, tp = (2, 2) if n >= 4 else (n, 1)
    train_cfg, decode_cfg = _full_width_configs()
    legs = [
        ("facade", lambda: leg_facade(n)),
        ("kernels", lambda: leg_kernels(n)),
        ("flagship", lambda: leg_flagship(
            train_cfg, decode_cfg, dp=dp, tp=tp, seq=1024, batch=8 * dp,
        )),
        ("zoo", lambda: leg_zoo(n)),
    ]
    failed = []
    t_all = time.perf_counter()
    for name, leg in legs:
        t0 = time.perf_counter()
        try:
            report = dict(leg(), ok=True)
        except Exception as e:  # noqa: BLE001 - one leg's failure is
            # reported and the others still run; the exit code says so
            traceback.print_exc()
            report = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
            failed.append(name)
        report["wall_s"] = round(time.perf_counter() - t0, 1)
        print(f"chip_smoke: leg {name} {json.dumps(report)}", flush=True)
    print(f"chip_smoke: total {time.perf_counter() - t_all:.1f} s", flush=True)
    result = {"ok": not failed, "device": device}
    if failed:
        result["failed"] = failed
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
