"""Traffic driver ``collective_sweep``: a closed loop of facade
collectives, one caller thread a rank, in three phases.

* large / small — blocking calls of each (op, size), cycling a fixed
  order in whole rounds.  A call is timed from the instant the rank
  threads are released together (a barrier's action stamps it) to the
  instant the LAST rank's output is ready on its device
  (``block_until_ready`` on the receive buffer's array, whatever the
  facade's own completion path does).
* window — ``with a.batch():`` windows of ``run_async=True`` collectives,
  timed the same way from release to every request waited and every
  output ready.

What is copied from the program's own scripts and kept here so that no
later PR can change the yardstick: one thread a rank re-raising the
first error, integer-valued float32 payloads checked bit-equal against
numpy, the gang's interaction counter read with nothing in flight, and
``transfer_guard("disallow")`` around every blocking call (all from
``chip_smoke.py``); the ops x sizes shape of ``benchmarks/sweep.py``.
The clock is NOT ``Request.get_duration_ns``: it is ``perf_counter_ns``
around work that ends in ``block_until_ready``.
"""

from __future__ import annotations

import statistics
import threading
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from perfbench import flops
from perfbench.drivers._base import DriverBase
from perfbench.tracing import Tracer

_JOIN_S = 600.0


def _run_ranks(n: int, fn: Callable) -> None:
    """``fn(rank)`` on one thread a rank; the first error is re-raised."""
    errors: List = [None] * n

    def runner(r):
        try:
            fn(r)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors[r] = e

    threads = [threading.Thread(target=runner, args=(r,), daemon=True)
               for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(_JOIN_S)
        if t.is_alive():
            raise TimeoutError("a rank did not finish (collective deadlock)")
    raised = [e for e in errors if e is not None]
    if raised:
        # the cause, not the broken gate the other ranks met because of it
        raise next((e for e in raised
                    if not isinstance(e, threading.BrokenBarrierError)),
                   raised[0])


def payload(key, rank, k, n: int):
    """``n`` integer-valued float32 in [-8, 8) for send buffer ``k`` of
    ``rank``: eight values a random word, so a sum over ranks is exact in
    float32 and gigabytes of them cost an eighth of the random bits.  The
    eight nibbles are the MAJOR axis: with them minor, the TPU pads each
    row of 8 to 128 lanes (1 GiB of scratch and 2.2 GiB of traffic for a
    64 MiB buffer, compile for v5e, PR 22).  Plain jax.numpy: the
    generator AND the on-device reference call it."""
    key = jax.random.fold_in(jax.random.fold_in(key, rank), k)
    words = jax.random.bits(key, (n // 8,), jnp.uint32)
    shifts = jnp.arange(0, 32, 4, dtype=jnp.uint32)
    nibbles = (words[None, :] >> shifts[:, None]) & jnp.uint32(15)
    return (nibbles.reshape(n).astype(jnp.int32) - 8).astype(jnp.float32)


def expected(op: str, data, per: int, mine, xp):
    """What a rank must receive when every rank p sends ``data[p]``: the
    plain reference, in numpy on the host (``xp=np``) or in jax.numpy on
    one device (``xp=jnp``).  ``mine(d)`` is the rank's own ``per``-long
    chunk of ``d``."""
    if op == "allreduce":
        return sum(data[1:], data[0])
    if op == "allgather":
        return xp.concatenate([d[:per] for d in data])
    if op == "reduce_scatter":
        return mine(sum(data[1:], data[0]))
    if op == "alltoall":
        return xp.concatenate([mine(d) for d in data])
    raise KeyError(op)


class _Call:
    """One (op, size): per-rank send buffers (a pool that cycles) and one
    receive buffer a rank."""

    def __init__(self, op: str, nbytes: int, world: int, send, recv, key):
        self.op = op
        self.key = key        # the PRNG key its send buffers were made from
        self.nbytes = nbytes
        self.n = nbytes // 4
        self.per = self.n // world
        self.send = send      # [rank][k] -> DeviceBuffer
        self.recv = recv      # [rank]    -> DeviceBuffer
        self.turn = [0] * world
        self.bus_bytes = flops.bus_bytes(op, nbytes, world)

    @property
    def label(self) -> str:
        return f"{self.op}@{self.nbytes}"

    def count(self) -> int:
        return self.n if self.op == "allreduce" else self.per

    def issue(self, a, r: int, run_async: bool = False):
        pool = self.send[r]
        k = self.turn[r]
        self.turn[r] = (k + 1) % len(pool)
        return getattr(a, self.op)(
            pool[k], self.recv[r], self.count(), run_async=run_async
        )


class Driver(DriverBase):
    def __init__(self, cell: dict, seed: int, devices, rehearse: bool):
        super().__init__(cell, seed, devices, rehearse)
        self.world = int(self.config["world"])
        self.devices = list(devices)[: self.world]
        self.mesh = Mesh(np.array(self.devices), ("x",))
        self.group = None
        self._checks: dict = {}  # nbytes -> the on-device reference program

    # -- set-up --------------------------------------------------------------

    def _key(self, salt: int):
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), salt)

    def _seeded(self, nbytes: int, copies: int, key):
        """``copies`` distinct seeded buffers of ``nbytes`` on EVERY
        rank's device, made on the devices in one program:
        [rank][k] -> single-device jax.Array."""
        P = PartitionSpec
        n = nbytes // 4

        def make(key):
            rank = jax.lax.axis_index("x")
            return tuple(payload(key, rank, k, n) for k in range(copies))

        prog = jax.jit(jax.shard_map(
            make, mesh=self.mesh, in_specs=P(), out_specs=P("x"),
            check_vma=False,
        ))
        return self._by_rank(prog(jax.device_put(key, NamedSharding(self.mesh, P()))))

    def _zeros(self, counts):
        """One zeroed float32 array of each of ``counts`` on every rank's
        device, made there: [rank][i] -> single-device jax.Array.  (A
        facade ``create_buffer`` commits a zeroed HOST array; at these
        sizes that was most of a run's set-up.)"""
        P = PartitionSpec
        prog = jax.jit(jax.shard_map(
            lambda: tuple(jnp.zeros((c,), jnp.float32) for c in counts),
            mesh=self.mesh, in_specs=(), out_specs=P("x"), check_vma=False,
        ))
        return self._by_rank(prog())

    def _by_rank(self, outs):
        return [
            [next(s.data for s in o.addressable_shards if s.device == dev)
             for o in outs]
            for dev in self.devices
        ]

    def _buffers(self, arrays_by_rank):
        from accl_tpu.buffer import DeviceBuffer
        from accl_tpu.constants import DataType

        return [
            [DeviceBuffer(int(x.shape[0]), DataType.FLOAT32, dev, array=x)
             for x in arrays]
            for dev, arrays in zip(self.devices, arrays_by_rank)
        ]

    def _recv_count(self, op: str, nbytes: int) -> int:
        return nbytes // 4 // (self.world if op == "reduce_scatter" else 1)

    def _calls(self, sizes, copies: int, salt: int) -> List[_Call]:
        calls = []
        ops = self.traffic["ops"]
        for i, nbytes in enumerate(sizes):
            if nbytes % (32 * self.world):
                raise ValueError(f"size {nbytes}: not 8 floats a rank")
            key = self._key(salt + i)
            send = self._buffers(self._seeded(nbytes, copies, key))
            recv = self._buffers(self._zeros([
                self._recv_count(op, nbytes) for op in ops
            ]))
            for j, op in enumerate(ops):
                calls.append(_Call(
                    op, nbytes, self.world, send,
                    [recv[r][j] for r in range(self.world)], key,
                ))
        # fixed order: ops at the first size, then ops at the next
        return calls

    def setup(self) -> None:
        from accl_tpu.core import xla_group

        if len(set(self.devices)) != self.world:
            raise RuntimeError(
                f"need {self.world} distinct devices, have {self.devices}"
            )
        self._mark("imports")
        self.group = xla_group(self.world)
        self._mark("group")
        tr = self.traffic
        self.large = self._calls(tr["large"]["sizes"], int(tr["pool"]), 100)
        self.small = self._calls(tr["small"]["sizes"], 1, 200)
        # the window: one send and one receive buffer a slot, as a job's
        # gradient buckets are distinct
        wsize = int(tr["window"]["size"])
        slots = tr["window"]["ops"]
        wkey = self._key(300)
        wsend = self._buffers(self._seeded(wsize, len(slots), wkey))
        wrecv = self._buffers(self._zeros(
            [self._recv_count(op, wsize) for op in slots]
        ))
        self.window = [
            _Call(op, wsize, self.world,
                  [[wsend[r][j]] for r in range(self.world)],
                  [wrecv[r][j] for r in range(self.world)], wkey)
            for j, op in enumerate(slots)
        ]
        self.counter = self.group[0].engine.gang.interactions
        jax.block_until_ready([b.device_array() for c in self.large
                               for b in c.send[0]])
        self._mark("buffers")
        # warm-up: every program this cell will run, checked against numpy
        self._verified_round("warm-up")
        self._mark("verified_round")
        # a second warm round: the first call of a shape also fills the
        # plan cache and the engine's prepared-call templates
        self._round_blocking(self.large)
        self._round_blocking(self.small)
        self._round_window()
        jax.block_until_ready(
            [c.recv[r].device_array() for c in self.large + self.small
             for r in range(self.world)]
        )
        self._mark("second_round")

    # -- rounds outside the timed window ---------------------------------------

    def _round_blocking(self, calls) -> None:
        def work(r):
            for c in calls:
                c.issue(self.group[r], r)
        _run_ranks(self.world, work)

    def _round_window(self) -> None:
        _run_ranks(self.world, lambda r: self._window_once(r))

    def _window_once(self, r: int) -> None:
        a = self.group[r]
        with a.batch():
            reqs = [c.issue(a, r, run_async=True) for c in self.window]
        for q in reqs:
            if not q.wait(_JOIN_S):
                raise TimeoutError("a batched collective never completed")
            q.check()
        jax.block_until_ready([c.recv[r].device_array() for c in self.window])

    def _verified_round(self, stage: str) -> None:
        """One round of every call, each output compared bit-equal with a
        plain reference: numpy on the host from what the send buffers
        hold (small calls and the window), and for the large calls plain
        jax.numpy on each rank's own device from the regenerated payloads
        (a gigabyte of outputs would otherwise cross to the host in every
        run's set-up and again after its window)."""
        for calls, run, check in (
            (self.large, self._round_blocking, self._differs_on_device),
            (self.small, self._round_blocking, self._differs_on_host),
            (self.window, lambda cs: self._round_window(),
             self._differs_on_host),
        ):
            turns = [list(c.turn) for c in calls]
            run(calls)
            for label in check(calls, turns):
                self.failed += 1
                self.problems.append(f"{stage}: {label} differs from the reference")
            self.attempted += len(calls)

    def _differs_on_host(self, calls, turns) -> List[str]:
        cache: Dict[int, np.ndarray] = {}

        def host(buf):
            if id(buf) not in cache:
                cache[id(buf)] = np.asarray(buf.device_array())
            return cache[id(buf)]

        bad = []
        for c, turn in zip(calls, turns):
            data = [host(c.send[r][turn[r]]) for r in range(self.world)]
            if not all(
                np.array_equal(
                    np.asarray(c.recv[r].device_array()),
                    expected(c.op, data, c.per,
                             lambda d: d[r * c.per:(r + 1) * c.per], np),
                )
                for r in range(self.world)
            ):
                bad.append(c.label)
        return bad

    def _differs_on_device(self, calls, turns) -> List[str]:
        """One program a size over the ranks' devices (no collective in
        it): each rank regenerates every rank's payload, works out what
        it must have received from each op, and compares."""
        P = PartitionSpec
        sharded = NamedSharding(self.mesh, P("x"))
        bad = []
        for nbytes in sorted({c.nbytes for c in calls}):
            group = [(c, t) for c, t in zip(calls, turns) if c.nbytes == nbytes]
            n, key = group[0][0].n, group[0][0].key
            (k,) = {t[r] for _, t in group for r in range(self.world)}
            ops = tuple(c.op for c, _ in group)

            def body(key, k, *outs, ops=ops, n=n):
                per = n // self.world
                start = jax.lax.axis_index("x") * per
                mine = lambda d: jax.lax.dynamic_slice(d, (start,), (per,))
                data = [payload(key, p, k, n) for p in range(self.world)]
                return jnp.stack([
                    jnp.array_equal(out, expected(op, data, per, mine, jnp))
                    for op, out in zip(ops, outs)
                ])[None]

            if nbytes not in self._checks:   # traced once a process
                self._checks[nbytes] = jax.jit(jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(P(), P()) + (P("x"),) * len(ops),
                    out_specs=P("x"), check_vma=False,
                ))
            prog = self._checks[nbytes]
            outs = [
                jax.make_array_from_single_device_arrays(
                    (self.world * c.recv[0].count,), sharded,
                    [c.recv[r].device_array() for r in range(self.world)],
                )
                for c, _ in group
            ]
            same = np.asarray(prog(key, jnp.int32(k), *outs))  # (world, ops)
            bad += [c.label for j, (c, _) in enumerate(group)
                    if not same[:, j].all()]
        return bad

    # -- the timed window --------------------------------------------------------

    def _phase(self, name: str, units: list, run_unit: Callable,
               seconds: float, tracer) -> dict:
        """Whole rounds of ``units`` until ``seconds`` are up.  One barrier
        a unit: its action (run by one thread while the others wait, so
        nothing is in flight) closes the previous unit's sample, decides
        at a round's start whether to go on, opens or closes the trace
        slice, and stamps the release."""
        P = self.world
        done_ns = [0] * P
        samples: List[List[int]] = [[] for _ in units]
        traced = [0] * len(units)
        st = {"i": 0, "prev": None, "go": True, "rounds": 0, "t0": 0,
              "deadline": time.perf_counter() + seconds, "trace_end": None,
              "was_traced": False}
        slice_s = float(self.traffic.get("trace_slice_s", 0.5))

        def action():
            i = st["i"]
            if st["prev"] is not None:
                samples[st["prev"]].append(max(done_ns) - st["t0"])
                if tracer.active:
                    traced[st["prev"]] += 1
            if i == 0:
                before = tracer.overhead_s
                now = time.perf_counter()
                if tracer.active and now >= st["trace_end"]:
                    tracer.stop()
                elif (tracer.enabled and not st["was_traced"]
                        and st["rounds"] >= 2):
                    tracer.start(name)
                    st["was_traced"] = True
                    st["trace_end"] = time.perf_counter() + slice_s
                st["deadline"] += tracer.overhead_s - before
                if (time.perf_counter() >= st["deadline"]
                        and st["rounds"] >= 3 and not tracer.active):
                    st["go"] = False
                else:
                    st["rounds"] += 1
            st["prev"] = i if st["go"] else None
            st["i"] = (i + 1) % len(units)
            st["t0"] = time.perf_counter_ns()

        gate = threading.Barrier(P, action=action, timeout=_JOIN_S)

        def work(r):
            try:
                while True:
                    for u in units:
                        gate.wait()
                        if not st["go"]:
                            return
                        run_unit(u, r)
                        done_ns[r] = time.perf_counter_ns()
            except BaseException:
                gate.abort()
                raise

        before = self.counter.read()
        t0 = time.perf_counter()
        _run_ranks(P, work)
        wall = time.perf_counter() - t0
        tracer.stop()
        return {
            "samples": samples, "traced": traced, "rounds": st["rounds"],
            "interactions": self.counter.read() - before, "wall_s": wall,
        }

    def measure(self, seconds: float, tracer) -> dict:
        tr = self.traffic
        g = self.group

        def blocking(phase):
            def run(c, r):
                with tracer.span(f"bench::{phase}::{c.op}"):
                    with jax.transfer_guard("disallow"):
                        c.issue(g[r], r)
                    jax.block_until_ready(c.recv[r].device_array())
            return run

        def window(_, r):
            with tracer.span("bench::window"):
                self._window_once(r)

        # what the yardstick itself costs: the same gate with nothing to do
        floor = self._phase("floor", [None] * 8, lambda u, r: None,
                            0.0, Tracer("", enabled=False))
        plans0 = [a.telemetry_snapshot()["plan_cache"] for a in g]
        ring0 = g[0].engine.telemetry_report()["cmdring"]
        t0 = time.perf_counter()
        large = self._phase("large", self.large, blocking("large"),
                            seconds * tr["large"]["share"], tracer)
        small = self._phase("small", self.small, blocking("small"),
                            seconds * tr["small"]["share"], tracer)
        win = self._phase("window", [None], window,
                          seconds * tr["window"]["share"], tracer)
        wall = time.perf_counter() - t0
        plans1 = [a.telemetry_snapshot()["plan_cache"] for a in g]
        ring1 = g[0].engine.telemetry_report()["cmdring"]
        self._verified_round("after the window")

        def med(xs):
            return statistics.median(xs)

        n_large = sum(len(s) for s in large["samples"])
        n_small = sum(len(s) for s in small["samples"])
        n_win = len(win["samples"][0])
        self.attempted += n_large + n_small + n_win
        per_call = (large["interactions"] + small["interactions"]) / max(
            n_large + n_small, 1
        )
        if per_call != 1.0:
            self.problems.append(
                f"{per_call} device interactions a blocking call, not 1"
            )
        slots = len(self.window)
        metrics = {
            "coll_busbw": sum(c.bus_bytes for c in self.large) / sum(
                med(s) for s in large["samples"]
            ),  # bytes/ns == GB/s
            "coll_small_p50": statistics.fmean(
                med(s) for s in small["samples"]
            ) / 1e3,
            "coll_batched_p50": med(win["samples"][0]) / slots / 1e3,
        }
        hits = sum(b["hits"] - a["hits"] for a, b in zip(plans0, plans1))
        misses = sum(b["misses"] - a["misses"] for a, b in zip(plans0, plans1))
        facts = {
            "wall_s": wall,
            "gate_floor_us": med(
                [x for s in floor["samples"] for x in s]) / 1e3,
            "samples": {
                "large": {c.label: len(s)
                          for c, s in zip(self.large, large["samples"])},
                "small": {c.label: len(s)
                          for c, s in zip(self.small, small["samples"])},
                "window": n_win,
            },
            "medians_us": {
                c.label: med(s) / 1e3
                for cs, ph in ((self.large, large), (self.small, small))
                for c, s in zip(cs, ph["samples"])
            },
            "interactions_per_call": per_call,
            "interactions_per_window": win["interactions"] / max(n_win, 1),
            "plan_hits": hits,
            "plan_lookups": hits + misses,
            "ring_fallbacks": sum(ring1["fallbacks"].values())
            - sum(ring0["fallbacks"].values()),
            "ring_lowering": ring1["lowering"],
            # bus bytes of the large calls that ran inside the trace slice
            "traced_large_bus_bytes": sum(
                c.bus_bytes * n for c, n in zip(self.large, large["traced"])
            ),
            "traced_large_calls": sum(large["traced"]),
            "world": self.world,
        }
        return {"metrics": metrics, "facts": facts}

    def close(self) -> None:
        if self.group is not None:
            for a in self.group:
                a.deinit()
