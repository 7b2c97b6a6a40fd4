"""Stress harness: sustained randomized traffic with integrity checks.

Role model: the reference's stress binary (``test/host/xrt/src/stress.cpp:
24`` — tight loops of send/recv between rank pairs).  This version drives
randomized mixed traffic — tag-matched send/recv pairs with varied sizes
and tags, interleaved with collectives — against any backend tier, and
verifies payload integrity on every iteration (the reference relies on the
gtest assertions around its loop).

Usage:
    python scripts/stress.py --backend emulator --world 4 --iters 500
    python scripts/stress.py --backend native --iters 2000
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
from typing import List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pairwise_sendrecv(group, rng, max_count: int) -> None:
    """Every even rank sends to the next odd rank, randomized size/tag."""
    world = len(group)
    count = int(rng.integers(1, max_count))
    tag = int(rng.integers(0, 1 << 16))
    payloads = [
        rng.standard_normal(count).astype(np.float32) for _ in range(world)
    ]
    errors: List[BaseException] = []

    def work(i):
        try:
            if i % 2 == 0 and i + 1 < world:
                buf = group[i].create_buffer_from(payloads[i])
                group[i].send(buf, count, dst=i + 1, tag=tag)
            elif i % 2 == 1:
                buf = group[i].create_buffer(count, np.float32)
                group[i].recv(buf, count, src=i - 1, tag=tag)
                buf.sync_from_device()
                np.testing.assert_array_equal(buf.data[:count], payloads[i - 1])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise errors[0]


def _random_collective(group, rng, max_count: int) -> None:
    world = len(group)
    count = int(rng.integers(1, max_count))
    op = rng.choice(["allreduce", "bcast", "allgather"])
    chunks = [
        rng.standard_normal(count).astype(np.float32) for _ in range(world)
    ]
    errors: List[BaseException] = []

    def work(i):
        try:
            a = group[i]
            if op == "allreduce":
                send = a.create_buffer_from(chunks[i])
                recv = a.create_buffer(count, np.float32)
                a.allreduce(send, recv, count)
                recv.sync_from_device()
                np.testing.assert_allclose(
                    recv.data[:count], np.sum(chunks, axis=0),
                    rtol=1e-5, atol=1e-5,
                )
            elif op == "bcast":
                data = chunks[0] if i == 0 else np.zeros(count, np.float32)
                buf = a.create_buffer_from(data)
                a.bcast(buf, count, root=0)
                buf.sync_from_device()
                np.testing.assert_array_equal(buf.data[:count], chunks[0])
            else:
                send = a.create_buffer_from(chunks[i])
                recv = a.create_buffer(world * count, np.float32)
                a.allgather(send, recv, count)
                recv.sync_from_device()
                np.testing.assert_array_equal(
                    recv.data[: world * count], np.concatenate(chunks)
                )
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise errors[0]


def stress(group, iters: int, max_count: int = 4096, seed: int = 0,
           report_every: int = 100) -> None:
    rng = np.random.default_rng(seed)
    for it in range(iters):
        if rng.random() < 0.6:
            _pairwise_sendrecv(group, rng, max_count)
        else:
            _random_collective(group, rng, max_count)
        if report_every and (it + 1) % report_every == 0:
            print(f"stress: {it + 1}/{iters} iterations OK", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--backend", choices=["emulator", "native", "xla"], default="emulator"
    )
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--max-count", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from accl_tpu import core

    if args.backend == "native":
        from accl_tpu.backends.native import native_group

        group = native_group(args.world)
    elif args.backend == "xla":
        group = core.xla_group(args.world)
    else:
        group = core.emulated_group(args.world)
    try:
        stress(group, args.iters, args.max_count, args.seed)
    finally:
        for a in group:
            a.deinit()
    print(f"stress complete: {args.iters} iterations, world={args.world}, "
          f"backend={args.backend}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
