"""CI topology smoke: the multi-slice descriptor + hierarchical
collective plane on the numpy-only footprint (no jax, the same
footprint as the ring/chaos/monitor smokes it runs next to,
.github/workflows/analysis.yml).

Three legs:

1. Descriptor units — slice/link-class math, signatures, JSON and env
   round-trips, subtopology remap, elastic append.
2. Subcomm derivation — the decomposition's rail/leader/representative
   index math every rank derives with zero wire bytes.
3. Hierarchical-vs-flat bit-equality — every hierarchical op against
   its flat twin on a live 2x4 emulator group (real frames, real
   decomposition dispatch), integer-valued data so equality is exact.

Usage::

    python scripts/topology_smoke.py
"""

import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from accl_tpu import LinkClass, Topology, emulated_group
from accl_tpu.hierarchical import (
    HIER_OPS,
    allreduce_mode,
    bcast_representatives,
    eligible,
    multi_slice,
    reduce_scatter_permutation,
)


def run_parallel(group, fn, timeout=60.0):
    results = [None] * len(group)
    errors = [None] * len(group)

    def runner(i):
        try:
            results[i] = fn(group[i], i)
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    threads = [
        threading.Thread(target=runner, args=(i,), daemon=True)
        for i in range(len(group))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "a rank wedged (deadline exceeded)"
    for e in errors:
        if e is not None:
            raise e
    return results


def descriptor_smoke() -> None:
    """Slice math, link classes, signatures, serialization round-trips."""
    t = Topology.from_slice_size(8, 4)
    assert t.world == 8 and t.num_slices == 2
    assert t.slice_of(0) == 0 and t.slice_of(7) == 1
    assert t.link_class(1, 1) is LinkClass.LOOPBACK
    assert t.link_class(1, 2) is LinkClass.ICI
    assert t.link_class(1, 6) is LinkClass.DCN
    assert t.leaders() == (0, 4)
    assert t.rail(2) == (2, 6)
    assert t.signature() == "2x4"
    # JSON round-trip preserves identity (slices, signature, hash)
    back = Topology.from_json(t.to_json())
    assert back == t and back.signature() == t.signature()
    assert hash(back) == hash(t)
    # env round-trip: explicit JSON beats slice-size, slice-size beats
    # nothing, absent env means None (flat dispatch)
    env = {"ACCL_TOPOLOGY": t.to_json()}
    assert Topology.from_env(8, environ=env) == t
    assert Topology.from_env(8, environ={"ACCL_SLICE_SIZE": "4"}) == t
    assert Topology.from_env(8, environ={}) is None
    # asymmetric layouts carry a content signature, not WxS
    ragged = Topology(((0, 1, 2), (3, 4)))
    assert ragged.signature() != "2x3"
    assert not ragged.symmetric
    # subtopology remap: evicting rank 1 renumbers densely and keeps
    # slice placement
    sub = t.subtopology([0, 2, 3, 4, 5, 6, 7])
    assert sub.world == 7
    assert sub.slice_of(0) == 0 and sub.slice_of(3) == 1
    # elastic JOIN: the appended rank lands on its OWN new slice (the
    # conservative DCN classification until re-described)
    grown = ragged.with_appended_rank()
    assert grown.world == 6 and grown.num_slices == 3
    assert grown.slice_of(5) == 2
    assert grown.link_class(4, 5) is LinkClass.DCN
    print("  descriptor units ok")


def subcomm_smoke() -> None:
    """The decomposition's derived index sets — pure math, every rank
    agrees by construction."""
    t = Topology.from_slice_size(8, 4)
    assert multi_slice(t)
    assert not multi_slice(Topology.flat(8))
    assert not multi_slice(Topology.from_slice_size(2, 1))  # leaders-only
    # symmetric layouts decompose over rails (count permitting);
    # ragged ones fall back to the leader mode's full-count DCN cost
    assert allreduce_mode(t, 1 << 16) == "rail"
    assert allreduce_mode(t, 3) == "leader"  # indivisible count
    assert allreduce_mode(Topology(((0, 1, 2), (3, 4))), 1 << 16) == "leader"
    assert allreduce_mode(Topology.flat(8), 1 << 16) is None
    # every hierarchical op is eligible on the 2x4 layout at size
    for op in HIER_OPS:
        assert eligible(op, t, 1 << 16), op
    # bcast representatives: the root for its own slice, the slice
    # leader elsewhere — sorted so every rank derives the same list
    reps = bcast_representatives(t, root=5)
    assert reps == [0, 5]
    assert {t.slice_of(r) for r in reps} == {0, 1}
    # reduce-scatter permutation maps hierarchical segment order back
    # to rank order, and is a true permutation
    perm = reduce_scatter_permutation(t)
    assert sorted(perm) == list(range(8))
    print("  subcomm derivation ok")


def bit_equality_smoke() -> None:
    """Every hierarchical op bit-matches its flat twin on a live 2x4
    emulator group — the SPMD-uniform dispatch contract the verifier
    convicts on."""
    world, n = 8, 1 << 10
    topo = Topology.from_slice_size(world, 4)
    rng = np.random.default_rng(17)
    data = [
        rng.integers(-64, 64, size=n).astype(np.float32)
        for _ in range(world)
    ]

    def run(op, hier):
        group = emulated_group(world, topology=topo)
        try:
            for a in group:
                a.set_tuning("hierarchical", 1 if hier else 0)

            def work(a, r):
                if op == "allreduce":
                    s = a.create_buffer_from(data[r])
                    d = a.create_buffer(n, np.float32)
                    a.allreduce(s, d, n)
                    return np.asarray(d.device_view()[:n]).copy()
                if op == "allgather":
                    seg = n // world
                    s = a.create_buffer_from(data[r][:seg])
                    d = a.create_buffer(n, np.float32)
                    a.allgather(s, d, seg)
                    return np.asarray(d.device_view()[:n]).copy()
                if op == "reduce_scatter":
                    seg = n // world
                    s = a.create_buffer_from(data[r])
                    d = a.create_buffer(seg, np.float32)
                    a.reduce_scatter(s, d, seg)
                    return np.asarray(d.device_view()[:seg]).copy()
                s = a.create_buffer_from(data[r])  # bcast
                a.bcast(s, n, root=3)
                return np.asarray(s.device_view()[:n]).copy()

            return run_parallel(group, work)
        finally:
            for a in group:
                a.deinit()

    for op in HIER_OPS:
        flat = run(op, hier=False)
        hier = run(op, hier=True)
        for r in range(world):
            assert np.array_equal(flat[r], hier[r]), (
                f"{op}: rank {r} hierarchical result diverged from flat"
            )
        print(f"  {op}: hierarchical == flat bit-exact on 2x4")


def main() -> None:
    print("descriptor round-trip:")
    descriptor_smoke()
    print("subcomm derivation:")
    subcomm_smoke()
    print("hierarchical vs flat (2x4 emulator):")
    bit_equality_smoke()
    print("topology smoke OK")


if __name__ == "__main__":
    main()
