"""Multi-slice topology plane (accl_tpu.topology + accl_tpu.
hierarchical): the slice/link-class descriptor, hierarchical collective
decomposition (bit-identical to flat on every tier), the link-class
plan-key axis with per-class wire ladders, topology-scoped error
feedback, the paced two-class fabric model, the autotuner's
hierarchical-vs-flat race and the TuningPlan topology provenance
refusal."""

from __future__ import annotations

import json
import socket as socketlib
import threading
import time

import numpy as np
import pytest

from accl_tpu.constants import DataType, Operation, ReduceFunction
from accl_tpu.core import emulated_group, socket_group_member, xla_group
from accl_tpu.hierarchical import (
    HIER_OPS,
    allreduce_mode,
    bcast_representatives,
    eligible,
    multi_slice,
    reduce_scatter_permutation,
)
from accl_tpu.topology import LinkClass, Topology

from helpers import run_parallel


def _deinit(group):
    for a in group:
        a.deinit()


# ---------------------------------------------------------------------------
# descriptor units
# ---------------------------------------------------------------------------


def test_descriptor_slice_and_link_class_math():
    t = Topology.from_slice_size(8, 4)
    assert t.world == 8 and t.num_slices == 2
    assert t.slices == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert [t.slice_of(r) for r in range(8)] == [0] * 4 + [1] * 4
    assert t.local_index(6) == 2
    assert t.link_class(3, 3) is LinkClass.LOOPBACK
    assert t.link_class(0, 3) is LinkClass.ICI
    assert t.link_class(3, 4) is LinkClass.DCN
    assert t.leaders() == (0, 4)
    assert t.slice_leader(6) == 4 and t.is_leader(4)
    assert not t.is_leader(6)
    assert t.rail(1) == (1, 5)
    assert t.symmetric and t.contiguous
    # flat: one slice, ICI everywhere, never multi-slice
    f = Topology.flat(4)
    assert f.num_slices == 1 and f.link_class(0, 3) is LinkClass.ICI
    assert not multi_slice(f)
    # the uniform-comm classifier: single-slice ICI, all-singleton DCN,
    # anything mixed None
    assert f.comm_link_class() is LinkClass.ICI
    assert Topology(((0,), (1,))).comm_link_class() is LinkClass.DCN
    assert t.comm_link_class() is None


def test_descriptor_signature_and_identity():
    t = Topology.from_slice_size(8, 4)
    assert t.signature() == "2x4"
    # equal layouts: equal signature, equal fingerprint, equal hash
    u = Topology(((0, 1, 2, 3), (4, 5, 6, 7)))
    assert t == u and hash(t) == hash(u)
    assert t.fingerprint() == u.fingerprint()
    # ragged / non-contiguous layouts get a content signature that
    # distinguishes them from each other and from the WxS form
    r1 = Topology(((0, 1, 2), (3, 4)))
    r2 = Topology(((0, 1), (2, 3, 4)))
    assert r1.signature() != r2.signature()
    assert r1.signature() != "2x3"
    # member order inside a slice canonicalizes
    assert Topology(((3, 2, 1, 0), (4, 5, 6, 7))) == t


def test_descriptor_validation_is_loud():
    with pytest.raises(ValueError):
        Topology(((0, 1), (1, 2)))  # duplicate rank
    with pytest.raises(ValueError):
        Topology(((0, 2),))  # gap: ranks must cover 0..world-1
    with pytest.raises(ValueError):
        Topology(())
    with pytest.raises(ValueError):
        Topology.from_slice_size(8, 3)  # indivisible


def test_descriptor_serialization_round_trips():
    t = Topology(((0, 1, 2), (3, 4)))
    assert Topology.from_dict(t.to_dict()) == t
    assert Topology.from_json(t.to_json()) == t
    sym = Topology.from_slice_size(6, 3)
    # env derivation: explicit JSON wins over slice size, slice size
    # over nothing, absent means None (flat dispatch everywhere)
    assert Topology.from_env(
        5, environ={"ACCL_TOPOLOGY": t.to_json()}
    ) == t
    assert Topology.from_env(6, environ={"ACCL_SLICE_SIZE": "3"}) == sym
    assert Topology.from_env(6, environ={}) is None
    # a JSON describing the wrong world is refused loudly
    with pytest.raises(ValueError):
        Topology.from_env(7, environ={"ACCL_TOPOLOGY": t.to_json()})


def test_subtopology_remap_and_elastic_append():
    t = Topology.from_slice_size(8, 4)
    # evict rank 5: dense renumber, slice placement survives
    sub = t.subtopology([0, 1, 2, 3, 4, 6, 7])
    assert sub.world == 7
    assert sub.slices == ((0, 1, 2, 3), (4, 5, 6))
    # an intra-slice subcomm classifies ICI-uniform; a rail subcomm
    # DCN-uniform — the truthfulness split() relies on
    assert t.subtopology([0, 1, 2, 3]).comm_link_class() is LinkClass.ICI
    assert t.subtopology([1, 5]).comm_link_class() is LinkClass.DCN
    with pytest.raises(ValueError):
        t.subtopology([0, 0])
    with pytest.raises(ValueError):
        t.subtopology([0, 99])
    # JOIN: the admitted rank lands alone on a new slice (conservative
    # DCN until re-described)
    g = t.with_appended_rank()
    assert g.world == 9 and g.num_slices == 3
    assert g.slice_of(8) == 2
    assert g.link_class(7, 8) is LinkClass.DCN


# ---------------------------------------------------------------------------
# decomposition eligibility math
# ---------------------------------------------------------------------------


def test_hierarchical_eligibility_and_modes():
    t = Topology.from_slice_size(8, 4)
    assert multi_slice(t)
    assert not multi_slice(None)
    assert not multi_slice(Topology.flat(8))
    # all-singleton slices (a rail subcomm's own topology) must never
    # decompose — the recursion guard
    assert not multi_slice(Topology(((0,), (1,), (2,))))
    assert allreduce_mode(t, 1 << 12) == "rail"
    assert allreduce_mode(t, 3) == "leader"  # count % slice_size != 0
    ragged = Topology(((0, 1, 2), (3, 4)))
    assert allreduce_mode(ragged, 1 << 12) == "leader"
    for op in HIER_OPS:
        assert eligible(op, t, 1 << 12), op
        assert not eligible(op, None, 1 << 12), op
    # gather-likes need symmetric contiguous slices; bcast does not
    assert not eligible("allgather", ragged, 1 << 12)
    assert not eligible("reduce_scatter", ragged, 1 << 12)
    assert eligible("bcast", ragged, 1 << 12)
    assert not eligible("alltoall", t, 1 << 12)


def test_bcast_representatives_and_rs_permutation():
    t = Topology.from_slice_size(8, 4)
    reps = bcast_representatives(t, root=5)
    assert reps == [0, 5]  # root for its slice, leader elsewhere
    assert bcast_representatives(t, root=0) == [0, 4]
    # the reduce-scatter staging permutation is a true permutation and
    # realizes the documented [s*S + i for i in range(S) for s in
    # range(L)] block order
    perm = reduce_scatter_permutation(t)
    assert sorted(perm) == list(range(8))
    S, L = 4, 2
    assert perm == [s * S + i for i in range(S) for s in range(L)]
    with pytest.raises(ValueError):
        reduce_scatter_permutation(Topology(((0, 1, 2), (3, 4))))


# ---------------------------------------------------------------------------
# plan-key axis + per-class wire ladders
# ---------------------------------------------------------------------------


def test_plan_key_topology_axis_and_invalidation():
    topo = Topology.from_slice_size(2, 1)  # two singleton slices: DCN
    g = emulated_group(2, topology=topo)
    try:
        a = g[0]
        p = a._plan_for(
            Operation.ALLREDUCE, a.comm, DataType.FLOAT32, 256, None,
            0, (0,),
        )
        # signature sits before extra (CollectivePlan.fuse reads
        # key[-1] as the extra tuple)
        assert p.key[-2] == "1x1" or p.key[-2] == topo.signature()
        assert p.key[-1] == (0,)
        assert p.link_class is LinkClass.DCN
        # detaching the topology re-keys: the flat plan is a DIFFERENT
        # cache entry with a None signature axis
        a.set_topology(None)
        p2 = a._plan_for(
            Operation.ALLREDUCE, a.comm, DataType.FLOAT32, 256, None,
            0, (0,),
        )
        assert p2.key[-2] is None and p2.key is not p.key
        assert p2.link_class is None
    finally:
        _deinit(g)


def test_per_class_wire_verdict_resolution(rng=None):
    """The per-class ladder: a DCN-uniform comm consults its class
    register first, 0 defers to the generic wire_dtype, and an
    ICI-uniform comm never reads the DCN lane."""
    rng = np.random.default_rng(3)
    n = 512
    dcn_topo = Topology(((0,), (1,)))

    def plan_of(a):
        return a._plan_for(
            Operation.ALLREDUCE, a.comm, DataType.FLOAT32, n, None,
            0, (0,),
        )

    g = emulated_group(2, topology=dcn_topo)
    try:
        for a in g:
            a.set_tuning("wire_dtype_dcn", "int8")
        assert plan_of(g[0]).wire_dtype == DataType.INT8
        # the quantized DCN lane stays value-correct end to end
        data = [rng.standard_normal(n).astype(np.float32) for _ in g]
        sends = [a.create_buffer_from(d.copy()) for a, d in zip(g, data)]
        recvs = [a.create_buffer(n, np.float32) for a in g]
        run_parallel(g, lambda a, r: a.allreduce(sends[r], recvs[r], n))
        recvs[0].sync_from_device()
        err = float(np.abs(recvs[0].data - (data[0] + data[1])).max())
        assert 0 < err < 0.2  # lossy lane engaged, bounded
        # class register 0 defers to the generic register
        for a in g:
            a.set_tuning("wire_dtype_dcn", "off")
            a.set_tuning("wire_dtype", "int8")
        assert plan_of(g[0]).wire_dtype == DataType.INT8
        # a nonzero class register OVERRIDES the generic
        for a in g:
            a.set_tuning("wire_dtype", "int8")
            a.set_tuning("wire_dtype_dcn", "float8_e4m3")
        assert plan_of(g[0]).wire_dtype == DataType.FLOAT8_E4M3
    finally:
        _deinit(g)

    # an ICI-uniform comm ignores the DCN lane entirely
    g = emulated_group(2, topology=Topology.flat(2))
    try:
        for a in g:
            a.set_tuning("wire_dtype_dcn", "int8")
        assert plan_of(g[0]).wire_dtype is None
        for a in g:
            a.set_tuning("wire_dtype_ici", "int8")
        assert plan_of(g[0]).wire_dtype == DataType.INT8
    finally:
        _deinit(g)


def test_error_feedback_residuals_key_per_link_class():
    """EF residual streams carry the comm's link class so a topology
    swap re-classing the SAME comm cannot blend one lane's quantization
    error into the other's telescoping sum."""
    rng = np.random.default_rng(11)
    n = 512
    g = emulated_group(2, topology=Topology(((0,), (1,))))
    try:
        for a in g:
            a.set_tuning("wire_dtype_dcn", "int8")
            a.set_error_feedback(True)
        data = [rng.standard_normal(n).astype(np.float32) for _ in g]
        sends = [a.create_buffer_from(d.copy()) for a, d in zip(g, data)]
        recvs = [a.create_buffer(n, np.float32) for a in g]
        run_parallel(g, lambda a, r: a.allreduce(sends[r], recvs[r], n))
        a = g[0]
        key = (
            a.comm.id, a.comm.epoch, Operation.ALLREDUCE, n, 0,
            int(LinkClass.DCN),
        )
        assert a._residuals.residual(key) is not None
        # no stream under any other link class for this comm
        for other in (-1, int(LinkClass.ICI)):
            k = key[:-1] + (other,)
            assert a._residuals.residual(k) is None
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# hierarchical dispatch: bit-identical to flat on every tier
# ---------------------------------------------------------------------------


def _integer_data(world, n, seed=7):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(-64, 64, size=n).astype(np.float32)
        for _ in range(world)
    ]


def _run_op(group, op, data, n):
    world = len(data)

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
        a.bcast(s, n, root=1)
        return np.asarray(s.device_view()[:n]).copy()

    return run_parallel(group, work)


@pytest.mark.parametrize("op", HIER_OPS)
def test_hierarchical_bit_identical_to_flat_emulator(op):
    world, n = 4, 1 << 9
    topo = Topology.from_slice_size(world, 2)
    data = _integer_data(world, n)

    def run(hier):
        g = emulated_group(world, topology=topo)
        try:
            for a in g:
                a.set_tuning("hierarchical", 1 if hier else 0)
            return _run_op(g, op, data, n)
        finally:
            _deinit(g)

    flat, hier = run(False), run(True)
    for r in range(world):
        assert np.array_equal(flat[r], hier[r]), f"{op}: rank {r}"


def test_hierarchical_leader_mode_ragged_topology():
    """A ragged multi-slice layout takes the leader decomposition
    (reduce -> leaders allreduce -> bcast) and still bit-matches."""
    world, n = 5, 300
    topo = Topology(((0, 1, 2), (3, 4)))
    assert allreduce_mode(topo, n) == "leader"
    data = _integer_data(world, n, seed=23)

    def run(hier):
        g = emulated_group(world, topology=topo)
        try:
            for a in g:
                a.set_tuning("hierarchical", 1 if hier else 0)
            return _run_op(g, "allreduce", data, n)
        finally:
            _deinit(g)

    flat, hier = run(False), run(True)
    for r in range(world):
        assert np.array_equal(flat[r], hier[r])


def test_hierarchical_contract_fingerprint_convicts_skew():
    """A rank dispatching flat where its peers went hierarchical
    diverges within one verification window — the <op>.hier
    fingerprint on the PARENT comm."""
    world, n = 4, 1 << 9
    topo = Topology.from_slice_size(world, 2)
    data = _integer_data(world, n)
    g = emulated_group(world, topology=topo)
    try:
        for a in g:
            a.set_contract_verify(True, interval=1)
            a.set_tuning("hierarchical", 1)
        # rank 3 skews: its register says flat
        g[3]._engine_tuning()["hierarchical"] = 0
        g[3]._plans.invalidate("test_skew")
        errs = {}

        def work(a, r):
            s = a.create_buffer_from(data[r])
            d = a.create_buffer(n, np.float32)
            try:
                a.allreduce(s, d, n)
                # a second window so slower convictions land
                a.allreduce(s, d, n)
            except Exception as e:  # noqa: BLE001
                errs[r] = e

        run_parallel(g, work)
        assert errs, "flat-vs-hierarchical skew must convict"
    finally:
        _deinit(g)


def _free_addresses(n):
    socks, addrs = [], []
    for _ in range(n):
        s = socketlib.socket()
        s.setsockopt(socketlib.SOL_SOCKET, socketlib.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        addrs.append(f"127.0.0.1:{s.getsockname()[1]}")
    for s in socks:
        s.close()
    return addrs


def test_hierarchical_bit_identical_socket_tier():
    world, n = 4, 1 << 9
    topo = Topology.from_slice_size(world, 2)
    data = _integer_data(world, n, seed=31)

    def run(hier):
        last = None
        for _ in range(3):  # pre-picked ports can be re-grabbed: retry
            try:
                addrs = _free_addresses(world)
                g = [
                    socket_group_member(i, addrs, topology=topo)
                    for i in range(world)
                ]
                break
            except OSError as e:
                last = e
        else:
            raise last
        try:
            for a in g:
                a.set_tuning("hierarchical", 1 if hier else 0)
            return _run_op(g, "allreduce", data, n)
        finally:
            _deinit(g)

    flat, hier = run(False), run(True)
    for r in range(world):
        assert np.array_equal(flat[r], hier[r]), f"socket rank {r}"


def test_hierarchical_bit_identical_gang_tier():
    world, n = 4, 1 << 9
    topo = Topology.from_slice_size(world, 2)
    data = _integer_data(world, n, seed=43)

    def run(hier):
        g = xla_group(world, topology=topo)
        try:
            for a in g:
                a.set_tuning("hierarchical", 1 if hier else 0)
            return _run_op(g, "allreduce", data, n)
        finally:
            _deinit(g)

    flat, hier = run(False), run(True)
    for r in range(world):
        assert np.array_equal(flat[r], hier[r]), f"gang rank {r}"


def test_hierarchical_explicit_compression_stays_flat():
    """An explicit compress_dtype is honored exactly — the decomposed
    path never engages (only register-driven wire verdicts ride the
    per-class ladders)."""
    world, n = 4, 1 << 9
    topo = Topology.from_slice_size(world, 2)
    data = _integer_data(world, n, seed=5)
    g = emulated_group(world, topology=topo)
    try:
        for a in g:
            a.set_tuning("hierarchical", 1)
        before = dict(g[0]._hier_comms)

        def work(a, r):
            s = a.create_buffer_from(data[r])
            d = a.create_buffer(n, np.float32)
            a.allreduce(s, d, n, compress_dtype=np.float16)
            return np.asarray(d.device_view()[:n]).copy()

        run_parallel(g, work)
        # no subcomms were derived: the call stayed flat
        assert {
            k: v for k, v in g[0]._hier_comms.items()
            if k not in before
        } == {}
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# elastic lifecycle: shrink / grow / restore keep the descriptor truthful
# ---------------------------------------------------------------------------


def test_elastic_shrink_grow_restore_topology_lifecycle():
    from accl_tpu.communicator import Communicator, Rank

    ranks = [Rank(address=f"a{i}", session=i) for i in range(4)]
    comm = Communicator(ranks, 1, 101)
    comm.topology = Topology.from_slice_size(4, 2)
    # shrink: evict rank 3 -> dense renumber, slices follow
    comm.shrink([0, 1, 2])
    assert comm.topology.slices == ((0, 1), (2,))
    # grow the evicted session back: original world slot, but a
    # singleton slice — the conservative DCN classification (a
    # rejoiner's physical placement is unknown until re-described;
    # restore()/set_topology are the paths back to fast-link truth)
    comm.grow([3])
    assert comm.topology.world == 4
    assert comm.topology.slice_members(comm.topology.slice_of(3)) == (3,)
    assert comm.topology.link_class(2, 3) is LinkClass.DCN
    # a genuinely NEW session lands alone on a fresh slice too
    comm.grow([9], rank_info={9: Rank(address="a9", session=9)})
    assert comm.topology.world == 5
    joiner = comm.topology.slice_of(4)
    assert comm.topology.slice_members(joiner) == (4,)
    assert comm.topology.link_class(0, 4) is LinkClass.DCN
    # restore after a shrink brings the FULL pre-shrink descriptor back
    comm2 = Communicator(ranks, 0, 102)
    comm2.topology = Topology.from_slice_size(4, 2)
    comm2.shrink([0, 1, 3])
    assert comm2.topology.world == 3
    assert comm2.restore()
    assert comm2.topology == Topology.from_slice_size(4, 2)


def test_split_derived_subcomm_link_classes_truthful():
    topo = Topology.from_slice_size(4, 2)
    g = emulated_group(4, topology=topo)
    try:
        def work(a, r):
            if r in (0, 1):
                intra = a.create_communicator([0, 1])
                return intra.topology.comm_link_class()
            rail = a.create_communicator([2, 3])
            return rail.topology.comm_link_class()

        out = run_parallel(g, work)
        assert out[0] is LinkClass.ICI and out[2] is LinkClass.ICI

        def cross(a, r):
            if r in (0, 2):
                c = a.create_communicator([0, 2])
                return c.topology.comm_link_class()
            return None

        out = run_parallel(g, cross)
        assert out[0] is LinkClass.DCN
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# fabric: paced two-class bandwidth model + telemetry
# ---------------------------------------------------------------------------


def test_fabric_two_class_counters_and_pacing():
    topo = Topology.from_slice_size(4, 2)
    g = emulated_group(4, topology=topo)
    try:
        fabric = g[0].engine.fabric
        data = _integer_data(4, 256, seed=3)
        fabric.reset_wire_class_stats()
        _run_op(g, "allreduce", data, 256)
        stats = fabric.wire_class_stats()
        assert stats["bytes"]["ici"] > 0
        assert stats["bytes"]["dcn"] > 0
        assert stats["messages"]["ici"] > 0
        # flat ring at world 4: 6 chunk sends cross the slice boundary
        # out of every full rotation — DCN strictly below ICI+DCN
        total = stats["bytes"]["ici"] + stats["bytes"]["dcn"]
        assert stats["bytes"]["dcn"] < total
        # pacing: a slow modeled DCN stretches wall time measurably
        def timed():
            t0 = time.perf_counter()
            _run_op(g, "allreduce", data, 256)
            return time.perf_counter() - t0

        fabric.set_wire_rates(ici_gbps=None, dcn_gbps=None)
        fast = min(timed() for _ in range(2))
        fabric.set_wire_rates(ici_gbps=8.0, dcn_gbps=0.001)
        slow = timed()
        fabric.set_wire_rates(ici_gbps=None, dcn_gbps=None)
        assert slow > fast
        # reported model rates ride the stats doc
        fabric.set_wire_rates(ici_gbps=8.0, dcn_gbps=0.5)
        assert fabric.wire_class_stats()["rates_gbps"]["ici"] == 8.0
        assert fabric.wire_class_stats()["rates_gbps"]["dcn"] == 0.5
        fabric.set_wire_rates(ici_gbps=None, dcn_gbps=None)
        # reset zeroes the counters
        fabric.reset_wire_class_stats()
        z = fabric.wire_class_stats()
        assert z["bytes"]["dcn"] == 0 and z["messages"]["ici"] == 0
    finally:
        _deinit(g)


def test_telemetry_snapshot_carries_wire_classes():
    g = emulated_group(2, topology=Topology(((0,), (1,))))
    try:
        data = _integer_data(2, 128, seed=9)
        _run_op(g, "allreduce", data, 128)
        snap = g[0].telemetry_snapshot()
        wc = snap["engine"].get("wire_classes")
        assert wc is not None
        assert wc["bytes"]["dcn"] > 0
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# autotuner: topology axes + plan provenance refusal
# ---------------------------------------------------------------------------


def test_autotune_candidate_axes_include_topology_lanes():
    from accl_tpu.tuning import _candidates

    cands = _candidates(
        "emulator", "allreduce", 4, include_pallas=False,
        eager_candidates=(), segments=(1,), pipeline_thresholds=(),
        wire_dtypes=(),
        race_hierarchical=True, wire_dtypes_ici=(),
        wire_dtypes_dcn=("int8",),
    )
    assert {"hierarchical": 1} in cands
    assert {
        "hierarchical": 1, "wire_dtype_dcn": int(DataType.INT8)
    } in cands
    # per-class lanes race standalone too
    assert {"wire_dtype_dcn": int(DataType.INT8)} in cands
    # non-hierarchical ops never race the register
    flat_ops = _candidates(
        "emulator", "sendrecv", 4, include_pallas=False,
        eager_candidates=(), segments=(1,), pipeline_thresholds=(),
        wire_dtypes=(),
        race_hierarchical=True,
    )
    assert all("hierarchical" not in c for c in flat_ops)


@pytest.mark.slow
def test_autotune_races_hierarchical_and_stamps_topology():
    from accl_tpu.tuning import autotune

    topo = Topology.from_slice_size(4, 2)
    g = emulated_group(4, topology=topo)
    try:
        plan = autotune(
            g, collectives=["allreduce"], sizes=[256], runs=1,
        )
        assert plan.topology == topo.signature()
        assert plan.provenance.get("hierarchical_raced") is True
    finally:
        _deinit(g)


def test_tuning_plan_topology_provenance_refusal():
    from accl_tpu.tuning import TuningPlan

    doc = {
        "version": 1, "world": 2, "tier": "emulator",
        "topology": "2x1",
        "defaults": {}, "entries": {},
    }
    plan = TuningPlan.from_json(json.dumps(doc))
    assert plan.topology == "2x1"
    # round-trip preserves the provenance field
    assert TuningPlan.from_json(plan.to_json()).topology == "2x1"
    g = emulated_group(2)  # flat group: layout None
    try:
        a = g[0]
        with pytest.raises(ValueError, match="2x1"):
            a.load_tuning_plan(plan, strict=True)
        # non-strict (the ACCL_TUNING_PLAN env path): refuse quietly
        assert a.load_tuning_plan(plan, strict=False) is None
        # matching layout adopts
        a.set_topology(Topology.from_slice_size(2, 1))
        ok = a.load_tuning_plan(
            TuningPlan.from_json(json.dumps({
                **doc, "topology": a.topology.signature(),
            })), strict=True,
        )
        assert ok is not None
        # a plan with NO topology provenance loads on any layout (the
        # pre-topology plan corpus stays valid)
        flatdoc = dict(doc)
        del flatdoc["topology"]
        assert a.load_tuning_plan(
            TuningPlan.from_json(json.dumps(flatdoc)), strict=True
        ) is not None
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# acclint: the leader-only pattern stays clean
# ---------------------------------------------------------------------------


def test_acclint_leader_only_cross_slice_call_sanitized(tmp_path):
    """`if topo.is_leader(rank): leaders_comm.allreduce(...)` is the
    decomposition's cross-slice stage — every member of the leaders
    subcomm makes the call, so the branch is not a sequence skew."""
    import textwrap

    from accl_tpu.analysis import run_checks

    p = tmp_path / "scenario.py"
    p.write_text(textwrap.dedent("""
    def work(accl, topo, comm, rank):
        intra = accl.create_communicator(topo.slice_members(
            topo.slice_of(rank)))
        accl.reduce(a, b, 64, root=0, comm=intra)
        if topo.is_leader(rank):
            leaders = accl.create_communicator(topo.leaders())
            accl.allreduce(a, b, 64, comm=leaders)
        accl.bcast(a, 64, root=0, comm=intra)
    """))
    findings = [
        f for f in run_checks([str(p)], ["collective-sequence"])
        if not f.suppressed
    ]
    assert not findings, [f.message for f in findings]
