"""trace_reduce.py on small recorded traces.

``data/hand.json`` is written by hand so every expected number can be
worked out on paper; ``data/chip_*.json`` are cut from traces recorded on
the v5e by this benchmark (``trace_reduce.load`` then the first events),
so the reduction is checked on the names and shapes a chip really gives.
"""

import glob
import os

import pytest

from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def hand():
    return tr.load_reduced(os.path.join(DATA, "hand.json"))


def test_merge_unions_overlapping_and_touching_intervals():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 7), (10, 11),
    ]


def test_window_is_the_extent_of_the_benchmarks_spans(hand):
    # bench::small::allreduce 100..600 and 1000..1400 (two threads)
    assert tr.window_of(hand) == (100.0, 1400.0)


def test_busy_is_the_union_averaged_over_devices(hand):
    # TPU:0 ops: [200,300) [250,400) [1100,1200) -> 200 + 100 = 300
    # TPU:1 ops: [200,500)                        -> 300
    assert tr.busy_ns(hand, (100.0, 1400.0)) == 300.0
    # clipped to a window that cuts the first op in half
    assert tr.busy_ns(hand, (250.0, 1400.0)) == (250.0 + 250.0) / 2


def test_idle_gaps_and_their_host_spans(hand):
    gaps = tr.idle_gaps(hand["devices"]["/device:TPU:0"], (100.0, 1400.0))
    assert gaps == [(100.0, 200.0), (400.0, 1100.0), (1200.0, 1400.0)]
    by_span = dict(tr.gaps_by_span(hand, (100.0, 1400.0)))
    # 100..200 (middle 150) lies in accl::allreduce 120..580, the shortest
    # cover; 400..1100 (750) in no span; 1200..1400 (1300) in the second
    # bench span only
    assert by_span == {
        "accl::allreduce": 100e-9,
        tr.NO_SPAN: 700e-9,
        "bench::small::allreduce": 200e-9,
    }


def test_ops_by_name_and_kernel_time(hand):
    ops = dict(tr.ops_by_name(hand))
    assert ops["fusion.1"] == pytest.approx((100 + 100 + 300) / 2 / 1e9)
    assert ops["custom-call.2"] == pytest.approx(150 / 2 / 1e9)
    assert tr.kernel_ns(hand, lambda n: n.startswith("custom-call")) == 75.0


def test_self_time_pairs_spans_on_one_thread(hand):
    # thread A: bench 100..600 holds accl 120..580 -> 500 - 460 = 40
    # thread B: bench 1000..1400 holds no accl span -> nothing
    assert tr.nested_self_ns(hand, "bench::small::", "accl::") == [40.0]
    assert tr.span_durations_ns(hand, "accl::") == [460.0]


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(DATA, "chip_*.json")))
)
def test_recorded_chip_trace_reduces(path):
    r = tr.load_reduced(path)
    expect = r.pop("expect")
    window = tr.window_of(r)
    assert window[1] > window[0]
    busy = tr.busy_ns(r, window)
    assert 0 < busy <= window[1] - window[0]
    assert busy == pytest.approx(expect["busy_ns"])
    assert [n for n, _ in tr.ops_by_name(r, 3)] == expect["top_ops"]
    gaps = tr.gaps_by_span(r, window)
    idle = sum(s for _, s in tr.gaps_by_span(r, window, top=10 ** 9))
    first = sorted(r["devices"])[0]
    one = tr.busy_ns({"devices": {first: r["devices"][first]}, "host": []},
                     window)
    assert idle * 1e9 + one == pytest.approx(window[1] - window[0])
    assert gaps and len(gaps) <= 10
