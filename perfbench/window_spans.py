"""The stage spans of a batched window, grouped window by window.

``with a.batch():`` queues a rank's async collectives; its exit flushes
them as ONE gang event, which the command ring executes as one program.
The program marks the stages with host spans on the profiler's clock
(``accl_tpu/utils/profiling.py`` lists them)::

    rank threads   bench::window > accl.batch::flush > accl.batch::submit
                                                     > accl.batch::drain
    the thread that completed the gang slot (inside its submit)
                   accl.ring::batch > plan, deps, encode, assemble,
                                      accl::cmdring[n], adopt, park
    drainer        accl.window::ready, accl.window::complete

This module reads the ``window`` slice through ``stage_spans.load`` and
cuts it with ``stage_spans.group``: the sweep's gate lets one window run
at a time, so a window is one ``bench::window`` span a rank thread,
overlapping in time, and everything that starts inside their union — the
same dict as a gang call there, and ``busy`` besides (ns in which an op
ran on a device, averaged over the chips).  A window cut by the slice's
edge is left out, and so is one this module does not understand: not
exactly one of each of ``WHOLE``.  Against a program without the spans
that is every window, and every reader returns ``None``.

All readers give medians over the traced windows in microseconds a
WINDOW; the window's union is 8 x ``coll_batched_p50``.
"""

from __future__ import annotations

import functools
import os
import statistics
from typing import Dict, List, Optional

from perfbench import manifest, stage_spans, trace_reduce
from perfbench.stage_spans import COMPLETE, READY, end, median_us

SLICE = "window"
BENCH = "bench::window"

FLUSH = "accl.batch::flush"     # holds SUBMIT and DRAIN
SUBMIT = "accl.batch::submit"
DRAIN = "accl.batch::drain"
CALL = stage_spans.CALL         # one a queued collective, before FLUSH
RING = "accl.ring::batch"       # holds PARTS, in that order
PLAN = "accl.ring::plan"
DEPS = "accl.ring::deps"
ENCODE = "accl.ring::encode"
ASSEMBLE = "accl.ring::assemble"
CMDRING = "accl::cmdring["      # a prefix: the name ends in the slot count
ADOPT = "accl.ring::adopt"
PARK = "accl.ring::park"
PARTS = (PLAN, DEPS, ENCODE, ASSEMBLE, CMDRING, ADOPT, PARK)
#: what a window has exactly one of, or it is left out
WHOLE = (RING, CMDRING, READY, COMPLETE)


def spans(window: dict, name: str) -> list:
    if name == CMDRING:
        return [e for e in window["host"] if e[0].startswith(CMDRING)]
    return [e for e in window["host"] if e[0] == name]


def one(window: dict, name: str):
    found = spans(window, name)
    return found[0] if len(found) == 1 else None


def _busy_ns(devices: Dict[str, list], start: float, stop: float) -> float:
    if not devices:
        return 0.0
    return sum(
        sum(b - a for a, b in trace_reduce.merge(
            (o[1], o[1] + o[2]) for o in ops
            if o[2] > 0 and start <= o[1] < stop
        ))
        for ops in devices.values()
    ) / len(devices)


def group(events: dict) -> List[dict]:
    """Cut ``events`` (what ``stage_spans.load`` gives) into the windows
    this module understands.  ``stage_spans.group`` cuts a slice by the
    spans named ``stage_spans.BENCH``...: a window's are handed to it
    under that prefix."""
    as_calls = dict(events, host=[
        [stage_spans.BENCH + SLICE] + list(e[1:]) if e[0] == BENCH else e
        for e in events["host"]
    ])
    windows = [w for w in stage_spans.group(as_calls)
               if all(one(w, name) is not None for name in WHOLE)]
    for w in windows:
        w["busy"] = _busy_ns(events["devices"], w["start"], w["end"])
    return windows


@functools.lru_cache(maxsize=4)
def _windows_at(path: str, mtime: float) -> tuple:
    return tuple(group(stage_spans.load(path)))


def windows_of(ctx: dict) -> List[dict]:
    """The windows of this run's ``window`` slice, read once a process;
    empty where the run made no such slice."""
    if SLICE not in ctx.get("slices", {}):
        return []
    trace_dir = os.path.join(manifest.CHECKOUT, ".perfbench_trace",
                             ctx["cell"]["name"], SLICE)
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return []
    return list(_windows_at(path, os.path.getmtime(path)))


def rank_windows(window: dict) -> List[Dict[str, list]]:
    """For each rank thread of the window: its ``bench::window`` span
    under ``BENCH``, its first flush, submit and drain inside that by
    name, and under ``CALL`` the LIST of its queued calls' spans."""
    out = []
    for bench in window["bench"]:
        mine = [e for e in window["host"]
                if e[3] == bench[3] and e[1] >= bench[1]
                and end(e) <= end(bench)]
        inside: Dict[str, list] = {
            BENCH: bench, CALL: [e for e in mine if e[0] == CALL],
        }
        for e in mine:
            if e[0] in (FLUSH, SUBMIT, DRAIN):
                inside.setdefault(e[0], e)
        out.append(inside)
    return out


# -- the stages (ns of one window or of one rank thread's, or None) -----------


def queue(rw: Dict[str, list]) -> Optional[float]:
    if FLUSH not in rw:
        return None
    return rw[FLUSH][1] - rw[BENCH][1]


def queued_calls(rw: Dict[str, list]) -> Optional[float]:
    """Of ``queue``, what the queued collectives' own facade calls took."""
    if FLUSH not in rw:
        return None
    return sum(e[2] for e in rw[CALL] if end(e) <= rw[FLUSH][1])


def rank_wake(window: dict, rw: Dict[str, list]) -> float:
    """A rank thread's own wake: its ``bench::window`` end minus the end
    of the window's ``accl.window::complete`` (``wake`` is the latest)."""
    return end(rw[BENCH]) - end(one(window, COMPLETE))


def ring_thread_drain(window: dict) -> Optional[float]:
    """``accl.batch::drain`` on the thread that ran the window: the one
    drain that finds it in flight (``drain_inflight`` sees LAUNCHED
    calls only, and the other ranks flush before the launch)."""
    thread = one(window, RING)[3]
    found = [e for e in spans(window, DRAIN) if e[3] == thread]
    return found[0][2] if len(found) == 1 else None


def _arrivals(window: dict) -> Optional[list]:
    starts = [e[1] for e in spans(window, SUBMIT)]
    return starts if len(starts) == len(window["bench"]) else None


def first_arrival(window: dict) -> Optional[float]:
    starts = _arrivals(window)
    return None if starts is None else min(starts) - window["start"]


def arrival_spread(window: dict) -> Optional[float]:
    """Latest minus earliest ``accl.batch::submit`` start: the
    benchmark gate's release and the rank threads' turns at the GIL
    (reported in PERF.md, not a metric)."""
    starts = _arrivals(window)
    return None if starts is None else max(starts) - min(starts)


def rendezvous(window: dict) -> Optional[float]:
    starts = _arrivals(window)
    return None if starts is None else one(window, RING)[1] - max(starts)


def deps_encode(window: dict) -> Optional[float]:
    deps, encode = one(window, DEPS), one(window, ENCODE)
    if deps is None or encode is None:
        return None
    return deps[2] + encode[2]


def adopt_park(window: dict) -> Optional[float]:
    adopt, park = one(window, ADOPT), one(window, PARK)
    if adopt is None or park is None:
        return None
    return adopt[2] + park[2]


def ring_rest(window: dict) -> Optional[float]:
    """What no sub-span of ``accl.ring::batch`` covers."""
    parts = [one(window, name) for name in PARTS]
    if None in parts:
        return None
    return one(window, RING)[2] - sum(p[2] for p in parts)


def to_ready(window: dict) -> Optional[float]:
    """``accl.ring::park``'s end to ``accl.window::ready``'s end: the
    host waits for the device and for the news of it."""
    park = one(window, PARK)
    return None if park is None else end(one(window, READY)) - end(park)


def ready_lag(window: dict) -> Optional[float]:
    if window["device_end"] is None:
        return None
    return end(one(window, READY)) - window["device_end"]


def launch_lag(window: dict) -> Optional[float]:
    """First device op's start minus the start of ``accl::cmdring[n]``
    (reported in PERF.md beside ``window_ready_lag_us``, not a metric)."""
    if window["device_start"] is None:
        return None
    return window["device_start"] - one(window, CMDRING)[1]


def wake(window: dict) -> float:
    return window["end"] - end(one(window, COMPLETE))


def tiled(window: dict) -> Optional[float]:
    """The stages that should tile the window's union, summed."""
    parts = (first_arrival(window), arrival_spread(window),
             rendezvous(window), to_ready(window))
    if None in parts:
        return None
    return (sum(parts) + one(window, RING)[2] + one(window, COMPLETE)[2]
            + wake(window))


# -- what the readers call ---------------------------------------------------


def duration_us(ctx: dict, name: str) -> Optional[float]:
    """Median duration of the span ``name`` over the windows, us."""
    return median_us(
        e[2] for w in windows_of(ctx) for e in spans(w, name)
    )


def per_window_us(ctx: dict, fn) -> Optional[float]:
    return median_us(fn(w) for w in windows_of(ctx))


def per_rank_window_us(ctx: dict, fn) -> Optional[float]:
    return median_us(
        fn(rw) for w in windows_of(ctx) for rw in rank_windows(w)
    )


# -- the stage table of one trace, by hand -----------------------------------


def report(windows: List[dict]) -> dict:
    """Medians over ``windows`` (us a window) of every span and stage,
    of what no sub-span of ``accl.ring::batch`` covers and the share of
    it they do cover, of the sum that should tile the window, of the
    device's busy time, and the share of windows in which a lag between
    host and device came out negative: what PERF.md's table is made
    from."""
    rws = [(w, rw) for w in windows for rw in rank_windows(w)]

    def over(fn):
        return median_us(map(fn, windows))

    def over_ranks(fn):
        return median_us(fn(rw) for _, rw in rws)

    def negative(fn):
        values = [v for v in map(fn, windows) if v is not None]
        return sum(v < 0 for v in values) / len(values) if values else None

    def covered(window):
        rest = ring_rest(window)
        return None if rest is None else 1.0 - rest / one(window, RING)[2]

    shares = [c for c in map(covered, windows) if c is not None]
    out = {
        "windows": len(windows),
        "union": over(lambda w: w["end"] - w["start"]),
        "tiled": over(tiled),
        "queue": over_ranks(queue),
        "queued_calls": over_ranks(queued_calls),
        "first_arrival": over(first_arrival),
        "arrival_spread": over(arrival_spread),
        "rendezvous": over(rendezvous),
        "deps_encode": over(deps_encode),
        "adopt_park": over(adopt_park),
        "ring_rest": over(ring_rest),
        "ring_covered_share": statistics.median(shares) if shares else None,
        "to_ready": over(to_ready),
        "wake": over(wake),
        "rank_wake": median_us(rank_wake(w, rw) for w, rw in rws),
        "ring_thread_drain": over(ring_thread_drain),
        "ready_lag": over(ready_lag),
        "launch_lag": over(launch_lag),
        "device_busy": over(lambda w: w["busy"]),
        "ready_lag_negative_share": negative(ready_lag),
        "launch_lag_negative_share": negative(launch_lag),
    }
    for name in (FLUSH, SUBMIT, DRAIN, RING, *PARTS, READY, COMPLETE):
        out[name] = median_us(e[2] for w in windows for e in spans(w, name))
    # the medians of the stages, end to end: against "union", it says
    # whether the table's rows add up to a window
    stages = [out[k] for k in ("first_arrival", "arrival_spread",
                               "rendezvous", *PARTS, "to_ready", COMPLETE,
                               "wake")]
    out["stages_sum"] = None if None in stages else sum(stages)
    return out


if __name__ == "__main__":
    # python3 -m perfbench.window_spans <xplane.pb>: the stage table as JSON
    import json
    import sys

    print(json.dumps(report(group(stage_spans.load(sys.argv[1])))))
