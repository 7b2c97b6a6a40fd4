"""Inside the two host calls the chips wait on: the window's program call
and the drainer's wait, and the runtime's own host events in them.

The program opens ``accl::cmdring[n]`` into ``accl.ring::slots`` (the
slot words' ``device_put``) and ``accl.ring::program`` (the program's
lookup and its one call), and the window's ``accl.window::ready`` into
``accl.ring::wait`` (``block_until_ready``), ``accl.ring::status`` (the
status words' read-back) and ``accl.ring::settle``
(``accl_tpu/utils/profiling.py`` lists them).  What happens INSIDE a
program call no span of ours can see, but the runtime marks it: a trace
at the level ``perfbench/tracing.py`` takes holds jaxlib's and the
client's own host events on the same threads and the same clock.
``stage_spans.load`` drops them; ``load`` here keeps, beside everything
``stage_spans.load`` keeps, the events named in ``RUNTIME`` that lie
inside the interval of one of ``HOLDERS``: on its thread's own line, on
the line without a name that the TPU plugin's recorder writes beside it
(the same thread), and on the client's worker threads, one a chip.

Grouping is ``stage_spans.group`` (the ``small`` slice's gang calls) and
``window_spans.group`` (the ``window`` slice's windows) as they stand:
a runtime event is one more entry of a call's or a window's ``host``.
Readers give medians in microseconds, a WINDOW or a gang CALL; ``None``
against a program or a trace that lacks what they read.  The lags
between host and device and the two hand-overs are NOT clamped: the
drainer may enter ``accl.window::ready`` before the launching thread's
``park`` span closes, and a negative median is a finding.

``python3 -m perfbench.runtime_spans <xplane.pb>`` prints the tables of
one trace by hand: the stages, and chip 0's idle seconds by the
innermost span or runtime event that covers each gap's middle.
"""

from __future__ import annotations

import bisect
import functools
import os
import statistics
from typing import Callable, Dict, List, Optional

from perfbench import manifest, stage_spans, trace_reduce, window_spans
from perfbench.stage_spans import READY, end, median_us

SLOTS = "accl.ring::slots"
PROGRAM = "accl.ring::program"
WAIT = "accl.ring::wait"
STATUS = "accl.ring::status"
SETTLE = "accl.ring::settle"
DISPATCH = stage_spans.DISPATCH
#: the spans whose thread and interval the runtime's events are kept for
HOLDERS = (SLOTS, PROGRAM, DISPATCH, READY)

#: jaxlib's own, on the calling thread's line, on any client: the jitted
#: call from Python's side, its argument parsing, a ``device_put`` of
#: host data and its sharding, a device array read into numpy
JAXLIB = ("PjitFunction(", "ParseArguments", "DevicePutWithSharding",
          "shard_args", "np.asarray(jax.Array)")
#: the client's execute call.  ``PjRtCpuExecutable::Execute`` is the CPU
#: client's, on the calling thread's own line (the tier-1 mesh and
#: ``--rehearse``); ``PJRT_LoadedExecutable_Execute`` is the TPU
#: plugin's, read on four v5e at jax 0.9.0, jaxlib 0.9.0, libtpu 0.0.34.
#: The plugin's recorder writes a calling thread's events on a line of
#: its own WITHOUT a name beside the thread's ``python3`` line
#: (PERF.md, PR 50: each such line's events lie in the holder spans of
#: exactly one thread), so ``load`` hands them that thread
EXECUTE = ("PjRtCpuExecutable::Execute", "PJRT_LoadedExecutable_Execute")
#: the TPU plugin's other events on such a line (same versions)
CLIENT = ("CommonPjRtLoadedExecutable::Execute",
          "TpuClient::LinearizeIntoImpl", "CommonPjRtBuffer::ToLiteral")
#: the TPU client runs a call's part for each chip on a thread of its
#: own, named so; what such a thread does inside a holder's interval
#: keeps its own thread (same versions)
WORKER_THREADS = "py_xla_execute/"
WORKER = ("CommonPjRtLoadedExecutable::ExecutePrepare",
          "AllocateOutputBuffersWithInputReuse",
          "TpuLoadedExecutable::ExecuteLaunch",
          "tpu::System::AllocateAndFillTupleIndexTable")
RUNTIME = JAXLIB + EXECUTE + CLIENT + WORKER


def named(name: str, names) -> bool:
    """Whether an event's ``name`` is one of ``names``: a constant that
    ends in ``(`` is a prefix (the jitted function's name follows it),
    any other the whole name (``...::Execute`` is not
    ``...::ExecuteHelper``)."""
    return any(name.startswith(n) if n.endswith("(") else name == n
               for n in names)


def load(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """What ``stage_spans.load`` gives for one ``.xplane.pb``, and in
    ``host`` the ``RUNTIME`` events that lie inside the interval of a
    ``HOLDERS`` span, as events of the same form: those of the span's
    own line, those of a line without a name (given the thread of the
    ONE holder they lie in, or left out) and those of the client's
    ``WORKER_THREADS`` (under their own)."""
    from jax.profiler import ProfileData

    events = stage_spans.load(path, device_prefix)
    holders = sorted((e[1], end(e), e[3]) for e in events["host"]
                     if e[0] in HOLDERS)
    if not holders:
        return events
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"
            own = [h for h in holders if h[2] == thread]
            if not (own or line.name == ""
                    or line.name.startswith(WORKER_THREADS)):
                continue
            for e in line.events:
                if not named(e.name, RUNTIME):
                    continue
                a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
                around = {h[2] for h in (own or holders)
                          if h[0] <= a and b <= h[1]}
                if len(around) != 1:
                    continue
                events["host"].append([
                    e.name, a, b - a,
                    around.pop() if line.name == "" else thread,
                    {str(k): str(v) for k, v in e.stats},
                ])
    return events


@functools.lru_cache(maxsize=4)
def _grouped_at(path: str, mtime: float, which: str) -> tuple:
    cut = window_spans.group if which == window_spans.SLICE else (
        stage_spans.group)
    return tuple(cut(load(path)))


def _slice_of(ctx: dict, which: str) -> List[dict]:
    if which not in ctx.get("slices", {}):
        return []
    trace_dir = os.path.join(manifest.CHECKOUT, ".perfbench_trace",
                             ctx["cell"]["name"], which)
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return []
    return list(_grouped_at(path, os.path.getmtime(path), which))


def windows_of(ctx: dict) -> List[dict]:
    """The windows of this run's ``window`` slice with the runtime's
    events in them, read once a process."""
    return _slice_of(ctx, window_spans.SLICE)


def calls_of(ctx: dict) -> List[dict]:
    """The gang calls of this run's ``small`` slice, the same way."""
    return _slice_of(ctx, stage_spans.SLICE)


# -- what the readers share ---------------------------------------------------

one = window_spans.one  # a window's or a call's ONE span of a name, or None


def inside(group: dict, outer, names=EXECUTE) -> list:
    """The group's events named ``names``... on ``outer``'s thread and
    inside its interval."""
    return [e for e in group["host"]
            if e[3] == outer[3] and named(e[0], names)
            and outer[1] <= e[1] and end(e) <= end(outer)]


def execute_in(group: dict, holder: str) -> Optional[float]:
    """ns of the client's ONE execute event inside the group's one
    ``holder`` span, on its thread; None where there is no such span or
    not exactly one such event."""
    outer = one(group, holder)
    if outer is None:
        return None
    found = inside(group, outer)
    return found[0][2] if len(found) == 1 else None


def duration(group: dict, name: str) -> Optional[float]:
    span = one(group, name)
    return None if span is None else span[2]


def slots_put(window: dict) -> Optional[float]:
    return duration(window, SLOTS)


def program_call(window: dict) -> Optional[float]:
    return duration(window, PROGRAM)


def window_execute(window: dict) -> Optional[float]:
    return execute_in(window, PROGRAM)


def call_execute(call: dict) -> Optional[float]:
    return execute_in(call, DISPATCH)


def gate_spread(group: dict) -> Optional[float]:
    """Latest minus earliest start of the group's ``bench::`` spans, one
    a rank thread, each opened as the benchmark's gate released its
    thread: the gate's own release spread."""
    starts = [e[1] for e in group["bench"]]
    return max(starts) - min(starts) if len(starts) > 1 else None


# -- the window's stages (ns of one window, or None) -------------------------


def launch_lag(window: dict) -> Optional[float]:
    """First device op's start on any chip minus ``accl.ring::program``'s
    start."""
    program = one(window, PROGRAM)
    if program is None or window["device_start"] is None:
        return None
    return window["device_start"] - program[1]


def pickup(group: dict, park: str) -> Optional[float]:
    """``accl.window::ready``'s start minus the end of the launching
    thread's ``park`` span: the hand-over to the drainer."""
    parked, ready = one(group, park), one(group, READY)
    if parked is None or ready is None:
        return None
    return ready[1] - end(parked)


def window_pickup(window: dict) -> Optional[float]:
    return pickup(window, window_spans.PARK)


def wait_lag(window: dict) -> Optional[float]:
    """``accl.ring::wait``'s end minus the window's last device op's end."""
    wait = one(window, WAIT)
    if wait is None or window["device_end"] is None:
        return None
    return end(wait) - window["device_end"]


def status_read(window: dict) -> Optional[float]:
    status, settle = one(window, STATUS), one(window, SETTLE)
    if status is None or settle is None:
        return None
    return status[2] + settle[2]


def joined_by_id(window: dict) -> Optional[bool]:
    """Whether the drainer's three spans carry the ``window`` stat of the
    launching thread's ``accl.ring::encode``."""
    encode = one(window, window_spans.ENCODE)
    mine = [one(window, n) for n in (WAIT, STATUS, SETTLE)]
    if encode is None or None in mine:
        return None
    return all(e[4].get("window") == encode[4].get("window") for e in mine)


# -- the blocking call's (ns of one gang call, or None) ----------------------


def completion_pickup(call: dict) -> Optional[float]:
    return pickup(call, stage_spans.PARK)


# -- what the readers call ---------------------------------------------------


def per_window_us(ctx: dict, fn: Callable) -> Optional[float]:
    return median_us(fn(w) for w in windows_of(ctx))


def per_call_us(ctx: dict, fn: Callable) -> Optional[float]:
    return median_us(fn(c) for c in calls_of(ctx))


# -- the tables of one trace, by hand -----------------------------------------


def _negative(groups, fn) -> Optional[float]:
    values = [v for v in map(fn, groups) if v is not None]
    return sum(v < 0 for v in values) / len(values) if values else None


def _covered(group: dict, whole: str, parts) -> Optional[float]:
    """The share of the ``whole`` span that its ``parts`` cover."""
    outer = one(group, whole)
    found = [one(group, p) for p in parts]
    if outer is None or None in found or outer[2] <= 0:
        return None
    return sum(p[2] for p in found) / outer[2]


def _median(values) -> Optional[float]:
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def _runtime_in(groups, holder: str, same_thread: bool) -> Dict[str, float]:
    """Median us a group of each runtime name inside ``holder``'s
    interval: on its thread the union of the name's events (namesakes
    nest), on the other threads the ``WORKER`` names alone (the
    client's threads, one a chip), a thread's union averaged over them."""
    names = JAXLIB + EXECUTE + CLIENT if same_thread else WORKER
    totals: Dict[str, list] = {}
    for g in groups:
        outer = one(g, holder)
        if outer is None:
            continue
        mine: Dict[str, Dict[str, list]] = {}
        for e in g["host"]:
            if (named(e[0], names) and (e[3] == outer[3]) == same_thread
                    and outer[1] <= e[1] and end(e) <= end(outer)):
                mine.setdefault(e[0], {}).setdefault(e[3], []).append(
                    (e[1], end(e)))
        for name, threads in mine.items():
            totals.setdefault(name, []).append(sum(
                b - a for spans in threads.values()
                for a, b in trace_reduce.merge(spans)) / len(threads))
    return {name: median_us(v) for name, v in sorted(totals.items())}


def report_windows(windows: List[dict]) -> dict:
    """Medians over ``windows`` (us a window): the two spans opened, the
    runtime's events in them, the share of each whole span its parts
    cover, and the share of windows in which a lag came out negative."""
    def over(fn):
        return median_us(map(fn, windows))

    cmdring = window_spans.CMDRING
    return {
        "windows": len(windows),
        cmdring: over(lambda w: duration(w, cmdring)),
        SLOTS: over(slots_put),
        PROGRAM: over(program_call),
        "execute": over(window_execute),
        "cmdring_covered_share": _median(
            _covered(w, cmdring, (SLOTS, PROGRAM)) for w in windows),
        "runtime_in_slots": _runtime_in(windows, SLOTS, True),
        "runtime_in_program": _runtime_in(windows, PROGRAM, True),
        "workers_in_program": _runtime_in(windows, PROGRAM, False),
        "launch_lag": over(launch_lag),
        "launch_lag_negative_share": _negative(windows, launch_lag),
        "launch_lag_from_cmdring": over(window_spans.launch_lag),
        "device_start_before_program_end_share": _negative(
            windows, lambda w: None if launch_lag(w) is None
            else launch_lag(w) - program_call(w)),
        "gate_spread": over(gate_spread),
        "arrival_spread": over(window_spans.arrival_spread),
        "pickup": over(window_pickup),
        "pickup_negative_share": _negative(windows, window_pickup),
        READY: over(lambda w: duration(w, READY)),
        WAIT: over(lambda w: duration(w, WAIT)),
        STATUS: over(lambda w: duration(w, STATUS)),
        SETTLE: over(lambda w: duration(w, SETTLE)),
        "ready_covered_share": _median(
            _covered(w, READY, (WAIT, STATUS, SETTLE)) for w in windows),
        "runtime_in_ready": _runtime_in(windows, READY, True),
        "wait_lag": over(wait_lag),
        "wait_lag_negative_share": _negative(windows, wait_lag),
        "ready_lag": over(window_spans.ready_lag),
        "joined_by_id_share": _median(
            None if (j := joined_by_id(w)) is None else float(j)
            for w in windows),
    }


def report_calls(calls: List[dict]) -> dict:
    """The same for the gang calls of a ``small`` slice (us a call)."""
    def over(fn):
        return median_us(map(fn, calls))

    return {
        "calls": len(calls),
        DISPATCH: over(lambda c: duration(c, DISPATCH)),
        "execute": over(call_execute),
        "runtime_in_dispatch": _runtime_in(calls, DISPATCH, True),
        "workers_in_dispatch": _runtime_in(calls, DISPATCH, False),
        "launch_lag": over(stage_spans.launch_lag),
        "launch_lag_negative_share": _negative(calls, stage_spans.launch_lag),
        "device_start_before_dispatch_end_share": _negative(
            calls, lambda c: None if stage_spans.launch_lag(c) is None
            else stage_spans.launch_lag(c) - duration(c, DISPATCH)),
        "gate_spread": over(gate_spread),
        "pickup": over(completion_pickup),
        "pickup_negative_share": _negative(calls, completion_pickup),
        READY: over(lambda c: duration(c, READY)),
        "ready_lag": over(stage_spans.ready_lag),
        "ready_lag_negative_share": _negative(calls, stage_spans.ready_lag),
    }


def idle_by_innermost(events: dict, top: int = 12) -> List[list]:
    """Chip 0's idle seconds between the slice's first and last
    ``bench::`` span by the INNERMOST span or runtime event: each gap
    (``trace_reduce.idle_gaps``) is cut at the edges of the host events
    that overlap it, and each piece goes to the shortest event covering
    its middle (``trace_reduce.covering_span``).  Uncut, a window's
    idle time is ONE gap of milliseconds, whose middle names whatever
    happens to lie there."""
    if not events["devices"]:
        return []
    window = trace_reduce.window_of(events, "bench::")
    ops = events["devices"][sorted(events["devices"])[0]]
    host = sorted(events["host"], key=lambda e: e[1])
    starts = [e[1] for e in host]
    longest = max((e[2] for e in host), default=0.0)
    totals: Dict[str, float] = {}
    for a, b in trace_reduce.idle_gaps(ops, window):
        over = [e for e in host[bisect.bisect_left(starts, a - longest):
                                bisect.bisect_left(starts, b)]
                if end(e) > a]
        edges = sorted({a, b} | {t for e in over for t in (e[1], end(e))
                                 if a < t < b})
        active, k = [], 0
        for lo, hi in zip(edges, edges[1:]):  # no event starts or ends inside
            while k < len(over) and over[k][1] <= lo:
                active.append(over[k])
                k += 1
            active = [e for e in active if end(e) > lo]
            name = trace_reduce.covering_span(active, (lo + hi) / 2)
            totals[name] = totals.get(name, 0.0) + (hi - lo)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


if __name__ == "__main__":
    # python3 -m perfbench.runtime_spans <xplane.pb>: the tables as JSON
    import json
    import sys

    loaded = load(sys.argv[1])
    print(json.dumps({
        "idle_by_innermost": idle_by_innermost(loaded),
        "windows": report_windows(window_spans.group(loaded)),
        "calls": report_calls(stage_spans.group(loaded)),
    }))
