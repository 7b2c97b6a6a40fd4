"""The stage spans of a blocking gang call, grouped call by call.

The program marks the stages of ``ACCL.<collective>`` with host spans
named ``accl.<layer>::<stage>`` (``accl_tpu/utils/profiling.py`` lists
them), on the profiler's clock, which is the device trace's.
``trace_reduce.load`` keeps only ``accl::`` and ``bench::`` names, so
that the older metrics read what they read; this module keeps the
``accl.`` names too, with each event's thread and stats, and the device
op lines, and cuts the ``small`` slice into gang calls.

The sweep's gate releases one call at a time with nothing in flight, so
calls never overlap: a gang call is one ``bench::small::<op>`` span a
rank thread, overlapping in time, and everything that starts inside their
union.  A call is a dict::

    {"op": "allreduce", "start": ns, "end": ns,   # the union
     "bench": [event, ...],                       # one a rank thread
     "host": [event, ...],                        # accl. and accl:: spans
     "device_end": ns | None, "device_start": ns | None}

and an event is ``[name, start_ns, dur_ns, thread, stats]``.

``load`` is the only function that touches jax; grouping and the
helpers work on plain lists (``tests/data/stage_calls.json``).  Against
a program without the stage spans every helper finds nothing and the
readers return ``None``.
"""

from __future__ import annotations

import functools
import os
import statistics
from typing import Dict, List, Optional

from perfbench import manifest, trace_reduce

HOST_PREFIXES = ("accl.", "accl::", "bench::")
BENCH = "bench::small::"
SLICE = "small"

CALL = "accl.facade::call"
PREPARE = "accl.facade::prepare"   # holds PLAN
PLAN = "accl.facade::plan"
PLANES = ("accl.facade::membership", "accl.facade::arbiter",
          "accl.facade::contract", "accl.facade::meta")
SUBMIT = "accl.facade::submit"
WAIT = "accl.facade::wait"
ASSEMBLE = "accl.gang::assemble"
DISPATCH = "accl.gang::dispatch"
ADOPT = "accl.gang::adopt"
PARK = "accl.gang::park"
READY = "accl.window::ready"
COMPLETE = "accl.window::complete"
ENGINE = "accl::"            # the gang engine's own span of the call


def load(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """``{"host": [event, ...], "devices": {plane: [[name, start, dur]]}}``
    of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    host: list = []
    devices: Dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == trace_reduce.OP_LINE:
                    devices[plane.name] = [
                        [trace_reduce.short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)]
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([
                            e.name, float(e.start_ns), float(e.duration_ns),
                            f"{line.name}#{i}",
                            {str(k): str(v) for k, v in e.stats},
                        ])
    return {"host": host, "devices": devices}


def end(event) -> float:
    return event[1] + event[2]


def group(events: dict) -> List[dict]:
    """Cut ``events`` (what ``load`` gives) into gang calls.  A call cut
    by the slice's edge (fewer bench spans than rank threads) is left
    out."""
    bench = sorted((e for e in events["host"] if e[0].startswith(BENCH)),
                   key=lambda e: e[1])
    world = len({e[3] for e in bench})
    calls: List[dict] = []
    for e in bench:
        if calls and e[1] < calls[-1]["end"]:
            call = calls[-1]
            call["bench"].append(e)
            call["end"] = max(call["end"], end(e))
        else:
            calls.append({"op": e[0][len(BENCH):], "start": e[1],
                          "end": end(e), "bench": [e], "host": [],
                          "device_start": None, "device_end": None})
    calls = [c for c in calls
             if len(c["bench"]) == world
             and len({e[3] for e in c["bench"]}) == world]
    if not calls:
        return []
    others = sorted((e for e in events["host"] if not e[0].startswith(BENCH)),
                    key=lambda e: e[1])
    ops = sorted((o for plane in events["devices"].values() for o in plane
                  if o[2] > 0), key=lambda o: o[1])
    i = j = 0
    for call in calls:
        while i < len(others) and others[i][1] < call["start"]:
            i += 1
        while i < len(others) and others[i][1] < call["end"]:
            call["host"].append(others[i])
            i += 1
        while j < len(ops) and ops[j][1] < call["start"]:
            j += 1
        while j < len(ops) and ops[j][1] < call["end"]:
            o = ops[j]
            if call["device_start"] is None:
                call["device_start"] = o[1]
            call["device_end"] = max(call["device_end"] or 0.0, o[1] + o[2])
            j += 1
    return calls


@functools.lru_cache(maxsize=4)
def _calls_at(path: str, mtime: float) -> tuple:
    return tuple(group(load(path)))


def calls_of(ctx: dict) -> List[dict]:
    """The gang calls of this run's ``small`` slice, read once a process;
    empty where the run made no such slice."""
    if SLICE not in ctx.get("slices", {}):
        return []
    trace_dir = os.path.join(manifest.CHECKOUT, ".perfbench_trace",
                             ctx["cell"]["name"], SLICE)
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return []
    return list(_calls_at(path, os.path.getmtime(path)))


# -- what the readers share ---------------------------------------------------


def spans(call: dict, name: str) -> list:
    """The call's host spans named ``name``; with ``ENGINE``, the spans
    whose name starts with it."""
    if name == ENGINE:
        return [e for e in call["host"] if e[0].startswith(ENGINE)]
    return [e for e in call["host"] if e[0] == name]


def one(call: dict, name: str):
    """The call's one span named ``name``, or None where it has none or
    several (a call this module does not understand is left out, never
    guessed at)."""
    found = spans(call, name)
    return found[0] if len(found) == 1 else None


def rank_calls(call: dict) -> List[Dict[str, list]]:
    """For each rank thread of the call, its facade spans by name:
    the thread's outermost ``accl.facade::call`` and what lies in it."""
    out = []
    for thread in sorted({e[3] for e in call["bench"]}):
        mine = [e for e in call["host"] if e[3] == thread]
        outer = next((e for e in mine if e[0] == CALL), None)
        if outer is None:
            continue
        inside: Dict[str, list] = {CALL: outer}
        for e in mine:
            if e is not outer and e[1] >= outer[1] and end(e) <= end(outer):
                inside.setdefault(e[0], e)
        out.append(inside)
    return out


def median_us(values_ns) -> Optional[float]:
    values_ns = [v for v in values_ns if v is not None]
    return statistics.median(values_ns) / 1e3 if values_ns else None


def duration_us(ctx: dict, name: str) -> Optional[float]:
    """Median duration of the span ``name`` over the gang calls, us."""
    return median_us(
        e[2] for c in calls_of(ctx) for e in spans(c, name)
    )


def per_call_us(ctx: dict, fn) -> Optional[float]:
    """Median over the gang calls of ``fn(call)`` (ns, or None), us."""
    return median_us(fn(c) for c in calls_of(ctx))


def per_rank_call_us(ctx: dict, fn) -> Optional[float]:
    """Median over every rank thread's call of ``fn(spans by name)``."""
    return median_us(
        fn(rc) for c in calls_of(ctx) for rc in rank_calls(c)
    )


# -- the stages (ns of one call, or None) --------------------------------------


def intake(rc: Dict[str, list]) -> Optional[float]:
    if SUBMIT not in rc:
        return None
    return rc[SUBMIT][1] - rc[CALL][1]


def planes(rc: Dict[str, list]) -> Optional[float]:
    if not all(p in rc for p in PLANES):
        return None
    return sum(rc[p][2] for p in PLANES)


def rendezvous(call: dict) -> Optional[float]:
    engine, first = one(call, ENGINE), spans(call, CALL)
    if engine is None or not first:
        return None
    return engine[1] - min(e[1] for e in first)


def completion(call: dict) -> Optional[float]:
    engine, done = one(call, ENGINE), one(call, COMPLETE)
    if engine is None or done is None:
        return None
    return end(done) - end(engine)


def wake(call: dict) -> Optional[float]:
    done = one(call, COMPLETE)
    if done is None:
        return None
    return call["end"] - end(done)


def ready_lag(call: dict) -> Optional[float]:
    ready = one(call, READY)
    if ready is None or call["device_end"] is None:
        return None
    return end(ready) - call["device_end"]


def launch_lag(call: dict) -> Optional[float]:
    """First device op's start minus the start of ``accl.gang::dispatch``
    (reported in PERF.md beside ``ready_lag_us``, not a metric)."""
    dispatch = one(call, DISPATCH)
    if dispatch is None or call["device_start"] is None:
        return None
    return call["device_start"] - dispatch[1]


# -- the stage table of one trace, by hand --------------------------------------


def report(calls: List[dict]) -> dict:
    """Medians over ``calls`` (us) of every stage, of what no sub-span
    covers, of the sum that should tile the call, and the share of calls
    in which a lag between host and device clocks came out negative:
    what PERF.md's stage table is made from."""
    rcs = [rc for c in calls for rc in rank_calls(c)]

    def dur(name):
        return median_us(e[2] for c in calls for e in spans(c, name))

    def engine_rest(call):
        engine = one(call, ENGINE)
        parts = [one(call, n) for n in (ASSEMBLE, DISPATCH, ADOPT, PARK)]
        if engine is None or None in parts:
            return None
        return engine[2] - sum(p[2] for p in parts)

    def intake_rest(rc):
        if intake(rc) is None or planes(rc) is None or PREPARE not in rc:
            return None
        return intake(rc) - planes(rc) - rc[PREPARE][2]

    def tiled(call):
        parts = (rendezvous(call), one(call, ENGINE), completion(call),
                 wake(call))
        if None in parts:
            return None
        return parts[0] + parts[1][2] + parts[2] + parts[3]

    def negative(fn):
        values = [v for v in map(fn, calls) if v is not None]
        return sum(v < 0 for v in values) / len(values) if values else None

    out = {
        "calls": len(calls),
        "union": median_us(c["end"] - c["start"] for c in calls),
        "tiled": median_us(map(tiled, calls)),
        "intake": median_us(map(intake, rcs)),
        "intake_rest": median_us(map(intake_rest, rcs)),
        "planes": median_us(map(planes, rcs)),
        "rendezvous": median_us(map(rendezvous, calls)),
        "engine_rest": median_us(map(engine_rest, calls)),
        "completion": median_us(map(completion, calls)),
        "wake": median_us(map(wake, calls)),
        "ready_lag": median_us(map(ready_lag, calls)),
        "launch_lag": median_us(map(launch_lag, calls)),
        "ready_lag_negative_share": negative(ready_lag),
        "launch_lag_negative_share": negative(launch_lag),
    }
    for name in (CALL, PREPARE, PLAN, *PLANES, SUBMIT, WAIT, ENGINE,
                 ASSEMBLE, DISPATCH, ADOPT, PARK, READY, COMPLETE):
        out[name] = dur(name)
    return out


if __name__ == "__main__":
    # python3 -m perfbench.stage_spans <xplane.pb>: the stage table as JSON
    import json
    import sys

    print(json.dumps(report(group(load(sys.argv[1])))))
