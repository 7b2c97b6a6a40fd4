"""From a profiler trace (``.xplane.pb``) to busy and idle time, kernel
time by name, and idle gaps attributed to host spans.

The reduction is the yardstick: it lives with the benchmark so that
every PR computes the same number the same way.  ``load`` is the only
function that touches jax (``jax.profiler.ProfileData``); the rest works
on plain lists and is checked on a small recorded trace in
``tests/data``.

A reduced trace is a dict::

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host":    [[name, start_ns, dur_ns, thread], ...]}

``devices`` holds the events of each device plane's op line, ``host``
the host spans whose names start with one of ``HOST_PREFIXES`` (the
program's ``accl::`` TraceAnnotations and the benchmark's ``bench::``).
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_PREFIXES = ("accl::", "bench::")
#: the line of a device plane that holds one event for each executed HLO
#: op.  "XLA Modules" covers a whole program with the idle inside it, and
#: "Steps" / "XLA TraceMe" are not device work.
OP_LINE = "XLA Ops"
NO_SPAN = "no span"

Interval = Tuple[float, float]

#: the mark ``short_name`` leaves on a Mosaic (Pallas) kernel's events
KERNEL_MARK = "tpu_custom_call"
_HLO = re.compile(r"^%?(?P<name>\S+) = (?P<type>.*?) (?P<op>[\w\-]+)\(")


def short_name(text: str) -> str:
    """A device event on a TPU is named by the whole text of its HLO
    instruction (kilobytes).  Keep ``<name> <opcode> <result type>``, the
    type without its layout, and mark a Mosaic kernel by its call target:
    ``jvp__.6 custom-call tpu_custom_call bf16[256,1024,128]``."""
    m = _HLO.match(text)
    if m is None:
        return text[:120]
    kind = m["op"]
    if "custom_call_target=\"" + KERNEL_MARK + "\"" in text:
        kind += " " + KERNEL_MARK
    result = re.sub(r"\{[^}]*\}", "", m["type"])
    return f"{m['name']} {kind} {result}"[:120]


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """Reduce one ``.xplane.pb`` to the dict above."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] = [
                        [short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)]
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            # Python threads all carry the process's name: a line is told
            # from its namesakes by its place in the plane
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns),
                                     f"{line.name}#{i}"])
    return {"devices": devices, "host": host}


def describe(path: str, top: int = 12) -> dict:
    """Planes, lines, event counts and the first events with their stats:
    what to look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = {
                "events": len(events),
                "first": [
                    [e.name, e.start_ns, e.duration_ns,
                     {str(k): str(v)[:120] for k, v in e.stats}]
                    for e in events[:top]
                ],
            }
        out[plane.name] = lines
    return out


def load_reduced(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# -- intervals ---------------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _op_intervals(events: Sequence) -> List[Interval]:
    return [(e[1], e[1] + e[2]) for e in events if e[2] > 0]


def window_of(reduced: dict, prefix: str = "bench::") -> Interval:
    """The steady window of a slice: from the first start to the last end
    of the host spans named ``prefix``... (the benchmark wraps its calls
    into the program in them), which leaves out the profiler's own start
    and stop."""
    spans = [e for e in reduced["host"] if e[0].startswith(prefix)]
    if not spans:
        return (0.0, 0.0)
    return (min(e[1] for e in spans), max(e[1] + e[2] for e in spans))


def busy_ns(reduced: dict, window: Interval = None) -> float:
    """Nanoseconds in which an op ran on a device, averaged over the
    devices in the trace, clipped to ``window``."""
    per_device = []
    for events in reduced["devices"].values():
        merged = merge(_op_intervals(events))
        if window is not None:
            merged = [(max(a, window[0]), min(b, window[1]))
                      for a, b in merged
                      if b > window[0] and a < window[1]]
        per_device.append(sum(b - a for a, b in merged))
    return sum(per_device) / len(per_device) if per_device else 0.0


def idle_gaps(events: Sequence, window: Interval) -> List[Interval]:
    """The parts of ``window`` in which no op of ``events`` ran."""
    gaps, at = [], window[0]
    for a, b in merge(_op_intervals(events)):
        if b <= window[0] or a >= window[1]:
            continue
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < window[1]:
        gaps.append((at, window[1]))
    return gaps


def covering_span(host: Sequence, t: float) -> str:
    """The name of the SHORTEST host span that covers instant ``t`` (the
    innermost one), or ``NO_SPAN``."""
    best, best_dur = NO_SPAN, None
    for name, start, dur, *_ in host:
        if start <= t <= start + dur and (best_dur is None or dur < best_dur):
            best, best_dur = name, dur
    return best


def gaps_by_span(reduced: dict, window: Interval, device: str = None,
                 top: int = 10) -> List[list]:
    """Idle seconds of one device (the first by name unless given),
    grouped by the host span that covers each gap's middle; the ``top``
    groups, longest first."""
    if not reduced["devices"]:
        return []
    device = device or sorted(reduced["devices"])[0]
    totals: Dict[str, float] = {}
    for a, b in idle_gaps(reduced["devices"][device], window):
        name = covering_span(reduced["host"], (a + b) / 2)
        totals[name] = totals.get(name, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def ops_by_name(reduced: dict, top: int = 10) -> List[list]:
    """Device seconds by op name, averaged over devices; ``top`` first."""
    totals: Dict[str, float] = {}
    n = max(len(reduced["devices"]), 1)
    for events in reduced["devices"].values():
        for name, _, dur in events:
            totals[name] = totals.get(name, 0.0) + dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def kernel_ns(reduced: dict, matches) -> float:
    """Summed device nanoseconds of the ops whose name ``matches``
    (a predicate on the name), averaged over devices."""
    n = max(len(reduced["devices"]), 1)
    return sum(
        dur for events in reduced["devices"].values()
        for name, _, dur in events if matches(name)
    ) / n


def span_durations_ns(reduced: dict, prefix: str) -> List[float]:
    return [e[2] for e in reduced["host"] if e[0].startswith(prefix)]


def nested_self_ns(reduced: dict, outer_prefix: str,
                   inner_prefix: str) -> List[float]:
    """For every ``inner`` span, the duration of the ``outer`` span of
    the same thread that contains it, minus the inner's own: the outer
    layer's self time on the calls where both ran on one thread."""
    outers: Dict[str, list] = {}
    for name, start, dur, thread in reduced["host"]:
        if name.startswith(outer_prefix):
            outers.setdefault(thread, []).append((start, start + dur))
    out = []
    for name, start, dur, thread in reduced["host"]:
        if not name.startswith(inner_prefix):
            continue
        for a, b in outers.get(thread, ()):
            if a <= start and start + dur <= b:
                out.append((b - a) - dur)
                break
    return out


if __name__ == "__main__":
    # look at a trace by hand: planes, lines, first events, then the reduction
    import sys

    for plane, lines in describe(sys.argv[1]).items():
        print("PLANE", plane)
        for name, info in lines.items():
            print("   LINE", name, info["events"],
                  [e[0][:60] for e in info["first"][:3]])
    reduced = load(sys.argv[1])
    window = window_of(reduced)
    print("window_ns", window, "busy_ns", busy_ns(reduced, window))
    print("ops", json.dumps(ops_by_name(reduced)))
    print("gaps", json.dumps(gaps_by_span(reduced, window)))
