"""Device time by the program's in-program scopes (``device_scope`` names
such as ``accl.moe::experts``).

Where the scope is read from (looked at with ``trace_reduce.describe`` on
a v5e trace of this step, PR 26): the chip's trace carries ``op_name``
NOWHERE.  A device event's name is the HLO instruction's text without
its metadata, and its only stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``.  What it does carry
is the instruction's NAME (``%fusion.1043 = ...``; ``trace_reduce.load``
keeps it as the first word of a reduced event).  So the scope comes from
the compiled step's own HLO text, where every instruction has
``metadata={op_name="jit(step)/transpose(jvp(accl.moe::route))/..."}``
(a fusion carries its root's), joined to the trace by instruction name:
the driver hands ``scopes_of(step.as_text())`` to the readers as
``facts["scope_ops"]``.

One family of ops has no scope even there: the compiler lowers
``jax.lax.ragged_dot`` to its own Mosaic kernels and names them
``ragged-dot-none*`` (the grouped matmul, all three forms) and
``ragged-dot-metadata*`` (tile-to-group maps from the group sizes), with
that name as their whole ``op_name``.  The program calls ``ragged_dot``
only inside ``accl.moe::experts``, so those names are counted there.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List

SCOPE = re.compile(r"accl\.[a-z]+::[a-z_]+")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%(?P<name>\S+) = .*metadata=\{op_name="(?P<op>[^"]*)"'
)
#: ops the compiler names itself, and the scope their only caller has
NAMED = {"ragged-dot-": "accl.moe::experts"}


def scopes_of(hlo_text: str) -> Dict[str, List[str]]:
    """``{scope: [instruction names]}`` of a compiled module's text: the
    INNERMOST scope of the ``op_name`` of each instruction of the ENTRY
    computation (the ones that run as device events of their own; what a
    fusion calls runs inside the fusion's event, and this step has no
    loop whose body would need a walk of its own)."""
    out: Dict[str, List[str]] = {}
    start = hlo_text.find("\nENTRY ")
    entry = hlo_text[start + 1: hlo_text.find("\n}", start)] if start >= 0 else ""
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        found = SCOPE.findall(m["op"])
        if found:
            out.setdefault(found[-1], []).append(m["name"])
    return out


def instruction_name(event_name: str) -> str:
    """The instruction's name in a reduced event's
    ``<name> <opcode> <type>``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def scope_ns(reduced: dict, scope_ops: Dict[str, Iterable[str]]) -> Dict[str, float]:
    """Summed device nanoseconds by scope, averaged over the devices of a
    reduced trace; an event in no scope is counted nowhere."""
    where = {n: s for s, names in scope_ops.items() for n in names}
    n = max(len(reduced["devices"]), 1)
    out: Dict[str, float] = {}
    for events in reduced["devices"].values():
        for name, _, dur in events:
            op = instruction_name(name)
            scope = where.get(op) or next(
                (s for p, s in NAMED.items() if op.startswith(p)), None
            )
            if scope is not None:
                out[scope] = out.get(scope, 0.0) + dur / n
    return out
