"""acclint cross-file checks: the import graph and drain-path checks.

* **jax-free-module** — the modules the overlap/telemetry/chaos planes
  promise are importable from jax-free processes (``overlap``,
  ``telemetry``, ``faults``, ``plans``, ``constants``) must not import
  jax/numpy at module scope, directly OR through anything they import
  at module scope.  A socket-fabric rank process, the telemetry merge
  CLI, and the lock-order shim all rely on this staying true.
* **drain-before-config** — every config-write path (a function that
  constructs an ``Operation.CONFIG`` call) and every ``soft_reset``
  must reach a drain call before abandoning/overwriting engine state:
  a config write that overtakes in-flight work observes (and corrupts)
  a state snapshot mid-collective.  The check walks the intra-module
  call graph from each entry point looking for a drain-family call.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set

from .base import Finding, SourceFile, load_source, package_root

__all__ = [
    "CROSS_FILE_CHECKS",
    "check_jax_free_modules",
    "check_drain_before_config",
    "check_cmdring_slot_layout",
    "JAX_FREE_MODULES",
    "FORBIDDEN_HEAVY_IMPORTS",
]

#: accl_tpu modules that must stay importable without jax/numpy
JAX_FREE_MODULES = (
    "accl_tpu.overlap",
    "accl_tpu.telemetry",
    "accl_tpu.faults",
    "accl_tpu.plans",
    "accl_tpu.constants",
    "accl_tpu.contract",
    "accl_tpu.monitor",
    "accl_tpu.membership",
    "accl_tpu.arbiter",
    # quantized wire plane: the shared host codec + error-feedback
    # residual store (lazy numpy, the constants.py pattern) — socket
    # rank processes and the analysis tooling import both
    "accl_tpu.wire",
    "accl_tpu.errorfeedback",
    # multi-slice plane: the descriptor and decomposition math are
    # stdlib-only so every rank (and the analysis tooling, and the
    # numpy-only CI smokes) derives identical plans without jax
    "accl_tpu.topology",
    "accl_tpu.hierarchical",
)

#: top-level packages whose module-scope import breaks jax-freedom
#: (ml_dtypes transitively imports numpy)
FORBIDDEN_HEAVY_IMPORTS = frozenset((
    "jax", "jaxlib", "numpy", "ml_dtypes",
))


def _module_name(path: str, root: str) -> Optional[str]:
    """``accl_tpu.backends.base`` for ``<root>/backends/base.py`` where
    root is the accl_tpu package dir; None for files outside it."""
    rel = os.path.relpath(os.path.abspath(path), root)
    if rel.startswith(".."):
        return None
    parts = rel.replace(os.sep, "/").split("/")
    if parts[-1] == "__init__.py":
        parts = parts[:-1]
    else:
        parts[-1] = parts[-1][:-3]  # strip .py
    return ".".join(["accl_tpu"] + [p for p in parts if p])


def _module_scope_imports(tree: ast.Module):
    """(node, imported-module-name, level, from-aliases) for every
    import that runs at import time: top-level statements plus those
    nested in module-level ``if``/``try`` blocks (a ``try: import
    ml_dtypes`` still executes).  ``from-aliases`` carries the names an
    ImportFrom binds — ``from . import constants`` names a MODULE via
    its alias, which the consumer must try as a module too."""
    stack = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node, alias.name, 0, ()
        elif isinstance(node, ast.ImportFrom):
            yield node, node.module or "", node.level, tuple(
                a.name for a in node.names
            )
        elif isinstance(node, (ast.If, ast.Try, ast.With, ast.For,
                               ast.While)):
            # all of these EXECUTE their bodies at import time when they
            # sit at module level (`with suppress(ImportError): import
            # numpy` is the sneaky one — the idiom the old constants.py
            # try-block used, spelled via contextlib)
            for field in ("body", "orelse", "finalbody", "handlers"):
                for sub in getattr(node, field, ()):
                    if isinstance(sub, ast.ExceptHandler):
                        stack.extend(sub.body)
                    else:
                        stack.append(sub)
        # FunctionDef/ClassDef bodies do NOT run at import time


def _resolve_relative(mod: str, name: str, level: int, is_pkg: bool) -> str:
    """Absolute module name for a (possibly relative) import found in
    ``mod`` (e.g. level=1 name='constants' in accl_tpu.overlap ->
    accl_tpu.constants)."""
    if level == 0:
        return name
    parts = mod.split(".")
    if not is_pkg:
        parts = parts[:-1]
    if level > 1:
        parts = parts[: len(parts) - (level - 1)]
    return ".".join(parts + ([name] if name else []))


def check_jax_free_modules(sources: List[SourceFile]) -> List[Finding]:
    root = package_root()
    by_mod: Dict[str, SourceFile] = {}
    for src in sources:
        mod = _module_name(src.path, root)
        if mod:
            by_mod[mod] = src

    findings: List[Finding] = []
    if not by_mod:
        # analyzing loose files outside the package (fixture snippets,
        # a path override): the contract modules are out of scope
        return findings

    def _load_pkg_module(mod: str) -> Optional[SourceFile]:
        """The import closure is a WHOLE-PACKAGE fact: when the
        analyzer was pointed at a path subset, pull the missing
        package modules from disk so per-file invocations (pre-commit,
        editors) see the same verdict as the full run."""
        rel = mod.split(".")[1:]
        for cand in (
            os.path.join(root, *rel) + ".py",
            os.path.join(root, *rel, "__init__.py") if rel else None,
        ):
            if cand and os.path.isfile(cand):
                src, _ = load_source(cand)
                return src
        return None

    def _source_for(mod: str) -> Optional[SourceFile]:
        src = by_mod.get(mod)
        if src is None:
            src = _load_pkg_module(mod)
            if src is not None:
                by_mod[mod] = src
        return src

    # module -> [(line-node, imported absolute module)] at module scope;
    # ImportFrom aliases and ancestor subpackage __init__s are expanded
    # (both execute at import time)
    edge_cache: Dict[str, List] = {}

    def _edges(mod: str, src: SourceFile) -> List:
        outs = edge_cache.get(mod)
        if outs is not None:
            return outs
        is_pkg = src.path.endswith("__init__.py")
        outs = []
        for node, name, level, aliases in _module_scope_imports(src.tree):
            target = _resolve_relative(mod, name, level, is_pkg)
            candidates = [target]
            # 'from X import y': each alias may itself name a module
            for a in aliases:
                if a != "*":
                    candidates.append(f"{target}.{a}" if target else a)
            for t in candidates:
                outs.append((node, t))
                # importing accl_tpu.a.b also executes accl_tpu.a's
                # __init__ (the top package's init is bypassed by the
                # jax-free loaders, so it is deliberately excluded)
                parts = t.split(".")
                for i in range(2, len(parts)):
                    outs.append((node, ".".join(parts[:i])))
        edge_cache[mod] = outs
        return outs

    for entry in JAX_FREE_MODULES:
        if _source_for(entry) is None:
            findings.append(Finding(
                check="jax-free-module", path=entry, line=1,
                message=f"declared jax-free module {entry} not found in "
                        f"the package",
            ))
            continue
        # DFS over module-scope imports reachable from the entry module
        seen: Set[str] = set()
        reported: Set[tuple] = set()
        stack = [entry]
        while stack:
            mod = stack.pop()
            if mod in seen:
                continue
            seen.add(mod)
            src = _source_for(mod)
            if src is None:
                continue
            for node, target in _edges(mod, src):
                top = target.split(".")[0]
                if top in FORBIDDEN_HEAVY_IMPORTS:
                    key = (src.path, node.lineno, top)
                    if key in reported:
                        continue
                    reported.add(key)
                    chain = f" (imported via {mod})" if mod != entry else ""
                    findings.append(src.finding(
                        "jax-free-module", node,
                        f"module-scope import of {top!r} breaks the "
                        f"jax-free contract of {entry}{chain}; import it "
                        f"lazily inside the function that needs it",
                    ))
                elif top == "accl_tpu" and target != "accl_tpu":
                    stack.append(target)
    return findings


# ---------------------------------------------------------------------------
# drain-before-config
# ---------------------------------------------------------------------------

#: a call whose terminal attribute/name is one of these counts as
#: reaching the drain machinery
_DRAIN_NAMES = frozenset((
    "flush", "drain", "drain_key", "drain_inflight",
))


def _is_config_call(node: ast.AST) -> bool:
    """Is this node a CallOptions(op=Operation.CONFIG...) construction?"""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    name = (
        f.id if isinstance(f, ast.Name)
        else f.attr if isinstance(f, ast.Attribute) else None
    )
    if name != "CallOptions":
        return False
    for kw in node.keywords:
        if kw.arg == "op" and isinstance(kw.value, ast.Attribute):
            if (
                kw.value.attr == "CONFIG"
                and isinstance(kw.value.value, ast.Name)
                and kw.value.value.id == "Operation"
            ):
                return True
    return False


def _called_names(fn: ast.AST) -> Set[str]:
    """Terminal names of every call made in ``fn``'s body."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name):
                out.add(f.id)
            elif isinstance(f, ast.Attribute):
                out.add(f.attr)
    return out


def check_drain_before_config(sources: List[SourceFile]) -> List[Finding]:
    findings: List[Finding] = []
    for src in sources:
        # function name -> ALL same-named AST nodes, module-wide (two
        # classes in one module can both define soft_reset — every one
        # is an entry point, and a callee name may resolve to any of
        # them).  Use the shared flattened walk; call-name sets are
        # memoized per node.
        fns: Dict[str, List[ast.AST]] = {}
        config_lines: List[int] = []
        for node in src.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, []).append(node)
            elif _is_config_call(node):
                config_lines.append(node.lineno)
        called_cache: Dict[ast.AST, Set[str]] = {}

        def _called(f):
            got = called_cache.get(f)
            if got is None:
                got = called_cache[f] = _called_names(f)
            return got

        entries = [
            (name, fn)
            for name, nodes in fns.items()
            for fn in nodes
            if name == "soft_reset" or any(
                fn.lineno <= ln <= getattr(fn, "end_lineno", fn.lineno)
                for ln in config_lines
            )
        ]
        for name, fn in entries:
            # BFS through same-module callees (depth-limited) looking
            # for a drain-family call; a called name fans out to EVERY
            # same-named definition (static name resolution can't pick
            # the class, so reachability is the union)
            reached = False
            seen: Set[int] = set()
            frontier = [fn]
            for _ in range(4):  # entry + 3 levels of same-module calls
                nxt = []
                for f in frontier:
                    called = _called(f)
                    if called & _DRAIN_NAMES:
                        reached = True
                        break
                    for c in called:
                        for cand in fns.get(c, ()):
                            if id(cand) not in seen:
                                seen.add(id(cand))
                                nxt.append(cand)
                if reached or not nxt:
                    break
                frontier = nxt
            if not reached:
                findings.append(src.finding(
                    "drain-before-config", fn,
                    f"{name!r} writes engine config / resets state but "
                    f"never reaches a drain call "
                    f"({', '.join(sorted(_DRAIN_NAMES))}); in-flight "
                    f"work must complete before state is abandoned",
                ))
    return findings


# ---------------------------------------------------------------------------
# cmdring-slot-layout
# ---------------------------------------------------------------------------

#: names that constitute the command-ring slot contract; exactly ONE
#: definition (constants.py) may exist — the host-side encoder and the
#: device-side sequencer must both read it from there
_CMDRING_CANONICAL_NAMES = frozenset((
    "CMDRING_FIELDS", "CMDRING_SLOT_WORDS", "CmdOpcode",
    "CMDRING_ST_OK", "CMDRING_ST_BAD_OP",
))

#: modules that encode/decode slots (relative to the accl_tpu root)
_CMDRING_MODULES = (
    "cmdring.py",            # host half: slot codec + window shape
    "ops/cmdring.py",        # device half: decode loop + window program
    "backends/xla/cmdring.py",  # engine half: sessions + refills
)

#: the module holding the decode loop — it must reference every
#: executable opcode (the cross-file presence check)
_CMDRING_DECODE_MODULE = "ops/cmdring.py"

#: the shared device-side wire-lane module: its literal ``WIRE_LANES``
#: table must cover every dtype constants.WIRE_LANE_DTYPES registers
_WIRE_LANE_MODULE = "ops/wire.py"

#: the decode loop's per-slot function: it must route its wire cast
#: through the shared lane machinery (a wire value decoded privately
#: is a finding — the quantized-wire cross-check)
_CMDRING_DECODE_FUNC = "_decode_slot_xla"

#: names that constitute "routing through the shared lane machinery":
#: the roundtrip helper, or the cast+scaled lane pair it is built from
_WIRE_LANE_HELPERS = frozenset((
    "wire_lane_roundtrip", "_cast_lane", "quantize_int8",
    "dequantize_int8",
))

#: opcodes exempt from the decode-presence requirement: NOP is the
#: padding slot (decoded, skipped), HALT the teardown marker — neither
#: executes a collective
_CMDRING_MARKER_OPCODES = frozenset(("NOP", "HALT"))


def _cmdring_table(src: SourceFile):
    """(fields: {name: index} | None, slot_words: int | None) from the
    constants module's literal table."""
    fields = None
    slot_words = None
    for node in src.tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not isinstance(tgt, ast.Name):
            continue
        if tgt.id == "CMDRING_FIELDS" and isinstance(node.value, ast.Dict):
            fields = {}
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) and isinstance(
                    v, ast.Constant
                ):
                    fields[k.value] = v.value
        elif tgt.id == "CMDRING_SLOT_WORDS" and isinstance(
            node.value, ast.Constant
        ):
            slot_words = node.value.value
    return fields, slot_words


def _cmdring_opcodes(src: SourceFile):
    """(opcode name -> value, opcode-map line) from the constants
    module: the ``CmdOpcode`` IntEnum body (literal member assigns) and
    the names referenced as values of the ``CMDRING_OPCODES``
    Operation-map literal."""
    opcodes = None
    mapped = None
    map_line = 1
    for node in src.tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "CmdOpcode":
            opcodes = {}
            for stmt in node.body:
                if (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Constant)
                ):
                    opcodes[stmt.targets[0].id] = stmt.value.value
        elif (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "CMDRING_OPCODES"
            and isinstance(node.value, ast.Dict)
        ):
            map_line = node.lineno
            mapped = set()
            for v in node.value.values:
                if isinstance(v, ast.Attribute):
                    mapped.add(v.attr)
    return opcodes, mapped, map_line


def _wire_lane_dtypes(src: SourceFile):
    """constants.WIRE_LANE_DTYPES as a literal {member: numpy name}
    dict (None when absent — pre-quantized-wire trees)."""
    for node in src.tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "WIRE_LANE_DTYPES"
            and isinstance(node.value, ast.Dict)
        ):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) and isinstance(
                    v, ast.Constant
                ):
                    out[k.value] = v.value
            return out, node.lineno
    return None, 1


def _wire_lanes_table(src: SourceFile):
    """ops/wire.py's literal ``WIRE_LANES`` table (numpy-name keys)."""
    for node in src.tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "WIRE_LANES"
            and isinstance(node.value, ast.Dict)
        ):
            keys = set()
            for k in node.value.keys:
                if isinstance(k, ast.Constant):
                    keys.add(k.value)
            return keys, node.lineno
    return None, 1


def _func_wire_refs(src: SourceFile, fn_name: str):
    """(found_fn, helper names referenced) for one function: every ``X.helper`` / bare ``helper`` reference inside its body."""
    for node in src.tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == fn_name:
            refs = set()
            for sub in ast.walk(node):
                if (
                    isinstance(sub, ast.Attribute)
                    and sub.attr in _WIRE_LANE_HELPERS
                ):
                    refs.add(sub.attr)
                elif (
                    isinstance(sub, ast.Name)
                    and sub.id in _WIRE_LANE_HELPERS
                ):
                    refs.add(sub.id)
            return node, refs
    return None, set()


def _cmdopcode_refs(src: SourceFile):
    """Every ``CmdOpcode.<NAME>`` attribute referenced in a module (the
    presence evidence that its decode path handles the opcode)."""
    refs = set()
    for node in src.nodes:
        if isinstance(node, ast.Attribute):
            base = node.value
            if isinstance(base, ast.Name) and base.id == "CmdOpcode":
                refs.add(node.attr)
            elif (
                isinstance(base, ast.Attribute)
                and base.attr == "CmdOpcode"
            ):
                refs.add(node.attr)
    return refs


def check_cmdring_slot_layout(sources: List[SourceFile]) -> List[Finding]:
    """Encoder and sequencer must agree on the slot layout AND the
    opcode space from ONE definition each:

    * ``constants.CMDRING_FIELDS``/``CMDRING_SLOT_WORDS`` must be
      well-formed (dense, unique, in-bounds int indices); the cmdring
      modules may not REDEFINE any canonical layout name with a local
      literal (aliasing the imported table is fine), and every string
      subscript into a fields-table alias must name a field the
      canonical table defines — a typo'd or locally-invented field
      silently decodes the wrong word on device;
    * ``constants.CmdOpcode`` must be dense unique int values from 0
      (the sequencer's range-check status path depends on density);
    * every executable opcode (non-NOP/HALT) must appear as a value of
      the ``CMDRING_OPCODES`` Operation map (the engine's eligibility
      table covers the space) AND be referenced by the decode module's
      epilogue (``ops/cmdring.py``): the cross-file guarantee that
      growing the enum without wiring the decode loop fails the tree,
      not a workload."""
    root = package_root()
    findings: List[Finding] = []
    consts = None
    ringmods: List[SourceFile] = []
    decode_mod = None
    lane_mod = None
    for src in sources:
        mod = _module_name(src.path, root)
        if mod == "accl_tpu.constants":
            consts = src
        rel = os.path.relpath(os.path.abspath(src.path), root)
        rel = rel.replace(os.sep, "/")
        if rel in _CMDRING_MODULES:
            ringmods.append(src)
        if rel == _CMDRING_DECODE_MODULE:
            decode_mod = src
        if rel == _WIRE_LANE_MODULE:
            lane_mod = src
    if consts is None:
        return findings  # partial-scope run without constants.py
    fields, slot_words = _cmdring_table(consts)
    opcodes, mapped, map_line = _cmdring_opcodes(consts)
    # quantized-wire cross-check: every REGISTERED wire dtype must be
    # handled by the decode loop.  Handling is proven structurally:
    # (a) the decode function routes its wire cast through the shared
    # lane machinery (ops/wire helpers), the one lane table every
    # compressed program reads; (b) that table covers every registered
    # lane.  A lane the decode loop handles privately — or a registered
    # dtype the shared table misses — fails the tree before it can
    # surface as a silent workload fallback.
    lanes, lanes_line = _wire_lane_dtypes(consts)
    if lanes and decode_mod is not None:
        fn_name = _CMDRING_DECODE_FUNC
        fn_node, refs = _func_wire_refs(decode_mod, fn_name)
        if fn_node is None:
            findings.append(Finding(
                check="cmdring-slot-layout", path=decode_mod.path,
                line=1,
                message=f"decode module lost its decode function "
                        f"{fn_name!r}: the wire-lane cross-check "
                        "anchors on it by name",
            ))
        elif not refs:
            findings.append(decode_mod.finding(
                "cmdring-slot-layout", fn_node,
                f"decode function {fn_name!r} never routes through "
                f"the shared wire-lane helpers "
                f"({sorted(_WIRE_LANE_HELPERS)}): a wire dtype the "
                "ring decodes privately can diverge from every other "
                "compressed path's lane",
            ))
        if lane_mod is not None:
            table, table_line = _wire_lanes_table(lane_mod)
            if table is None:
                findings.append(Finding(
                    check="cmdring-slot-layout", path=lane_mod.path,
                    line=1,
                    message="ops/wire.py lost its literal WIRE_LANES "
                            "table — the registered-lane coverage "
                            "cross-check reads it",
                ))
            else:
                missing = sorted(set(lanes.values()) - table)
                if missing:
                    findings.append(Finding(
                        check="cmdring-slot-layout",
                        path=lane_mod.path, line=table_line,
                        message=f"registered wire dtypes {missing} "
                                "(constants.WIRE_LANE_DTYPES) missing "
                                "from the shared WIRE_LANES table: "
                                "the decode loop would fall back on "
                                "them",
                    ))
    if opcodes is not None and ringmods:
        vals = list(opcodes.values())
        if (
            not all(isinstance(v, int) for v in vals)
            or len(set(vals)) != len(vals)
            or sorted(vals) != list(range(len(vals)))
        ):
            findings.append(Finding(
                check="cmdring-slot-layout", path=consts.path, line=1,
                message=f"CmdOpcode values {sorted(vals)} must be "
                        "dense, unique ints from 0 — the sequencer's "
                        "status range-check depends on density",
            ))
        executable = set(opcodes) - set(_CMDRING_MARKER_OPCODES)
        if mapped is not None:
            missing_map = sorted(executable - mapped)
            if missing_map:
                findings.append(Finding(
                    check="cmdring-slot-layout", path=consts.path,
                    line=map_line,
                    message=f"CMDRING_OPCODES maps no Operation onto "
                            f"{missing_map}: the engine can never "
                            "encode these opcodes — dead enum growth",
                ))
        if decode_mod is not None:
            refs = _cmdopcode_refs(decode_mod)
            missing_dec = sorted(executable - refs)
            if missing_dec:
                findings.append(Finding(
                    check="cmdring-slot-layout", path=decode_mod.path,
                    line=1,
                    message=f"decode module never references CmdOpcode "
                            f"{missing_dec}: every window runs this "
                            "module's decode loop, so an unreferenced "
                            "opcode is an unimplemented one",
                ))
    if fields is None or slot_words is None:
        if ringmods:  # the ring exists but its contract table is gone
            findings.append(Finding(
                check="cmdring-slot-layout", path=consts.path, line=1,
                message="constants.py lost the literal CMDRING_FIELDS/"
                        "CMDRING_SLOT_WORDS table the encoder and "
                        "sequencer decode slots from",
            ))
        return findings
    # table well-formedness: dense unique int indices inside the slot
    idxs = list(fields.values())
    if (
        not all(isinstance(i, int) for i in idxs)
        or len(set(idxs)) != len(idxs)
        or any(i < 0 or i >= slot_words for i in idxs)
        or sorted(idxs) != list(range(len(idxs)))
    ):
        findings.append(Finding(
            check="cmdring-slot-layout", path=consts.path, line=1,
            message=f"CMDRING_FIELDS indices {sorted(idxs)} must be "
                    f"dense, unique ints in [0, CMDRING_SLOT_WORDS="
                    f"{slot_words})",
        ))
    for src in ringmods:
        # aliases of the canonical fields table in this module
        aliases = set()
        for node in src.nodes:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if not isinstance(tgt, ast.Name):
                    continue
                val = node.value
                refs_canonical = (
                    isinstance(val, ast.Name)
                    and val.id == "CMDRING_FIELDS"
                ) or (
                    isinstance(val, ast.Attribute)
                    and val.attr == "CMDRING_FIELDS"
                )
                if refs_canonical:
                    aliases.add(tgt.id)
                elif tgt.id in _CMDRING_CANONICAL_NAMES:
                    findings.append(src.finding(
                        "cmdring-slot-layout", node,
                        f"{tgt.id!r} redefined locally: the slot layout "
                        f"has exactly one definition (constants.py); "
                        f"import it instead of re-deriving",
                    ))
        aliases.add("CMDRING_FIELDS")
        for node in src.nodes:
            if not isinstance(node, ast.Subscript):
                continue
            base = node.value
            base_name = (
                base.id if isinstance(base, ast.Name)
                else base.attr if isinstance(base, ast.Attribute)
                else None
            )
            if base_name not in aliases:
                continue
            key = node.slice
            if isinstance(key, ast.Constant) and isinstance(
                key.value, str
            ) and key.value not in fields:
                findings.append(src.finding(
                    "cmdring-slot-layout", node,
                    f"slot field {key.value!r} is not in "
                    f"constants.CMDRING_FIELDS ({sorted(fields)}): "
                    f"encoder and sequencer must agree on one table",
                ))
    return findings


# ---------------------------------------------------------------------------
# postmortem-path
# ---------------------------------------------------------------------------

#: facade error codes the postmortem plane covers: every ACCLError the
#: facade raises with one of these must reach the BlackBox capture hook
_POSTMORTEM_ERROR_CODES = frozenset((
    "CONTRACT_VIOLATION", "RANK_EVICTED", "DEADLOCK_SUSPECTED",
))

#: a call whose terminal name is one of these counts as reaching the
#: postmortem machinery
_POSTMORTEM_NAMES = frozenset((
    "_structured_failure", "capture",
))

#: the module the rule scopes to (the facade owns the covered raises;
#: engines surface codes through Request retcodes, which the facade's
#: _check_failed funnels)
_POSTMORTEM_MODULE = "core.py"


def _postmortem_code_of(node: ast.AST) -> Optional[str]:
    """The covered ErrorCode name when ``node`` constructs
    ``ACCLError(ErrorCode.<covered>, ...)``; None otherwise."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    name = (
        f.id if isinstance(f, ast.Name)
        else f.attr if isinstance(f, ast.Attribute) else None
    )
    if name != "ACCLError" or not node.args:
        return None
    code = node.args[0]
    if (
        isinstance(code, ast.Attribute)
        and isinstance(code.value, ast.Name)
        and code.value.id == "ErrorCode"
        and code.attr in _POSTMORTEM_ERROR_CODES
    ):
        return code.attr
    return None


def check_postmortem_path(sources: List[SourceFile]) -> List[Finding]:
    """Every facade construction of a covered structured-failure
    ACCLError (CONTRACT_VIOLATION / RANK_EVICTED / DEADLOCK_SUSPECTED)
    must reach the BlackBox hook (``_structured_failure`` /
    ``capture``) within a depth-bounded walk of the same-module call
    graph — the drain-before-config machinery applied to the
    postmortem contract: a covered failure that skips the hook dies
    with only the local flight-recorder tail, exactly the evidence
    loss the bundle plane exists to remove."""
    findings: List[Finding] = []
    for src in sources:
        if not src.path.replace("\\", "/").endswith(
            "accl_tpu/" + _POSTMORTEM_MODULE
        ):
            continue
        fns: Dict[str, List[ast.AST]] = {}
        for node in src.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, []).append(node)
        called_cache: Dict[int, Set[str]] = {}

        def _called(f):
            got = called_cache.get(id(f))
            if got is None:
                got = called_cache[id(f)] = _called_names(f)
            return got

        for node in src.nodes:
            code = _postmortem_code_of(node)
            if code is None:
                continue
            # the function whose body constructs the error is the walk
            # entry (innermost enclosing function)
            entry = None
            for name, defs in fns.items():
                for fn in defs:
                    if fn.lineno <= node.lineno <= getattr(
                        fn, "end_lineno", fn.lineno
                    ):
                        if entry is None or fn.lineno > entry.lineno:
                            entry = fn
            if entry is None:
                findings.append(src.finding(
                    "postmortem-path", node,
                    f"module-scope ACCLError(ErrorCode.{code}) can "
                    f"never reach the BlackBox hook",
                ))
                continue
            reached = False
            seen: Set[int] = set()
            frontier = [entry]
            for _ in range(4):  # entry + 3 levels of same-module calls
                nxt = []
                for f in frontier:
                    called = _called(f)
                    if called & _POSTMORTEM_NAMES:
                        reached = True
                        break
                    for c in called:
                        for cand in fns.get(c, ()):
                            if id(cand) not in seen:
                                seen.add(id(cand))
                                nxt.append(cand)
                if reached or not nxt:
                    break
                frontier = nxt
            if not reached:
                findings.append(src.finding(
                    "postmortem-path", node,
                    f"{entry.name!r} raises ACCLError(ErrorCode.{code}) "
                    f"but never reaches the BlackBox hook "
                    f"({', '.join(sorted(_POSTMORTEM_NAMES))}); covered "
                    f"structured failures must capture their evidence "
                    f"bundle",
                ))
    return findings


CROSS_FILE_CHECKS = {
    "jax-free-module": check_jax_free_modules,
    "drain-before-config": check_drain_before_config,
    "cmdring-slot-layout": check_cmdring_slot_layout,
    "postmortem-path": check_postmortem_path,
}
