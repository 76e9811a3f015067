"""Device phases: which `jax.named_scope` each instruction of a compiled
program runs under (DESIGN.md §15).

A serving program wraps its parts in named scopes (``repair``,
``gather``, ``step``, …).  XLA keeps the scope path in every optimized
instruction's ``op_name`` metadata, but a device trace names an op only
by its instruction text.  `Phased` compiles a jitted program ahead of
time at its first call, parses the optimized HLO once into a map from
instruction name to top-level phase, and registers the map here under
the program's name (``tick`` for ``jit_tick``).  `phase_of` then gives
the phase of any op event from its instruction text, which is what the
profiler records:

    for text, start_ns, dur_ns in xla_ops_events:
        phase = obs.phase_of(text, "tick")     # "gather", … or None

Instructions without a scope of their own take the phase of the
instruction that calls their computation (a ``while`` body its loop's),
else of an operand or a user; the map flags such a phase as inherited,
and `phase_lookup` gives the flag, so that a reduction can say how much
of a phase's time it took by inheritance.  An instruction that XLA
merged from several scopes keeps one ``op_name``, and so one phase.
Programs compiled in several shapes (one admission program per prompt
bucket) keep one map each; the lookup tells them apart by the
instruction's result type.  The registry holds the small maps only,
never executables, so it outlives the programs.
"""
from __future__ import annotations

import re
import threading
import zlib
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax

__all__ = ["Phased", "parse_phases", "register_phases", "phase_of",
           "phase_lookup", "phase_maps", "load_phase_maps"]

#: instruction name -> (phase or None, result-type signature, whether the
#: phase is inherited rather than the instruction's own scope)
PhaseMap = Dict[str, Tuple[Optional[str], int, bool]]

#: maps kept per program name (programs compiled in many shapes)
MAX_VARIANTS = 16

_LOCK = threading.Lock()
_MAPS: Dict[str, Dict[int, PhaseMap]] = {}

_MODULE = re.compile(r"^HloModule (\S+?),?\s")
_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(body|condition|to_apply|calls|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_REF = re.compile(r"%([\w.\-]+)")
_TRANSFORM = re.compile(r"^(?:vmap|pmap|jvp|transpose|remat|checkpoint|"
                        r"shard_map)\((.*)\)$")
_SHAPE = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\]")
#: opcodes that run no work of their own: left out of coverage counts
STRUCTURAL = frozenset({"parameter", "constant", "tuple",
                        "get-tuple-element", "bitcast"})


def _split_type(rest: str) -> Tuple[str, str, str]:
    """``<type> <opcode>(<operands>)<attrs>`` -> (type, opcode, the rest
    from the operand list on); brackets nest in tuple types and layouts."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            j = rest.find("(", i)
            if j < 0:
                return rest[:i], rest[i + 1:], ""
            return rest[:i], rest[i + 1:j], rest[j:]
    return rest, "", ""


def _operand_list(tail: str) -> str:
    """The ``(...)`` operand list at the head of `tail`."""
    depth = 0
    for i, ch in enumerate(tail):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                return tail[:i + 1]
    return tail


def _signature(rtype: str, opcode: str) -> int:
    """A short hash of an instruction's result shapes and opcode (layouts
    left out, as a trace may print them differently)."""
    key = opcode + ":" + ";".join(f"{d}[{s}]" for d, s in
                                  _SHAPE.findall(rtype))
    return zlib.crc32(key.encode())


def signature_of(text: str) -> Optional[Tuple[str, int]]:
    """(instruction name, signature) of an instruction's text, as a
    trace event or an HLO listing gives it."""
    m = _INSTR.match(text)
    if m is None:
        return None
    rtype, opcode, _ = _split_type(m.group(2))
    return m.group(1), _signature(rtype, opcode)


def _scope_phase(op_name: str, phases: Sequence[str]) -> Optional[str]:
    """The outermost named scope in `phases` on an op_name path, seen
    through transforms (``vmap(gather)``); the last component is the
    primitive, never a scope."""
    for part in op_name.split("/")[:-1]:
        while (t := _TRANSFORM.match(part)):
            part = t.group(1)
        if part in phases:
            return part
    return None


def parse_phases(hlo_text: str, phases: Sequence[str]
                 ) -> Tuple[str, PhaseMap, Dict[str, str]]:
    """Parse optimized HLO text into (program name, phase map, opcode by
    instruction).  Only computations that run as ops are mapped: the
    entry and what control flow calls, not fusion bodies or reducers."""
    m = _MODULE.match(hlo_text)
    module = m.group(1) if m else ""
    program = module[4:] if module.startswith("jit_") else module
    comps: Dict[str, List[Tuple[str, str, str, str]]] = {}
    entry = None
    cur: Optional[List] = None
    for line in hlo_text.splitlines():
        if cur is None:
            c = _COMP.match(line)
            if c:
                cur = comps.setdefault(c.group(2), [])
                if c.group(1):
                    entry = c.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        i = _INSTR.match(line)
        if i:
            rtype, opcode, tail = _split_type(i.group(2))
            cur.append((i.group(1), rtype, opcode, tail))
    out: PhaseMap = {}
    opcodes: Dict[str, str] = {}
    if entry is None:
        return program, out, opcodes
    # walk what runs, from the entry, each computation inheriting the
    # phase of the instruction that calls it
    todo = [(entry, None, False)]
    seen = set()
    while todo:
        comp, inherited, is_cond = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        instrs = comps[comp]
        phase: Dict[str, Optional[str]] = {}
        own = set()
        for name, rtype, opcode, tail in instrs:
            op = _OP_NAME.search(tail)
            scope = _scope_phase(op.group(1), phases) if op else None
            if scope is not None:
                own.add(name)
            phase[name] = scope or inherited
        # unscoped instructions (copies XLA inserted) take an operand's
        # phase, else a user's
        operands = {name: _REF.findall(_operand_list(tail))
                    for name, _, _, tail in instrs}
        users: Dict[str, List[str]] = {}
        for name, ops in operands.items():
            for o in ops:
                users.setdefault(o, []).append(name)
        for name, *_ in instrs:
            if phase[name] is None:
                phase[name] = next((phase[o] for o in operands[name]
                                    if phase.get(o)), None)
        for name, *_ in reversed(instrs):
            if phase[name] is None:
                phase[name] = next((phase[u] for u in users.get(name, ())
                                    if phase[u]), None)
        for name, rtype, opcode, tail in instrs:
            out[name] = (phase[name], _signature(rtype, opcode),
                         phase[name] is not None and name not in own)
            opcodes[name] = "loop-control" if is_cond else opcode
            called = [(k, c) for k, c in _CALLED.findall(tail)
                      if not (k == "calls" and opcode == "fusion")
                      and not (k == "to_apply" and opcode != "call")]
            for b in _BRANCHES.findall(tail):
                called += [("branch", c) for c in _REF.findall(b)]
            for kind, c in called:
                todo.append((c, phase[name], kind == "condition"))
    return program, out, opcodes


def register_phases(hlo_text: str, phases: Sequence[str]
                    ) -> Tuple[str, PhaseMap, Dict[str, str]]:
    """Parse and register one compiled program's map; returns what
    `parse_phases` gives."""
    program, pmap, opcodes = parse_phases(hlo_text, phases)
    key = zlib.crc32(hlo_text.encode())
    with _LOCK:
        variants = _MAPS.setdefault(program, {})
        variants.pop(key, None)
        variants[key] = pmap
        while len(variants) > MAX_VARIANTS:
            variants.pop(next(iter(variants)))
    return program, pmap, opcodes


def phase_lookup(text: str, program: Optional[str] = None
                 ) -> Optional[Tuple[str, bool]]:
    """(phase, inherited) of the op whose instruction text is `text` (a
    device trace's XLA Ops event name), among the maps of `program` (or
    of every registered program); None where no map gives it a phase, or
    several maps with the same instruction disagree on it.  `inherited`
    holds where any of them took the phase from a loop, an operand or a
    user rather than the instruction's own scope."""
    sig = signature_of(text)
    if sig is None:
        return None
    name, s = sig
    with _LOCK:
        maps = [m for p, v in _MAPS.items()
                if program is None or p == program for m in v.values()]
    found = [m[name] for m in maps if name in m and m[name][1] == s]
    phases = {e[0] for e in found}
    if len(phases) != 1 or None in phases:
        return None
    return phases.pop(), any(e[2] for e in found)


def phase_of(text: str, program: Optional[str] = None) -> Optional[str]:
    """The phase of the op whose instruction text is `text`, as
    `phase_lookup` finds it, or None."""
    hit = phase_lookup(text, program)
    return hit[0] if hit else None


def phase_maps() -> Dict[str, List[Dict[str, list]]]:
    """A JSON-ready copy of the registry: program -> its maps."""
    with _LOCK:
        return {p: [{n: list(e) for n, e in m.items()} for m in v.values()]
                for p, v in _MAPS.items()}


def load_phase_maps(maps: Dict[str, Iterable[Dict[str, list]]]) -> None:
    """Register maps saved by `phase_maps` (a recorded trace's, say)."""
    with _LOCK:
        for p, ms in maps.items():
            variants = _MAPS.setdefault(p, {})
            for m in ms:
                pmap = {n: (e[0], int(e[1]), bool(e[2]))
                        for n, e in m.items()}
                variants[zlib.crc32(repr(sorted(pmap.items())).encode())] \
                    = pmap


class Phased:
    """A jitted program compiled ahead of time at its first call and its
    phase map registered.  Every call then goes through the jitted
    function, whose caches hold that same executable: one trace and one
    compile, as a plain first call would take.  (Where later arguments
    come with other shardings, as a tick's outputs do on a mesh, jit
    compiles anew as it always did; that variant registers no map.)

    The persistent compilation cache keys this compile with its metadata:
    by default the key leaves the scopes out, and an executable cached
    from the same program without them would come back with none."""

    def __init__(self, jitted, phases: Sequence[str]):
        self.jitted, self.phases = jitted, tuple(phases)
        self.compiled = None
        #: after the first call: the program's name, its phase map and
        #: each mapped instruction's opcode
        self.program: Optional[str] = None
        self.phase_map: PhaseMap = {}
        self.opcodes: Dict[str, str] = {}

    def __call__(self, *args):
        if self.compiled is None:
            key = "jax_compilation_cache_include_metadata_in_key"
            was = getattr(jax.config, key)
            jax.config.update(key, True)
            try:
                self.compiled = self.jitted.lower(*args).compile()
            finally:
                jax.config.update(key, was)
            text = self.compiled.as_text()
            if text:
                self.program, self.phase_map, self.opcodes = \
                    register_phases(text, self.phases)
        return self.jitted(*args)
