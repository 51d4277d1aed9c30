"""Phases of the training step on the device, read from the trace.

The program names the phases of its step with ``jax.named_scope``
(``repro.launch.steps.STEP_PHASES``); each compiled instruction carries
its scope in the ``op_name`` of its metadata, e.g.
``jit(step)/agent_grads/vmap(transpose(jvp()))/while``.  A device event
in the trace is named by its instruction's HLO text without that
metadata.  So the compiled step's text (the fact ``step_hlo``:
``jstep.as_text()`` after a traced window) is parsed into one key per
instruction, and each device event is joined to it by the same key: the name, the result
type, the opcode, the operands' names and the attributes before the
metadata.  Instruction names are unique only within one program, and
the token feed's program (``jit__tokens``) runs in the window too; its
instructions share names such as ``%fusion.1`` with the step's, and the
rest of the key tells them apart.

Phases: ``forward`` and ``backward`` split ``agent_grads`` by
``transpose(`` in the ``op_name``, which marks the backward pass.
Recomputation counts as backward, where it runs: a layer recomputed
under ``jax.checkpoint`` (its ``op_name`` holds ``transpose(``), and a
copy the compiler's rematerialization made of a forward instruction
(named ``<name>.remat``).  ``attack``, ``aggregate`` and ``optimizer``
are their scopes.  Instructions the compiler adds have no metadata: a fusion whose root is
such a bitcast takes the phase of the instruction nearest its root that
has one, and a layout copy, a zero fill or a cast takes the phase of
the first instruction that uses it (the kernel's input copies count to
``aggregate``).  Events nest (a scanned layer's ``while`` holds its
body's operations), so only the outermost events count: a body
operation is counted once, through its ``while``.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

# The program's scope names (repro.launch.steps.STEP_PHASES), spelled
# out so that this file reads a program that lacks them.
SCOPES = ("agent_grads", "attack", "aggregate", "optimizer")
PHASES = ("forward", "backward", "attack", "aggregate", "optimizer")

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?(%[\w.\-]+ = .*)$")
_OPCODE = re.compile(r"[\]\})] ([a-z][a-z0-9_\-]*)\(")
_OPERAND = re.compile(r"%[\w.\-]+")
_CALLS = re.compile(r"\b(?:calls|body|to_apply)=(%[\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_NOT_TRACED = (", metadata=", ", backend_config=")


def _split(text: str):
    """(name, result type, opcode, operands' text, attributes before the
    metadata) of one instruction's text, or None."""
    head, sep, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    if not sep or m is None:
        return None
    depth = 0
    for i in range(m.end() - 1, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            break
    attrs = rest[i + 1:]
    for cut in _NOT_TRACED:
        attrs = attrs.split(cut, 1)[0]
    return (head.strip(), rest[:m.start() + 1], m.group(1),
            rest[m.end():i], attrs.strip())


def key(text: str) -> Optional[Tuple]:
    """The join key of one instruction, from a line of the compiled
    program's text or from a device event's name: (name, result type,
    opcode, operand names, attributes before the metadata).  The trace
    prints each operand with its type and leaves the metadata out; the
    program's text prints operands by name alone."""
    parts = _split(text)
    if parts is None:
        return None
    name, typ, opcode, operands, attrs = parts
    return (name, typ, opcode, tuple(_OPERAND.findall(operands)), attrs)


def parse_hlo(text: str) -> Dict[Tuple, Optional[str]]:
    """Join key -> phase of every instruction of a compiled step's text
    (None: no phase, as for the program's parameters)."""
    ins: Dict[str, dict] = {}
    roots: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    comp = None
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c is not None:
            comp = c.group(1)
            continue
        m = _INSTRUCTION.match(line)
        parts = m and _split(m.group(2))
        if not parts:
            continue
        on = _OP_NAME.search(line)
        name = parts[0]
        operands = _OPERAND.findall(parts[3])
        ins[name] = {"key": key(m.group(2)), "operands": operands,
                     "phase": phase_of(on.group(1) if on else None),
                     "calls": _CALLS.findall(parts[4])}
        if m.group(1):
            roots[comp] = name
        for o in operands:
            users.setdefault(o, []).append(name)

    def nearest_root(comp_name):
        """The phase of the instruction nearest the computation's root
        that has one (operands breadth first)."""
        todo, seen = [roots.get(comp_name)], set()
        while todo:
            n = todo.pop(0)
            if n in ins and n not in seen:
                seen.add(n)
                if ins[n]["phase"]:
                    return ins[n]["phase"]
                todo += ins[n]["operands"]
        return None

    # a computation lists operands before their users, so in reverse
    # text order every user is resolved before the instruction it uses
    phase: Dict[str, Optional[str]] = {}
    for n in reversed(list(ins)):
        i = ins[n]
        phase[n] = i["phase"] or next(
            (p for p in map(nearest_root, i["calls"]) if p), None) or next(
            (phase[u] for u in users.get(n, ()) if phase.get(u)), None)
    # the compiler's rematerialized copies (``<name>.remat``) recompute
    # forward values for the backward pass, where they run
    return {i["key"]: "backward" if phase[n] == "forward" and ".remat" in n
            else phase[n] for n, i in ins.items()}


def phase_of(op_name: Optional[str]) -> Optional[str]:
    """The step phase an ``op_name`` belongs to, or None."""
    for part in (op_name or "").split("/"):
        if part == "agent_grads":
            return "backward" if "transpose(" in op_name else "forward"
        if part in SCOPES:
            return part
    return None


def outermost(events: Iterable[Tuple[str, float, float]]
              ) -> List[Tuple[str, float, float]]:
    """The events that no other event contains, in time order.  An event
    that starts inside another and ends after it keeps only its part
    beyond the other's end."""
    out, reach = [], float("-inf")
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        if e <= reach:
            continue
        out.append((n, max(s, reach), e))
        reach = e
    return out


def phase_seconds(trace, step_hlo: str) -> Dict[Optional[str], float]:
    """Device seconds inside the window per phase (None: operations of
    no phase, the token feed's among them), outermost events only, mean
    over the chips."""
    table = parse_hlo(step_hlo)
    keys: Dict[str, Optional[str]] = {}
    w0, w1 = trace.window
    tot: Dict[Optional[str], float] = {}
    for c in trace.chips:
        for n, s, e in outermost(trace.ops[c]):
            if e <= w0 or s >= w1:
                continue
            if n not in keys:
                keys[n] = table.get(key(n))
            ph = keys[n]
            tot[ph] = tot.get(ph, 0.0) + min(e, w1) - max(s, w0)
    n_chips = max(len(trace.chips), 1)
    return {ph: v * 1e-9 / n_chips for ph, v in tot.items()}


def phase_ms(ctx, phase: str) -> Optional[float]:
    """Device milliseconds per step of ``phase`` in the traced window,
    mean over chips; None where the run has no compiled step's text, or
    no operation of that phase ran (a program without the scopes).  The
    train driver keeps no ``step_hlo`` yet, so no metric reads this
    (PERF.md, section 7)."""
    f = ctx.facts
    if not f.get("steps") or not f.get("step_hlo"):
        return None
    s = phase_seconds(ctx.trace, f["step_hlo"]).get(phase, 0.0)
    return s / f["steps"] * 1e3 if s > 0 else None
