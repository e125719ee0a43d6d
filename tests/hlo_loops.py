"""The loops of a compiled program, read from its optimized HLO text.

``loops(compiled.as_text())`` gives one ``Loop`` per ``while``: its
trip count (``known_trip_count`` where the compiler wrote one, as the
CPU's does; else the one constant its condition compares the counter
with, as the TPU's leaves it) and every instruction its body reaches —
through fusions, calls and nested loops — as ``(opcode, result shape,
text)``. The train segment's structure tests hold the step loop's body
to "no cut of the population's state" with it, on the CPU's compiler
and on the TPU's.
"""

import re
from typing import NamedTuple

import jax

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = (.*)$")
_OPCODE = re.compile(r"^([a-z][a-z\-]*)\(")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONSTANT = re.compile(r" constant\((\d+)\)")


class Loop(NamedTuple):
    trips: int | None
    body: list  # [(opcode, result shape, the instruction's text)]


def _split(rest: str):
    """``shape opcode(...)`` -> (shape, opcode): the shape is one token,
    or a parenthesised tuple that may hold spaces."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        shape, tail = rest[: i + 1], rest[i + 1 :].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    m = _OPCODE.match(tail)
    return shape, (m.group(1) if m else "")


def _computations(text: str) -> dict:
    comps, name = {}, None
    for line in text.splitlines():
        m = _HEADER.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            m = _INSTR.match(line)
            if m:
                shape, opcode = _split(m.group(1))
                comps[name].append((opcode, shape, line.strip()))
    return comps


def _callees(line: str) -> list:
    names = _CALLED.findall(line)
    for group in _BRANCHES.findall(line):
        names += [n.strip().lstrip("%") for n in group.split(",")]
    return names


def loops(text: str) -> list:
    comps = _computations(text)

    def reach(name, seen):
        if name in seen or name not in comps:
            return []
        seen.add(name)
        out = []
        for instr in comps[name]:
            out.append(instr)
            for callee in _callees(instr[2]):
                out += reach(callee, seen)
        return out

    found = []
    for instrs in comps.values():
        for opcode, _, line in instrs:
            if opcode == "while":
                trips = _TRIPS.findall(line)
                if not trips:
                    cond = re.search(r"condition=%?([\w.\-]+)", line).group(1)
                    trips = [n for _, _, text in comps[cond] for n in _CONSTANT.findall(text)]
                body = re.search(r"body=%?([\w.\-]+)", line).group(1)
                found.append(Loop(int(trips[0]) if len(trips) == 1 else None, reach(body, set())))
    return found


def dims(shape: str) -> tuple:
    """``f32[32,3,3,64]{...}`` -> (32, 3, 3, 64); () for a scalar or a tuple."""
    m = re.match(r"^[a-z]+\d*\[([\d,]+)\]", shape)
    return tuple(int(d) for d in m.group(1).split(",")) if m else ()


def state_cuts(loop: Loop, state, copies_of: int | None = None) -> list:
    """The instructions of ``loop`` that cut, stitch or (with
    ``copies_of`` = the population's size) copy a leaf of the
    population's ``state``: a ``dynamic-slice`` or
    ``dynamic-update-slice`` whose result ends in a weight leaf's
    per-member dimensions, a ``copy`` of a whole such leaf. Weight
    leaves only (three dimensions or more): a bias's ``[n, c]`` is the
    shape of activations too."""
    rests = {tuple(leaf.shape[1:]) for leaf in jax.tree.leaves(state) if len(leaf.shape) >= 3}
    found = []
    for opcode, shape, text in loop.body:
        d = dims(shape)
        if opcode in ("dynamic-slice", "dynamic-update-slice"):
            if any(d[-len(r):] == r and len(d) > len(r) for r in rests):
                found.append(text)
        elif opcode == "copy" and copies_of is not None:
            if d[:1] == (copies_of,) and d[1:] in rests:
                found.append(text)
    return found
