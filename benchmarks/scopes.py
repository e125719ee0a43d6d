"""Device seconds by the names the program gave its work.

    python3 benchmarks/scopes.py <file.xplane.pb>     # the phase x class table

The program wraps the phases of a fused generation in `jax.named_scope`s
(`mpi_opt_tpu/obs/events.py` `DEVICE_SCOPES`; `SCOPES` below is the
benchmark's own copy). A scope is part of every HLO operation's
`op_name` path, and the TPU's trace carries that path for every executed
operation — not on the event (`jax.profiler.ProfileData` shows an
event's own stats only) but on the event's METADATA, as the stats
`tf_op` (the path, with a trailing `:`) and `hlo_category`. So the times
come from `xplane._self_times` over the `XLA Ops` line, as in
`device_ops`, and a walk of the protobuf wire format supplies the path
of each name. No dependency: the walk knows the six message types it
crosses and nothing else.

A device operation is booked to one PHASE by the innermost scope on its
path, and to one CLASS by what it computes:

    forward     under member_loss, not transposed (augment apart)
    backward    under transpose(...member_loss...): JAX's own wrapping
    optimizer   optimizer_update
    input       train_input, augment
    train_rest  train_segment / map_members and none of the above:
                member-chunk stitching, loop carries, copies
    eval        eval_population
    exploit     exploit, gather_members
    unscoped    no scope on the path (or no path at all)

A fusion carries ONE operation's path, so work fused across a scope's
edge is booked to one side, and a copy carries a neighbour's path: the
phases are an exact partition of the busy self time and approximate at
those edges.
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xplane  # noqa: E402

# the program's DEVICE_SCOPES (tests/test_device_scopes.py holds them equal)
SCOPES = (
    "train_segment", "train_input", "map_members", "member_loss", "augment",
    "optimizer_update", "eval_population", "exploit", "gather_members",
)
PHASE_OF_SCOPE = {
    "member_loss": "forward",  # "backward" where transposed
    "optimizer_update": "optimizer",
    "train_input": "input",
    "augment": "input",
    "train_segment": "train_rest",
    "eval_population": "eval",
    "exploit": "exploit",
    "gather_members": "exploit",
}
PHASES = ("forward", "backward", "optimizer", "input", "train_rest", "eval", "exploit", "unscoped")
CLASSES = ("conv", "groupnorm", "matmul", "pool", "copy", "other")

# -- the wire walk ---------------------------------------------------------


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed word, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i : i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i : i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def plane_name(buf) -> str:
    return next((_text(v) for n, w, v in _fields(buf) if n == 2 and w == 2), "")


def _plane(buf):
    """{metadata id: (event name, {stat name: value})} of one XPlane.
    XPlane: name=2, event_metadata=4 and stat_metadata=5 (maps: key=1,
    value=2). XEventMetadata: id=1, name=2, stats=5. XStat:
    metadata_id=1, uint64=3, int64=4, str=5, ref=7 (a stat_metadata
    id whose name is the value). XStatMetadata: id=1, name=2."""
    raw_events, stat_names = [], {}
    for num, wire, value in _fields(buf):
        if num in (4, 5) and wire == 2:
            entry = [v for n, w, v in _fields(value) if n == 2 and w == 2]
            if not entry:
                continue
            if num == 4:
                raw_events.append(entry[0])
            else:
                f = {n: v for n, w, v in _fields(entry[0]) if n in (1, 2)}
                stat_names[f.get(1, 0)] = _text(f.get(2, b""))
    events = {}
    for raw in raw_events:
        mid, ename, stats = 0, "", {}
        for num, wire, value in _fields(raw):
            if num == 1 and wire == 0:
                mid = value
            elif num == 2 and wire == 2:
                ename = _text(value)
            elif num == 5 and wire == 2:
                key, val = None, None
                for n, w, v in _fields(value):
                    if n == 1:
                        key = stat_names.get(v)
                    elif n in (3, 4):
                        val = v
                    elif n == 5:
                        val = _text(v)
                    elif n == 7:
                        val = stat_names.get(v)
                if key is not None:
                    stats[key] = val
        events[mid] = (ename, stats)
    return events


def event_paths(pb: str) -> dict:
    """{event name: (tf_op, hlo_category)} over the device planes of an
    `.xplane.pb`: the event name is the string `ProfileData` gives as
    `event.name`, the path is stripped of `tf_op`'s trailing `:`. An
    operation without the stat maps to ("", "")."""
    with open(pb, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, wire, value in _fields(space):  # XSpace: planes=1
        if num != 1 or wire != 2 or not plane_name(value).startswith(xplane.DEVICE_PREFIX):
            continue
        for ename, stats in _plane(value).values():
            path = str(stats.get("tf_op") or "")
            out[ename] = (path[:-1] if path.endswith(":") else path, str(stats.get("hlo_category") or ""))
    return out


# -- names to phases and classes ------------------------------------------

_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")


def _core(component: str) -> str:
    """`vmap(transpose(jvp(member_loss)))` -> `member_loss`: a scope
    under JAX's transformations. `jit(eval_population)` stays as it is:
    that is a jitted function's name, not a scope."""
    while True:
        m = _WRAPPED.match(component)
        if not m or m.group(1) in ("jit", "pjit"):
            return component
        component = m.group(2)


def scope_of(path: str):
    """(innermost scope, its path component) or (None, None).
    `map_members` only where no other scope is on the path: it wraps the
    member functions of the train step and of evaluation alike."""
    fallback = (None, None)
    for comp in reversed(path.split("/")):
        core = _core(comp)
        if core == "map_members":
            if fallback[0] is None:
                fallback = (core, comp)
        elif core in PHASE_OF_SCOPE:
            return core, comp
    return fallback


def phase_of(path: str) -> str:
    scope, comp = scope_of(path)
    if scope is None:
        return "unscoped"
    if scope == "map_members":
        return "train_rest"
    if scope == "member_loss" and "transpose(" in comp:
        return "backward"
    return PHASE_OF_SCOPE[scope]


_GROUPNORM = re.compile(r"^(gn|GroupNorm|PallasGN)", re.I)


def class_of(path: str, category: str) -> str:
    comps = [_core(c) for c in path.split("/")]
    leaf = comps[-1] if comps else ""
    if "convolution" in category or leaf == "conv_general_dilated":
        return "conv"
    if any(_GROUPNORM.match(c) for c in comps[:-1]):
        return "groupnorm"
    if leaf == "dot_general":
        return "matmul"
    if leaf.startswith(("reduce_window", "select_and_scatter", "select_and_gather")):
        return "pool"
    if category == "data formatting" or leaf in ("copy", "transpose", "reshape"):
        return "copy"
    return "other"


# -- the reduction ----------------------------------------------------------


def reduce(pb: str) -> dict:
    """{"busy_s", "scoped_s", "phase": {phase: s}, "class": {class: s},
    "table": {phase: {class: s}}, "unscoped": [[name, tf_op, s] x 10],
    "top": {phase: [[name, tf_op, s, class] x 5]}} of the trace's
    device operations, self times summed over chips (as
    `device_ops` is); {} where no device operation ran. `busy_s` is the
    sum of the self times: the phases add up to it exactly."""
    import jax

    paths = event_paths(pb)
    totals: dict = {}
    data = jax.profiler.ProfileData.from_file(pb)
    for plane in data.planes:
        if not plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            evs = [
                (float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
                for e in line.events
            ]
            for name, sec in xplane._self_times(evs).items():
                totals[name] = totals.get(name, 0.0) + sec
    if not totals:
        return {}
    table = {p: {c: 0.0 for c in CLASSES} for p in PHASES}
    ops = {p: [] for p in PHASES}
    scoped = 0.0
    for name, sec in totals.items():
        path, category = paths.get(name, ("", ""))
        phase, cls = phase_of(path), class_of(path, category)
        table[phase][cls] += sec
        ops[phase].append([xplane._short(name), path, sec, cls])
        if any(_core(c) in SCOPES for c in path.split("/")):
            scoped += sec
    for rows in ops.values():
        rows.sort(key=lambda r: -r[2])
    return {
        "busy_s": sum(totals.values()),
        "scoped_s": scoped,
        "phase": {p: sum(table[p].values()) for p in PHASES},
        "class": {c: sum(table[p][c] for p in PHASES) for c in CLASSES},
        "table": table,
        "unscoped": [r[:3] for r in ops["unscoped"][:10]],
        "top": {p: rows[:5] for p, rows in ops.items()},
    }


def for_run(run):
    """The reduction of a benchmark run's device trace, parsed once for
    all the readers: the trace lies where the program's `profile` span
    says (`dir`). None with no such span, no trace, or no device in it
    (a rehearsal; a program from before the span)."""
    if not hasattr(run, "_scopes"):
        dirs = [s["dir"] for s in run.spans if s.get("span") == "profile" and s.get("dir")]
        pb = xplane.find_xplane(dirs[-1]) if dirs else None
        run._scopes = (reduce(pb) or None) if pb else None
    return run._scopes


def format_table(red: dict) -> str:
    busy = red["busy_s"]
    rows = ["| phase | " + " | ".join(CLASSES) + " | all | share |", "| --- |" + " ---: |" * (len(CLASSES) + 2)]
    for p in PHASES:
        cells = " | ".join(f"{red['table'][p][c]:.4f}" for c in CLASSES)
        rows.append(f"| {p} | {cells} | {red['phase'][p]:.4f} | {100 * red['phase'][p] / busy:.1f}% |")
    cells = " | ".join(f"{red['class'][c]:.4f}" for c in CLASSES)
    rows.append(f"| all | {cells} | {busy:.4f} | 100% |")
    rows.append("")
    rows.append(f"busy self time {busy:.6f} s; under a scope {100 * red['scoped_s'] / busy:.2f}%")
    for name, path, sec in red["unscoped"]:
        rows.append(f"unscoped {sec:.6f} s ({100 * sec / busy:.2f}%) {name} [{path or 'no path'}]")
    rows.append("")
    for p in PHASES[:-1]:  # the largest operations of each phase, by the tail of their path
        for name, path, sec, cls in red["top"][p]:
            tail = "/".join(path.split("/")[-3:])
            rows.append(f"top {p} {sec:.4f} s ({100 * sec / busy:.2f}%) {cls} {name} [{tail}]")
    return "\n".join(rows)


if __name__ == "__main__":
    reduction = reduce(sys.argv[1])
    if not reduction:
        sys.exit("no device operation in this trace")
    print(format_table(reduction))
