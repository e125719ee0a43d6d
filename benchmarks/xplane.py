"""Reduction of a profiler trace (`.xplane.pb`) to device numbers.

Read with `jax.profiler.ProfileData` and nothing else. What the TPU's
trace holds (looked at by hand, PR 24): one plane a chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event an executed HLO
operation (nested: a `while` spans its body's operations) and whose line
`XLA Modules` has one event a program run; and `/host:CPU`, whose thread
lines carry the program's spans as `TraceAnnotation`s of the same name.
Times are in nanoseconds on one clock (the device's line ran about a
millisecond ahead of the host's in the recorded trace).

Busy is the union of the `XLA Ops` intervals, averaged over the chips
that ran anything; the window runs from the first to the last event of
those lines and of the host's annotations; an idle gap is a stretch of
the window no operation covers, named after the innermost annotation
the host was in at its middle.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(profile_dir: str):
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def _union(intervals: list) -> list:
    """Sorted (start, end) pairs -> merged, disjoint, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events: list) -> dict:
    """{name: seconds not covered by a nested operation}, for events
    (start, end, name) of one line, where a child lies inside its
    parent."""
    totals: dict = {}
    stack: list = []  # [end, name, child_ns, start]

    def close(frame):
        end, name, child, start = frame
        totals[name] = totals.get(name, 0.0) + max(0.0, (end - start) - child)
        if stack:
            stack[-1][2] += end - start

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, 0.0, s])
    while stack:
        close(stack.pop())
    return {k: v * 1e-9 for k, v in totals.items()}


def _short(name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:80] or name[:80]


def reduce_trace(path: str, span_names=()) -> dict:
    """The trace's device numbers, or {} where no device operation ran.

    Returns busy_s, window_s, chips, device_ops [[name, seconds]] (ten
    largest by self time, summed over chips) and idle_gaps [[host span,
    seconds]] (ten longest, on the chip that was busiest).
    """
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    per_chip, op_events, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = [
                    (float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
                    for e in line.events
                ]
                if evs:
                    per_chip.append(_union([(s, e) for s, e, _ in evs]))
                    op_events.append(evs)
        elif plane.name == HOST_PLANE and span_names:
            wanted = set(span_names)
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        s = float(e.start_ns)
                        host.append((s, s + float(e.duration_ns), e.name))
    if not per_chip:
        return {}
    lo = min([u[0][0] for u in per_chip] + [h[0] for h in host])
    hi = max([u[-1][1] for u in per_chip] + [h[1] for h in host])
    busy = [sum(e - s for s, e in u) for u in per_chip]
    totals: dict = {}
    for evs in op_events:
        for name, sec in _self_times(evs).items():
            key = _short(name)
            totals[key] = totals.get(key, 0.0) + sec
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    # idle gaps of the busiest chip, named by the host's innermost span
    u = per_chip[busy.index(max(busy))]
    edges = [lo] + [t for iv in u for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    by_cause: dict = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inside = [h for h in host if h[0] <= mid <= h[1]]
        cause = min(inside, key=lambda h: h[1] - h[0])[2] if inside else "outside_spans"
        by_cause[cause] = by_cause.get(cause, 0.0) + (e - s) * 1e-9
    idle = sorted(by_cause.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "chips": len(per_chip),
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in idle],
    }
