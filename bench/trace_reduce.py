"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time per jitted program, the operations that took most time, and
idle gaps named by what the host was doing.

A device is a plane named ``/device:TPU:<n>``.  Its ``XLA Ops`` line holds
one event per operation that ran, its ``XLA Modules`` line one event per
execution of a compiled program (named like ``jit__decode_fn(<id>)``).
The benchmark's own host spans (``jax.profiler.TraceAnnotation``, named
``bench.<what>``) lie on host planes on the same clock, and the span
``bench.window`` marks the traced window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]


def program_name(name: str) -> str:
    """``jit__decode_fn(123)`` -> ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_label(name: str) -> str:
    """An HLO op event's name is its instruction's text; keep the name,
    the shape without layouts, and the opcode."""
    s = name
    while True:
        t = re.sub(r"\{[^{}]*\}", "", s)
        if t == s:
            break
        s = t
    m = re.match(r"(%\S+ = (?:\([^()]*\)|\S+) [\w\-]+)\(", s)
    return m.group(1) if m else s[:120]


def leaves(events):
    """The events that contain no other event: nested ones (a while loop
    and its body's ops) are counted once, at the innermost level."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    parent = [False] * len(evs)
    stack: list[int] = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _events(profile):
    """Yield (plane name, line name, event name, start_ns, end_ns)."""
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name, float(ev.start_ns),
                       float(ev.start_ns + ev.duration_ns))


def reduce_events(events) -> dict:
    """The reduction, from (plane, line, name, start_ns, end_ns) tuples."""
    ops: dict[str, list] = defaultdict(list)
    modules: dict[str, list] = defaultdict(list)
    spans = []
    for plane, line, name, s, e in events:
        if DEVICE_PLANE.match(plane):
            if line == OPS_LINE:
                ops[plane].append((name, s, e))
            elif line == MODULES_LINE:
                modules[plane].append((program_name(name), s, e))
        elif name.startswith(SPAN_PREFIX):
            spans.append((name, s, e))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    window_s = (hi - lo) * 1e-9
    devices = sorted(set(ops) | set(modules))
    if not devices:
        raise ValueError("the trace holds no TPU device plane with events")
    busy_s, gaps = [], []
    op_time: dict[str, float] = defaultdict(float)
    prog_time: dict[str, float] = defaultdict(float)
    prog_calls: dict[str, int] = defaultdict(int)
    for dev in devices:
        busy = union(clip([(s, e) for _, s, e in ops[dev]], lo, hi))
        busy_s.append(sum(e - s for s, e in busy) * 1e-9)
        for name, s, e in leaves(ops[dev]):
            op_time[op_label(name)] += max(0.0, min(e, hi) - max(s, lo)) * 1e-9
        for name, s, e in modules[dev]:
            if s >= lo and s < hi:
                prog_time[name] += (e - s) * 1e-9
                prog_calls[name] += 1
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    host = sorted((a, b, name) for name, a, b in spans if name != WINDOW)
    starts = [a for a, _, _ in host]
    named = [(_host_activity(host, starts, s, e), (e - s) * 1e-9)
             for s, e in gaps]
    idle_by_activity: dict[str, float] = defaultdict(float)
    for what, sec in named:
        idle_by_activity[what] += sec / n
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": sum(busy_s) / n,
        "programs": {k: {"seconds": prog_time[k] / n,
                         "calls": prog_calls[k] / n} for k in prog_time},
        "top_ops": sorted(((k, v / n) for k, v in op_time.items()),
                          key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(named, key=lambda kv: -kv[1])[:10],
        "idle_by_activity": dict(idle_by_activity),
    }


def _host_activity(host, starts, s, e) -> str:
    """The host span that covers most of [s, e).  The benchmark's spans
    follow one another without nesting, so those that overlap the gap are
    consecutive in ``host`` (sorted by start)."""
    best, cover = "none", 0.0
    i = bisect.bisect_left(starts, e) - 1
    while i >= 0 and host[i][1] > s:
        a, b, name = host[i]
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = name, c
        i -= 1
    return best


def reduce_trace(path: str) -> dict:
    """Reduce the trace in ``path``: an ``.xplane.pb`` file, or the
    directory the profiler wrote it under."""
    import jax

    if not str(path).endswith(".xplane.pb"):
        path = find_xplane(path)
    profile = jax.profiler.ProfileData.from_file(str(path))
    return reduce_events(_events(profile))
