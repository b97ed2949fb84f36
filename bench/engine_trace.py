"""The serving engine's own spans and named scopes in a profiler trace.

Adds to ``bench/trace_reduce.py``'s reduction of the same ``.xplane.pb``:

- ``engine_spans``: per host span named ``serve.<what>``
  (``ServeEngine.tick`` and its parts) that lies inside ``bench.window``,
  its ``calls`` and summed ``seconds``; ``serve.tick`` also gets
  ``self_s``, its duration less the part of it its child spans cover.
- ``scopes``: per jitted program (``jit__decode_fn``), device seconds per
  leaf scope of the step programs (``models/transformer.py``: ``embed``,
  ``attn_in``, ``kv_write``, ``attention``, ``attn_out``, ``mlp``,
  ``head``, ``sample``).  Each innermost op counts under the innermost
  of these names in its ``tf_op`` path (its ``jax.named_scope`` stack),
  else under ``(none)``: put there by the layer scan's slicing of the
  pool, or by the compiler.
- ``idle_gaps`` and ``idle_by_activity`` named anew: a device idle gap
  takes the name of the innermost host span (``bench.*`` or
  ``serve.*``) that covers more than half of it, else of the span that
  covers most of it.  With no ``serve.*`` span in the trace the names
  are ``trace_reduce``'s.

Per-op metadata is read with ``bench/xplane.py``; times are those of
``jax.profiler.ProfileData`` (whole nanoseconds).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace_reduce as R
from bench import xplane

SCOPES = ("embed", "attn_in", "kv_write", "attention", "attn_out", "mlp",
          "head", "sample")
UNSCOPED = "(none)"
ENGINE_PREFIX = "serve."
TICK = "serve.tick"
HOST_PREFIXES = (R.SPAN_PREFIX, ENGINE_PREFIX)
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def scope_of(tf_op: str | None) -> str:
    """The innermost leaf scope in an op's ``tf_op`` path, e.g.
    ``jit(_decode_fn)/while/body/closed_call/attention/dot_general:``
    -> ``attention``."""
    for part in reversed((tf_op or "").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _value(stats: dict, key: str, names: dict) -> str | None:
    """A stat's value as text; a ``ref_value`` is the id of the stat
    metadata whose name is the text."""
    stat = stats.get(key)
    if stat is None:
        return None
    kind = stat.WhichOneof("value")
    return names.get(stat.ref_value) if kind == "ref_value" else str(
        getattr(stat, kind))


def _events(space):
    """Yield (plane, line, name, start_ns, end_ns, program, tf_op); the
    last two are None except for device ops."""
    for plane in space.planes:
        device = bool(R.DEVICE_PLANE.match(plane.name))
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        if device:
            programs = {}
            for line in plane.lines:
                if line.name == R.MODULES_LINE:
                    for ev in line.events:
                        name = plane.event_metadata[ev.metadata_id].name
                        m = _PROGRAM_ID.search(name)
                        if m:
                            programs[m.group(1)] = R.program_name(name)
            for k, md in plane.event_metadata.items():
                stats = {stat_names.get(s.metadata_id): s for s in md.stats}
                meta[k] = (
                    programs.get(_value(stats, "program_id", stat_names)),
                    _value(stats, "tf_op", stat_names))
        for line in plane.lines:
            ops = device and line.name == R.OPS_LINE
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps // 1000
                end = start + ev.duration_ps // 1000
                program, tf_op = (meta[ev.metadata_id] if ops
                                  else (None, None))
                yield (plane.name, line.name,
                       plane.event_metadata[ev.metadata_id].name,
                       float(start), float(end), program, tf_op)


def reduce_events(events) -> dict:
    """The reduction, from the tuples ``_events`` yields."""
    ops: dict[str, list] = defaultdict(list)
    host = []
    for plane, line, name, s, e, program, tf_op in events:
        if R.DEVICE_PLANE.match(plane):
            if line == R.OPS_LINE:
                ops[plane].append(((program, scope_of(tf_op)), s, e))
        elif name.startswith(HOST_PREFIXES):
            host.append((s, e, name))
    windows = [(s, e) for s, e, n in host if n == R.WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {R.WINDOW!r} span")
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    host = sorted(h for h in host if h[2] != R.WINDOW)
    n = max(len(ops), 1)
    scopes: dict[str, dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    gaps = []
    for dev in sorted(ops):
        for (program, scope), s, e in R.leaves(ops[dev]):
            if program is not None and min(e, hi) > max(s, lo):
                scopes[program][scope] += (min(e, hi) - max(s, lo)) / n
        busy = R.union(R.clip([(s, e) for _, s, e in ops[dev]], lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    namer = _SpanNamer(host)
    named = [(namer.name(s, e), (e - s) * 1e-9) for s, e in gaps]
    idle_by_activity: dict[str, float] = defaultdict(float)
    for what, sec in named:
        idle_by_activity[what] += sec / n
    return {
        "engine_spans": engine_spans(
            [h for h in host if h[2].startswith(ENGINE_PREFIX)
             and lo <= h[0] and h[1] <= hi]),
        "scopes": {p: {k: v * 1e-9 for k, v in t.items()}
                   for p, t in scopes.items()},
        "idle_gaps": sorted(named, key=lambda kv: -kv[1])[:10],
        "idle_by_activity": dict(idle_by_activity),
    }


def engine_spans(spans) -> dict:
    """Calls and seconds per span name, from sorted (start, end, name);
    ``serve.tick`` also gets its self time."""
    out: dict[str, dict] = {}
    for s, e, name in spans:
        d = out.setdefault(name, {"calls": 0, "seconds": 0.0})
        d["calls"] += 1
        d["seconds"] += (e - s) * 1e-9
    if TICK in out:
        starts = [s for s, _, _ in spans]
        self_s = 0.0
        for s, e, name in spans:
            if name != TICK:
                continue
            inner = [(max(a, s), min(b, e)) for a, b, nm in
                     spans[bisect.bisect_left(starts, s):
                           bisect.bisect_left(starts, e)]
                     if nm != TICK and min(b, e) > max(a, s)]
            covered = sum(b - a for a, b in R.union(inner))
            self_s += (e - s - covered) * 1e-9
        out[TICK]["self_s"] = self_s
    return out


class _SpanNamer:
    """Names an interval by the host spans that overlap it.  Spans of one
    thread nest, so of two spans that each cover more than half of an
    interval the shorter is the inner one."""

    def __init__(self, spans):
        self.spans = spans                   # sorted (start, end, name)
        self.starts = [s for s, _, _ in spans]
        self.reach = []                      # running max of the ends
        for _, e, _ in spans:
            self.reach.append(max(e, self.reach[-1]) if self.reach else e)

    def name(self, s: float, e: float) -> str:
        half, best, inner = (e - s) / 2, ("none", 0.0), None
        i = bisect.bisect_left(self.starts, e) - 1
        while i >= 0 and self.reach[i] > s:
            a, b, name = self.spans[i]
            cover = min(b, e) - max(a, s)
            if cover > best[1]:
                best = (name, cover)
            if cover > half and (inner is None or b - a < inner[0]):
                inner = (b - a, name)
            i -= 1
        return inner[1] if inner else best[0]


def reduce_trace(path: str) -> dict:
    """``trace_reduce.reduce_trace`` with this module's keys: one read of
    the ``XSpace`` feeds both reductions (its events are the ones
    ``jax.profiler.ProfileData`` yields, to the nanosecond), and
    ``idle_gaps`` / ``idle_by_activity`` are this module's."""
    if not str(path).endswith(".xplane.pb"):
        path = R.find_xplane(path)
    events = list(_events(xplane.read(str(path))))
    return {**R.reduce_events([ev[:5] for ev in events]),
            **reduce_events(events)}


def scope_share(trace: dict | None, program: str, scope: str):
    """Percent of ``program``'s scoped device time under ``scope``; None
    where the program did not run or none of its ops carries a leaf
    scope (a program built without them)."""
    times = ((trace or {}).get("scopes") or {}).get(program)
    if not times or not any(v for k, v in times.items() if k != UNSCOPED):
        return None
    return 100.0 * times.get(scope, 0.0) / sum(times.values())


def install() -> None:
    """Make the harness's ``trace_reduce.reduce_trace`` this module's
    ``reduce_trace``.  Called by the readers of the metrics that read its
    keys, when the harness loads them, before it reduces the run's trace
    (``bench/run.py`` calls ``trace_reduce.reduce_trace``; a later
    benchmark change can fold this module into that one and drop the
    hook)."""
    R.reduce_trace = reduce_trace
