"""Reduce a profiler trace to device busy time, idle share and a breakdown.

The profiler writes an ``.xplane.pb`` file.  :func:`read_events` keeps the
two kinds of event the reduction needs:

* device operations: the ``XLA Ops`` line of every ``/device:TPU:<n>``
  plane, with the ``XLA Modules`` line beside it to name the program each
  operation ran in;
* the benchmark's own host spans (``jax.profiler.TraceAnnotation``), by
  name, from every host plane.

:func:`reduce_events` then works on plain :class:`Event` records, so a test
can feed it a small synthetic trace in the same form.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
NO_SPAN = "outside any span"
TOP = 10


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass(frozen=True)
class Reduction:
    window_s: float
    busy_s: float  # mean over devices
    busy_by_device: dict[str, float]
    idle_share: float  # mean over devices, 0..1
    device_ops: list[tuple[str, float]]  # longest first, seconds over all devices
    idle_gaps: list[tuple[str, float]]  # idle seconds per chip, by host span
    span_counts: dict[str, int]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def read_events(path: str, spans: frozenset[str]) -> list[Event]:
    """Device op/module events and the named host spans of one trace file."""
    from jax.profiler import ProfileData

    keep_spans = spans | {WINDOW_SPAN}
    out: list[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                out.extend(
                    Event(plane.name, line.name, e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                )
            elif not device and plane.name.startswith("/host"):
                out.extend(
                    Event(plane.name, line.name, e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name in keep_spans
                )
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def short_name(name: str) -> str:
    """``jit_f(123)`` → ``jit_f``; an HLO instruction's text → its name."""
    name = name.split(" = ", 1)[0]
    return name.split("(", 1)[0] if name.endswith(")") else name


def _clip(a: float, b: float, lo: float, hi: float) -> tuple[float, float] | None:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _innermost(spans: list[Event], t: float) -> str:
    """Name of the shortest host span covering time ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t < s.end_ns and (best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else NO_SPAN


def reduce_events(events: list[Event]) -> Reduction:
    windows = [e for e in events if e.name == WINDOW_SPAN and not DEVICE_PLANE.match(e.plane)]
    if not windows:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} host span")
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns
    spans = sorted(
        (e for e in events
         if not DEVICE_PLANE.match(e.plane) and e.name != WINDOW_SPAN
         and e.end_ns > lo and e.start_ns < hi),
        key=lambda e: e.start_ns,
    )
    span_counts: dict[str, int] = defaultdict(int)
    for s in spans:
        span_counts[s.name] += 1

    ops_by_dev: dict[str, list[Event]] = defaultdict(list)
    mods_by_dev: dict[str, list[Event]] = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane):
            (ops_by_dev if e.line == OPS_LINE else mods_by_dev)[e.plane].append(e)
    if not ops_by_dev:
        raise RuntimeError("the trace holds no device operation")

    busy: dict[str, float] = {}
    op_time: dict[str, float] = defaultdict(float)
    gap_time: dict[str, float] = defaultdict(float)
    for dev, ops in sorted(ops_by_dev.items()):
        mods = sorted(mods_by_dev.get(dev, []), key=lambda e: e.start_ns)
        mod_starts = [m.start_ns for m in mods]
        clipped = []
        for op in ops:
            iv = _clip(op.start_ns, op.end_ns, lo, hi)
            if iv is None:
                continue
            clipped.append(iv)
            k = bisect.bisect_right(mod_starts, op.start_ns) - 1
            module = mods[k].name if k >= 0 and op.start_ns < mods[k].end_ns else "?"
            op_time[f"{short_name(module)}/{short_name(op.name)}"] += (iv[1] - iv[0]) * 1e-9
        merged = _union(clipped)
        busy[dev] = sum(b - a for a, b in merged) * 1e-9
        t = lo
        for a, b in merged + [(hi, hi)]:
            if a > t:
                # Attribute the gap piecewise: split it where host spans
                # begin or end, so a long gap is not charged to one span.
                cuts = sorted(
                    {t, a}
                    | {s.start_ns for s in spans if t < s.start_ns < a}
                    | {s.end_ns for s in spans if t < s.end_ns < a}
                )
                for x, y in zip(cuts, cuts[1:]):
                    gap_time[_innermost(spans, (x + y) / 2)] += (y - x) * 1e-9
            t = max(t, b)
    n = len(busy)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy.values()) / n
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(
        ((name, s / n) for name, s in gap_time.items()), key=lambda kv: -kv[1]
    )[:TOP]
    return Reduction(
        window_s=window_s,
        busy_s=busy_s,
        busy_by_device=busy,
        idle_share=1.0 - busy_s / window_s,
        device_ops=top_ops,
        idle_gaps=gaps,
        span_counts=dict(span_counts),
    )
