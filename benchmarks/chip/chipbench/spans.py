"""The program's own spans in a profiler trace, reduced per layer.

The program opens spans on the profiler's clock where it does its host
work (:data:`PROGRAM_SPANS`): ``swirl.*`` in the SWIRL runtime, the
``jax`` backend and ``Lowered.compile``, ``launch.*`` in the trainer's
step bodies.  :func:`read_events` reads them beside the device events of
:mod:`chipbench.xtrace`, with the thread each ran on, and
:func:`reduce_spans` reduces them to:

* ``span_self_s``: per name, each span's time inside the window less the
  part its children on the same thread cover;
* ``span_counts``: spans inside the window, per name;
* ``idle_by_span``: the chips' idle time per chip, by the innermost host
  span open at each instant, every name (``xtrace`` keeps the top ten).

:func:`readings` turns these into per-layer numbers.  ``harness.run_cell``
does not call this module yet; ``attribute.py`` does.
"""

from __future__ import annotations

import heapq
from collections import Counter, defaultdict
from dataclasses import dataclass

from chipbench.xtrace import (
    DEVICE_PLANE,
    MODULES_LINE,
    NO_SPAN,
    OPS_LINE,
    WINDOW_SPAN,
    Event,
    _clip,
    _union,
)

PROGRAM_SPANS = frozenset({
    "swirl.run", "swirl.exec", "swirl.segment", "swirl.comm", "swirl.wait",
    "swirl.compile", "launch.compress", "launch.gradsync", "launch.sync",
})
# A jax-backend run and the spans it opens inside itself.
RUN_SPANS = frozenset({"swirl.run", "swirl.exec", "swirl.segment", "swirl.comm"})


@dataclass(frozen=True)
class SpanReduction:
    window_s: float
    span_self_s: dict[str, float]
    span_counts: dict[str, int]
    idle_by_span: dict[str, float]  # idle seconds per chip, by innermost span

    @property
    def idle_s(self) -> float:
        return sum(self.idle_by_span.values())


def read_events(path: str, spans: frozenset[str]) -> list[Event]:
    """The events ``xtrace.read_events`` keeps, with each host span's line
    named ``<line>#<k>``, ``k`` its index in the plane: the profiler names
    every Python thread's line ``python``, and self time needs the thread."""
    from jax.profiler import ProfileData

    keep = spans | {WINDOW_SPAN}
    out: list[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            out.extend(
                Event(plane.name, line.name, e.name, e.start_ns, e.duration_ns)
                for line in plane.lines if line.name in (OPS_LINE, MODULES_LINE)
                for e in line.events
            )
        elif plane.name.startswith("/host"):
            for k, line in enumerate(plane.lines):
                out.extend(
                    Event(plane.name, f"{line.name}#{k}", e.name, e.start_ns, e.duration_ns)
                    for e in line.events if e.name in keep
                )
    return out


def _self_times(spans: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Per name: clipped duration less the clipped time of the direct
    children on the same host line (spans on one thread nest)."""
    out: dict[str, float] = defaultdict(float)
    by_line: dict[tuple[str, str], list[Event]] = defaultdict(list)
    for s in spans:
        by_line[(s.plane, s.line)].append(s)

    def close(entry: list) -> None:
        s, children = entry
        iv = _clip(s.start_ns, s.end_ns, lo, hi)
        out[s.name] += ((iv[1] - iv[0]) - children) * 1e-9 if iv else 0.0

    for line in by_line.values():
        stack: list[list] = []  # [span, ns its children cover]
        for s in sorted(line, key=lambda e: (e.start_ns, -e.dur_ns)):
            while stack and stack[-1][0].end_ns <= s.start_ns:
                close(stack.pop())
            if stack:
                parent = stack[-1][0]
                iv = _clip(s.start_ns, s.end_ns, max(lo, parent.start_ns), min(hi, parent.end_ns))
                if iv:
                    stack[-1][1] += iv[1] - iv[0]
            stack.append([s, 0.0])
        while stack:
            close(stack.pop())
    return dict(out)


def _innermost_segments(spans: list[Event], lo: float, hi: float) -> list[tuple[float, float, str]]:
    """[lo, hi) cut where spans begin or end, each piece named by the
    shortest span covering it (the earliest on a tie), as
    ``xtrace._innermost`` names a point."""
    cuts = sorted(
        {lo, hi} | {t for s in spans for t in (s.start_ns, s.end_ns) if lo < t < hi}
    )
    by_start = sorted(spans, key=lambda e: e.start_ns)
    heap: list[tuple] = []
    i = 0
    out = []
    for x, y in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i].start_ns <= x:
            s = by_start[i]
            heapq.heappush(heap, (s.dur_ns, s.start_ns, i, s.end_ns, s.name))
            i += 1
        while heap and heap[0][3] <= x:
            heapq.heappop(heap)
        out.append((x, y, heap[0][4] if heap else NO_SPAN))
    return out


def reduce_spans(events: list[Event]) -> SpanReduction:
    windows = [e for e in events if e.name == WINDOW_SPAN and not DEVICE_PLANE.match(e.plane)]
    if not windows:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} host span")
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns
    spans = [
        e for e in events
        if not DEVICE_PLANE.match(e.plane) and e.name != WINDOW_SPAN
        and e.end_ns > lo and e.start_ns < hi
    ]
    ops_by_dev: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE:
            busy = ops_by_dev[e.plane]  # a chip idle all window still counts
            iv = _clip(e.start_ns, e.end_ns, lo, hi)
            if iv:
                busy.append(iv)
    if not ops_by_dev:
        raise RuntimeError("the trace holds no device operation")

    segments = _innermost_segments(spans, lo, hi)
    idle: dict[str, float] = defaultdict(float)
    for intervals in ops_by_dev.values():
        j = 0
        t = lo
        for a, b in _union(intervals) + [(hi, hi)]:
            if a > t:  # the chip idles over [t, a)
                while segments[j][1] <= t:
                    j += 1
                k = j
                while k < len(segments) and segments[k][0] < a:
                    x, y, name = segments[k]
                    idle[name] += (min(y, a) - max(x, t)) * 1e-9
                    k += 1
            t = max(t, b)
    n = len(ops_by_dev)
    return SpanReduction(
        window_s=(hi - lo) * 1e-9,
        span_self_s=_self_times(spans, lo, hi),
        span_counts=dict(Counter(s.name for s in spans)),
        idle_by_span={name: s / n for name, s in idle.items()},
    )


def readings(runner: str, sr: SpanReduction, units: int, comm_bytes: int | None) -> dict[str, float]:
    """The per-layer numbers these spans and the ``comm_bytes`` counter
    give for one cell, by metric name; a number with nothing to read is
    left out.  ``units``: instances or iterations in the window;
    ``comm_bytes``: the ``jax`` backend's ``stats["comm_bytes"]`` per
    instance."""
    out: dict[str, float] = {}
    if not units:
        return out
    if runner == "swirl_workflow":
        if "swirl.run" in sr.span_self_s:
            out["wf.interp_self_ms"] = 1e3 * sr.span_self_s["swirl.run"] / units
            if sr.idle_s > 0:
                in_run = sum(v for k, v in sr.idle_by_span.items() if k in RUN_SPANS)
                out["wf.idle_in_run_share"] = 100.0 * in_run / sr.idle_s
        if comm_bytes is not None:
            out["wf.comm_gb_per_instance"] = comm_bytes / 1e9
    elif runner == "swirl_trainer":
        if "swirl.run" in sr.span_self_s:
            swirl = sr.span_self_s["swirl.run"] + sr.span_self_s.get("swirl.compile", 0.0)
            out["train.swirl_self_ms"] = 1e3 * swirl / units
        if "launch.sync" in sr.span_self_s:
            out["train.host_sync_ms"] = 1e3 * sr.span_self_s["launch.sync"] / units
    return out
