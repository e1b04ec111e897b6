"""Device time of the program's named scopes in one jitted program.

The model wraps its windowed and full attention, its router and its held
experts in ``jax.named_scope`` (:data:`SCOPES`).  The compiled HLO keeps
each instruction's scope in its ``op_name`` metadata, and a profiler trace
names each device operation by its instruction.  :func:`op_scopes` reads
the first from the HLO text, and :func:`scope_seconds` sums the second,
per scope, over the runs of one module inside the traced window.

XLA's TPU ``ragged_dot`` kernels carry an ``op_name`` of their own
(``ragged-dot-*``) in place of the caller's: the held experts are the
program's only grouped products, so such instructions count as
``moe.held``.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass

from chipbench.xtrace import DEVICE_PLANE, MODULES_LINE, OPS_LINE, WINDOW_SPAN, Event, short_name

SCOPES = ("attn.window", "attn.full", "moe.route", "moe.held")
GROUPED = "moe.held"  # the scope of the ragged-dot kernels

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s.*?op_name=\"([^\"]*)\"")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(re.escape(s) for s in SCOPES) + r")(?:/|$)")


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> the innermost named scope in its ``op_name``."""
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, op_name = m.groups()
        found = _SCOPE.findall(op_name)
        if found:
            out[name] = found[-1]
        elif op_name.startswith("ragged-dot"):
            out[name] = GROUPED
    return out


def op_name(event_name: str) -> str:
    """A trace op's instruction name: ``%fusion.3 = ...`` -> ``fusion.3``."""
    return short_name(event_name).lstrip("%")


@dataclass(frozen=True)
class ScopeTimes:
    runs: int  # runs of the module that lie wholly inside the window
    module_s: float  # their operations' device seconds, all scopes or none
    scope_s: dict[str, float]  # device seconds per scope, summed over the runs


def scope_seconds(events: list[Event], scopes: dict[str, str], module: str) -> ScopeTimes:
    windows = [e for e in events if e.name == WINDOW_SPAN and not DEVICE_PLANE.match(e.plane)]
    if not windows:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} host span")
    win = max(windows, key=lambda e: e.dur_ns)
    runs = defaultdict(list)
    ops = defaultdict(list)
    for e in events:
        if not DEVICE_PLANE.match(e.plane):
            continue
        if e.line == MODULES_LINE and short_name(e.name) == module:
            if e.start_ns >= win.start_ns and e.end_ns <= win.end_ns:
                runs[e.plane].append(e)
        elif e.line == OPS_LINE:
            ops[e.plane].append(e)
    total, per_scope, n = 0.0, dict.fromkeys(SCOPES, 0.0), 0
    for plane, mods in runs.items():
        n += len(mods)
        mods.sort(key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        for op in ops[plane]:
            k = bisect.bisect_right(starts, op.start_ns) - 1
            if k < 0 or op.start_ns >= mods[k].end_ns:
                continue
            total += op.dur_ns * 1e-9
            scope = scopes.get(op_name(op.name))
            if scope is not None:
                per_scope[scope] += op.dur_ns * 1e-9
    return ScopeTimes(runs=n, module_s=total, scope_s=per_scope)
