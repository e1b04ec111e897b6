"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name the manifest gives:

* ``configs/<config>.json`` — the sizes as run, with ``runner`` naming the
  module under ``chipbench/runners/`` that runs this kind of system;
* ``configs/<config>.py`` — the plain reference beside it (and, for a
  workflow, the user's step bodies);
* ``traffic/<traffic>.json`` — the parameters the runner's generator reads;
* ``metrics/<metric>.py`` — a ``read(outcome)`` that returns the metric's
  value, or ``None`` where the run holds nothing to read.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

from . import peaks as peaks_mod
from . import xtrace

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]

# Host spans the runners write into the profiler's trace.
SPANS = frozenset({
    "train.step", "train.plan_compile", "train.run", "wf.instance",
})


class CellError(RuntimeError):
    """The manifest, a configuration or a traffic file is not usable."""


def load_json(path: Path) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise CellError(f"missing {path.relative_to(BENCH)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # a dataclass looks its module up here
    spec.loader.exec_module(mod)
    return mod


def config_path(name: str) -> Path:
    return BENCH / "configs" / f"{name}.json"


def reference_path(name: str) -> Path:
    return BENCH / "configs" / f"{name}.py"


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def runner_path(kind: str) -> Path:
    return BENCH / "chipbench" / "runners" / f"{kind}.py"


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


@dataclass
class Cell:
    """One workload of the manifest, with its files loaded."""

    name: str
    config: dict
    ref: Any  # the configuration's reference module
    traffic: dict
    seed: int
    seconds: float
    devices: list
    log: Callable[[str], None] = print


@dataclass
class Outcome:
    """What a runner measured and checked in one run."""

    window_start: float  # perf_counter at the window's start
    e2e: dict[str, float]
    units: int  # iterations or instances completed in the window
    memory_peak_bytes: int
    checks: dict[str, tuple[float, float]]  # name -> (value, limit)
    counters: dict[str, float] = field(default_factory=dict)
    host: dict[str, float] = field(default_factory=dict)
    cost: dict[str, Any] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    # Filled by the harness after a traced run.
    reduction: xtrace.Reduction | None = None
    peaks: peaks_mod.Peaks | None = None
    chips: int = 1


def load_cell(manifest: dict, workload: str, **run: Any) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    ref = load_module(reference_path(w["config"]), f"chipbench_ref_{len(sys.modules)}")
    traffic = load_json(traffic_path(w["traffic"]))
    if traffic["runner"] != config["runner"]:
        raise CellError(f"{workload}: traffic for {traffic['runner']!r}, config for {config['runner']!r}")
    return Cell(name=workload, config=config, ref=ref, traffic=traffic, **run)


def peak_bytes(devices: list) -> int:
    """``peak_bytes_in_use`` of the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls would swamp the trace
    opts.host_tracer_level = 2  # keeps TraceAnnotation spans
    return opts


def _run_queue_wait_s() -> float | None:
    """Seconds this thread has waited for a CPU while runnable."""
    try:
        with open("/proc/thread-self/schedstat", encoding="ascii") as f:
            return int(f.read().split()[1]) / 1e9
    except (OSError, IndexError, ValueError):
        return None


def _steal_s() -> float | None:
    """Seconds of CPU time the hypervisor gave to others, over all CPUs."""
    try:
        with open("/proc/stat", encoding="ascii") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


class _HostWatch:
    """What held the host back during a window: the main thread's wait for
    a CPU, the machine's steal time, and the time spent collecting garbage."""

    def __init__(self):
        self.gc_s = 0.0
        self._gc_t0 = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_s += time.perf_counter() - self._gc_t0

    def __enter__(self):
        self.wait0, self.steal0 = _run_queue_wait_s(), _steal_s()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        wait, steal = _run_queue_wait_s(), _steal_s()
        self.wait_s = None if wait is None or self.wait0 is None else wait - self.wait0
        self.steal_s = None if steal is None or self.steal0 is None else steal - self.steal0

    def summary(self, durations: list[float]) -> str:
        med = statistics.median(durations)
        slow = [d for d in durations if d > 1.5 * med]
        return (f"host in the window: main thread waited {self.wait_s!r} s for a CPU, "
                f"steal {self.steal_s!r} s, gc {self.gc_s!r} s; units: median {med!r} s, "
                f"slowest {max(durations)!r} s, {len(slow)} over 1.5x the median "
                f"({sum(slow) - med * len(slow)!r} s beyond it)")


def measure(
    seconds: float, unit: Callable[[int], None], *, trace_dir: str | None,
    span: str, log: Callable[[str], None] = print,
) -> tuple[float, list[float], float]:
    """Run ``unit(i)`` back to back until ``seconds`` have passed.

    Returns (window start, per-unit seconds, window seconds).  Each unit
    ends in ``block_until_ready``; the window ends with the last unit.
    Objects made in set-up are frozen out of the garbage collector for the
    window, so a full collection does not walk them; what held the host
    back is logged.
    """
    import jax

    gc.collect()
    gc.freeze()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=profiler_options())
    watch = _HostWatch()
    try:
        with watch, jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            t0 = time.perf_counter()
            durations: list[float] = []
            while True:
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation(span):
                    unit(len(durations))
                durations.append(time.perf_counter() - a)
                if time.perf_counter() - t0 >= seconds:
                    break
            window = time.perf_counter() - t0
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        gc.unfreeze()
    log(watch.summary(durations))
    return t0, durations, window


def passes(checks: dict[str, tuple[float, float]]) -> bool:
    """Every number compared is a number, and within its limit."""
    return all(v == v and v <= limit for v, limit in checks.values())


def p95(values: list[float]) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def run_cell(
    manifest: dict, workload: str, *, seed: int, seconds: float, trace: bool,
    devices: list, t_start: float, log: Callable[[str], None] = print,
) -> dict:
    """Drive one run of ``workload``; return the result line's object."""
    import jax

    kind = devices[0].device_kind
    chip_peaks = peaks_mod.peaks(kind)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        cell = load_cell(
            manifest, workload, seed=seed, seconds=seconds, devices=devices, log=log,
        )
        runner = load_module(
            runner_path(cell.config["runner"]), f"chipbench_runner_{cell.config['runner']}"
        )
        out: Outcome = runner.run(cell, trace_dir=trace_dir)
        out.peaks, out.chips = chip_peaks, len(devices)
        if trace_dir is not None:
            events = xtrace.read_events(xtrace.find_xplane(trace_dir), SPANS)
            out.reduction = xtrace.reduce_events(events)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    out.e2e["setup_s"] = out.window_start - t_start
    out.e2e["peak_hbm_gb"] = out.memory_peak_bytes / 1e9
    metrics: dict[str, dict] = {}
    if trace:
        for entry in manifest["per_layer"]:
            if not applies(entry, workload):
                continue
            reader = load_module(metric_path(entry["name"]), f"chipbench_metric_{entry['name']}")
            value = reader.read(out)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        for entry in manifest["end_to_end"]:
            if applies(entry, workload):
                metrics[entry["name"]] = {"value": out.e2e[entry["name"]], "unit": entry["unit"]}
    correct = passes(out.checks)
    line: dict[str, Any] = {
        "correct": correct,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": out.memory_peak_bytes,
        },
    }
    if out.reduction is not None:
        r = out.reduction
        line["device"]["busy_s"] = r.busy_s
        line["device"]["window_s"] = r.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in r.device_ops],
            "idle_gaps": [[n, s] for n, s in r.idle_gaps],
        }
    line["checks"] = {
        name: {"value": v, "limit": lim} for name, (v, lim) in out.checks.items()
    }
    return line
