"""Run one cell traced and name its chip idle time by the program's spans.

    python3 benchmarks/chip/attribute.py --workload <name> --seed <n> \\
        --seconds <s> [--events <path>]

Runs the cell as ``run.py --trace 1`` does: set-up, the window under the
profiler with the same options, the reference check.  It then reads the
trace with the program's spans (``chipbench.spans``) as well as the
benchmark's, and prints one JSON line: what the window did (units, its
end-to-end numbers), the benchmark's reduction (``chipbench.xtrace``, whose
idle gaps now name the innermost program span), every span's self time
and count, the idle time per chip by innermost span, the per-layer
readings of ``spans.readings``, and whether the check passed.  With
``--events`` it also writes the trace's events there as JSON rows (device
operations by their short names), the form of ``tests/bench``'s recorded
samples.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def count_comm_bytes() -> list:
    """``stats["comm_bytes"]`` of every run of the ``jax`` backend in this
    process, warm-up included: the runner keeps only its own counters."""
    from repro.backends.jax_backend import JaxMeshProgram

    seen: list = []
    run = JaxMeshProgram.run

    def counted(self, initial_payloads=None):
        result = run(self, initial_payloads)
        seen.append(result.stats.get("comm_bytes"))
        return result

    JaxMeshProgram.run = counted
    return seen


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events", type=Path)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import configure_compile_cache

    from chipbench import harness, spans, xtrace

    configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"attribute.py: no TPU (JAX sees {devices[0].platform})", file=sys.stderr)
        return 1
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in manifest["workloads"]}[args.workload]
    if len(devices) < chips:
        print(f"attribute.py: {args.workload} needs {chips} chips", file=sys.stderr)
        return 1

    def log(msg: str) -> None:
        print(f"# {msg}", flush=True)

    cell = harness.load_cell(
        manifest, args.workload, seed=args.seed, seconds=args.seconds,
        devices=devices[:chips], log=log,
    )
    runner = cell.config["runner"]
    mod = harness.load_module(harness.runner_path(runner), f"chipbench_runner_{runner}")
    comm = count_comm_bytes()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-attribute-")
    try:
        out = mod.run(cell, trace_dir=trace_dir)
        events = spans.read_events(
            xtrace.find_xplane(trace_dir), harness.SPANS | spans.PROGRAM_SPANS
        )
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    r = xtrace.reduce_events(events)
    sr = spans.reduce_spans(events)
    per_run = sorted({c for c in comm if c is not None})
    comm_bytes = per_run[0] if len(per_run) == 1 else None
    line = {
        "workload": args.workload,
        "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "chips": chips},
        "units": out.units,
        "e2e": out.e2e,
        "correct": harness.passes(out.checks),
        "comm_bytes_per_run": per_run,  # one value: every run copies the same
        "reduction": dataclasses.asdict(r),
        "spans": dataclasses.asdict(sr),
        "readings": spans.readings(runner, sr, out.units, comm_bytes),
    }
    if args.events is not None:
        rows = [
            [e.plane, e.line,
             xtrace.short_name(e.name) if xtrace.DEVICE_PLANE.match(e.plane) else e.name,
             e.start_ns, e.dur_ns]
            for e in events
        ]
        args.events.parent.mkdir(parents=True, exist_ok=True)
        args.events.write_text(json.dumps(rows))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
