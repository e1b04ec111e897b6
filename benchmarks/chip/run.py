"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up (loading, data and weights from the seed, warm-up, compiles) runs
first; then the cell's traffic runs for ``--seconds``; then the reference
checks what the window produced.  Progress goes to earlier lines of
standard output, the numbers compared (each beside its limit) to the last
lines of standard error, and one JSON object to the last line of standard
output.  With ``--trace 1`` the window runs under the profiler and the
line carries the per-layer metrics; with ``--trace 0`` the end-to-end
ones.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.cache import configure_compile_cache
    except ImportError as e:
        print(f"run.py: the system under test is missing ({e})", file=sys.stderr)
        return 2
    from chipbench import harness

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in manifest["workloads"]}
    if args.workload not in chips:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    import jax

    # Every program of the cell goes into the cache, however quick its
    # compile, so that a run after the first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX sees {devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < chips[args.workload]:
        print(
            f"run.py: {args.workload} needs {chips[args.workload]} chips, "
            f"JAX sees {len(devices)}", file=sys.stderr,
        )
        return 1
    log(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}; jax {jax.__version__}, cache {cache_dir}, "
        f"{len(devices)} x {devices[0].device_kind}")
    line = harness.run_cell(
        manifest, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices[: chips[args.workload]],
        t_start=T_START, log=log,
    )
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
