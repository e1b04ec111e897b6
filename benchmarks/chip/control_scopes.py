"""Read a ``swirl_trainer_scopes`` cell's control and planted faults on the
chip, with every gap its check computes.

    python3 benchmarks/chip/control_scopes.py --workload train_mellum2_8k --seeds 1 2

For each seed: the control, the float32 reference with every matrix
product's operands rounded to float8 (e4m3) straight through, against the
float32 reference (as ``control.py`` reads a ``swirl_trainer`` cell's);
then each fault of :mod:`chipbench.planted` (all, or those named with
``--faults``) planted in the program, run through the cell's runner with a
2-second window, and checked as the cell checks.  Prints one JSON line per
seed and reading with ``loss_gap``, ``grad_norm_gap`` and
``param_change_gap``, so that the limits can be set between the program's
readings and these.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def main(argv: list[str] | None = None) -> int:
    from chipbench import planted

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", choices=sorted(planted.FAULTS), default=sorted(planted.FAULTS))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import configure_compile_cache

    from chipbench import harness

    configure_compile_cache()
    import jax
    import jax.numpy as jnp
    import pytest

    from chipbench.runners import swirl_trainer_scopes
    from chipbench.runners.swirl_trainer import compare

    if jax.devices()[0].platform != "tpu":
        print("control_scopes.py: no TPU", file=sys.stderr)
        return 1
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    for seed in args.seeds:
        cell = harness.load_cell(
            manifest, args.workload, seed=seed, seconds=2.0, devices=jax.devices()[:1],
            log=lambda msg: print("#", msg, flush=True),
        )
        if cell.config["runner"] != "swirl_trainer_scopes":
            print(f"control_scopes.py: {args.workload} is not a swirl_trainer_scopes cell", file=sys.stderr)
            return 1
        # Every gap the runner computes is reported: an unlimited loss_gap
        # where the configuration sets no limit.
        cell.config["limits"] = {"loss_gap": math.inf, **cell.config["limits"]}
        m, job, readings = cell.config["model"], cell.traffic, cell.ref.reference_readings
        cache: dict = {}

        def cached(m, job, seed, **kw):
            key = str(kw.get("compute_dtype"))
            if key not in cache:
                cache[key] = readings(m, job, seed, **kw)
            return cache[key]

        cell.ref.reference_readings = cached
        with jax.default_matmul_precision("highest"):
            want = cached(m, job, seed)
            low = cached(m, job, seed, compute_dtype=jnp.float8_e4m3fn)
        print(json.dumps({"workload": args.workload, "seed": seed, "control": compare(low, want)}), flush=True)
        del low, want
        for name in args.faults:
            t = time.perf_counter()
            with pytest.MonkeyPatch.context() as mp:
                planted.FAULTS[name](mp, m)
                out = swirl_trainer_scopes.run(cell, trace_dir=None)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "fault": name,
                "gaps": {k: v for k, (v, _) in out.checks.items()},
                "seconds": time.perf_counter() - t,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
