"""Read a cell's control on the chip: the reference put in the program's
place, computed one precision step below the configuration's.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3

For the trainer, the float32 reference with every matrix product's
operands rounded to float8 (e4m3), gradients passing the rounding
unchanged, against the float32 reference; for a
workflow, the reference accumulating in bfloat16 against the exact one.
With ``--fault`` (trainer), the reference with that fault planted
(``half_batch`` or ``no_exchange``) in place of the lower precision.
Prints one JSON line per seed with the gaps the cell's check compares, so
that the limits can be set between the program's readings and these.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=("half_batch", "no_exchange"))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import configure_compile_cache

    from chipbench import harness

    configure_compile_cache()
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.load_cell(
        manifest, args.workload, seed=0, seconds=0, devices=jax.devices(), log=print,
    )
    cfg, ref = cell.config, cell.ref
    for seed in args.seeds:
        if cfg["runner"] == "swirl_trainer":
            from chipbench.runners.swirl_trainer import compare

            with jax.default_matmul_precision("highest"):
                want = ref.reference_readings(cfg["model"], cell.traffic, seed)
                low = ref.reference_readings(
                    cfg["model"], cell.traffic, seed,
                    **({"fault": args.fault} if args.fault else {"compute_dtype": jnp.float8_e4m3fn}),
                )
            gaps = compare(low, want)
        else:
            data = ref.make_data(cfg, seed)
            gaps = {}
            for v in range(min(3, cfg["variants"])):
                g = ref.compare(
                    cfg, ref.reference(cfg, data, v, accumulate=jnp.bfloat16),
                    ref.reference(cfg, data, v),
                )
                gaps = {k: max(gaps.get(k, 0.0), x) for k, x in g.items()}
            del data
        what = args.fault or "control"
        print(json.dumps({"workload": args.workload, "seed": seed, what: gaps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
