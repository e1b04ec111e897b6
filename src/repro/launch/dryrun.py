import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The lines above MUST stay first: JAX locks the host device count on
first init, and the production meshes need 512 placeholder devices.  The
placeholders are CPU devices: on a TPU host the dry-run (and every child
of its fleet mode) would otherwise open the chip.

Single-cell mode (one compile per process — compile memory is bounded)::

    python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k \
        --mesh pod1 --out experiments/dryrun/llama3.2-3b_train_4k_pod1.json

Fleet mode (fans out subprocesses, collects JSON)::

    python -m repro.launch.dryrun --all --jobs 4 --out-dir experiments/dryrun

Each record carries ``cost_analysis`` FLOPs/bytes, parsed collective
traffic, ``memory_analysis`` and the three roofline terms — EXPERIMENTS.md
§Dry-run/§Roofline are generated from these files.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402


MESHES = ("pod1", "pod2")  # 16×16 single pod; 2×16×16 multi-pod


def run_cell(
    arch: str, shape_name: str, mesh_name: str,
    *, unroll: bool = False, variant: str = "baseline",
) -> dict:
    import jax

    from repro.configs import get_config
    from repro.configs.shapes import SHAPES, shape_applicable
    from repro.models import Model
    from repro.optim import AdamWConfig
    from repro.roofline import (
        model_flops,
        parse_collectives,
        roofline,
        slstm_extra_flops,
    )
    from . import steps as S
    from .mesh import make_production_mesh
    from .sharding import (
        batch_specs,
        cache_specs,
        param_specs,
        to_shardings,
    )
    from repro.optim.zero import zero1_specs
    from jax.sharding import PartitionSpec as P

    t_start = time.monotonic()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "skipped", "reason": why,
        }
    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    chips = mesh.devices.size
    repeats = cfg.repeats
    ssm_chunk = cfg.ssm.chunk
    if shape.kind in ("train", "prefill"):
        ssm_chunk = max(cfg.ssm.chunk, shape.seq_len // 16)
    if unroll:
        # Validation mode: unroll the layer stack so cost_analysis sees every
        # layer (used to calibrate the analytic model; ~10× slower compile).
        from repro.models import unrolled_variant

        cfg = unrolled_variant(cfg, ssm_chunk=ssm_chunk)
        repeats = 1
    elif ssm_chunk != cfg.ssm.chunk:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, ssm=_dc.replace(cfg.ssm, chunk=ssm_chunk))
    model = Model(cfg)

    optimized = variant == "opt"
    if optimized:
        from repro.models.hints import ShardHints, set_hints
        from .mesh import data_axes

        set_hints(ShardHints(mesh=mesh, dp_axes=data_axes(mesh)))
    else:
        from repro.models.hints import set_hints

        set_hints(None)

    p_shape = S.abstract_params(model)
    p_specs = param_specs(cfg, p_shape, mesh)
    p_shard = to_shardings(mesh, p_specs)
    b_shape = S.input_specs(cfg, shape)
    b_specs = batch_specs(cfg, b_shape, mesh)
    b_shard = to_shardings(mesh, b_specs)

    rec: dict = {
        "variant": variant,
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "chips": chips,
        "params": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }

    with mesh:
        if shape.kind == "train":
            o_shape = S.abstract_opt_state(p_shape)
            # m/v specs: param specs augmented with a data-axis split (ZeRO-1)
            from repro.optim.adamw import AdamWState

            mv_spec = zero1_specs(
                param_specs(cfg, p_shape, mesh), p_shape,
                data_axis="data", data_size=mesh.shape["data"],
            )
            o_specs = AdamWState(step=P(), m=mv_spec, v=mv_spec)
            o_shard = to_shardings(mesh, o_specs)
            fn = S.make_train_step(model, AdamWConfig())
            metric_spec = jax.tree.map(
                lambda _: jax.sharding.NamedSharding(mesh, P()),
                {"loss": 0, "ce": 0, "aux": 0, "tokens": 0, "grad_norm": 0, "lr": 0},
            )
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, metric_spec),
            )
            t0 = time.monotonic()
            lowered = jitted.lower(p_shape, o_shape, b_shape)
        elif shape.kind == "prefill":
            c_shape = S.abstract_cache(model, shape.global_batch, shape.seq_len)
            c_specs = cache_specs(cfg, c_shape, mesh, optimized=optimized)
            c_shard = to_shardings(mesh, c_specs)
            fn = S.make_prefill_step(model)
            tok_out = jax.sharding.NamedSharding(
                mesh, batch_specs(cfg, {"t": jax.ShapeDtypeStruct((shape.global_batch,), 'int32')}, mesh)["t"]
            )
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, b_shard, c_shard),
                out_shardings=(tok_out, c_shard),
            )
            t0 = time.monotonic()
            lowered = jitted.lower(p_shape, b_shape, c_shape)
        else:  # decode
            c_shape = S.abstract_cache(model, shape.global_batch, shape.seq_len)
            c_specs = cache_specs(cfg, c_shape, mesh, optimized=optimized)
            c_shard = to_shardings(mesh, c_specs)
            fn = S.make_serve_step(model)
            tok_in = b_shard["tokens"]
            tok_out = jax.sharding.NamedSharding(
                mesh, batch_specs(cfg, {"t": jax.ShapeDtypeStruct((shape.global_batch, 1), 'int32')}, mesh)["t"]
            )
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, c_shard, tok_in),
                out_shardings=(tok_out, c_shard),
            )
            t0 = time.monotonic()
            lowered = jitted.lower(
                p_shape, c_shape, b_shape["tokens"]
            )

        rec["lower_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        rec["compile_s"] = time.monotonic() - t0

        cost = compiled.cost_analysis() or {}
        flops = float(cost.get("flops", 0.0))
        hbm_bytes = float(cost.get("bytes accessed", 0.0))
        rec["cost_analysis"] = {
            "flops": flops,
            "bytes_accessed": hbm_bytes,
            "utilization_ops": float(cost.get("utilization", 0.0)),
        }
        try:
            mem = compiled.memory_analysis()
            rec["memory_analysis"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "generated_code_bytes": int(mem.generated_code_size_in_bytes),
            }
        except Exception as e:  # noqa: BLE001 — backend-dependent
            rec["memory_analysis"] = {"error": str(e)}

        hlo = compiled.as_text()
        # Scale collectives inside while-loop bodies by the layer-scan trip
        # count (the HLO shows the body once; it runs `repeats` times).
        stats = parse_collectives(hlo, body_scale=max(1, repeats))
        rec["collectives"] = stats.as_dict()
        rec["hlo_bytes"] = len(hlo)

        # Analytic FLOP/HBM models (validated vs. the unrolled cell — see
        # EXPERIMENTS.md §Roofline): scanned-body cost_analysis undercounts
        # FLOPs ×repeats and the CPU backend overcounts unfused bytes.
        from repro.configs import get_config as _gc
        from repro.roofline.analytic import (
            analytic_flops_global,
            analytic_hbm_bytes_per_device,
        )

        base_cfg = _gc(arch)
        a_flops = analytic_flops_global(base_cfg, shape)
        mm = analytic_hbm_bytes_per_device(
            base_cfg, shape,
            model_ways=mesh.shape["model"],
            data_ways=chips // mesh.shape["model"],
        )
        rec["analytic"] = {
            "flops_global": a_flops,
            "hbm_bytes_per_device": mm.total,
            "hbm_breakdown": {
                "params": mm.params_bytes,
                "opt": mm.opt_bytes,
                "grads": mm.grad_bytes,
                "acts": mm.act_bytes,
                "kv": mm.kv_bytes,
                "logits": mm.logits_bytes,
            },
        }
        rl = roofline(
            flops_per_device=a_flops / chips,
            hbm_bytes_per_device=mm.total,
            link_bytes_per_device=stats.total_link_bytes,
            model_flops_global=model_flops(base_cfg, shape),
            chips=chips,
        )
        rec["roofline"] = rl.as_dict()
        rec["status"] = "ok"
        rec["total_s"] = time.monotonic() - t_start
    return rec


def _cell_out(out_dir: Path, arch: str, shape: str, mesh: str) -> Path:
    safe = arch.replace("/", "_")
    return out_dir / f"{safe}__{shape}__{mesh}.json"


def run_all(out_dir: Path, jobs: int, meshes: tuple[str, ...], timeout: int, force: bool, variant: str = "baseline") -> int:
    from repro.configs import ARCHS
    from repro.configs.shapes import SHAPES

    out_dir.mkdir(parents=True, exist_ok=True)
    cells = [
        (a, s, m)
        for a in ARCHS
        for s in SHAPES
        for m in meshes
    ]
    pending = []
    for cell in cells:
        out = _cell_out(out_dir, *cell)
        if force or not out.exists():
            pending.append(cell)
    print(f"{len(cells)} cells total, {len(pending)} to run, jobs={jobs}")

    procs: dict = {}
    failures = []
    queue = list(pending)
    while queue or procs:
        while queue and len(procs) < jobs:
            cell = queue.pop(0)
            out = _cell_out(out_dir, *cell)
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", cell[0], "--shape", cell[1], "--mesh", cell[2],
                "--out", str(out), "--variant", variant,
            ]
            procs[subprocess.Popen(cmd)] = (cell, out, time.monotonic())
        done = [p for p in procs if p.poll() is not None]
        for p in done:
            cell, out, t0 = procs.pop(p)
            dt = time.monotonic() - t0
            if p.returncode != 0 or not out.exists():
                failures.append(cell)
                print(f"FAIL {cell} rc={p.returncode} ({dt:.0f}s)")
            else:
                rec = json.loads(out.read_text())
                print(
                    f"ok   {cell} status={rec.get('status')} "
                    f"compile={rec.get('compile_s', 0):.0f}s ({dt:.0f}s)"
                )
        for p, (cell, out, t0) in list(procs.items()):
            if time.monotonic() - t0 > timeout:
                p.kill()
                failures.append(cell)
                print(f"TIMEOUT {cell}")
                procs.pop(p)
        time.sleep(0.5)
    print(f"done; {len(failures)} failures: {failures}")
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=MESHES, default="pod1")
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", choices=("baseline", "opt"), default="baseline")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer stack (analytic-model validation)")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        return run_all(
            Path(args.out_dir), args.jobs, MESHES, args.timeout, args.force,
            variant=args.variant,
        )

    assert args.arch and args.shape, "--arch and --shape required"
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, unroll=args.unroll, variant=args.variant)
    except Exception as e:  # noqa: BLE001
        rec = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "status": "error", "error": f"{type(e).__name__}: {e}",
        }
    text = json.dumps(rec, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0 if rec.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
