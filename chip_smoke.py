"""Bring-up check: the SWIRL main path on a TPU, through its normal entry points.

Run from the repository root, with no install and no ``PYTHONPATH``::

    python chip_smoke.py             # one chip: every phase below but "devices"
    python chip_smoke.py --chips 4   # four chips: the "devices" phase only

Phases (each prints one JSON line with its checks, timings and memory):

* ``jax_backend`` — a ``swirl.trace(...).lower("jax", fuse=True)`` workflow
  of Pallas ``rmsnorm`` steps on bf16 (8192, 1024) payloads over two
  locations: every location on a TPU, no eager fallback, a Mosaic kernel
  (``tpu_custom_call``) in each fused segment's HLO, agreement with
  ``fuse=False``, and two runs from the same caller-held payloads.
* ``multiprocess`` — the quickstart DAG on the ``multiprocess`` backend
  while this process holds the TPU, with numpy and with ``jax.Array``
  payloads: it finishes with workers that never open the TPU, and
  unpicklable step functions are refused with a typed error.
* ``train`` — ``repro.launch.train.train()`` on granite-moe-1b-a400m at its
  published widths, cut to 4 layers: 2 pods, global batch 8, sequence
  1024, int8-compressed gradsync.  Every loss is finite, and the step-0
  loss agrees with a float32 reference computed on the host's CPU device.
* ``devices`` (``--chips 4``) — a 4-location ring workflow whose COMMs are
  device-to-device copies, every location on its own TPU, against the same
  workflow with every location on device 0.

The last line is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.  Without a TPU, or when a phase fails, the script exits
non-zero and prints no such line.  It never continues on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# jax backend: fused Pallas rmsnorm workflow
# ---------------------------------------------------------------------------

# Pallas rmsnorm and a tanh mix, each rounding its output to bf16.  The
# fused and the op-by-op runs compute the same float32 mathematics and can
# differ only where XLA reassociates it, by one bf16 rounding per step; the
# mix contracts (gain <= 0.75) and every norm renormalises, so those
# differences do not compound.  The tolerance is two bf16 ulps at the
# payloads' unit scale; a step computed in a narrower type (fp8, 2**-3
# spacing) or a skipped step exceeds it by orders of magnitude.
FUSED_RTOL = FUSED_ATOL = 2.0**-6


def ring_workflow(n_locs: int, rounds: int) -> str:
    """SWIRL text of a ring of locations that normalise their ``x`` in place.

    Location ``l<i>`` holds ``x<i>`` and a norm weight ``w<i>``.  Each round
    it runs two straight-line runs of norm→mix on ``x<i>`` (so the second
    run consumes a buffer the first produced), then sends ``x<i>`` to the
    next location; the next round's first norm also reads the neighbour's
    ``x``.  Step names order the firing so that each location's runs fuse.
    """
    traces = []
    for i in range(n_locs):
        prev = (i - 1) % n_locs
        nxt = (i + 1) % n_locs
        acts = []
        for k in range(rounds):
            for j in range(2):
                ins = {f"x{i}", f"w{i}"}
                if n_locs > 1 and k > 0 and j == 0:
                    ins.add(f"x{prev}")
                acts.append(
                    f"exec(r{k}s{j}_{i}a, {{{', '.join(sorted(ins))}}} -> "
                    f"{{x{i}}}, {{l{i}}})"
                )
                acts.append(f"exec(r{k}s{j}_{i}b, {{x{i}}} -> {{x{i}}}, {{l{i}}})")
            if n_locs > 1 and k < rounds - 1:
                acts.append(
                    f"(send(x{i}->q{k}_{i}, l{i}, l{nxt}) | "
                    f"recv(q{k}_{prev}, l{prev}, l{i}))"
                )
        traces.append(f"<l{i}, {{x{i}, w{i}}}, {'.'.join(acts)}>")
    return " | ".join(traces)


def _norm_step(inputs, *, own, weight, neighbour, interpret):
    import jax.numpy as jnp

    from repro.kernels.ops import rmsnorm

    x = inputs[own]
    if neighbour is not None:
        x = ((x.astype(jnp.float32) + inputs[neighbour]) * 0.5).astype(x.dtype)
    return {own: rmsnorm(x, inputs[weight], interpret=interpret)}


def _mix_step(inputs, *, own):
    import jax.numpy as jnp

    x = inputs[own].astype(jnp.float32)
    return {own: (0.5 * x + 0.25 * jnp.tanh(x)).astype(inputs[own].dtype)}


def ring_steps(n_locs: int, rounds: int, *, interpret: bool) -> dict:
    fns = {}
    for i in range(n_locs):
        for k in range(rounds):
            for j in range(2):
                neighbour = (
                    f"x{(i - 1) % n_locs}" if n_locs > 1 and k > 0 and j == 0
                    else None
                )
                fns[f"r{k}s{j}_{i}a"] = functools.partial(
                    _norm_step, own=f"x{i}", weight=f"w{i}",
                    neighbour=neighbour, interpret=interpret,
                )
                fns[f"r{k}s{j}_{i}b"] = functools.partial(_mix_step, own=f"x{i}")
    return fns


def ring_payloads(n_locs: int, rows: int, d: int, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed)
    out = {}
    for i in range(n_locs):
        kx, kw = jax.random.split(jax.random.fold_in(key, i))
        out[(f"l{i}", f"x{i}")] = jax.random.normal(kx, (rows, d), jnp.bfloat16)
        out[(f"l{i}", f"w{i}")] = (
            0.1 * jax.random.normal(kw, (d,), jnp.float32)
        ).astype(jnp.bfloat16)
    return out


def _compare(a: dict, b: dict) -> dict:
    """Largest deviation of ``a`` from ``b`` over their array payloads."""
    import numpy as np

    worst, exact, n = 0.0, True, 0
    for loc in b:
        for d, want in b[loc].items():
            got = np.asarray(a[loc][d], np.float32)
            want = np.asarray(want, np.float32)
            n += 1
            exact &= bool(np.array_equal(got, want))
            excess = np.abs(got - want) - (FUSED_ATOL + FUSED_RTOL * np.abs(want))
            worst = max(worst, float(excess.max()))
    return {"arrays": n, "bit_identical": exact, "within_tol": worst <= 0.0}


def phase_jax_backend(
    *,
    rows: int = 8192,
    d: int = 1024,
    n_locs: int = 2,
    rounds: int = 2,
    interpret: bool = False,
    platform: str = "tpu",
) -> dict:
    """Fused Pallas workflow on the ``jax`` backend; see the module doc."""
    import jax
    import numpy as np

    from repro import swirl

    plan = swirl.trace(ring_workflow(n_locs, rounds)).optimize()
    steps = ring_steps(n_locs, rounds, interpret=interpret)
    init = ring_payloads(n_locs, rows, d)
    # Snapshot copies: a host view of a CPU array would pin its buffer.
    before = {k: np.asarray(v.copy()) for k, v in init.items()}

    fused = plan.lower("jax", fuse=True).compile(steps)
    t0 = time.perf_counter()
    r1 = fused.run(initial_payloads=init)
    jax.block_until_ready(r1.data)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    r2 = fused.run(initial_payloads=init)  # the same caller-held arrays
    jax.block_until_ready(r2.data)
    warm_s = time.perf_counter() - t0
    eager = plan.lower("jax").compile(steps).run(initial_payloads=init)

    devices = {loc: d for loc, d in r1.stats["devices"].items()}
    check(
        all(platform in str(dev).lower() for dev in devices.values()),
        f"locations not all on {platform}: {devices}",
    )
    fstats = r1.stats["fused"]
    check(fstats["fallbacks"] == 0, f"fused fallbacks: {fstats['fallbacks']}")
    check(fstats["fused_calls"] > 0, "no fused segment ran")
    check(
        all(not v.is_deleted() for v in init.values()),
        "a caller-held initial payload was deleted",
    )
    check(
        all(np.array_equal(np.asarray(v), before[k]) for k, v in init.items()),
        "a caller-held initial payload changed",
    )
    vs_eager = _compare(r1.data, eager.data)
    check(vs_eager["within_tol"], f"fused run differs from fuse=False: {vs_eager}")
    repeat = _compare(r2.data, r1.data)
    check(repeat["bit_identical"], "the repeated fused run differs from the first")
    hlo = fused.program.segment_hlo()
    mosaic = sum("tpu_custom_call" in text for text in hlo.values())
    if not interpret:
        check(
            mosaic == len(hlo),
            f"{len(hlo) - mosaic} of {len(hlo)} fused segments hold no Mosaic kernel",
        )
    return {
        "payload": [rows, d, "bfloat16"],
        "locations": n_locs,
        "devices": sorted(set(devices.values())),
        "fused_calls": fstats["fused_calls"],
        "fused_execs": fstats["fused_execs"],
        "fallbacks": fstats["fallbacks"],
        "donated": fstats["donated"] + r2.stats["fused"]["donated"],
        "segments_with_tpu_custom_call": f"{mosaic}/{len(hlo)}",
        "vs_fuse_false": vs_eager,
        "second_run_from_same_payloads": "ok",
        "first_run_s": first_s,
        "second_run_s": warm_s,
    }


def phase_devices(
    *,
    n_locs: int = 4,
    rows: int = 8192,
    d: int = 1024,
    rounds: int = 3,
    interpret: bool = False,
    platform: str = "tpu",
) -> dict:
    """The ring on one device per location against all on device 0."""
    import jax

    from repro import swirl

    devs = jax.devices()
    check(len(devs) >= n_locs, f"{n_locs} locations need {n_locs} devices, have {len(devs)}")
    plan = swirl.trace(ring_workflow(n_locs, rounds)).optimize()
    steps = ring_steps(n_locs, rounds, interpret=interpret)
    init = ring_payloads(n_locs, rows, d)
    spread = plan.lower("jax", fuse=True).compile(steps)
    single = plan.lower("jax", fuse=True, devices=[devs[0]]).compile(steps)
    out = {}
    for name, exe in (("spread", spread), ("device0", single)):
        exe.run(initial_payloads=init)  # compile
        t0 = time.perf_counter()
        res = exe.run(initial_payloads=init)
        jax.block_until_ready(res.data)
        out[name] = (res, time.perf_counter() - t0)
    res, _ = out["spread"]
    placed = res.stats["devices"]
    check(
        len(set(placed.values())) == n_locs,
        f"locations share devices: {placed}",
    )
    check(
        all(platform in dev.lower() for dev in placed.values()),
        f"locations not all on {platform}: {placed}",
    )
    by_name = {str(dv): dv for dv in devs}
    for loc, values in res.data.items():
        for name, v in values.items():
            check(
                v.devices() == {by_name[placed[loc]]},
                f"{loc}/{name} is on {v.devices()}, not {placed[loc]}",
            )
    check(res.stats["fused"]["fallbacks"] == 0, "fused fallbacks on the spread run")
    match = _compare(res.data, out["device0"][0].data)
    check(match["within_tol"], f"spread run differs from device 0 run: {match}")
    return {
        "locations": n_locs,
        "placement": placed,
        "comms": res.stats["comms"],
        "bytes_moved": res.stats["bytes_moved"],
        "vs_device0": match,
        "spread_run_s": out["spread"][1],
        "device0_run_s": out["device0"][1],
    }


# ---------------------------------------------------------------------------
# multiprocess backend while this process holds the chip
# ---------------------------------------------------------------------------

QUICKSTART_EDGES = {
    "preprocess": ["train_a", "train_b"],
    "train_a": ["evaluate"],
    "train_b": ["evaluate"],
    "evaluate": ["report"],
    "report": [],
}
QUICKSTART_MAPPING = {
    "preprocess": ("cpu0",),
    "train_a": ("gpu0",),
    "train_b": ("gpu1",),
    "evaluate": ("gpu0",),
    "report": ("cpu0",),
}


def quickstart_instance():
    """The quickstart DAG, its source step reading a caller's ``seed``."""
    from repro.core.graph import DistributedWorkflowInstance, make_workflow

    producers = [s for s, succ in QUICKSTART_EDGES.items() if succ]
    deps = [("p^seed", "preprocess")]
    for s in producers:
        deps.append((s, f"p^{s}"))
        deps += [(f"p^{s}", t) for t in QUICKSTART_EDGES[s]]
    return DistributedWorkflowInstance(
        workflow=make_workflow(
            list(QUICKSTART_EDGES), ["p^seed"] + [f"p^{s}" for s in producers],
            deps,
        ),
        locations=frozenset(l for ls in QUICKSTART_MAPPING.values() for l in ls),
        mapping=QUICKSTART_MAPPING,
        data=frozenset(["seed"] + [f"d^{s}" for s in producers]),
        placement={"seed": "p^seed", **{f"d^{s}": f"p^{s}" for s in producers}},
        initial_data={"cpu0": frozenset({"seed"})},
    )


def _worker_probe() -> list:
    """(pid, accelerator this worker's JAX has opened) — run in a worker."""
    from repro.backends.multiprocess import held_accelerator

    return [(os.getpid(), held_accelerator())]


def _qs_preprocess(inputs, *, xp):
    seed = inputs["seed"]  # a numpy or jax array of 0.0, 1.0, 2.0, 3.0
    if xp == "jax":
        import jax.numpy as jnp

        v = jnp.arange(10) + (seed[0] * 0).astype(jnp.int32)
    else:
        import numpy as np

        v = np.arange(10) + (seed[0] * 0).astype(np.int64)
    return {"d^preprocess": (v, _worker_probe())}


def _qs_reduce(inputs, *, out, how):
    v, probe = inputs["d^preprocess"]
    return {out: (getattr(v, how)(), probe + _worker_probe())}


def _qs_evaluate(inputs):
    (a, pa), (b, pb) = inputs["d^train_a"], inputs["d^train_b"]
    return {"d^evaluate": (a + b, pa + pb + _worker_probe())}


def _qs_report(inputs):
    return {}


def quickstart_steps(xp: str) -> dict:
    return {
        "preprocess": functools.partial(_qs_preprocess, xp=xp),
        "train_a": functools.partial(_qs_reduce, out="d^train_a", how="sum"),
        "train_b": functools.partial(_qs_reduce, out="d^train_b", how="max"),
        "evaluate": _qs_evaluate,
        "report": _qs_report,
    }


def phase_multiprocess(*, timeout_s: float = 120.0) -> dict:
    """The quickstart DAG on ``multiprocess`` from a process holding JAX."""
    import jax
    import numpy as np

    from repro import swirl
    from repro.backends.multiprocess import AcceleratorHeldError, held_accelerator

    held = held_accelerator()
    plan = swirl.trace(quickstart_instance()).optimize()
    out: dict = {"parent_holds": held}
    for xp in ("numpy", "jax"):
        seed = np.arange(4.0) if xp == "numpy" else jax.numpy.arange(4.0)
        t0 = time.perf_counter()
        res = plan.lower("multiprocess", timeout_s=timeout_s).compile(
            quickstart_steps(xp)
        ).run(initial_payloads={("cpu0", "seed"): seed})
        dt = time.perf_counter() - t0
        score, probes = res.payload("cpu0", "d^evaluate")
        check(int(score) == 54, f"{xp}: score {score} != 54")
        check(
            np.array_equal(np.asarray(res.payload("cpu0", "seed")), np.arange(4.0)),
            f"{xp}: the initial payload did not come back intact",
        )
        pids = sorted({pid for pid, _ in probes})
        check(os.getpid() not in pids, f"{xp}: a step ran in the parent")
        opened = sorted({str(acc) for _, acc in probes if acc is not None})
        check(not opened, f"{xp}: workers opened {opened}")
        out[xp] = {
            "score": int(score),
            "start_method": res.stats["start_method"],
            "worker_pids": pids,
            "workers_opened_accelerator": opened or None,
            "seconds": dt,
        }
    # The quickstart's own lambdas cannot reach a spawned worker.
    lambdas = {s: (lambda inputs: {}) for s in QUICKSTART_EDGES}
    t0 = time.perf_counter()
    try:
        plan.lower("multiprocess", timeout_s=timeout_s).compile(lambdas).run()
        refused = None
    except AcceleratorHeldError as e:
        refused = str(e)
    check(
        (refused is not None) == (held is not None),
        f"lambdas with parent holding {held}: refused={refused!r}",
    )
    out["lambda_steps"] = {
        "refused": refused, "seconds": time.perf_counter() - t0,
    }
    return out


# ---------------------------------------------------------------------------
# SWIRL-planned trainer
# ---------------------------------------------------------------------------

# granite-moe-1b-a400m at its published widths, 24 layers cut to 4.
TRAIN_LAYERS = 4
TRAIN_RUN = dict(steps=3, n_pods=2, global_batch=8, seq_len=1024)

# The chip computes the step-0 loss in bfloat16 (weights, activations and
# logits rounded to 8-bit mantissas, float32 accumulation); the reference
# keeps every value in float32.  Each rounding perturbs a token's CE by
# O(2**-8) of a logit in a random direction, and the loss averages those
# over 8192 tokens, so the two agree to well inside one bf16 ulp of the
# loss itself: the tolerance is 2**-8 of the reference loss (about 0.044
# at the ~11.3 that random weights give over a 49155-token vocabulary).
LOSS_RTOL = 2.0**-8


def granite_cut(layers: int = TRAIN_LAYERS):
    from repro.configs import get_config

    return dataclasses.replace(get_config("granite-moe-1b-a400m"), n_layers=layers)


def reference_loss(cfg, params, *, n_pods, global_batch, seq_len, device):
    """Float32 step-0 loss of ``cfg`` over every pod's batch, on ``device``."""
    import jax
    import jax.numpy as jnp

    from repro.data import SyntheticLM
    from repro.models import Model

    ref = Model(dataclasses.replace(cfg, dtype="float32", remat=False))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq_len, global_batch=global_batch)
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(
            lambda a: jax.device_put(a, device).astype(jnp.float32), params
        )
        loss = jax.jit(lambda p, b: ref.loss(p, b)[0])
        losses = [
            float(loss(p32, jax.device_put(data.batch(0, shard=i, n_shards=n_pods), device)))
            for i in range(n_pods)
        ]
    return sum(losses) / n_pods


def phase_train(cfg=None, *, steps, n_pods, global_batch, seq_len, ref_device) -> dict:
    """``train()`` on ``cfg`` plus the float32 step-0 reference."""
    import jax

    from repro.launch.train import train
    from repro.models import Model

    cfg = cfg or granite_cut()
    # The same initial parameters train() makes (same key, same device).
    params0 = jax.device_get(Model(cfg).init(jax.random.key(0)))
    t0 = time.perf_counter()
    out = train(
        cfg, steps=steps, n_pods=n_pods, global_batch=global_batch,
        seq_len=seq_len, ckpt_dir=None, compress_grads=True, log_every=1,
    )
    train_s = time.perf_counter() - t0
    losses = [float(h["loss"]) for h in out["history"]]
    retries = out["retries"]
    del out
    stats = jax.devices()[0].memory_stats() or {}
    check(all(math.isfinite(x) for x in losses), f"non-finite losses {losses}")
    t0 = time.perf_counter()
    ref = reference_loss(
        cfg, params0, n_pods=n_pods, global_batch=global_batch,
        seq_len=seq_len, device=ref_device,
    )
    ref_s = time.perf_counter() - t0
    diff = abs(losses[0] - ref)
    check(
        diff <= LOSS_RTOL * abs(ref),
        f"step-0 loss {losses[0]} vs float32 reference {ref}: |diff| {diff} "
        f"> {LOSS_RTOL * abs(ref)}",
    )
    return {
        "model": cfg.name,
        "layers": cfg.n_layers,
        "params": cfg.param_count(),
        "run": dict(n_pods=n_pods, global_batch=global_batch, seq_len=seq_len,
                    steps=steps, compress_grads=True),
        "losses": losses,
        "step0_reference_f32": ref,
        "step0_abs_diff": diff,
        "step0_tolerance": LOSS_RTOL * abs(ref),
        "retries": retries,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "train_s": train_s,
        "reference_s": ref_s,
    }


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def _run_phase(name: str, fn) -> bool:
    t0 = time.perf_counter()
    try:
        out, ok = fn(), True
    except Exception as e:  # every phase reports; the exit code gathers them
        traceback.print_exc()
        out, ok = {"error": f"{type(e).__name__}: {e}"}, False
    line = {"phase": name, "ok": ok, "seconds": time.perf_counter() - t0, **out}
    print(json.dumps(line, default=str), flush=True)
    return ok


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro.launch.cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing ({e})", file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX sees {devs[0].platform} devices)",
            file=sys.stderr,
        )
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} TPUs", file=sys.stderr)
        return 1
    print(json.dumps({
        "phase": "setup", "jax": jax.__version__, "cache_dir": cache_dir,
        "devices": [str(d) for d in devs], "kind": devs[0].device_kind,
    }), flush=True)

    if args.chips == 4:
        phases = [("devices", lambda: phase_devices(n_locs=4))]
    else:
        cpu = jax.devices("cpu")[0]
        phases = [
            ("jax_backend", phase_jax_backend),
            ("multiprocess", phase_multiprocess),
            ("train", lambda: phase_train(**TRAIN_RUN, ref_device=cpu)),
        ]
    ok = all([_run_phase(name, fn) for name, fn in phases])
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
