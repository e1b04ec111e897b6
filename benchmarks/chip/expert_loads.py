"""Log a held-experts cell's routing layer by layer over its first steps.

    python3 benchmarks/chip/expert_loads.py --workload train_mellum2_8k --seed 1 --steps 12

Drives the cell's trainer (``swirl_trainer.Trainer``) and, before each
step, runs the model's forward pass on that step's batch with a probe in
each held-experts layer (:func:`make_probe`).  Prints one JSON line a step: the
step's counters as the trainer read them, and per layer

* ``held``: the choices landing on each held expert;
* ``share``: held choices ÷ all choices;
* ``load_max``: the busiest held expert ÷ the held mean (the program's
  ``moe_load_max`` is the largest over layers);
* ``routed_max``: the busiest of all routed experts ÷ their mean;
* ``common``: the share of the router input's energy in its mean over the
  step's tokens, ``|mean_t h_t|² ÷ mean_t |h_t|²``, and ``top``, the
  share in its strongest direction (the top eigenvalue of ``Σ_t h_t h_tᵀ``
  over its trace, by power iteration from the mean; at least ``common``);
* ``offset_std``: the spread over experts of their mean logit, beside
  ``token_std``, the spread of a logit over tokens (root mean square over
  experts);
* ``load_max_centred``: ``load_max`` with the router input's mean over
  tokens taken out.

The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def _routing(cfg, p, x, j: int, out: list) -> None:
    """Record layer ``j``'s routing of ``x`` (traced; read by a callback)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    m = cfg.moe
    h = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    held = slice(m.first_expert, m.first_expert + m.n_experts)

    def counts(h):
        _, experts = jax.lax.top_k(h @ p["router"]["w"], m.top_k)
        return jnp.bincount(experts.reshape(-1), length=m.router_width)

    mean = jnp.mean(h, axis=0)
    gram = h.T @ h
    u = mean / jnp.linalg.norm(mean)
    for _ in range(20):
        u = gram @ u
        u = u / jnp.linalg.norm(u)
    logits = h @ p["router"]["w"]
    stats = {
        "counts": counts(h),
        "centred": counts(h - mean)[held],
        "common": jnp.sum(mean * mean) / jnp.mean(jnp.sum(h * h, axis=-1)),
        "top": u @ gram @ u / jnp.trace(gram),
        "offset_std": jnp.std(jnp.mean(logits, axis=0)),
        "token_std": jnp.sqrt(jnp.mean(jnp.var(logits, axis=0))),
    }

    def record(stats):
        c = np.asarray(stats["counts"], float)
        mine, centred = c[held], np.asarray(stats["centred"], float)
        out.append((j, {
            "held": [int(v) for v in mine],
            "share": float(mine.sum() / c.sum()),
            "load_max": float(mine.max() * len(mine) / max(mine.sum(), 1.0)),
            "routed_max": float(c.max() / c.mean()),
            "common": float(stats["common"]),
            "top": float(stats["top"]),
            "offset_std": float(stats["offset_std"]),
            "token_std": float(stats["token_std"]),
            "load_max_centred": float(centred.max() * len(centred) / max(centred.sum(), 1.0)),
        }))

    jax.debug.callback(record, stats)


def make_probe(model):
    """A function of (params, tokens) giving each held-experts layer's
    routing of ``tokens``, in layer order, from a forward pass of ``model``
    (compiled on its first call)."""
    import jax
    import pytest

    import repro.models.model as model_mod

    out: list = []
    traced = [0]
    held_layer = model_mod.apply_moe_held

    def probed(cfg, p, x):
        _routing(cfg, p, x, traced[0], out)
        traced[0] += 1
        return held_layer(cfg, p, x)

    forward = jax.jit(lambda p, t: model._forward(p, t, src_embeds=None, patch_embeds=None)[1])

    def run(params, tokens) -> list[dict]:
        out.clear()
        traced[0] = 0
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(model_mod, "apply_moe_held", probed)
            jax.block_until_ready(forward(params, tokens))
            jax.effects_barrier()
        return [stats for _, stats in sorted(out, key=lambda e: e[0])]

    return run


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import configure_compile_cache

    from chipbench import harness

    configure_compile_cache()
    import jax

    from chipbench.runners.swirl_trainer import Trainer, model_config
    from repro.models import Model

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.load_cell(
        manifest, args.workload, seed=args.seed, seconds=0, devices=jax.devices()[:1],
        log=lambda msg: print("#", msg, flush=True),
    )
    trainer = Trainer(cell)
    probe = make_probe(Model(model_config(cell.config)))
    n = cell.traffic["n_pods"]
    for step in range(args.steps):
        tokens = trainer.dataset.batch(step, shard=0, n_shards=n)["tokens"]
        layers = probe(trainer.params, tokens)
        metrics = trainer.step()
        counters = {k: float(metrics[k]) for k in ("loss", "moe_held_share", "moe_load_max")}
        print(json.dumps({"step": step, **counters, "layers": layers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
