"""SWIRL-planned multi-pod training driver.

The distribution logic is NOT hand-written: each training iteration is a
*distributed workflow instance* (steps: per-pod ``shard`` → ``fwdbwd`` →
synchronised ``gradsync`` → per-pod ``update`` → ``ckpt``), translated by
the paper's encoding ``⟦·⟧`` into per-pod SWIRL traces, rewritten by the
paper's optimisation (R1 removes same-pod transfers, R2 coalesces duplicate
broadcasts), and executed by the fault-tolerant workflow runtime.  Inside a
pod, each step body is a jitted SPMD program (GSPMD over the pod mesh).

Cross-pod gradient traffic goes through int8 error-feedback compression
(:mod:`repro.optim.compress`) — the explicit send/recv structure of the
SWIRL plan is what makes the compression insertion point well-defined.

The step bodies open profiler spans (:func:`repro.obs.profiler_span`)
around the host work of an iteration: ``launch.compress`` (one pod's
int8 compression, one jitted program), ``launch.gradsync`` (the mean
over pods of their decompressed payloads, one jitted program) and
``launch.sync`` (each read of device values to the host, which waits for
the device).  ``launch.compress`` and ``launch.gradsync`` carry
``traces``, how many times the jitted bodies behind them had been traced
when the span opened (:data:`repro.optim.compress.TRACES`): it stays put
from one iteration to the next once each program is compiled.  The spans
nest in the SWIRL runtime's ``swirl.exec`` spans on the thread that runs
the step.

All "pods" run in this one process and share its default device: the
host under ``JAX_PLATFORMS=cpu``, or the one chip of a TPU host, where
every pod's step bodies run on that chip.  The orchestration path (plans,
channels, checkpoints, recovery) is the one a multi-controller deployment
runs, where each pod process executes its own trace.

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
        --steps 20 --pods 2 --global-batch 8 --seq-len 64

``train()`` also takes a :class:`~repro.models.ModelConfig` in place of the
arch name, e.g. a published config cut in depth with
``dataclasses.replace`` (``chip_smoke.py`` does this).
"""

from __future__ import annotations

import argparse
import time
from functools import partial
from typing import Any

import jax
import numpy as np

from repro import swirl
from repro.configs import get_config
from repro.core.translate import TrainPipelineTranslator
from repro.data import SyntheticLM
from repro.models import Model, ModelConfig
from repro.obs import profiler_span
from repro.optim import AdamWConfig
from repro.optim import adamw as adamw_mod
from repro.optim.compress import TRACES, allreduce_mean, compress, decompress_mean
from repro.workflow import RetryPolicy
from repro.ckpt import async_save, latest_step, load_checkpoint
from .cache import configure_compile_cache
from .steps import make_grad_step

PyTree = Any


def build_step_fns(
    grad_fn,
    update_fn,
    dataset: SyntheticLM,
    n_pods: int,
    *,
    compress_grads: bool = True,
    error_feedback: dict[int, PyTree] | None = None,
    ckpt_dir: str | None = None,
):
    """Step-name → pure-fn registry for one training iteration."""
    err = error_feedback if error_feedback is not None else {}

    fns: dict[str, Any] = {}
    for i in range(n_pods):

        def shard(inputs, i=i):
            step = int(inputs[f"iter_{i}"])
            b = dataset.batch(step, shard=i, n_shards=n_pods)
            return {f"batch_{i}": b}

        def fwdbwd(inputs, i=i):
            params = inputs[f"params_{i}"]
            grads, metrics = grad_fn(params, inputs[f"batch_{i}"])
            if compress_grads:
                with profiler_span(
                    "launch.compress", pod=i, traces=TRACES["compress"]
                ):
                    grads, err[i] = compress(grads, err.get(i))
            return {f"grad_{i}": (grads, metrics)}

        def update(inputs, i=i):
            params = inputs[f"params_{i}"]
            opt_state = inputs[f"opt_{i}"]
            mean_grads, metrics = inputs["grad_sync"]
            new_params, new_opt, om = update_fn(mean_grads, opt_state, params)
            with profiler_span("launch.sync", pod=i):
                om = {k: float(v) for k, v in om.items()}
            return {
                f"state_{i}": {
                    "params": new_params,
                    "opt": new_opt,
                    "metrics": {**metrics, **om},
                }
            }

        fns[f"shard_{i}"] = shard
        fns[f"fwdbwd_{i}"] = fwdbwd
        fns[f"update_{i}"] = update

    def gradsync(inputs):
        grads = [inputs[f"grad_{i}"] for i in range(n_pods)]
        payloads = [payload for payload, _ in grads]
        traces = TRACES["decompress_mean"] + TRACES["allreduce_mean"]
        with profiler_span("launch.gradsync", traces=traces):
            if compress_grads:
                mean = decompress_mean(allreduce_mean, payloads)
            else:
                mean = allreduce_mean(payloads)
        pod_metrics = []
        for i, (_, m) in enumerate(grads):
            with profiler_span("launch.sync", pod=i):
                pod_metrics.append({k: float(v) for k, v in m.items()})
        # Pods hold equal shards, so the mean of their losses is the
        # step's loss over the global batch (per-pod router aux terms).
        metrics = {
            k: sum(m[k] for m in pod_metrics) / n_pods
            for k in pod_metrics[0]
        }
        return {"grad_sync": (mean, metrics)}

    def ckpt(inputs):
        state = inputs["state_0"]
        if ckpt_dir:
            saver = async_save(
                ckpt_dir,
                int(state["opt"].step),
                {"params": state["params"], "opt": state["opt"]._asdict()},
            )
            saver.wait()
        return {}

    fns["gradsync"] = gradsync
    fns["ckpt"] = ckpt
    return fns, err


def train(
    arch: str | ModelConfig,
    *,
    smoke: bool = False,
    steps: int,
    n_pods: int,
    global_batch: int,
    seq_len: int,
    ckpt_dir: str | None,
    compress_grads: bool = True,
    log_every: int = 5,
) -> dict:
    cfg = arch if isinstance(arch, ModelConfig) else get_config(arch, smoke=smoke)
    model = Model(cfg)
    dataset = SyntheticLM(
        vocab=cfg.vocab, seq_len=seq_len, global_batch=global_batch
    )
    opt_cfg = AdamWConfig(warmup_steps=max(2, steps // 10), total_steps=steps)

    # The SWIRL plan for one iteration (encode ∘ optimise).
    translator = TrainPipelineTranslator(
        n_pods=n_pods, with_checkpoint=ckpt_dir is not None
    )
    plan = swirl.trace(translator).optimize(rules=("R1R2", "R3"))
    opt_stats, r3_stats = (r.stats for r in plan.rewrites)
    print(
        f"[swirl] plan: {plan.system.total_actions()} actions, "
        f"{plan.system.comm_count()} comms (Def.15 removed "
        f"{opt_stats.removed}, R3 removed {r3_stats.removed})"
    )
    lowered = plan.lower("inprocess", retry=RetryPolicy(max_retries=2))

    # Resume or init per-pod replicas (identical params across pods).
    params = model.init(jax.random.key(0))
    opt_state = adamw_mod.init(params)
    start = 0
    if ckpt_dir and (last := latest_step(ckpt_dir)) is not None:
        restored = load_checkpoint(
            ckpt_dir, last,
            {"params": params, "opt": opt_state._asdict()},
        )
        params = restored["params"]
        opt_state = adamw_mod.AdamWState(**restored["opt"])
        start = int(np.asarray(restored["opt"]["step"]))
        print(f"[ckpt] resumed from step {start}")

    err: dict[int, PyTree] = {}
    history = []
    retries = 0
    grad_fn = jax.jit(make_grad_step(model))
    update_fn = jax.jit(partial(adamw_mod.update, opt_cfg))
    t0 = time.monotonic()
    t_first = t0  # end of the first iteration, which pays the compiles
    for it in range(start, start + steps):
        fns, err = build_step_fns(
            grad_fn, update_fn, dataset, n_pods,
            compress_grads=compress_grads, error_feedback=err,
            ckpt_dir=ckpt_dir,
        )
        payloads = {}
        for i in range(n_pods):
            payloads[(f"pod{i}", f"iter_{i}")] = it
            payloads[(f"pod{i}", f"params_{i}")] = params
            payloads[(f"pod{i}", f"opt_{i}")] = opt_state
        # ``shard_i``/``fwdbwd_i`` read iter/params from the pod's local data
        # scope: declare them as part of each pod's initial D set.
        result = lowered.compile(fns).run(initial_payloads=payloads)
        retries += result.stats.retries
        state = result.payload("pod0", "state_0")
        # Free this iteration's other pods' states, gradients and means
        # (and the previous params) before the next run allocates its own.
        del result, payloads
        params, opt_state = state["params"], state["opt"]
        m = state["metrics"]
        history.append(m)
        if it == start:
            t_first = time.monotonic()
        if (it - start) % log_every == 0:
            print(
                f"step {it:5d} loss={m['loss']:.4f} ce={m['ce']:.4f} "
                f"gnorm={m.get('grad_norm', 0):.3f}"
            )
    t_end = time.monotonic()
    # The metrics were read to the host, so every iteration has finished.
    per_step = (
        f", {(t_end - t_first) / (steps - 1):.2f}s/step after the first"
        if steps > 1 else ""
    )
    print(
        f"[done] {steps} steps in {t_end - t0:.1f}s (first "
        f"{t_first - t0:.1f}s with its compiles{per_step}), "
        f"{retries} step retries"
    )
    return {
        "history": history, "params": params, "opt": opt_state,
        "retries": retries,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--no-compress", dest="compress", action="store_false")
    args = ap.parse_args()
    configure_compile_cache()
    train(
        args.arch,
        smoke=args.smoke,
        steps=args.steps,
        n_pods=args.pods,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
        ckpt_dir=args.ckpt_dir,
        compress_grads=args.compress,
    )


if __name__ == "__main__":
    main()
