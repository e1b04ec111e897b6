"""Runner for the SWIRL-planned trainer (``repro.launch.train``).

Set-up builds one :class:`Trainer`: the plan, the jitted grad step and
AdamW update, and the weights made on the device from the seed.  It drives
that object through the configuration's first steps, which compile every
program and give the readings the reference checks; the window then runs
the same object's iterations back to back (a closed loop).  Each iteration
is the body of ``train()``: ``build_step_fns``, then
``lowered.compile(fns).run(...)``.  ``train()`` itself takes no seed and
has no per-step hook, so the benchmark drives its body.
"""

from __future__ import annotations

import gc
import statistics
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from chipbench.flops import lm_train_flops_per_token
from chipbench.harness import Cell, Outcome, measure, peak_bytes

CHECKED_STEPS = 3
# Leaves whose reference gradient is under this share of the median
# leaf's move under AdamW by round-off alone; their change is not compared.
STILL_LEAF = 1e-3


def model_config(config: dict):
    from repro.models import ModelConfig, MoECfg

    m = dict(config["model"])
    m["pattern"] = tuple(tuple(k) for k in m["pattern"])
    m["moe"] = MoECfg(**m["moe"])
    return ModelConfig(name=config["name"], **m)


def check_layout(want: Any, have: Any) -> None:
    if jax.tree.structure(want) != jax.tree.structure(have):
        raise RuntimeError(
            f"parameter tree differs from the program's:\n{jax.tree.structure(want)}\n"
            f"{jax.tree.structure(have)}"
        )
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise RuntimeError(f"parameter {a} differs from the program's {b}")


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _change_norms(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))),
        a, b,
    )


def named(tree) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


class Trainer:
    """The trainer's per-iteration body as ``train()`` runs it."""

    def __init__(self, cell: Cell):
        from repro import swirl
        from repro.core.translate import TrainPipelineTranslator
        from repro.data import SyntheticLM
        from repro.launch.steps import make_grad_step
        from repro.models import Model
        from repro.optim import AdamWConfig
        from repro.optim import adamw
        from repro.workflow import RetryPolicy

        job, m = cell.traffic, cell.config["model"]
        self.job = job
        model = Model(model_config(cell.config))
        check_layout(cell.ref.param_layout(m), jax.eval_shape(model.init, jax.random.key(0)))
        self.dataset = SyntheticLM(
            vocab=m["vocab"], seq_len=job["seq_len"],
            global_batch=job["global_batch"], seed=cell.seed,
        )
        self.opt_cfg = AdamWConfig(**job["optimizer"])
        plan = swirl.trace(
            TrainPipelineTranslator(n_pods=job["n_pods"], with_checkpoint=False)
        ).optimize(rules=("R1R2", "R3"))
        self.lowered = plan.lower("inprocess", retry=RetryPolicy(max_retries=job["max_retries"]))
        self.grad_fn = jax.jit(make_grad_step(model))
        self.update_fn = jax.jit(partial(adamw.update, self.opt_cfg))
        self.init = jax.jit(partial(cell.ref.init_params, m))
        self.params = self.init(cell.ref.seed_key_data(cell.seed))
        self.opt = adamw.init(self.params)
        self.err: dict = {}
        self.it = 0
        self.retries = 0
        self.compile_s: list[float] = []

    def step(self) -> dict:
        from repro.launch.train import build_step_fns

        n = self.job["n_pods"]
        fns, self.err = build_step_fns(
            self.grad_fn, self.update_fn, self.dataset, n,
            compress_grads=self.job["compress_grads"], error_feedback=self.err,
            ckpt_dir=None,
        )
        payloads = {}
        for i in range(n):
            payloads[(f"pod{i}", f"iter_{i}")] = self.it
            payloads[(f"pod{i}", f"params_{i}")] = self.params
            payloads[(f"pod{i}", f"opt_{i}")] = self.opt
        with jax.profiler.TraceAnnotation("train.plan_compile"):
            t0 = time.perf_counter()
            exe = self.lowered.compile(fns)
            self.compile_s.append(time.perf_counter() - t0)
        with jax.profiler.TraceAnnotation("train.run"):
            result = exe.run(initial_payloads=payloads)
        self.retries += result.stats.retries
        state = result.payload("pod0", "state_0")
        del result, payloads
        self.params, self.opt = state["params"], state["opt"]
        self.it += 1
        return state["metrics"]


def still_leaves(grad_norms: dict[str, float], *, keep: bool = False) -> list[str]:
    """Leaves whose reference gradient is under ``STILL_LEAF`` of the median
    leaf's (or, with ``keep``, every other leaf)."""
    cut = STILL_LEAF * statistics.median(grad_norms.values())
    return [k for k, v in grad_norms.items() if (v >= cut) == keep]


def compare(prog: dict, ref: dict) -> dict[str, float]:
    """Worst gaps of the program's readings from the reference's."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))

    def worst(p: dict, r: dict, keys) -> float:
        med = statistics.median(r[k] for k in keys)
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keys)

    g_ref = ref["grad_norms"]
    moving = still_leaves(g_ref, keep=True)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": worst(prog["grad_norms"], g_ref, list(g_ref)),
        "param_change_gap": worst(prog["change_norms"], ref["change_norms"], moving),
    }


def run(cell: Cell, *, trace_dir: str | None) -> Outcome:
    job, m = cell.traffic, cell.config["model"]
    log = cell.log
    trainer = Trainer(cell)
    b1 = trainer.opt_cfg.b1
    readings: dict[str, Any] = {"losses": []}
    for k in range(CHECKED_STEPS):
        metrics = trainer.step()
        readings["losses"].append(float(metrics["loss"]))
        if k == 0:
            # The gradient AdamW received: its first moment is (1 - b1) g.
            readings["grad_norms"] = {
                name: v / (1 - b1) for name, v in named(_leaf_norms(trainer.opt.m)).items()
            }
        log(f"set-up step {k}: loss {readings['losses'][-1]!r}")
    p0 = trainer.init(cell.ref.seed_key_data(cell.seed))
    readings["change_norms"] = named(_change_norms(trainer.params, p0))
    del p0
    jax.block_until_ready(trainer.params)
    log(f"set-up done: peak {peak_bytes(cell.devices)} bytes, "
        f"{trainer.retries} retries")
    trainer.compile_s.clear()

    t0, durations, window = measure(
        cell.seconds, lambda i: (trainer.step(), jax.block_until_ready(trainer.params)),
        trace_dir=trace_dir, span="train.step", log=log,
    )
    memory = peak_bytes(cell.devices)
    iters = len(durations)
    tokens = iters * job["global_batch"] * job["seq_len"]
    log(f"window: {iters} iterations in {window!r} s, peak {memory} bytes, "
        f"{trainer.retries} retries")
    compile_ms = 1e3 * statistics.mean(trainer.compile_s)
    del trainer, metrics
    gc.collect()

    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = cell.ref.reference_readings(m, job, cell.seed, steps=CHECKED_STEPS)
    log(f"reference: {time.perf_counter() - t_ref!r} s, losses {ref['losses']!r}, "
        f"program {readings['losses']!r}")
    gaps = compare(readings, ref)
    log(f"gaps {gaps!r}; leaves left out of the change: {still_leaves(ref['grad_norms'])}")
    limits = cell.config["limits"]
    return Outcome(
        window_start=t0,
        e2e={"train_tokens_per_s": tokens / window},
        units=iters,
        memory_peak_bytes=memory,
        checks={k: (gaps[k], limit) for k, limit in limits.items()},
        host={"plan_compile_ms": compile_ms},
        cost={"flops_per_token": lm_train_flops_per_token(m, job["seq_len"])},
        attempted=iters,
    )

