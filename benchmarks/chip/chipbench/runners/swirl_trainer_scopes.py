"""Runner for the SWIRL-planned trainer that also times the model's named
scopes on the device.

It drives :class:`swirl_trainer.Trainer` (``train()``'s body) as the
``swirl_trainer`` runner does, and checks the first steps against the
configuration's reference with the same :func:`swirl_trainer.compare`.
Beside that:

* ``flops_per_token`` comes from :mod:`chipbench.train_counts`: windowed
  keys, the causal mean, the held share of the expert choices and the
  vocabulary slice;
* the window keeps each iteration's MoE counters, ``moe_held_share`` and
  ``moe_load_max``, which the trainer reads with the loss;
* a traced run, once the window is over, takes the grad step's compiled
  HLO, maps its instructions to the model's named scopes
  (:mod:`chipbench.scopes`), and puts each scope's device seconds per
  step, their share of the step's, and the executed work the roofline
  shares divide by into ``Outcome.counters``.
"""

from __future__ import annotations

import collections
import gc
import statistics
import time
from typing import Any

import jax

from chipbench import scopes, train_counts, xtrace
from chipbench.harness import Cell, Outcome, measure, peak_bytes
from chipbench.runners.swirl_trainer import (
    CHECKED_STEPS,
    Trainer,
    _change_norms,
    _leaf_norms,
    compare,
    named,
    still_leaves,
)

GRAD_MODULE = "jit_grad_step"
COUNTERS = ("moe_held_share", "moe_load_max")


class CompileClock:
    """Seconds jax spends in each compile phase, from ``jax.monitoring``'s
    duration events, and the compile cache's hits; one listener for the
    process, read as the difference of two readings."""

    PHASES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "compile",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
    }
    _totals: collections.Counter = collections.Counter()
    _listening = False

    @classmethod
    def read(cls) -> collections.Counter:
        if not cls._listening:
            from jax import monitoring

            def on_duration(name: str, secs: float, **_: Any) -> None:
                if name in cls.PHASES:
                    cls._totals[cls.PHASES[name]] += secs

            def on_event(name: str, **_: Any) -> None:
                if name == "/jax/compilation_cache/cache_hits":
                    cls._totals["cache_hits"] += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
            cls._listening = True
        return collections.Counter(cls._totals)


def scope_counters(
    m: dict, job: dict, times: scopes.ScopeTimes, held_share: float,
) -> dict[str, float]:
    """Per grad step: each scope's device seconds, their share of the step's,
    the windowed layer's time over the full layer's (per layer), and the
    work executed in attention and in the held experts."""
    if times.runs == 0:
        return {}
    out = {f"scope_s.{s}": v / times.runs for s, v in times.scope_s.items()}
    out["grad_step_s"] = times.module_s / times.runs
    out["scope_share"] = sum(times.scope_s.values()) / times.module_s
    kinds = [mixer for mixer, _ in train_counts.layers(m)]
    n_window, n_full = kinds.count("attn_local"), kinds.count("attn")
    if n_window and n_full and out["scope_s.attn.full"] > 0:
        out["attn.window_over_full"] = (
            (out["scope_s.attn.window"] / n_window) / (out["scope_s.attn.full"] / n_full)
        )
    rows = job["global_batch"] // job["n_pods"]
    flops = bytes_ = 0.0
    for kind in ("attn_local", "attn"):
        f, b = train_counts.attention_work(m, rows, job["seq_len"], kind)
        flops, bytes_ = flops + f, bytes_ + b
    out["attn.flops"], out["attn.bytes"] = flops, bytes_
    choices = held_share * rows * job["seq_len"] * m["moe"]["top_k"] * len(kinds)
    out["experts.flops"], out["experts.bytes"] = train_counts.expert_work(m, choices)
    return out


def run(cell: Cell, *, trace_dir: str | None) -> Outcome:
    job, m = cell.traffic, cell.config["model"]
    log = cell.log
    jax0, t = CompileClock.read(), time.perf_counter()
    parts: dict[str, float] = {}
    trainer = Trainer(cell)
    parts["trainer"] = time.perf_counter() - t
    b1 = trainer.opt_cfg.b1
    readings: dict[str, Any] = {"losses": []}
    for k in range(CHECKED_STEPS):
        t = time.perf_counter()
        metrics = trainer.step()
        readings["losses"].append(float(metrics["loss"]))
        parts[f"step{k}"] = time.perf_counter() - t
        if k == 0:
            readings["grad_norms"] = {
                name: v / (1 - b1) for name, v in named(_leaf_norms(trainer.opt.m)).items()
            }
        log(f"set-up step {k}: loss {readings['losses'][-1]!r}, "
            + ", ".join(f"{c} {metrics[c]!r}" for c in COUNTERS))
    t = time.perf_counter()
    p0 = trainer.init(cell.ref.seed_key_data(cell.seed))
    readings["change_norms"] = named(_change_norms(trainer.params, p0))
    del p0
    parts["change_norms"] = time.perf_counter() - t
    hlo = None
    if trace_dir is not None:
        t = time.perf_counter()
        batch = trainer.dataset.batch(0, shard=0, n_shards=job["n_pods"])
        hlo = trainer.grad_fn.lower(trainer.params, batch).compile().as_text()
        parts["traced_compile"] = time.perf_counter() - t
    jax.block_until_ready(trainer.params)
    jax1 = CompileClock.read()
    parts.update({f"jax.{k}": jax1[k] - jax0[k] for k in (*CompileClock.PHASES.values(), "cache_hits")})
    log(f"set-up parts (s): {parts!r}")
    log(f"set-up done: peak {peak_bytes(cell.devices)} bytes, {trainer.retries} retries")
    trainer.compile_s.clear()

    seen: dict[str, list[float]] = {c: [] for c in COUNTERS}

    def unit(i: int) -> None:
        metrics = trainer.step()
        jax.block_until_ready(trainer.params)
        for c in COUNTERS:
            seen[c].append(metrics[c])

    t0, durations, window = measure(
        cell.seconds, unit, trace_dir=trace_dir, span="train.step", log=log,
    )
    memory = peak_bytes(cell.devices)
    iters = len(durations)
    tokens = iters * job["global_batch"] * job["seq_len"]
    log(f"window: {iters} iterations in {window!r} s, peak {memory} bytes, "
        f"{trainer.retries} retries")
    compile_ms = 1e3 * statistics.mean(trainer.compile_s)
    counters = {c: statistics.mean(v) for c, v in seen.items()}
    del trainer, metrics
    gc.collect()

    if trace_dir is not None:
        events = xtrace.read_events(xtrace.find_xplane(trace_dir), frozenset())
        times = scopes.scope_seconds(events, scopes.op_scopes(hlo), GRAD_MODULE)
        counters.update(scope_counters(m, job, times, counters["moe_held_share"]))
        log(f"scopes over {times.runs} grad steps: {counters!r}")
        del events

    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = cell.ref.reference_readings(m, job, cell.seed, steps=CHECKED_STEPS)
    log(f"reference: {time.perf_counter() - t_ref!r} s, losses {ref['losses']!r}, "
        f"program {readings['losses']!r}")
    gaps = compare(readings, ref)
    log(f"gaps {gaps!r}; leaves left out of the change: {still_leaves(ref['grad_norms'])}")
    return Outcome(
        window_start=t0,
        e2e={"train_tokens_per_s": tokens / window},
        units=iters,
        memory_peak_bytes=memory,
        checks={k: (gaps[k], limit) for k, limit in cell.config["limits"].items()},
        counters=counters,
        host={"plan_compile_ms": compile_ms},
        cost={"flops_per_token": train_counts.model_flops_per_token(m, job["seq_len"])},
        attempted=iters,
    )
