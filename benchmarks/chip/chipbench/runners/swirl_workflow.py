"""Runner for a SWIRL workflow on the ``jax`` backend.

Set-up makes the configuration's data on the device from the seed, traces
the DAG (``swirl.trace(inst).optimize()``), lowers it to ``jax`` and
compiles the configuration's step bodies once; two instances then warm up
every program.  The window runs instances one at a time, back to back, on
the caller-held inputs (a closed loop with one client), each ending in
``block_until_ready`` on its outputs.  One instance drawn from the seed
among the first ``SAMPLE_EVERY`` copies its outputs to the host, and the
window's last keeps them on the device; the reference checks both after
the window.  So the device holds the same outputs whatever the window's
length, and its peak is the workflow's own.
"""

from __future__ import annotations

import gc
import time

import jax

from chipbench.harness import Cell, Outcome, measure, p95, peak_bytes

SAMPLE_EVERY = 16  # the checked instance is one of the window's first 16


def with_sink_outputs(inst, out_name):
    """``inst`` with one output port on every step that produces no data."""
    from repro.core.graph import DistributedWorkflowInstance, make_workflow

    wf = inst.workflow
    ports, deps = set(wf.ports), set(wf.deps)
    data, placement = set(inst.data), dict(inst.placement)
    for step in sorted(wf.steps):
        if not inst.out_data(step) and step != "s0":
            port, datum = f"p^out_{step}", out_name(step)
            ports.add(port)
            deps.add((step, port))
            data.add(datum)
            placement[datum] = port
    return DistributedWorkflowInstance(
        workflow=make_workflow(wf.steps, ports, deps),
        locations=inst.locations,
        mapping=inst.mapping,
        data=frozenset(data),
        placement=placement,
        initial_data=inst.initial_data,
    )


def build(cell: Cell):
    """(plan instance, lowered plan) for the cell's DAG and placement."""
    from repro import swirl
    from repro.core import translate

    dag = dict(cell.config["dag"])
    inst = with_sink_outputs(getattr(translate, dag.pop("make"))(**dag), cell.ref.out_name)
    placement = cell.traffic["placement"]
    if placement == "one_device":
        devices = cell.devices[:1]
    elif placement == "round_robin":
        devices = cell.devices
    else:
        raise ValueError(f"unknown placement {placement!r}")
    plan = swirl.trace(inst).optimize()
    return inst, plan.lower("jax", fuse=cell.traffic["fuse"], devices=devices)


def run(cell: Cell, *, trace_dir: str | None) -> Outcome:
    cfg, ref, log = cell.config, cell.ref, cell.log
    inst, lowered = build(cell)
    (source_loc,) = [loc for loc, ds in inst.initial_data.items() if ds]
    sinks = {
        ref.out_name(s): inst.mapping[s][0]
        for s in inst.workflow.steps if ref.out_name(s) in inst.data
    }
    data = ref.make_data(cfg, cell.seed)
    log(f"data made: peak {peak_bytes(cell.devices)} bytes")
    current: dict = {}
    exe = lowered.compile(ref.steps(cfg, lambda: current["inputs"]))
    costs = ref.step_costs(cfg)
    counters = {"comms": 0, "execs": 0, "fused_execs": 0}
    kept: list[tuple[int, int, dict]] = []  # (instance, variant, outputs)
    last: list = [None]
    sample = cell.seed % SAMPLE_EVERY

    def instance(i: int, *, count: bool = True) -> None:
        v = i % cfg["variants"]
        current["inputs"] = ref.initial_payloads(cfg, data, v)
        res = exe.run(initial_payloads={
            (source_loc, d): x for d, x in current["inputs"].items()
        })
        outs = {d: res.data[loc][d] for d, loc in sinks.items()}
        jax.block_until_ready(outs)
        if count:
            counters["comms"] += res.stats["comms"]
            counters["execs"] += res.stats["execs"]
            counters["fused_execs"] += res.stats.get("fused", {}).get("fused_execs", 0)
            last[0] = (i, v, outs)
            if i == sample:
                kept.append((i, v, jax.device_get(outs)))

    for w in range(2):  # compiles every step body and the fused segments
        instance(w, count=False)
    gc.collect()
    log(f"warm: peak {peak_bytes(cell.devices)} bytes")
    t0, durations, window = measure(
        cell.seconds, instance, trace_dir=trace_dir, span="wf.instance", log=log,
    )
    memory = peak_bytes(cell.devices)
    n = len(durations)
    if not kept or kept[-1][0] != n - 1:
        kept.append(last[0])  # the window's last instance is checked too
    last.clear()
    log(f"window: {n} instances in {window!r} s, peak {memory} bytes, "
        f"{counters['comms']} comms, checking instances {[k for k, _, _ in kept]}")
    del exe
    current.clear()
    gc.collect()

    t_ref = time.perf_counter()
    gaps: dict[str, float] = {}
    failed = 0
    checked = 0
    for i, v, outs in kept:
        mine = ref.compare(cfg, outs, ref.reference(cfg, data, v))
        checked += 1
        failed += any(not (g <= cfg["limits"][k]) for k, g in mine.items())
        for k, g in mine.items():
            gaps[k] = max(gaps.get(k, 0.0), g) if g == g else g
    log(f"reference: {checked} instances in {time.perf_counter() - t_ref!r} s")
    if not checked:
        gaps = {k: float("nan") for k in cfg["limits"]}
    return Outcome(
        window_start=t0,
        e2e={
            "wf_makespan_ms": 1e3 * window / n,
            "wf_makespan_p95_ms": 1e3 * p95(durations) if n > 1 else 1e3 * durations[0],
        },
        units=n,
        memory_peak_bytes=memory,
        checks={k: (gaps[k], cfg["limits"][k]) for k in cfg["limits"]},
        counters={k: float(v) for k, v in counters.items()},
        cost={"step_costs": [costs[s] for s in sorted(inst.workflow.steps)]},
        attempted=n,
        failed=failed,
    )

