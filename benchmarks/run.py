"""Benchmark harness — one section per paper table/figure + system benches.

Prints ``name,value,unit,derived`` CSV rows.  Sections:

* ``encoding``  — ⟦·⟧ encoding time vs workflow size (§3.2);
* ``optimise``  — rewriting time + removed comms vs (m, b) — the Appendix-B
  broadcast-collapse numbers (the paper's only quantitative claim);
* ``runtime``   — 1000 Genomes end-to-end on the decentralised runtime,
  optimised vs unoptimised plan (§6 experiment analogue: 10 locations,
  one chromosome/instance);
* ``dist``      — 1000 Genomes wall-clock, threaded vs the multiprocess
  backend (real OS processes over the ack-based socket transport);
* ``dataplane`` — data-plane raw speed (hard-gated): a 3-consumer scatter
  pump of 64k-float payloads across the seed socket framing vs pickle-5
  out-of-band vs shared-memory vs hybrid (shm must be ≥5x the seed
  framing, zero checksum mismatches), plus fused jitted JAX location
  programs vs the op-by-op interpreter on a 12-step Pallas-rmsnorm
  pipeline (≥3x, allclose outputs, roofline fraction);
* ``sched``     — cost-model-driven placement (repro.sched) vs round-robin
  on the 1000 Genomes workflow under the two-rack network preset;
* ``compile``   — compilation pipeline at scale: encode+R1R2+R3 wall-clock
  on random layered DAGs at 100/1k/2k/10k steps, recursive tree engine vs
  the flat indexed IR, plus ``auto_placement`` on a 500-step DAG (the
  incremental placement scorer);
* ``serve``     — compile-once/run-many serving throughput: 100 workflow
  instances over one lowered program (``Executable.run_many``, shared
  transport) vs the naive per-instance trace→lower→compile→run loop;
* ``gateway``   — workflow-as-a-service over HTTP (repro.serve): sustained
  cache-hit throughput across mixed plan shapes from concurrent keep-alive
  clients (p50/p99 + hit rate), plus an overload run (429s counted, zero
  dropped in-flight executions);
* ``chaos``     — elastic recovery under chaos: run_many throughput and
  result-correctness on the multiprocess backend while every instance's
  worker is SIGKILLed mid-flight and recovered onto a spare (rename) or a
  survivor (fold / pool resize); plus straggler mitigation (a delayed
  worker declared dead by the FaultPolicy heartbeat, spare vs fold vs
  no-policy makespan) and a whole-run deadline abort;
* ``bisim``     — LTS sizes + exact bisimulation check time (Thm. 1);
* ``kernels``   — Pallas kernels (interpret mode) vs jnp references;
* ``train``     — SWIRL-planned trainer steps/s (smoke config);
* ``roofline``  — re-prints the dry-run roofline summary if present.

Usage: ``PYTHONPATH=src python -m benchmarks.run [section ...] [--json]``

``--json`` additionally writes one ``BENCH_<section>.json`` per section —
the CSV rows as a JSON list plus run metadata — so the perf trajectory is
machine-trackable across PRs (CI uploads them as workflow artifacts).
"""

from __future__ import annotations

import glob as _glob
import json
import platform
import sys
import time
from pathlib import Path

import numpy as np

#: Rows of the section currently running (for --json); see main().
_ROWS: list[dict[str, str]] = []


def _t(fn, *args, repeat=3, **kw):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return best, out


def row(name: str, value, unit: str, derived: str = "") -> None:
    print(f"{name},{value},{unit},{derived}")
    _ROWS.append(
        {"name": name, "value": str(value), "unit": unit, "derived": derived}
    )


# ---------------------------------------------------------------------------


def bench_encoding() -> None:
    from repro.core import encode
    from repro.core.translate import genomes_1000

    for n, m in [(4, 3), (16, 8), (64, 32), (256, 128)]:
        inst = genomes_1000(n=n, m=m, a=4, b=4, c=4)
        dt, w = _t(encode, inst)
        row(
            f"encoding/genomes_n{n}_m{m}", f"{dt * 1e6:.0f}", "us",
            f"actions={w.total_actions()}",
        )


def bench_optimise() -> None:
    from repro.core import encode, rewrite_system
    from repro.core.translate import genomes_1000

    for m, b in [(2, 2), (8, 2), (32, 2), (32, 8)]:
        inst = genomes_1000(n=8, m=m, a=2, b=b, c=b)
        w = encode(inst)
        dt, (o, stats) = _t(rewrite_system, w)
        row(
            f"optimise/m{m}_b{b}", f"{dt * 1e6:.0f}", "us",
            f"comms {w.comm_count()}->{o.comm_count()} removed={stats.removed}",
        )


def bench_runtime() -> None:
    from repro import swirl
    from repro.core.translate import genomes_1000

    # 10 locations, single instance — the paper's experiment scale.
    inst = genomes_1000(n=4, m=3, a=2, b=2, c=2)
    rng = np.random.default_rng(0)
    init = {("l^d", d): rng.random(65536) for d in inst.g("l^d")}

    def fns():
        out = {}
        for s in inst.workflow.steps:
            outs = inst.out_data(s)
            if s == "s0":
                out[s] = lambda i, outs=outs: {o: init[("l^d", o)] for o in outs}
            else:
                out[s] = lambda i, outs=outs: {
                    o: sum(np.sum(np.asarray(v)) for v in i.values()) * np.ones(65536)
                    for o in outs
                }
        return out

    raw = swirl.trace(inst)
    for label, plan in [
        ("unoptimised", raw),
        ("optimised", raw.optimize()),
    ]:
        lowered = plan.lower("threaded", timeout_s=60)

        def drive(lowered=lowered):
            return lowered.compile(fns()).run(initial_payloads=dict(init))

        dt, result = _t(drive, repeat=2)
        sent = result.stats["sent"]
        row(
            f"runtime/genomes_{label}", f"{dt * 1e3:.1f}", "ms",
            f"messages={sent} comms_planned={plan.system.comm_count()}",
        )


def bench_dist() -> None:
    """Threaded (one process, queues) vs multiprocess (real OS processes,
    ack-based sockets) wall-clock on the 1000 Genomes workflow."""
    from repro import swirl
    from repro.core.translate import genomes_1000

    inst = genomes_1000(n=4, m=3, a=2, b=2, c=2)
    rng = np.random.default_rng(0)
    init = {("l^d", d): rng.random(65536) for d in inst.g("l^d")}

    def fns():
        out = {}
        for s in inst.workflow.steps:
            outs = inst.out_data(s)
            if s == "s0":
                out[s] = lambda i, outs=outs: {o: init[("l^d", o)] for o in outs}
            else:
                out[s] = lambda i, outs=outs: {
                    o: sum(np.sum(np.asarray(v)) for v in i.values())
                    * np.ones(65536)
                    for o in outs
                }
        return out

    plan = swirl.trace(inst).optimize()
    n_locs = len(inst.locations)
    cases = [
        ("threaded", {"timeout_s": 120}, "in-process threads"),
        ("multiprocess", {"timeout_s": 240}, f"{n_locs} worker processes"),
        (
            "multiprocess",
            {"timeout_s": 240, "workers": 2},
            "packed onto 2 worker processes",
        ),
    ]
    for backend, options, label in cases:
        lowered = plan.lower(backend, **options)

        def drive(lowered=lowered):
            return lowered.compile(fns()).run(initial_payloads=dict(init))

        dt, result = _t(drive, repeat=2)
        workers = (
            result.stats.get("workers", 1)
            if isinstance(result.stats, dict)
            else 1
        )
        name = backend
        if "workers" in options:
            name += f"_w{options['workers']}"
        row(
            f"dist/genomes_{name}", f"{dt * 1e3:.1f}", "ms",
            f"{label}; locations={n_locs} workers={workers}",
        )


def bench_dataplane() -> None:
    """Data-plane raw speed: zero-copy transports + fused JAX programs.

    Two experiments, both hard-gated (asserts, not just rows):

    * *pump* — a genomes-shaped scatter pump: one source process fans
      bursts of 64k-float payloads out to 3 consumer processes which
      checksum and release each message (streaming consumption, so the
      shm arenas recycle).  Four arms over identical payload streams:
      the seed-era socket framing (inline pickle, per-message acks), the
      current pickle-5 out-of-band socket framing, the shared-memory
      transport, and a hybrid route over shm.  Acceptance: shm ≥ 5x the
      seed framing per send, zero checksum mismatches across arms.
    * *fused* — a 12-step single-location pipeline on the JAX backend
      (Pallas rmsnorm every 4th step, tanh-mix elementwise between),
      op-by-op interpreter vs ``fuse=True`` (straight-line EXEC runs
      compiled into one donated-buffer jit per segment).
      Acceptance: fused ≥ 3x, outputs allclose (float32 jit-fusion
      reassociation drift is ~1 ULP), roofline fraction reported.
    """
    import multiprocessing as mp
    import tempfile

    from repro.workflow.transport import (
        HybridTransport,
        SharedMemoryTransport,
        SocketTransport,
        shm_namespace,
        socket_addresses,
    )

    class ClassicSocketTransport(SocketTransport):
        """The seed-era framing: inline pickle, one ack per message."""

        name = "classic"

        def _send_frame(self, conn, frame):
            conn.send(frame)

        @staticmethod
        def _recv_frame(conn):
            return conn.recv()

        def send_many(self, endpoint, items):
            for data_name, payload in items:
                self.send(endpoint, data_name, payload)

        def scatter(self, sends):
            for endpoint, items in sends:
                self.send_many(endpoint, items)

    NDEST, BURST, WARM, NBURST = 3, 8, 3, 30
    AUTHKEY = b"bench-dataplane"
    DESTS = [f"w{i}" for i in range(NDEST)]
    kw = dict(authkey=AUTHKEY, ack_timeout=5.0, connect_timeout=30.0)

    def make(kind, addrs, serve):
        if kind == "classic":
            return ClassicSocketTransport(addrs, serve=serve, **kw)
        if kind == "socket":
            return SocketTransport(addrs, serve=serve, **kw)
        remote = SharedMemoryTransport(addrs, serve=serve, **kw)
        if kind == "hybrid":
            return HybridTransport(remote, serve)
        return remote

    def child(kind, addrs, me, n_msgs, out_q):
        t = make(kind, addrs, (me,))
        ep = ("src", me, "p")
        checksum = 0.0
        for _ in range(n_msgs):
            arr = t.recv(ep, timeout=60.0).payload
            checksum += float(arr[0]) + float(arr[-1])
            del arr  # consume-and-release: lets the sender recycle arenas
        out_q.put((me, checksum))
        t.close()

    from repro.backends.multiprocess import (
        AcceleratorHeldError,
        held_accelerator,
    )

    if (held := held_accelerator()) is not None:
        raise AcceleratorHeldError(
            f"the dataplane pump forks its consumers, and this process "
            f"holds a {held} device; run the section on its own"
        )
    ctx = mp.get_context("fork")

    def pump(kind):
        tmp = tempfile.mkdtemp(prefix=f"swirl-dp-{kind}-")
        addrs = socket_addresses(["src"] + DESTS, base_dir=tmp)
        q = ctx.SimpleQueue()
        n_msgs = (WARM + NBURST) * BURST
        procs = [
            ctx.Process(
                target=child, args=(kind, addrs, d, n_msgs, q), daemon=True
            )
            for d in DESTS
        ]
        for p in procs:
            p.start()
        t = make(kind, addrs, ("src",))
        rng = np.random.default_rng(0)
        timed, expect = 0.0, 0.0
        try:
            for b in range(WARM + NBURST):
                arrs = [rng.random(65536) for _ in range(BURST)]
                sends = [
                    (
                        ("src", d, "p"),
                        [(f"b{b}x{i}", a) for i, a in enumerate(arrs)],
                    )
                    for d in DESTS
                ]
                t0 = time.perf_counter()
                t.scatter(sends)
                if b >= WARM:
                    timed += time.perf_counter() - t0
                expect += sum(float(a[0]) + float(a[-1]) for a in arrs)
            sums = dict(q.get() for _ in DESTS)
            for p in procs:
                p.join(30.0)
            stats = t.stats()
        finally:
            t.close()
        mismatches = sum(
            1
            for d in DESTS
            if abs(sums[d] - expect) > 1e-9 * max(abs(expect), 1.0)
        )
        per_send = timed / (NBURST * BURST * NDEST)
        return per_send, mismatches, stats

    per_send: dict[str, float] = {}
    mismatch_total = 0
    for kind in ("classic", "socket", "shm", "hybrid"):
        best, detail = float("inf"), ""
        for _ in range(3):
            dt, mis, stats = pump(kind)
            mismatch_total += mis
            if dt < best:
                best = dt
                inner = stats.get("remote", stats)
                if "segments_created" in inner:
                    detail = (
                        f"arenas created={inner['segments_created']} "
                        f"reused={inner['segments_reused']} "
                        f"dedup={inner['dedup_sends']}"
                    )
        per_send[kind] = best
        row(
            f"dataplane/pump_{kind}_per_send",
            f"{best * 1e6:.1f}", "us",
            detail
            or f"{NDEST} consumers x {NBURST} bursts x {BURST} x 512KB",
        )
    speedup = per_send["classic"] / per_send["shm"]
    row(
        "dataplane/pump_shm_speedup", f"{speedup:.2f}", "x",
        "shm vs seed socket framing — target >= 5x (acceptance)",
    )
    row(
        "dataplane/pump_mismatches", mismatch_total, "checksums",
        f"{NDEST} consumers x 4 transports x 3 runs (must be 0)",
    )
    assert mismatch_total == 0, "transport arms disagreed on payloads"
    assert speedup >= 5.0, f"shm speedup {speedup:.2f}x < 5x floor"
    leaked = _glob.glob(f"/dev/shm/{shm_namespace(AUTHKEY)}-*")
    row("dataplane/pump_shm_leaked", len(leaked), "segments", "(must be 0)")
    assert not leaked, f"leaked shm segments: {leaked}"

    # -- fused jitted location programs --------------------------------------
    import jax.numpy as jnp

    from repro import swirl
    from repro.core.graph import DistributedWorkflowInstance, make_workflow
    from repro.kernels.ops import rmsnorm

    n_steps, shape = 12, (64, 256)
    steps = [f"s{i}" for i in range(1, n_steps + 1)]
    ports = [f"p{i}" for i in range(n_steps + 1)]
    deps = []
    for i, s in enumerate(steps):
        deps += [(f"p{i}", s), (s, f"p{i + 1}")]
    inst = DistributedWorkflowInstance(
        workflow=make_workflow(steps, ports, deps),
        locations=frozenset({"l0"}),
        mapping={s: ("l0",) for s in steps},
        data=frozenset(f"d{i}" for i in range(n_steps + 1)),
        placement={f"d{i}": f"p{i}" for i in range(n_steps + 1)},
        initial_data={"l0": frozenset({"d0"})},
    )
    W = jnp.ones((shape[1],), jnp.float32)

    def norm(v):
        return rmsnorm(v, W)

    def mix(v):
        # Contraction (Lipschitz < 1): fused-vs-eager 1-ULP drift cannot
        # compound down the chain past the allclose gate.
        return 0.5 * v + 0.1 * jnp.tanh(v)

    fns = {
        s: (
            lambda i, a=f"d{k}", b=f"d{k + 1}",
            f=(norm if k % 4 == 0 else mix): {b: f(i[a])}
        )
        for k, s in enumerate(steps)
    }
    x = jnp.asarray(
        np.random.default_rng(7).standard_normal(shape), jnp.float32
    )
    init = {("l0", "d0"): x}
    plan = swirl.trace(inst).optimize()
    interp = plan.lower("jax").compile(fns)
    fused = plan.lower("jax", fuse=True).compile(fns)
    res_i = interp.run(initial_payloads=dict(init))  # warm (traces jits)
    res_f = fused.run(initial_payloads=dict(init))
    mism = sum(
        0
        if np.allclose(
            np.asarray(res_i.data[l][d]), np.asarray(res_f.data[l][d]),
            rtol=1e-5, atol=1e-6,
        )
        else 1
        for l in res_i.data
        for d in res_i.data[l]
    )
    dt_i, _ = _t(
        lambda: interp.run(initial_payloads=dict(init)), repeat=7
    )
    dt_f, res_f = _t(
        lambda: fused.run(initial_payloads=dict(init)), repeat=7
    )
    fstats = res_f.stats["fused"]
    row(
        "dataplane/fused_interp", f"{dt_i * 1e3:.2f}", "ms",
        f"{n_steps}-step pallas-rmsnorm+tanh pipeline {shape}, op-by-op",
    )
    row(
        "dataplane/fused_jit", f"{dt_f * 1e3:.2f}", "ms",
        f"segments={fstats['fused_calls']} "
        f"execs_fused={fstats['fused_execs']}/{n_steps}",
    )
    fspeed = dt_i / dt_f
    row(
        "dataplane/fused_speedup", f"{fspeed:.2f}", "x",
        "fused jit vs op-by-op interpreter — target >= 3x (acceptance)",
    )
    row(
        "dataplane/fused_mismatches", mism, "arrays",
        "allclose rtol=1e-5 atol=1e-6 (must be 0)",
    )
    assert mism == 0, "fused and interpreted runs diverged"
    assert fspeed >= 3.0, f"fused speedup {fspeed:.2f}x < 3x floor"


def bench_sched() -> None:
    from repro import swirl
    from repro.core.translate import genomes_1000
    from repro.sched import CostModel, NetworkModel, SizeModel

    # Same payload scale as the runtime section (64k-float arrays).
    inst = genomes_1000(n=8, m=6, a=2, b=2, c=2)
    network = NetworkModel.preset("two-rack")
    sizes = SizeModel(default_bytes=8 * 65536)
    costs = CostModel(default_exec_s=2e-3)
    plan = swirl.trace(inst).optimize()

    for objective in ("makespan", "bytes"):
        dt, sched = _t(
            lambda: plan.schedule(
                network, objective=objective, sizes=sizes, costs=costs
            ),
            repeat=1,
        )
        r = sched.schedule_report
        row(
            f"sched/genomes_{objective}_search", f"{dt * 1e3:.0f}", "ms",
            f"steps={len(r.placement)} locations={len(inst.locations)}",
        )
        row(
            f"sched/genomes_{objective}_bytes",
            r.predicted.cross_bytes, "bytes",
            f"round_robin={r.baseline.cross_bytes} "
            f"saved={r.bytes_saved_frac * 100:.0f}%",
        )
        row(
            f"sched/genomes_{objective}_makespan",
            f"{r.predicted.makespan * 1e3:.2f}", "ms",
            f"round_robin={r.baseline.makespan * 1e3:.2f}ms "
            f"speedup={r.makespan_speedup:.2f}x",
        )


def bench_compile() -> None:
    """Compilation at 10k-step scale: tree engine vs flat indexed IR.

    The DAG family is collective-heavy (40% of steps are two-location
    spatial constraints, the multi-pod-trainer profile) so rule R3 — whose
    tree implementation rebuilds the trace per removed action — has real
    work to do.  The tree pipeline is ``encode`` + the recursive reference
    engines; the flat pipeline is ``encode_flat`` + the single-pass flat
    engines + one tree reconstruction.  Both must produce the identical
    system (asserted) before their times are compared.
    """
    from repro.core import encode, encode_flat
    from repro.core.flat import FLAT_RULES
    from repro.core.optimizer import rewrite_spatial_tree, rewrite_system_tree
    from repro.core.randgen import random_layered_instance
    from repro.sched import CostModel, NetworkModel, SizeModel, auto_placement

    def tree_pipeline(inst):
        w = encode(inst)
        o, _ = rewrite_system_tree(w)
        return rewrite_spatial_tree(o)[0]

    def flat_pipeline(inst):
        fs = encode_flat(inst)
        FLAT_RULES["R1R2"](fs)
        FLAT_RULES["R3"](fs)
        return fs.rebuild_system()

    cases = [(100, True, 3), (1000, True, 3), (2000, True, 2), (10000, False, 1)]
    for n, tree_too, repeat in cases:
        inst = random_layered_instance(
            n, n_locations=4, seed=0, p_spatial=0.4
        )
        # Warm the instance-level adjacency/topology caches once — both
        # pipelines share them, so neither arm pays the one-off build.
        encode(inst)
        dt_flat, flat_sys = _t(flat_pipeline, inst, repeat=repeat)
        row(
            f"compile/flat_{n}steps", f"{dt_flat * 1e3:.1f}", "ms",
            f"actions={flat_sys.total_actions()}",
        )
        if tree_too:
            dt_tree, tree_sys = _t(tree_pipeline, inst, repeat=repeat)
            assert tree_sys == flat_sys, "engines diverged — do not compare"
            row(
                f"compile/tree_{n}steps", f"{dt_tree * 1e3:.1f}", "ms",
                "recursive reference engines",
            )
            row(
                f"compile/speedup_{n}steps", f"{dt_tree / dt_flat:.1f}", "x",
                "flat vs tree, end-to-end encode+R1R2+R3",
            )
        else:
            row(
                f"compile/tree_{n}steps", "skipped", "",
                "quadratic R3 — minutes at this size",
            )

    # Placement search at scale: the incremental scorer patches rows and
    # re-schedules through the shared array core instead of re-encoding,
    # re-rewriting and re-simulating trees per candidate move.
    inst = random_layered_instance(500, n_locations=4, seed=1, p_spatial=0.1)
    dt, report = _t(
        lambda: auto_placement(
            inst,
            NetworkModel.preset("two-rack"),
            sizes=SizeModel(default_bytes=1 << 18),
            costs=CostModel(default_exec_s=2e-3),
        ),
        repeat=1,
    )
    row(
        "compile/auto_placement_500steps", f"{dt:.1f}", "s",
        f"target <30s; bytes saved {report.bytes_saved_frac * 100:.0f}% "
        f"makespan {report.makespan_speedup:.2f}x vs round-robin",
    )


def _serve_workload(n_instances: int):
    """The serving-shaped workload shared by the serve / obs sections."""
    from repro.core.graph import DistributedWorkflowInstance, make_workflow

    # A serving-shaped workflow: a source step consumes the per-request
    # seed datum, fans out to two parallel workers, and a sink aggregates.
    wf = make_workflow(
        ["ingest", "work_a", "work_b", "merge"],
        ["p_seed", "p_ingest", "p_a", "p_b"],
        [
            ("p_seed", "ingest"),
            ("ingest", "p_ingest"),
            ("p_ingest", "work_a"),
            ("p_ingest", "work_b"),
            ("work_a", "p_a"),
            ("work_b", "p_b"),
            ("p_a", "merge"),
            ("p_b", "merge"),
        ],
    )
    inst = DistributedWorkflowInstance(
        workflow=wf,
        locations=frozenset({"l0", "l1", "l2"}),
        mapping={
            "ingest": ("l0",),
            "work_a": ("l1",),
            "work_b": ("l2",),
            "merge": ("l0",),
        },
        data=frozenset({"d_seed", "d_ingest", "d_a", "d_b"}),
        placement={
            "d_seed": "p_seed",
            "d_ingest": "p_ingest",
            "d_a": "p_a",
            "d_b": "p_b",
        },
        initial_data={"l0": frozenset({"d_seed"})},
    )
    fns = {
        "ingest": lambda i: {"d_ingest": i["d_seed"] * 2},
        "work_a": lambda i: {"d_a": i["d_ingest"] + 1},
        "work_b": lambda i: {"d_b": i["d_ingest"] + 2},
        "merge": lambda i: {},
    }
    inputs = [{("l0", "d_seed"): i} for i in range(n_instances)]
    return inst, fns, inputs


def bench_serve() -> None:
    """Compile-once/run-many serving throughput (instances/sec).

    100 workflow instances through the threaded backend, two ways:

    * *per-instance* — the naive serving loop: every instance pays the full
      trace → optimize → lower → compile → run pipeline;
    * *run-many* — one ``trace → optimize → lower → compile`` then
      ``Executable.run_many`` over the same lowered program IR with a
      shared transport and a bounded instance pool.

    Acceptance: run-many ≥ 5× instances/sec vs per-instance.
    """
    from repro import swirl

    n_instances = 100
    inst, fns, inputs = _serve_workload(n_instances)

    def per_instance():
        results = []
        for payloads in inputs:
            results.append(
                swirl.trace(inst)
                .optimize()
                .lower("threaded", timeout_s=60)
                .compile(fns)
                .run(initial_payloads=payloads)
            )
        return results

    def run_many():
        exe = (
            swirl.trace(inst)
            .optimize()
            .lower("threaded", timeout_s=60)
            .compile(fns)
        )
        return exe.run_many(inputs, max_concurrent=8)

    dt_per, res_per = _t(per_instance, repeat=1)
    dt_many, res_many = _t(run_many, repeat=1)
    assert [r.data for r in res_many] == [r.data for r in res_per], (
        "run-many results diverged from per-instance runs — do not compare"
    )
    ips_per = n_instances / dt_per
    ips_many = n_instances / dt_many
    row(
        "serve/per_instance_ips", f"{ips_per:.1f}", "instances/s",
        f"{n_instances} x trace->optimize->lower->compile->run",
    )
    row(
        "serve/run_many_ips", f"{ips_many:.1f}", "instances/s",
        f"{n_instances} instances, compile-once, max_concurrent=8",
    )
    row(
        "serve/speedup", f"{ips_many / ips_per:.1f}", "x",
        "target >= 5x (acceptance)",
    )


def _spin(n: int = 1500) -> int:
    """~50µs of pure-Python arithmetic — a stand-in for real step work."""
    acc = 0
    for i in range(n):
        acc += i * i
    return acc


def bench_obs() -> None:
    """Tracing overhead on the serving hot path (target < 5%).

    The same serve-shaped run_many batch through one compiled Executable:
    untraced (the ``recorder is None`` fast path) vs traced (``trace=True``
    span capture on every exec/send/recv), on two workloads:

    * *work* — steps do ~50µs of real computation each, the smallest
      plausible production step; the < 5% acceptance applies here;
    * *empty* — steps return constants, so every op is pure framework
      and tracing cost has nothing to amortise against.  This is the
      stress ceiling, reported for honesty, not gated.

    Each number is the **median of paired per-round ratios**: the two
    arms alternate within each round, because on a loaded container the
    machine drifts more between separate timing blocks than the
    few-percent signal being measured.
    """
    import statistics

    from repro import swirl

    n_instances = 100
    inst, fns, inputs = _serve_workload(n_instances)
    work_fns = {
        "ingest": lambda i: {"d_ingest": i["d_seed"] * 2 + 0 * _spin()},
        "work_a": lambda i: {"d_a": i["d_ingest"] + 1 + 0 * _spin()},
        "work_b": lambda i: {"d_b": i["d_ingest"] + 2 + 0 * _spin()},
        "merge": lambda i: (_spin(), {})[1],
    }
    plan = swirl.trace(inst).optimize()

    def paired_overhead(step_fns, rounds: int = 9):
        plain = plan.lower("threaded", timeout_s=60).compile(step_fns)
        traced = plan.lower(
            "threaded", timeout_s=60, trace=True
        ).compile(step_fns)
        # Warm both paths (thread pools, lazy imports) before timing.
        plain.run_many(inputs, max_concurrent=8)
        res = traced.run_many(inputs, max_concurrent=8)
        ratios, best_plain, best_traced = [], float("inf"), float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            plain.run_many(inputs, max_concurrent=8)
            dt_p = time.perf_counter() - t0
            t0 = time.perf_counter()
            traced.run_many(inputs, max_concurrent=8)
            dt_t = time.perf_counter() - t0
            ratios.append(dt_t / dt_p)
            best_plain = min(best_plain, dt_p)
            best_traced = min(best_traced, dt_t)
        overhead = (statistics.median(ratios) - 1.0) * 100.0
        spans = sum(len(r.profile.spans) for r in res)
        return overhead, best_plain, best_traced, spans

    over_work, dt_p, dt_t, spans = paired_overhead(work_fns)
    row(
        "obs/untraced_ips", f"{n_instances / dt_p:.1f}", "instances/s",
        f"{n_instances} instances, ~50µs steps, trace off",
    )
    row(
        "obs/traced_ips", f"{n_instances / dt_t:.1f}", "instances/s",
        f"{n_instances} instances, ~50µs steps, trace on "
        f"({spans} spans/batch)",
    )
    row(
        "obs/overhead_pct", f"{over_work:.1f}", "%",
        "median paired ratio, ~50µs steps — target < 5% (acceptance)",
    )
    over_empty, _, _, _ = paired_overhead(fns)
    row(
        "obs/overhead_empty_pct", f"{over_empty:.1f}", "%",
        "empty steps: every op is pure framework (stress ceiling)",
    )


def bench_gateway() -> None:
    """Workflow-as-a-service over HTTP: cache-hit serving + overload.

    Phase 1 submits three differently-shaped workflows (1-location chain,
    3-location diamond, 3-location fan-out), then drives a mixed stream of
    ``run_many`` batches from several keep-alive HTTP clients against the
    cached fingerprints — every request is a content-address cache hit.
    Acceptance: sustained >= 1000 instances/s aggregate, p50/p99 request
    latency and cache hit rate reported.

    Phase 2 overloads a tight tenant quota (2 in flight + 2 queued) with
    30 concurrent runs: the shed requests 429, every admitted run
    completes, and graceful close drains with nothing dropped.
    """
    import threading

    from repro.serve import (
        Gateway,
        GatewayClient,
        GatewayError,
        TenantConfig,
        WorkflowService,
    )

    shapes = {
        "chain": {
            "dag": {
                "edges": {"c_a": ["c_b"], "c_b": []},
                "mapping": {"c_a": ["l0"], "c_b": ["l0"]},
            }
        },
        "diamond": {
            "dag": {
                "edges": {
                    "d_pre": ["d_x", "d_y"],
                    "d_x": ["d_merge"],
                    "d_y": ["d_merge"],
                    "d_merge": [],
                },
                "mapping": {
                    "d_pre": ["l0"],
                    "d_x": ["l1"],
                    "d_y": ["l2"],
                    "d_merge": ["l0"],
                },
            }
        },
        "fan": {
            "dag": {
                "edges": {
                    "f_src": ["f_w1", "f_w2", "f_w3", "f_w4"],
                    "f_w1": [],
                    "f_w2": [],
                    "f_w3": [],
                    "f_w4": [],
                },
                "mapping": {
                    "f_src": ["l0"],
                    "f_w1": ["l1"],
                    "f_w2": ["l1"],
                    "f_w3": ["l2"],
                    "f_w4": ["l2"],
                },
            }
        },
    }

    def _steps():
        registry = {}
        for body in shapes.values():
            for s, succs in body["dag"]["edges"].items():
                if succs:
                    registry[s] = (
                        lambda inp, _d=f"d^{s}": {_d: 1}
                    )
                else:
                    registry[s] = lambda inp: {}
        return registry

    svc = WorkflowService(
        _steps(),
        tenants=[
            TenantConfig(
                "bench", api_key="bench", max_concurrent=64, max_queue=256
            )
        ],
        batch_max_concurrent=8,
    )
    n_clients, batches_per_client, batch_size = 6, 4, 50
    n_instances = n_clients * batches_per_client * batch_size
    latencies: list[float] = []
    lock = threading.Lock()
    with Gateway(svc) as gw:
        with GatewayClient(gw.url, api_key="bench") as c:
            fps = [
                c.submit(body)["fingerprint"] for body in shapes.values()
            ]
            for body in shapes.values():  # resubmits: source-digest hits
                assert c.submit(body)["cached"]

        def worker(i: int) -> None:
            with GatewayClient(gw.url, api_key="bench") as c:
                for b in range(batches_per_client):
                    fp = fps[(i + b) % len(fps)]  # mixed plan shapes
                    t0 = time.perf_counter()
                    r = c.run_many(fp, [{}] * batch_size)
                    dt = time.perf_counter() - t0
                    assert len(r["results"]) == batch_size
                    with lock:
                        latencies.append(dt)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = svc.stats()

    ips = n_instances / wall
    lat = np.array(sorted(latencies))
    hit_rate = stats["cache"]["hit_rate"]
    row(
        "gateway/cache_hit_ips", f"{ips:.0f}", "instances/s",
        f"{n_instances} instances, {n_clients} HTTP clients, "
        f"3 shapes, batch={batch_size} (target >= 1000)",
    )
    row(
        "gateway/request_p50", f"{np.percentile(lat, 50) * 1e3:.1f}", "ms",
        f"run_many batch of {batch_size}",
    )
    row(
        "gateway/request_p99", f"{np.percentile(lat, 99) * 1e3:.1f}", "ms",
        f"n={len(lat)} requests",
    )
    row(
        "gateway/cache_hit_rate", f"{hit_rate:.3f}", "",
        f"compiles={stats['counters']['compiles']} of "
        f"{stats['counters']['submissions']} submissions",
    )
    assert stats["counters"]["instances_failed"] == 0

    # -- overload: tight quota, concurrent burst -----------------------------
    slow = WorkflowService(
        {
            "s_a": lambda inp: (time.sleep(0.05), {"d^s_a": 1})[1],
            "s_b": lambda inp: {},
        },
        tenants=[
            TenantConfig(
                "tight", api_key="tight", max_concurrent=2, max_queue=2
            )
        ],
    )
    burst = 30
    outcome = {"ok": 0, "429": 0}
    gw2 = Gateway(slow).start()
    with GatewayClient(gw2.url, api_key="tight") as c:
        fp = c.submit(
            {
                "dag": {
                    "edges": {"s_a": ["s_b"], "s_b": []},
                    "mapping": {"s_a": ["l0"], "s_b": ["l0"]},
                }
            }
        )["fingerprint"]

    def overload_worker() -> None:
        with GatewayClient(gw2.url, api_key="tight") as c:
            try:
                c.run(fp)
                with lock:
                    outcome["ok"] += 1
            except GatewayError as e:
                assert e.status == 429 and e.retry_after >= 1
                with lock:
                    outcome["429"] += 1

    threads = [
        threading.Thread(target=overload_worker) for _ in range(burst)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    drained = gw2.close(drain_timeout_s=10)
    counters = slow.stats()["counters"]
    assert outcome["ok"] + outcome["429"] == burst
    assert counters["instances_completed"] == outcome["ok"]
    assert counters["instances_failed"] == 0 and drained
    row(
        "gateway/overload_429", outcome["429"], "requests",
        f"burst={burst}, quota 2+2, served={outcome['ok']}",
    )
    row(
        "gateway/overload_dropped", 0, "runs",
        f"drained={drained}; every admitted run completed",
    )


def bench_chaos() -> None:
    """Elastic recovery under chaos: sustained throughput while workers die.

    Drives ``run_many`` batches through the multiprocess backend with a
    SIGKILL injected into every instance mid-flight, in both recovery
    modes: ``spare`` (the dead location's program is renamed onto a spare
    and a fresh fleet respawned) and ``fold`` (the pool is resized — the
    dead location's op array is spliced onto a survivor).  Acceptance:
    every chaos-run instance produces the unperturbed run's data modulo
    the recovery renaming, no step body re-executes after checkpointed
    completion, and throughput under sustained kills stays a reasonable
    fraction of the fault-free baseline.
    """
    from repro import swirl

    edges = {
        "c_pre": ["c_a", "c_b"],
        "c_a": ["c_join"],
        "c_b": ["c_join"],
        "c_join": ["c_out"],
        "c_out": [],
    }
    mapping = {
        "c_pre": ("n0",),
        "c_a": ("n1",),
        "c_b": ("n2",),
        "c_join": ("n1",),
        "c_out": ("n0",),
    }

    def steps():
        return {
            "c_pre": lambda inp: {"d^c_pre": list(range(64))},
            "c_a": lambda inp: {"d^c_a": sum(inp["d^c_pre"])},
            "c_b": lambda inp: {"d^c_b": max(inp["d^c_pre"])},
            "c_join": lambda inp: {
                "d^c_join": inp["d^c_a"] * inp["d^c_b"]
            },
            "c_out": lambda inp: {},
        }

    plan = swirl.trace(edges, mapping=mapping).optimize()
    clean = (
        plan.lower("multiprocess", timeout_s=60)
        .compile(steps())
        .run()
        .data
    )
    n = 8

    def fold_expect(ren):
        out: dict = {}
        for l, d in clean.items():
            out.setdefault(ren.get(l, l), {}).update(d)
        return out

    # Fault-free baseline throughput.
    exe = plan.lower("multiprocess", timeout_s=60).compile(steps())
    dt, results = _t(lambda: exe.run_many([None] * n), repeat=1)
    assert all(r.data == clean for r in results)
    baseline_ips = n / dt
    row(
        "chaos/baseline_ips", f"{baseline_ips:.1f}", "instances/s",
        f"{n} instances, 3 worker processes, no faults",
    )

    # Sustained kills, spare replacement: every instance loses the
    # c_join worker to SIGKILL and is renamed onto a spare location.
    mismatches, recoveries = 0, 0
    for mode, lower_opts in [
        ("spare", dict(recover="spare", spares=["hot0"])),
        ("fold", dict(recover="fold")),
    ]:
        exe = plan.lower(
            "multiprocess",
            timeout_s=120,
            _kill_at_step="c_join",
            **lower_opts,
        ).compile(steps())
        dt, results = _t(lambda: exe.run_many([None] * n), repeat=1)
        for r in results:
            recs = r.stats["recoveries"]
            recoveries += len(recs)
            ren = recs[0]["renaming"] if recs else {}
            if r.data != fold_expect(ren):
                mismatches += 1
        ips = n / dt
        row(
            f"chaos/{mode}_ips", f"{ips:.1f}", "instances/s",
            f"{n} instances, 1 SIGKILL each, "
            f"{ips / baseline_ips * 100:.0f}% of fault-free",
        )
    row(
        "chaos/recoveries", recoveries, "events",
        f"expected {2 * n} (one per killed instance)",
    )
    row(
        "chaos/result_mismatches", mismatches, "instances",
        "recovered data vs clean run modulo renaming (must be 0)",
    )
    assert mismatches == 0
    assert recoveries == 2 * n

    # -- stragglers: a delayed (never killed) c_join worker -------------------
    # The FaultPolicy progress heartbeat declares the silent worker dead and
    # elastic recovery reruns its step on a spare (rename) or a survivor
    # (fold); without a policy the run simply waits out the whole delay.
    import tempfile

    from repro.exec import FaultPolicy, RunDeadlineExceeded
    from repro.workflow.fault import SlowOnceAcrossProcesses

    delay_s = 8.0
    policy = FaultPolicy(heartbeat_interval_s=0.2, heartbeat_timeout_s=1.0)
    straggler_s: dict[str, float] = {}
    corrupted = 0
    with tempfile.TemporaryDirectory() as tmp:
        for mode, opts in [
            ("spare", dict(policy=policy, recover="spare", spares=["hot0"])),
            ("fold", dict(policy=policy, recover="fold")),
            ("no_policy", {}),
        ]:
            fns = steps()
            fns["c_join"] = SlowOnceAcrossProcesses(
                fns["c_join"],
                flag_path=str(Path(tmp) / f"straggle-{mode}"),
                delay_s=delay_s,
            )
            exe = plan.lower(
                "multiprocess", timeout_s=120, **opts
            ).compile(fns)
            dt, res = _t(exe.run, repeat=1)
            recs = res.stats.get("recoveries") or []
            ren = recs[0]["renaming"] if recs else {}
            if res.data != fold_expect(ren):
                corrupted += 1
            if mode == "no_policy":
                detail = f"{delay_s:.0f}s straggler, no fault policy"
            else:
                assert len(recs) == 1
                assert recs[0]["declared_by"] == "heartbeat"
                detail = (
                    f"{delay_s:.0f}s straggler declared dead by heartbeat "
                    f"after {policy.heartbeat_timeout_s:.0f}s silence"
                )
            straggler_s[mode] = dt
            row(f"chaos/straggler_{mode}_s", f"{dt:.2f}", "s", detail)
    row(
        "chaos/straggler_corrupted", corrupted, "runs",
        "straggler-run data vs clean run modulo renaming (must be 0)",
    )
    assert corrupted == 0
    # Recovery must beat sitting out the delay, in both modes.
    assert straggler_s["spare"] < straggler_s["no_policy"]
    assert straggler_s["fold"] < straggler_s["no_policy"]

    # -- whole-run deadline: typed abort, promptly ----------------------------
    slow = steps()
    slow["c_join"] = lambda inp: (time.sleep(30), {"d^c_join": 0})[1]
    exe = plan.lower(
        "threaded", timeout_s=60, policy=FaultPolicy(deadline_s=0.5)
    ).compile(slow)
    t0 = time.perf_counter()
    try:
        exe.run()
        aborted = False
    except RunDeadlineExceeded:
        aborted = True
    abort_s = time.perf_counter() - t0
    row(
        "chaos/deadline_abort_s", f"{abort_s:.2f}", "s",
        "0.5s run deadline over a 30s straggling c_join (threaded)",
    )
    assert aborted and abort_s < 5.0


def bench_bisim() -> None:
    from repro.core import encode, rewrite_system, weak_barbed_bisimilar
    from repro.core.semantics import reachable_states
    from repro.core.translate import genomes_1000

    inst = genomes_1000(n=2, m=2, a=1, b=1, c=1)
    w = encode(inst)
    o, _ = rewrite_system(w)
    dt, states = _t(lambda: len(reachable_states(w, max_states=100_000)))
    row("bisim/states_W", states, "states", f"explore={dt * 1e3:.0f}ms")
    dt, ok = _t(lambda: weak_barbed_bisimilar(w, o, max_states=100_000), repeat=1)
    row("bisim/check", f"{dt * 1e3:.0f}", "ms", f"bisimilar={ok}")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    key = jax.random.key(0)
    b, hq, hkv, l, d = 1, 4, 2, 512, 64
    q = jax.random.normal(key, (b, hq, l, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, l, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, l, d))

    out = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out - want)))
    row("kernels/flash_attn_maxerr", f"{err:.2e}", "abs", f"shape={q.shape}")

    fn = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True))
    fn(q, k, v).block_until_ready()
    dt, _ = _t(lambda: fn(q, k, v).block_until_ready())
    row("kernels/xla_ref_latency", f"{dt * 1e3:.2f}", "ms", "CPU jit reference")


def bench_train() -> None:
    from repro.launch.train import train

    t0 = time.perf_counter()
    out = train(
        "llama3.2-3b", smoke=True, steps=5, n_pods=2,
        global_batch=4, seq_len=32, ckpt_dir=None, log_every=100,
    )
    dt = time.perf_counter() - t0
    losses = [float(h["loss"]) for h in out["history"]]
    row(
        "train/swirl_2pod_smoke", f"{dt / 5:.2f}", "s/step",
        f"loss {losses[0]:.3f}->{losses[-1]:.3f}",
    )


def bench_roofline() -> None:
    d = Path("experiments/dryrun")
    if not d.exists():
        row("roofline/dryrun", "missing", "", "run repro.launch.dryrun --all")
        return
    recs = [json.loads(p.read_text()) for p in sorted(d.glob("*.json"))]
    ok = [r for r in recs if r.get("status") == "ok"]
    skips = [r for r in recs if r.get("status") == "skipped"]
    row("roofline/cells_ok", len(ok), "cells", f"skipped={len(skips)}")
    for r in ok:
        if r["mesh"] != "pod1":
            continue
        rl = r["roofline"]
        row(
            f"roofline/{r['arch']}/{r['shape']}",
            f"{rl['bound_s']:.4g}", "s",
            f"dom={rl['dominant']} mfu_bound={rl['mfu_bound'] * 100:.1f}%",
        )


SECTIONS = {
    "encoding": bench_encoding,
    "optimise": bench_optimise,
    "runtime": bench_runtime,
    "dist": bench_dist,
    "dataplane": bench_dataplane,
    "sched": bench_sched,
    "compile": bench_compile,
    "serve": bench_serve,
    "obs": bench_obs,
    "gateway": bench_gateway,
    "chaos": bench_chaos,
    "bisim": bench_bisim,
    "kernels": bench_kernels,
    "train": bench_train,
    "roofline": bench_roofline,
}


def main() -> None:
    args = sys.argv[1:]
    emit_json = "--json" in args
    which = [a for a in args if a != "--json"] or list(SECTIONS)
    unknown = [name for name in which if name not in SECTIONS]
    if unknown:
        raise SystemExit(
            f"unknown sections {unknown}; known: {list(SECTIONS)}"
        )
    from repro.launch.cache import configure_compile_cache

    configure_compile_cache()
    print("name,value,unit,derived")
    for name in which:
        _ROWS.clear()
        SECTIONS[name]()
        if emit_json:
            out = Path(f"BENCH_{name}.json")
            out.write_text(
                json.dumps(
                    {
                        "section": name,
                        "generated_unix": time.time(),
                        "python": platform.python_version(),
                        "platform": platform.platform(),
                        "rows": list(_ROWS),
                    },
                    indent=2,
                )
                + "\n"
            )
            print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
