"""Observability (repro.obs): tracing, exporters, profiling, metrics.

Covers the repro.obs acceptance criteria:

* **identical span schemas across backends** — the same workflow traced
  on every registered backend yields the same timing-free
  :meth:`SpanEvent.identity` multiset (the differential unit);
* **zero-cost disabled path** — a disabled recorder performs no
  allocations per rejected span, and untraced results carry no profile;
* **crash-resilient multiprocess spans** — a SIGKILLed worker's
  previously shipped spans survive in ``program.last_profile``;
* **exporters** — Chrome trace JSON is schema-valid and survives a
  file round-trip;
* **predicted-vs-actual** — :meth:`Plan.profile` aligns recorded spans
  against the sched simulator, and :meth:`CostModel.from_profile`
  calibrates the simulator to measured step durations on 1000 Genomes.
"""

from __future__ import annotations

import gc
import json
import signal
import sys
import time

import numpy as np
import pytest

from repro import swirl
from repro.backends import WorkerFailedError, available_backends
from repro.core.translate import genomes_1000
from repro.obs import (
    RunProfile,
    SpanEvent,
    TraceRecorder,
    chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.sched import CostModel, NetworkModel

EDGES = {
    "preprocess": ["train_a", "train_b"],
    "train_a": ["evaluate"],
    "train_b": ["evaluate"],
    "evaluate": ["report"],
    "report": [],
}
MAPPING = {
    "preprocess": ("cpu0",),
    "train_a": ("gpu0",),
    "train_b": ("gpu1",),
    "evaluate": ("gpu0",),
    "report": ("cpu0",),
}

BACKEND_OPTIONS = {
    "threaded": {"timeout_s": 60},
    "multiprocess": {"timeout_s": 120},
}


def quickstart_steps():
    return {
        "preprocess": lambda inp: {"d^preprocess": list(range(10))},
        "train_a": lambda inp: {"d^train_a": sum(inp["d^preprocess"])},
        "train_b": lambda inp: {"d^train_b": max(inp["d^preprocess"])},
        "evaluate": lambda inp: {
            "d^evaluate": inp["d^train_a"] + inp["d^train_b"]
        },
        "report": lambda inp: {},
    }


@pytest.fixture
def plan():
    return swirl.trace(EDGES, mapping=MAPPING).optimize()


def traced_run(plan, backend, **extra):
    opts = {**BACKEND_OPTIONS.get(backend, {}), **extra}
    exe = plan.lower(backend, trace=True, **opts).compile(quickstart_steps())
    return exe.run()


# ---------------------------------------------------------------------------
# The recorder primitive
# ---------------------------------------------------------------------------


class TestTraceRecorder:
    def test_span_roundtrip(self):
        rec = TraceRecorder()
        rec.span("exec", "l0", "s1", 0.1, 0.2)
        rec.span("send", "l0", "d1", 0.2, 0.3, src="l0", dst="l1", nbytes=8)
        assert len(rec) == 2
        spans = rec.drain()
        assert len(rec) == 0
        assert [s.kind for s in spans] == ["exec", "send"]
        assert spans[1].nbytes == 8 and spans[1].duration == pytest.approx(0.1)

    def test_absorb_applies_clock_offset(self):
        rec = TraceRecorder(t_zero=0.0)
        worker_spans = [SpanEvent("exec", "w0", "s", 10.0, 11.0)]
        rec.absorb(worker_spans, offset=-9.5)
        (merged,) = rec.drain()
        assert merged.start == pytest.approx(0.5)
        assert merged.end == pytest.approx(1.5)

    def test_drain_merge_ordered_by_location(self):
        rec = TraceRecorder()
        rec.span("exec", "z", "s1", 0.0, 1.0)
        rec.span("exec", "a", "s2", 0.0, 1.0)
        assert [s.location for s in rec.drain()] == ["a", "z"]

    def test_disabled_span_allocates_nothing(self):
        """The disabled hot path must not allocate per rejected span."""
        rec = TraceRecorder(enabled=False)
        rec.span("exec", "l0", "warmup", 0.0, 1.0)  # warm any lazy state
        gc.disable()
        try:
            gc.collect()
            before = sys.getallocatedblocks()
            for _ in range(10_000):
                rec.span("exec", "l0", "step", 0.0, 1.0)
            after = sys.getallocatedblocks()
        finally:
            gc.enable()
        # Zero in principle; a few blocks of slack for interpreter noise.
        assert after - before <= 16
        assert len(rec) == 0


class TestPayloadSizing:
    """``payload_nbytes`` must size without serializing the payload."""

    def test_array_and_buffer_sizes_are_exact(self):
        from repro.obs.events import payload_nbytes

        assert payload_nbytes(np.zeros(65536)) == 65536 * 8
        assert payload_nbytes(b"x" * 4096) == 4096
        assert payload_nbytes(bytearray(8192)) == 8192
        assert payload_nbytes(memoryview(bytearray(1024))) == 1024
        # Opaque objects still get the getsizeof fallback.
        assert payload_nbytes({"a": 1}) == sys.getsizeof({"a": 1})

    def test_sizing_large_array_allocates_nothing(self):
        """Like the disabled-recorder path: O(1) blocks, no copy."""
        from repro.obs.events import payload_nbytes

        arr = np.zeros(1 << 20)  # 8 MB — a copy or pickle would show
        payload_nbytes(arr)  # warm any lazy state
        gc.disable()
        try:
            gc.collect()
            before = sys.getallocatedblocks()
            for _ in range(1_000):
                payload_nbytes(arr)
            after = sys.getallocatedblocks()
        finally:
            gc.enable()
        assert after - before <= 16

    def test_sizing_buffer_payload_is_zero_copy(self):
        """memoryview sizing must not materialise the buffer's bytes."""
        import tracemalloc

        from repro.obs.events import payload_nbytes

        buf = bytearray(4 << 20)  # no .nbytes attribute — memoryview path
        payload_nbytes(buf)
        tracemalloc.start()
        try:
            payload_nbytes(buf)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert peak < 64 * 1024  # a copy would show up as ≥4 MB


# ---------------------------------------------------------------------------
# Identical span schemas across every backend
# ---------------------------------------------------------------------------


class TestCrossBackendSpans:
    def test_span_schema_identical_on_all_backends(self, plan):
        backends = available_backends()
        profiles = {}
        for b in backends:
            result = traced_run(plan, b)
            assert isinstance(result.profile, RunProfile), b
            assert result.profile.backend == b
            profiles[b] = result.profile
        reference = profiles[backends[0]].span_schema()
        assert reference, "traced run recorded no spans"
        for b in backends[1:]:
            assert profiles[b].span_schema() == reference, (
                f"{b} span schema diverged from {backends[0]}"
            )

    @pytest.mark.parametrize("seed", range(5))
    def test_span_schema_identical_on_seeded_dags(self, seed):
        """Random layered DAGs: every backend, same span multiset."""
        import random

        from test_differential import random_instance

        inst = random_instance(random.Random(1000 + seed))
        dag_plan = swirl.trace(inst).optimize()
        fns = {
            s: (lambda i, _outs=inst.out_data(s): {d: 1 for d in _outs})
            for s in inst.workflow.steps
        }
        schemas = {}
        for b in available_backends():
            opts = BACKEND_OPTIONS.get(b, {})
            exe = dag_plan.lower(b, trace=True, **opts).compile(fns)
            schemas[b] = exe.run().profile.span_schema()
        reference_backend = available_backends()[0]
        for b, schema in schemas.items():
            assert schema == schemas[reference_backend], (
                f"seed {seed}: {b} diverged from {reference_backend}"
            )

    def test_exec_spans_cover_every_step_placement(self, plan):
        profile = traced_run(plan, "inprocess").profile
        execs = {
            (ev.name, ev.location)
            for ev in profile.spans
            if ev.kind == "exec"
        }
        expected = {
            (step, loc)
            for step, locs in plan.placement().items()
            for loc in locs
        }
        assert execs == expected

    def test_send_recv_pair_and_carry_bytes(self, plan):
        profile = traced_run(plan, "threaded").profile
        sends = [ev for ev in profile.spans if ev.kind == "send"]
        recvs = [ev for ev in profile.spans if ev.kind == "recv"]
        assert sends and len(sends) == len(recvs)
        # Every transfer shows up once per side, on the right endpoint.
        assert {(s.src, s.dst) for s in sends} == {
            (r.src, r.dst) for r in recvs
        }
        assert all(s.location == s.src for s in sends)
        assert all(r.location == r.dst for r in recvs)
        assert all(s.src != s.dst for s in sends)
        assert all((s.nbytes or 0) > 0 for s in sends)
        assert profile.cross_bytes() == sum(s.nbytes for s in sends)

    def test_untraced_run_has_no_profile(self, plan):
        exe = plan.lower("inprocess").compile(quickstart_steps())
        assert exe.run().profile is None

    def test_run_many_attaches_one_profile_per_result(self, plan):
        exe = plan.lower("threaded", trace=True, timeout_s=60).compile(
            quickstart_steps()
        )
        results = exe.run_many([None, None, None])
        schemas = {r.profile.span_schema() for r in results}
        assert len(schemas) == 1  # instances are schema-identical
        assert all(len(r.profile.spans) > 0 for r in results)

    def test_profile_carries_pipeline_phases(self, plan):
        result = traced_run(plan, "inprocess")
        labels = [label for label, _ in result.profile.phases]
        assert "lower" in labels
        assert "compile[inprocess]" in labels

    def test_explain_renders_lower_and_compile_timings(self, plan):
        plan.lower("inprocess").compile(quickstart_steps())
        report = plan.explain()
        assert "lower" in report
        assert "compile[inprocess]" in report


# ---------------------------------------------------------------------------
# Multiprocess: spans survive a killed worker
# ---------------------------------------------------------------------------


class TestMultiprocessSpans:
    def test_spans_survive_sigkill_up_to_last_merge(self, plan):
        exe = plan.lower(
            "multiprocess",
            trace=True,
            _kill_at_step="evaluate",
            timeout_s=120,
        ).compile(quickstart_steps())
        with pytest.raises(WorkerFailedError) as e:
            exe.run()
        assert e.value.exitcode == -signal.SIGKILL
        profile = exe.program.last_profile
        assert profile is not None
        # train_a ran on the killed worker (gpu0) *before* evaluate; its
        # spans were shipped on the pre-step flush and must survive.
        exec_steps = {ev.name for ev in profile.spans if ev.kind == "exec"}
        assert "train_a" in exec_steps
        assert "evaluate" not in exec_steps

    def test_worker_spans_align_to_coordinator_clock(self, plan):
        result = traced_run(plan, "multiprocess")
        spans = result.profile.spans
        assert spans
        # Realigned worker times are small offsets from run start — never
        # raw worker-monotonic stamps (hours of uptime).
        assert all(0.0 <= s.start < 120.0 for s in spans)
        assert all(s.end >= s.start for s in spans)


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------


class TestChromeTrace:
    def test_schema_valid_and_roundtrips(self, plan, tmp_path):
        profile = traced_run(plan, "threaded").profile
        obj = profile.chrome_trace()
        validate_chrome_trace(obj)
        path = tmp_path / "trace.json"
        profile.save_chrome_trace(str(path))
        loaded = json.loads(path.read_text())
        validate_chrome_trace(loaded)
        assert loaded == json.loads(json.dumps(obj))

    def test_tracks_named_after_locations(self, plan):
        obj = traced_run(plan, "inprocess").profile.chrome_trace()
        names = {
            ev["args"]["name"]
            for ev in obj["traceEvents"]
            if ev["ph"] == "M" and ev["name"] == "thread_name"
        }
        assert {"cpu0", "gpu0", "gpu1"} <= names

    def test_flow_events_pair_sends_to_recvs(self, plan):
        obj = traced_run(plan, "threaded").profile.chrome_trace()
        starts = [e for e in obj["traceEvents"] if e["ph"] == "s"]
        finishes = [e for e in obj["traceEvents"] if e["ph"] == "f"]
        assert starts and len(starts) == len(finishes)
        assert {e["id"] for e in starts} == {e["id"] for e in finishes}

    def test_validator_rejects_malformed(self):
        with pytest.raises(ValueError):
            validate_chrome_trace({"no": "traceEvents"})
        with pytest.raises(ValueError):
            validate_chrome_trace(
                {"traceEvents": [{"ph": "X", "pid": 1, "tid": 1}]}
            )

    def test_plain_export_from_bare_spans(self, tmp_path):
        spans = (
            SpanEvent("exec", "l0", "s1", 0.0, 0.5),
            SpanEvent("exec", "l0", "s2", 0.5, 0.9),
        )
        path = tmp_path / "bare.json"
        write_chrome_trace(str(path), spans, phases=(("lower", 0.001),))
        obj = json.loads(path.read_text())
        validate_chrome_trace(obj)
        assert chrome_trace(spans)["traceEvents"]


# ---------------------------------------------------------------------------
# Predicted vs actual: Plan.profile + CostModel.from_profile
# ---------------------------------------------------------------------------


def _genomes_setup(sleep_s):
    inst = genomes_1000(n=2, m=2, a=1, b=1, c=1)
    rng = np.random.default_rng(0)
    init = {("l^d", d): rng.random(256) for d in inst.g("l^d")}
    fns = {}
    for s in inst.workflow.steps:
        outs = inst.out_data(s)

        def fn(ins, _outs=outs):
            if sleep_s:
                time.sleep(sleep_s)
            return {
                d: sum(float(np.sum(np.atleast_1d(v))) for v in ins.values())
                for d in _outs
            }

        fns[s] = fn
    return inst, init, fns


class TestPredictedVsActual:
    def test_profile_aligns_scheduled_genomes(self):
        inst, init, fns = _genomes_setup(sleep_s=0.0)
        plan = swirl.trace(inst).optimize().schedule(
            NetworkModel.preset("uniform")
        )
        exe = plan.lower("threaded", trace=True, timeout_s=60).compile(fns)
        result = exe.run(initial_payloads=init)
        report = plan.profile(result)
        assert report.predicted_makespan > 0
        assert report.actual_makespan > 0
        assert report.drifts, "no steps aligned"
        predicted_steps = {d.step for d in report.drifts}
        assert predicted_steps <= set(plan.steps())
        assert not report.unmatched_actual
        assert "predicted vs actual" in report.summary()

    def test_profile_requires_traced_result(self):
        inst, init, fns = _genomes_setup(sleep_s=0.0)
        plan = swirl.trace(inst).optimize()
        exe = plan.lower("inprocess").compile(fns)
        result = exe.run(initial_payloads=init)
        with pytest.raises(ValueError, match="trace=True"):
            plan.profile(result)

    def test_cost_model_calibration_closes_the_loop(self):
        """from_profile → re-schedule → prediction within tolerance."""
        sleep_s = 0.02
        inst, init, fns = _genomes_setup(sleep_s)
        network = NetworkModel.preset("uniform", bandwidth=1e9, latency=1e-5)
        plan = swirl.trace(inst).optimize().schedule(network)
        result = (
            plan.lower("threaded", trace=True, timeout_s=60)
            .compile(fns)
            .run(initial_payloads=init)
        )
        model = CostModel.from_profile(result.profile)
        # Every measured step slept for sleep_s: the calibrated cost must
        # be ≥ the sleep and within loose overhead bounds of it.
        for step in plan.steps():
            assert sleep_s * 0.9 <= model.exec_s(step) <= sleep_s * 5.0, step
        replan = swirl.trace(inst).optimize().schedule(
            network, costs=model
        )
        report = replan.profile(
            result, network=network, costs=model
        )
        # The calibrated simulator predicts the measured makespan within
        # a generous CI-safe tolerance (sleeps dominate, comms are ~free).
        ratio = report.predicted_makespan / report.actual_makespan
        assert 0.2 <= ratio <= 3.0, report.summary()

    def test_from_profile_accepts_mappings(self):
        m = CostModel.from_profile({"a": 0.5, "b": [0.1, 0.3]})
        assert m.exec_s("a") == pytest.approx(0.5)
        assert m.exec_s("b") == pytest.approx(0.2)
        with pytest.raises(TypeError):
            CostModel.from_profile(42)


# ---------------------------------------------------------------------------
# Spans on the profiler's clock
# ---------------------------------------------------------------------------


def _profiled_spans(tmp_path, fn, prefix):
    """(name, arguments) of every span named ``prefix*`` in a profiler
    trace of ``fn()``, in start order."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [
        (e.start_ns, e.name, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
        if e.name.startswith(prefix)
    ]
    return [(name, stats) for _, name, stats in sorted(found, key=lambda x: x[0])]


class TestProfilerSpans:
    def test_fire_span_records_rows_only_when_the_fire_succeeds(self):
        from repro.exec.interp import exec_span
        from repro.exec.program import ExecOp

        op = ExecOp(step="s", inputs=(), outputs=("d",), locations=("a", "b"), leader=True)
        rec = TraceRecorder()
        with exec_span(rec, op, "a"):
            pass
        with pytest.raises(ValueError), exec_span(rec, op, "a"):
            raise ValueError("step failed")
        with exec_span(None, op, "a"):  # no recorder: the profiler span only
            pass
        spans = rec.drain()
        assert [(s.kind, s.location, s.name) for s in spans] == [
            ("exec", "a", "s"), ("exec", "b", "s"),
        ]
        assert spans[0].start <= spans[0].end

    def test_trainer_step_bodies_open_launch_spans(self, tmp_path):
        """Compression per pod, the gradient mean, and every read of device
        values to the host, with the pod each belongs to; once compiled, the
        compression and the mean carry their programs' trace counts."""
        import jax.numpy as jnp

        from repro.launch.train import build_step_fns
        from repro.optim.compress import TRACES

        def grad_fn(params, batch):
            return {"w": params["w"] * 0.5}, {"loss": jnp.float32(2.0)}

        def update_fn(grads, opt, params):
            return params, opt, {"lr": jnp.float32(0.1)}

        class Data:
            def batch(self, step, shard, n_shards):
                return step

        fns, _ = build_step_fns(grad_fn, update_fn, Data(), 2)
        params = {"w": jnp.ones(64)}

        def iteration():
            grads = {
                f"grad_{i}": fns[f"fwdbwd_{i}"]({f"params_{i}": params, f"batch_{i}": 0})[f"grad_{i}"]
                for i in range(2)
            }
            sync = fns["gradsync"](grads)
            for i in range(2):
                fns[f"update_{i}"]({f"params_{i}": params, f"opt_{i}": None, **sync})

        iteration()  # compiles the compression, decompression and mean
        compress_traces = TRACES["compress"]
        sync_traces = TRACES["decompress_mean"] + TRACES["allreduce_mean"]
        assert _profiled_spans(tmp_path, iteration, "launch.") == [
            ("launch.compress", {"pod": 0, "traces": compress_traces}),
            ("launch.compress", {"pod": 1, "traces": compress_traces}),
            ("launch.gradsync", {"traces": sync_traces}),
            ("launch.sync", {"pod": 0}),
            ("launch.sync", {"pod": 1}),
            ("launch.sync", {"pod": 0}),
            ("launch.sync", {"pod": 1}),
        ]

    def test_fused_segment_span(self, tmp_path):
        import jax.numpy as jnp

        chain = (
            "<l0, {x}, exec(s1, {x} -> {y}, {l0}) . exec(s2, {y} -> {z}, {l0})>"
        )
        steps = {"s1": lambda i: {"y": i["x"] * 2}, "s2": lambda i: {"z": i["y"] + 1}}
        exe = swirl.trace(chain).lower("jax", fuse=True, trace=True).compile(steps)
        init = {("l0", "x"): jnp.ones(16)}
        exe.run(initial_payloads=init)
        result = []

        def run():
            result.append(exe.run(initial_payloads=init))

        assert _profiled_spans(tmp_path, run, "swirl.") == [
            ("swirl.run", {"backend": "jax", "run": 1}),
            ("swirl.segment", {"leader": "l0", "execs": 2}),
        ]
        # the recorder keeps one exec row per step, over the fused call
        (profile,) = [r.profile for r in result]
        execs = [s for s in profile.spans if s.kind == "exec"]
        assert [s.name for s in execs] == ["s1", "s2"]
        assert execs[0].start == execs[1].start and execs[0].end == execs[1].end
