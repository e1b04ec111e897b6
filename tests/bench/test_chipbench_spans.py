"""The program's spans in a trace: self time per thread, idle time by
innermost span, the per-layer readings, and the spans the program really
emits, read back from a CPU profiler trace."""

from __future__ import annotations

import json
import threading
from pathlib import Path

import pytest

from chipbench import spans, xtrace
from chipbench.xtrace import Event

DEV0 = "/device:TPU:0"
MAIN, WORKER = "python#0", "python#1"
HOST = "/host:CPU"
SAMPLE = Path(__file__).with_name("trace_sample_v5e.json")
SPANS_SAMPLE = Path(__file__).with_name("trace_sample_v5e_spans.json")


def ev(line, name, start, dur, plane=HOST):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    return [
        ev(MAIN, "bench.window", 0, 1000),
        ev(MAIN, "wf.instance", 0, 600),
        ev(MAIN, "swirl.run", 100, 400),
        ev(MAIN, "swirl.comm", 150, 50),
        ev(MAIN, "swirl.exec", 250, 100),
        # another thread's span inside the run: not a child of it
        ev(WORKER, "swirl.exec", 300, 150),
        # a run that outlasts the window, with a child cut by its end
        ev(MAIN, "swirl.run", 700, 400),
        ev(MAIN, "swirl.exec", 950, 100),
        ev("XLA Modules", "jit_a", 160, 30, plane=DEV0),
        ev("XLA Ops", "fusion.1", 160, 30, plane=DEV0),
        ev("XLA Modules", "jit_b", 600, 50, plane=DEV0),
        ev("XLA Ops", "dot.2", 600, 50, plane=DEV0),
    ]


def test_self_time_nets_same_thread_children_only():
    sr = spans.reduce_spans(synthetic())
    self_ns = {k: v * 1e9 for k, v in sr.span_self_s.items()}
    assert self_ns["wf.instance"] == pytest.approx(600 - 400)
    # (400 - 50 - 100) + (300 - 50): the worker's exec does not count
    assert self_ns["swirl.run"] == pytest.approx(500)
    assert self_ns["swirl.comm"] == pytest.approx(50)
    assert self_ns["swirl.exec"] == pytest.approx(100 + 150 + 50)
    assert sr.span_counts == {"wf.instance": 1, "swirl.run": 2, "swirl.comm": 1, "swirl.exec": 3}


def test_idle_by_innermost_span_sums_to_idle_and_agrees_with_xtrace():
    events = synthetic()
    sr = spans.reduce_spans(events)
    idle_ns = {k: v * 1e9 for k, v in sr.idle_by_span.items()}
    # Idle 0-160, 190-600, 650-1000; innermost over every thread, so the
    # worker's exec names 350-450.
    assert idle_ns == pytest.approx({
        "wf.instance": 100 + 100, "swirl.run": 50 + 50 + 50 + 250,
        "swirl.comm": 10 + 10, "swirl.exec": 200 + 50, xtrace.NO_SPAN: 50,
    })
    r = xtrace.reduce_events(events)
    assert sr.idle_s == pytest.approx(r.window_s - r.busy_s)
    assert sr.idle_by_span == pytest.approx(dict(r.idle_gaps))


def test_a_chip_idle_all_window_counts():
    events = synthetic() + [ev("XLA Ops", "early", -90, 40, plane="/device:TPU:1")]
    sr = spans.reduce_spans(events)
    assert sr.idle_s == pytest.approx((920 + 1000) / 2 * 1e-9)


@pytest.mark.parametrize("sample", [SAMPLE, SPANS_SAMPLE], ids=["benchmark_spans", "program_spans"])
def test_recorded_chip_trace(sample):
    """Traces recorded on one TPU v5e: the older holds only the
    benchmark's spans; the newer, three genomes instances of a short
    window with the program's spans as well.  Reading the program's spans
    leaves busy time, idle share and the operations as they were, and the
    idle time by innermost span agrees with ``xtrace``'s gaps."""
    events = [Event(*row) for row in json.loads(sample.read_text())]
    benchmark_only = [e for e in events if e.name not in spans.PROGRAM_SPANS]
    old, new = xtrace.reduce_events(benchmark_only), xtrace.reduce_events(events)
    assert (new.window_s, new.busy_s, new.idle_share, new.device_ops) == (
        old.window_s, old.busy_s, old.idle_share, old.device_ops
    )
    assert sum(s for _, s in new.idle_gaps) == pytest.approx(sum(s for _, s in old.idle_gaps))
    sr = spans.reduce_spans(events)
    assert sr.idle_s == pytest.approx(new.window_s - new.busy_s)
    assert sr.idle_by_span == pytest.approx(dict(new.idle_gaps))
    for name, seconds in sr.span_self_s.items():
        assert -1e-9 <= seconds <= sr.window_s, name


def test_recorded_program_spans_nest_in_each_instance():
    events = [Event(*row) for row in json.loads(SPANS_SAMPLE.read_text())]
    sr = spans.reduce_spans(events)
    n = sr.span_counts["wf.instance"]
    assert n >= 2 and sr.span_counts["swirl.run"] == n
    # the 1000 Genomes plan: 39 COMMs and 25 step fires per instance
    assert sr.span_counts["swirl.comm"] == 39 * n
    assert sr.span_counts["swirl.exec"] == 25 * n
    readings = spans.readings("swirl_workflow", sr, n, 0)
    assert 0 < readings["wf.interp_self_ms"] < 1e3 * sr.window_s / n
    assert 0 <= readings["wf.idle_in_run_share"] <= 100
    assert readings["wf.comm_gb_per_instance"] == 0


def _reduction(self_s, idle):
    return spans.SpanReduction(
        window_s=10.0, span_self_s=self_s, span_counts={}, idle_by_span=idle,
    )


def test_workflow_readings():
    sr = _reduction(
        {"swirl.run": 0.5, "swirl.exec": 0.1, "wf.instance": 2.0},
        {"swirl.run": 0.2, "swirl.comm": 0.1, "swirl.exec": 0.1, "wf.instance": 0.4},
    )
    got = spans.readings("swirl_workflow", sr, 10, 3_000_000_000)
    assert got == pytest.approx({
        "wf.interp_self_ms": 50.0, "wf.idle_in_run_share": 50.0,
        "wf.comm_gb_per_instance": 3.0,
    })


def test_trainer_readings():
    sr = _reduction(
        {"swirl.run": 0.3, "swirl.compile": 0.1, "swirl.wait": 5.0, "launch.sync": 0.8},
        {"swirl.wait": 1.0},
    )
    got = spans.readings("swirl_trainer", sr, 20, None)
    assert got == pytest.approx({"train.swirl_self_ms": 20.0, "train.host_sync_ms": 40.0})


def test_readings_leave_out_what_the_trace_lacks():
    """A program without spans or the counter (an older commit) reads
    nothing, and raises nothing."""
    sr = _reduction({"wf.instance": 2.0}, {"wf.instance": 1.0})
    assert spans.readings("swirl_workflow", sr, 10, None) == {}
    assert spans.readings("swirl_trainer", sr, 10, None) == {}
    assert spans.readings("swirl_workflow", sr, 0, 5) == {}


# ---------------------------------------------------------------------------
# The spans the program emits, read from a CPU profiler trace
# ---------------------------------------------------------------------------

CHAIN2 = (
    "<a, {x}, exec(s1, {x} -> {y}, {a}) . send(y -> p, a, b)>"
    " | <b, {}, recv(p, a, b) . exec(s2, {y} -> {z}, {b})>"
)


def _profile(tmp_path, fn):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = xtrace.find_xplane(str(tmp_path))
    events = spans.read_events(path, spans.PROGRAM_SPANS)
    args = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in spans.PROGRAM_SPANS:
                    args[(e.name, e.start_ns)] = dict(e.stats)
    return events, args


def _inside(child, parent):
    return parent.start_ns <= child.start_ns and child.end_ns <= parent.end_ns


def test_jax_backend_spans_on_the_profiler(tmp_path):
    import jax.numpy as jnp

    from repro import swirl

    steps = {"s1": lambda i: {"y": i["x"] * 2}, "s2": lambda i: {"z": i["y"] + 1}}
    exe = swirl.trace(CHAIN2).lower("jax").compile(steps)
    exe.run(initial_payloads={("a", "x"): jnp.ones(8)})

    def runs():
        exe.run(initial_payloads={("a", "x"): jnp.ones(8)})

    events, args = _profile(tmp_path, runs)
    (run,) = [e for e in events if e.name == "swirl.run"]
    assert args[(run.name, run.start_ns)] == {"backend": "jax", "run": 1}
    inner = [e for e in events if e.name in ("swirl.exec", "swirl.comm")]
    assert sorted(e.name for e in inner) == ["swirl.comm", "swirl.exec", "swirl.exec"]
    assert all(_inside(e, run) and e.line == run.line for e in inner)
    metas = sorted(
        (e.name, tuple(sorted(args[(e.name, e.start_ns)].items()))) for e in inner
    )
    assert metas == [
        ("swirl.comm", (("data", "y"), ("dst", "b"), ("src", "a"))),
        ("swirl.exec", (("location", "a"), ("step", "s1"))),
        ("swirl.exec", (("location", "b"), ("step", "s2"))),
    ]


def test_inprocess_spans_on_the_profiler(tmp_path):
    from repro import swirl

    steps = {"s1": lambda i: {"y": i["x"] * 2}, "s2": lambda i: {"z": i["y"] + 1}}
    lowered = swirl.trace(CHAIN2).lower("inprocess")
    main = threading.get_ident()
    fired_on = []

    def step(i):
        fired_on.append(threading.get_ident())
        return {"y": i["x"] * 2}

    def runs():
        lowered.compile({**steps, "s1": step}).run(initial_payloads={("a", "x"): 3})

    events, args = _profile(tmp_path, runs)
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    (compile_span,) = by_name["swirl.compile"]
    (run,) = by_name["swirl.run"]
    assert args[(run.name, run.start_ns)] == {"backend": "inprocess", "run": 0}
    assert compile_span.line == run.line and compile_span.end_ns <= run.start_ns
    assert fired_on and main not in fired_on
    execs = by_name["swirl.exec"]
    assert len(execs) == 2
    for e in execs:
        assert _inside(e, run) and e.line != run.line  # a worker thread
    assert by_name["swirl.wait"] and all(
        _inside(w, run) and w.line == run.line for w in by_name["swirl.wait"]
    )
