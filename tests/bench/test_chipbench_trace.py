"""The trace reduction: busy union, idle share, top operations, and idle
gaps named by the host span they fell in."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench.xtrace import Event, reduce_events

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
SAMPLE = Path(__file__).with_name("trace_sample_v5e.json")


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "wf.instance", 0, 500),
        ev(HOST, "python", "wf.instance", 500, 500),
        ev(HOST, "python", "train.run", 600, 100),
        # device 0: ops overlap inside one module; 300 ns busy in all
        ev(DEV0, "XLA Modules", "jit_a", 100, 200),
        ev(DEV0, "XLA Ops", "fusion.1", 100, 150),
        ev(DEV0, "XLA Ops", "copy.2", 200, 100),
        ev(DEV0, "XLA Modules", "jit_b", 700, 100),
        ev(DEV0, "XLA Ops", "dot.3", 700, 100),
        # device 1: one op, half of it outside the window
        ev(DEV1, "XLA Modules", "jit_c", 900, 200),
        ev(DEV1, "XLA Ops", "dot.4", 900, 200),
        # a host event that is not a span of the benchmark's and an op
        # before the window are ignored
        ev(DEV0, "XLA Ops", "early", -50, 40),
    ]


def test_busy_idle_and_ops():
    r = reduce_events(synthetic())
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_by_device[DEV0] == pytest.approx(300e-9)
    assert r.busy_by_device[DEV1] == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(200e-9)
    assert r.idle_share == pytest.approx(0.8)
    ops = dict(r.device_ops)
    assert ops["jit_a/fusion.1"] == pytest.approx(150e-9)
    assert ops["jit_a/copy.2"] == pytest.approx(100e-9)
    assert ops["jit_c/dot.4"] == pytest.approx(100e-9)
    assert "early" not in " ".join(ops)
    assert r.span_counts == {"wf.instance": 2, "train.run": 1}


def test_gaps_named_by_host_span():
    gaps = dict(reduce_events(synthetic()).idle_gaps)
    # Device 0 idles 0-100, 300-500 and 500-600 in instances, 600-700 in
    # train.run (the innermost span), 800-1000 in an instance.  Device 1
    # idles 0-600 and 700-900 in instances and 600-700 in train.run.  Per
    # chip: the mean of the two.
    assert gaps["train.run"] == pytest.approx((100 + 100) * 1e-9 / 2)
    assert gaps["wf.instance"] == pytest.approx((600 + 800) * 1e-9 / 2)
    assert sum(gaps.values()) == pytest.approx(0.8 * 1000e-9)


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(RuntimeError, match="bench.window"):
        reduce_events([e for e in synthetic() if e.name != "bench.window"])
    with pytest.raises(RuntimeError, match="no device operation"):
        reduce_events([e for e in synthetic() if e.plane == HOST])


@pytest.mark.skipif(not SAMPLE.exists(), reason="no recorded chip trace sample")
def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: three annotated instances of two
    small jitted programs."""
    events = [Event(*row) for row in json.loads(SAMPLE.read_text())]
    r = reduce_events(events)
    assert 0 < r.busy_s < r.window_s
    assert r.span_counts == {"wf.instance": 3}
    assert all(name.split("/")[0].startswith("jit_") for name, _ in r.device_ops)
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s)
