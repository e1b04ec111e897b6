"""``jax`` backend fused segments: when they go eager, when they fail,
and which peak their roofline is measured against."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import roofline, swirl

CHAIN = "<l0, {x}, exec(s1, {x} -> {y}, {l0}) . exec(s2, {y} -> {z}, {l0})>"


def _run(steps, x):
    exe = swirl.trace(CHAIN).lower("jax", fuse=True).compile(steps)
    return exe.run(initial_payloads={("l0", "x"): x})


def test_untraceable_step_runs_eagerly():
    def branchy(inputs):  # Python control flow on a value: not traceable
        y = inputs["x"] * 2 if float(inputs["x"].sum()) > 0 else inputs["x"]
        return {"y": y}

    res = _run({"s1": branchy, "s2": lambda i: {"z": i["y"] + 1}}, jnp.ones(4))
    assert res.stats["fused"]["fallbacks"] == 1
    np.testing.assert_array_equal(np.asarray(res.data["l0"]["z"]), np.full(4, 3.0))


def test_runtime_error_in_traced_segment_propagates():
    def boom(x):
        raise ValueError("device-side failure")

    def failing(inputs):
        out = jax.pure_callback(
            boom, jax.ShapeDtypeStruct((4,), jnp.float32), inputs["x"]
        )
        return {"y": out}

    with pytest.raises(Exception, match="device-side failure"):
        _run({"s1": failing, "s2": lambda i: {"z": i["y"]}}, jnp.ones(4))


def test_roofline_only_for_devices_with_published_peaks(monkeypatch):
    steps = {"s1": lambda i: {"y": i["x"] * 2}, "s2": lambda i: {"z": i["y"] + 1}}
    exe = swirl.trace(CHAIN).lower("jax", fuse=True).compile(steps)
    init = {("l0", "x"): jnp.ones(1024)}
    exe.run(initial_payloads=init)
    assert exe.run(initial_payloads=init).stats["fused"]["roofline"] == {}

    kind = jax.devices()[0].device_kind
    peaks = roofline.DevicePeaks(1e12, 1e9, 1e9, source="test")
    monkeypatch.setitem(roofline.DEVICE_PEAKS, kind, peaks)
    rl = exe.run(initial_payloads=init).stats["fused"]["roofline"]["l0"]
    assert rl["device_kind"] == kind
    assert rl["theoretical_bytes_per_s"] == 1e9
