"""``jax`` backend: the firing order planned at compile and replayed by
every run; fused segments, when they go eager and when they fail; the
bytes its COMMs copy between devices."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import swirl
from repro.backends import jax_backend

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


_COMM_BYTES = """
import json, jax, jax.numpy as jnp
from repro import swirl
from repro.backends import jax_backend
plan = swirl.trace(
    "<a, {x}, exec(s1, {x} -> {y}, {a}) . send(y -> p, a, b)>"
    " | <b, {}, recv(p, a, b) . exec(s2, {y} -> {z}, {b})>"
)
steps = {"s1": lambda i: {"y": i["x"] * 2}, "s2": lambda i: {"z": i["y"] + 1}}
out = {}
for n in (1, 2):
    exe = plan.lower("jax", devices=jax.devices()[:n]).compile(steps)
    stats = exe.run(initial_payloads={("a", "x"): jnp.ones(256, jnp.float32)}).stats
    out[n] = {k: stats[k] for k in ("comm_bytes", "bytes_moved", "comms")}
print(json.dumps(out))
"""


def test_comm_bytes_count_only_copies_to_another_device():
    """One COMM of 1 KiB: on one device its ``device_put`` shares the
    buffer and copies nothing; across two it copies the payload.
    ``bytes_moved`` counts every placement either way."""
    root = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(
        JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"),
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    proc = subprocess.run(
        [sys.executable, "-c", _COMM_BYTES], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["1"]["comms"] == out["2"]["comms"] == 1
    assert out["1"]["comm_bytes"] == 0
    assert out["2"]["comm_bytes"] == 1024
    assert out["1"]["bytes_moved"] == out["2"]["bytes_moved"] > 1024


# Two fusable runs on two locations with one COMM between them.
TWO_RUNS = (
    "<a, {x}, exec(s1, {x} -> {y}, {a}) . exec(s2, {y} -> {z}, {a})"
    " . send(z -> p, a, b)>"
    " | <b, {}, recv(p, a, b) . exec(s3, {z} -> {w}, {b})"
    " . exec(s4, {w} -> {v}, {b})>"
)
TWO_RUNS_STEPS = {
    "s1": lambda i: {"y": i["x"] * 2},
    "s2": lambda i: {"z": i["y"] + 1},
    "s3": lambda i: {"w": i["z"] * 3},
    "s4": lambda i: {"v": i["w"] - 1},
}


@pytest.mark.parametrize("fuse", [False, True])
def test_run_replays_the_plan_without_scanning_cursors(monkeypatch, fuse):
    """Once compiled, a run needs neither enabledness predicate: it
    replays the order planned at compile, fused or not."""
    exe = swirl.trace(TWO_RUNS).lower("jax", fuse=fuse).compile(TWO_RUNS_STEPS)
    kinds = [entry[0] for entry in exe.program.plan]
    assert kinds == (
        ["segment", "comm", "segment"] if fuse
        else ["exec", "exec", "comm", "exec", "exec"]
    )

    def scan(*args, **kwargs):
        raise AssertionError("a run rescanned the cursors")

    monkeypatch.setattr(jax_backend, "first_enabled_comm", scan)
    monkeypatch.setattr(jax_backend, "enabled_exec_picks", scan)
    x = jnp.ones(4, jnp.float32)
    res = exe.run(initial_payloads={("a", "x"): x})
    y = x * 2
    z = y + 1
    w = z * 3
    want = {"a": {"x": x, "y": y, "z": z}, "b": {"z": z, "w": w, "v": w - 1}}
    assert {loc: set(d) for loc, d in res.data.items()} == {
        loc: set(d) for loc, d in want.items()
    }
    for loc, data in want.items():
        for d, v in data.items():
            np.testing.assert_array_equal(
                np.asarray(res.data[loc][d]), np.asarray(v)
            )
    assert (res.stats["execs"], res.stats["comms"]) == (4, 1)
    if fuse:
        fused = res.stats["fused"]
        assert fused["segments_planned"] == fused["fused_calls"] == 2
        assert fused["fused_execs"] == 4
        assert fused["fallbacks"] == 0
        assert fused["locations"] == {
            "a": {"calls": 1, "execs": 2}, "b": {"calls": 1, "execs": 2},
        }
    else:
        assert "fused" not in res.stats


def test_run_that_cannot_finish_fires_what_it_can_then_raises():
    """A recv with no send: the planned steps fire, then the run raises
    with the system the plan left unreduced."""
    fired = []

    def s1(inputs):
        fired.append("s1")
        return {"y": inputs["x"] + 1}

    exe = swirl.trace(
        "<a, {x}, exec(s1, {x} -> {y}, {a})>"
        " | <b, {}, recv(p, a, b) . exec(s2, {y} -> {z}, {b})>"
    ).lower("jax").compile({"s1": s1, "s2": lambda i: {"z": i["y"]}})
    with pytest.raises(RuntimeError, match="did not terminate") as err:
        exe.run(initial_payloads={("a", "x"): jnp.ones(2)})
    assert fired == ["s1"]
    assert "recv(p,a,b).exec(s2,{y}->{z},{b})" in str(err.value)
    assert exe.program.remaining.pretty() in str(err.value)


def test_max_rounds_is_not_an_option():
    with pytest.raises(TypeError, match="max_rounds"):
        swirl.trace(CHAIN).lower("jax", max_rounds=10)
    assert jax_backend.JaxBackend().known_options() == {
        "schedule", "trace", "policy", "devices", "platform", "fuse",
    }
