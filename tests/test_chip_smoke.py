"""``chip_smoke.py`` at smoke size on the CPU.

Each phase function runs with its Pallas kernels in interpret mode, asked
for explicitly.  ``main()`` itself must refuse to run without a TPU.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

from repro.backends import multiprocess as mp_backend  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch import cache  # noqa: E402
from repro.models import smoke_variant  # noqa: E402

SMALL = dict(rows=64, d=128, interpret=True, platform="cpu")


def _cpu_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_jax_backend_phase():
    out = chip_smoke.phase_jax_backend(**SMALL)
    assert out["fallbacks"] == 0
    assert out["fused_calls"] > 0
    assert out["vs_fuse_false"]["within_tol"]
    assert out["donated"] > 0  # buffers the backend owns are consumed


def test_repeat_run_from_caller_payloads_keeps_them():
    """Fused segments may donate their in-place inputs, but never an array
    the caller still holds: a second run from the same dict succeeds."""
    from repro import swirl

    plan = swirl.trace(chip_smoke.ring_workflow(2, 2)).optimize()
    steps = chip_smoke.ring_steps(2, 2, interpret=True)
    init = chip_smoke.ring_payloads(2, 16, 128)
    exe = plan.lower("jax", fuse=True).compile(steps)
    first = exe.run(initial_payloads=init)
    second = exe.run(initial_payloads=init)
    assert not any(v.is_deleted() for v in init.values())
    assert first.stats["fused"]["donated"] > 0
    got = chip_smoke._compare(second.data, first.data)
    assert got["bit_identical"]


def test_devices_phase_on_four_host_devices():
    code = (
        "import chip_smoke, json; print(json.dumps(chip_smoke.phase_devices("
        "n_locs=4, rows=64, d=128, rounds=2, interpret=True, platform='cpu'),"
        " default=str))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(set(out["placement"].values())) == 4
    assert out["comms"] > 0
    assert out["vs_device0"]["within_tol"]


def test_multiprocess_phase_with_parent_holding_accelerator(monkeypatch):
    """The chip case, simulated: workers are spawned with JAX on the CPU,
    finish with numpy and jax.Array payloads, and lambdas are refused."""
    monkeypatch.setattr(mp_backend, "held_accelerator", lambda: "tpu")
    out = chip_smoke.phase_multiprocess(timeout_s=120)
    for xp in ("numpy", "jax"):
        assert out[xp]["score"] == 54
        assert out[xp]["start_method"] == "spawn"
        assert out[xp]["workers_opened_accelerator"] is None
    assert "cannot be pickled" in out["lambda_steps"]["refused"]


def test_train_phase_smoke():
    cfg = dataclasses.replace(
        smoke_variant(get_config("granite-moe-1b-a400m")), n_layers=2
    )
    out = chip_smoke.phase_train(
        cfg, steps=2, n_pods=2, global_batch=4, seq_len=32,
        ref_device=jax.devices("cpu")[0],
    )
    assert len(out["losses"]) == 2
    assert out["step0_abs_diff"] <= out["step0_tolerance"]
    assert out["retries"] == 0


@pytest.mark.parametrize("lone", [False, True], ids=["checkout", "lone_copy"])
def test_main_refuses_without_tpu(tmp_path, lone):
    script = ROOT / "chip_smoke.py"
    if lone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent,
        capture_output=True, text=True, timeout=120, env=_cpu_env(),
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    yield compilation_cache
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    compilation_cache.reset_cache()


def test_compile_cache_follows_env_var(tmp_path, monkeypatch, restore_cache_dir):
    target = tmp_path / "xla-cache"
    monkeypatch.setenv(cache.ENV_VAR, str(target))
    checkout_before = (
        sorted(os.listdir(cache.CHECKOUT_CACHE))
        if cache.CHECKOUT_CACHE.exists() else None
    )
    assert cache.configure_compile_cache() == str(target)
    assert jax.config.jax_compilation_cache_dir == str(target)
    restore_cache_dir.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 5 - 3)(jax.numpy.ones(11)).block_until_ready()
    assert any(target.iterdir())
    checkout_after = (
        sorted(os.listdir(cache.CHECKOUT_CACHE))
        if cache.CHECKOUT_CACHE.exists() else None
    )
    assert checkout_after == checkout_before


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    path = cache.configure_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_importing_configures_no_cache():
    code = (
        "import jax, repro.launch.train, repro.launch.cache, repro.swirl; "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    env = _cpu_env()
    env.pop(cache.ENV_VAR, None)
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT / "src", capture_output=True,
        text=True, timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "None"
