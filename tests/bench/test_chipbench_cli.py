"""The benchmark's command refuses to run where it cannot measure: with no
TPU, and without the system under test beside it."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from chipbench import harness

ARGS = ["--workload", "genomes_chr22_1chip", "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"]


def _run(cwd, *, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def _no_result(proc) -> bool:
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    manifest = harness.load_json(harness.ROOT / "BENCHMARK.json")
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(
            harness.ROOT / p, tmp_path / p,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run(tmp_path, env=env)
    assert proc.returncode != 0
    assert _no_result(proc)
