"""BENCHMARK.json against the rules its format keeps, and every file it names
found by name."""

from __future__ import annotations

import re

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def test_keys_and_names(manifest):
    assert set(manifest) == KEYS["top"]
    for section, kind in (("configs", "config"), ("workloads", "workload"),
                          ("end_to_end", "end_to_end"), ("per_layer", "per_layer")):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names)), section
        for e in manifest[section]:
            assert set(e) - {"workloads"} == KEYS[kind] or set(e) == KEYS[kind], e
            if "workloads" in e:
                assert kind in ("end_to_end", "per_layer"), e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
                assert e["source"] in SOURCES
            for key in ("why", "layer", "source"):
                if key in e and kind != "end_to_end":
                    assert TEXT.match(e[key]), e[key]
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    all_metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(all_metrics) == len(set(all_metrics))


def test_command_and_paths(manifest):
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert (harness.ROOT / p).is_dir()
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32
    assert all(not w.startswith("/") and ".." not in w for w in cmd)
    files = [w for w in cmd if w.endswith(".py")]
    assert files and all(
        any(f.startswith(p.rstrip("/") + "/") for p in manifest["paths"]) for f in files
    )


def test_cells_and_configs(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs), "every configuration has a cell and every cell a configuration"
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for name, c in configs.items():
        assert any(c["file"].startswith(p.rstrip("/") + "/") for p in manifest["paths"])
        doc = harness.load_json(harness.ROOT / c["file"])
        assert doc["name"] == name
        assert set(c["reduced"]) == set(doc["reduced"]), name
        assert harness.reference_path(name).is_file(), name
        assert harness.runner_path(doc["runner"]).is_file(), name
    for w in manifest["workloads"]:
        traffic = harness.load_json(harness.traffic_path(w["traffic"]))
        assert traffic["runner"] == harness.load_json(
            harness.ROOT / configs[w["config"]]["file"]
        )["runner"], w["name"]


def test_metrics_reported_and_readers_found(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:
        reported = [n for n, m in e2e.items() if harness.applies(m, cell)]
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert any(harness.applies(m, cell) for m in manifest["per_layer"]), cell
    layers: dict[str, set] = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert harness.applies(e2e[m["moves"]], cell), (m["name"], cell)
        assert harness.metric_path(m["name"]).is_file(), m["name"]
        reader = harness.load_module(harness.metric_path(m["name"]), f"t_{m['name']}")
        assert callable(reader.read)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%", m["name"]
    assert all(len(v) == 1 for v in layers.values()), "one spelling per layer"


def test_run_seconds_fit_a_full_check(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_manifest_is_small(manifest):
    assert (harness.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
