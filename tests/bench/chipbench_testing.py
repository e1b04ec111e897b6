"""The chip benchmark's configurations cut to sizes a CPU test can hold
(every width shrunk, which no benchmark cell may do), and a cell maker."""

from __future__ import annotations

import copy

from chipbench import harness

TRAIN_CONFIG = "granite-moe-1b-a400m.4l"
WF_CONFIG = "genomes1k.chr22"


def load_ref(name: str):
    return harness.load_module(harness.reference_path(name), f"chipbench_test_ref_{name}")


def tiny_train() -> tuple[dict, dict]:
    cfg = copy.deepcopy(harness.load_json(harness.config_path(TRAIN_CONFIG)))
    cfg["model"].update(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, vocab=500,
    )
    cfg["model"]["moe"].update(n_experts=8, top_k=2, d_expert=32)
    job = copy.deepcopy(harness.load_json(harness.traffic_path("pods2_b8x1024_int8")))
    job.update(global_batch=4, seq_len=32)
    return cfg, job


def tiny_genomes(individuals: int = 16, sites: int = 1024, sifted: int = 128) -> dict:
    cfg = copy.deepcopy(harness.load_json(harness.config_path(WF_CONFIG)))
    per = individuals // 16
    cfg.update(
        individuals=individuals, sites=sites, sifted_sites=sifted, frequency_bins=8,
        variants=2,
        populations=[
            ["ALL", individuals], ["AFR", 4 * per], ["AMR", 3 * per], ["EAS", 3 * per],
            ["EUR", 3 * per], ["GBR", per], ["SAS", 3 * per],
        ],
    )
    return cfg


def make_cell(config: dict, ref, traffic: dict, *, seed: int, seconds: float = 0.3):
    import jax

    return harness.Cell(
        name="test", config=config, ref=ref, traffic=traffic, seed=seed,
        seconds=seconds, devices=jax.devices(), log=lambda msg: None,
    )
