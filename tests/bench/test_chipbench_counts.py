"""The FLOP and byte counts the per-layer metrics divide by, against counts
made by hand at tiny sizes."""

from __future__ import annotations

import pytest
from .chipbench_testing import WF_CONFIG, load_ref, tiny_genomes, tiny_train

from chipbench.flops import lm_forward_flops_per_token, lm_train_flops_per_token


def test_lm_flops_by_hand():
    cfg, _ = tiny_train()
    m = cfg["model"]  # d 64, 4 heads of 16 (2 kv), 8 experts top 2 of 32, vocab 500
    seq = 32
    attn_proj = 2 * 64 * (64 + 2 * 32 + 64)  # q, k, v, o
    attn_core = 2 * 2 * 64 * (seq + 1) / 2  # scores and values at the causal mean
    moe = 2 * 64 * 8 + 2 * 3 * 2 * 64 * 32  # router, 2 routed gated MLPs
    per_layer = attn_proj + attn_core + moe
    head = 2 * 64 * 500
    assert lm_forward_flops_per_token(m, seq) == pytest.approx(2 * per_layer + head)
    assert lm_train_flops_per_token(m, seq) == pytest.approx(3 * (2 * per_layer + head))


def test_granite_flops_per_token():
    from chipbench import harness

    m = harness.load_json(harness.config_path("granite-moe-1b-a400m.4l"))["model"]
    # 4 layers of 6.29 M (projections) + 2.10 M (causal attention at 1024)
    # + 25.2 M (8 experts) + 65.5 k (router), and a 100.7 M LM head.
    per_layer = 6291456 + 2 * 2 * 1024 * 1025 / 2 + 25165824 + 65536
    assert lm_forward_flops_per_token(m, 1024) == pytest.approx(4 * per_layer + 100669440)


def test_genomes_step_costs_by_hand():
    cfg = tiny_genomes(individuals=32, sites=1024, sifted=128)
    costs = load_ref(WF_CONFIG).step_costs(cfg)
    assert costs["s0"] == (0.0, 0.0)
    assert costs["sI_1"] == (0.0, 2 * 128 * 32)  # 128 sites a range, int8 in and out
    assert costs["sIM"] == (0.0, 2 * 32 * 1024)
    assert costs["sSF"] == (0.0, 4 * 1024 + 4 * 128)
    # ALL: 32 members, the 128 sifted sites of each, a 32 x 32 float32 result
    assert costs["sMO_1"] == (2 * 32 * 32 * 128, 32 * 128 + 4 * (128 + 32 + 32 * 32))
    # GBR (2 members): their rows, then counts and the histogram
    assert costs["sF_6"] == (0.0, 2 * 1024 + 4 * (2 + 8 + 1024))
    assert len(costs) == 1 + 8 + 1 + 1 + 2 * 7


def test_readers_on_a_made_up_run():
    from chipbench import harness
    from chipbench.peaks import peaks
    from chipbench.xtrace import Reduction

    def read(name, out):
        return harness.load_module(harness.metric_path(name), f"t_read_{name}").read(out)

    v5e = peaks("TPU v5 lite")
    # Two steps per instance: 1.97e12 FLOPs (10 ms at the bf16 peak) and
    # 8.19e9 bytes (10 ms at the HBM bandwidth); 4 instances on 2 chips
    # that were busy 50 ms each.
    out = harness.Outcome(
        window_start=0.0, e2e={"train_tokens_per_s": 1e5}, units=4,
        memory_peak_bytes=1, checks={},
        counters={"comms": 8.0, "execs": 10.0, "fused_execs": 4.0},
        host={"plan_compile_ms": 2.5},
        cost={"step_costs": [(1.97e12, 0.0), (0.0, 8.19e9)], "flops_per_token": 1e9},
        peaks=v5e, chips=2,
        reduction=Reduction(
            window_s=0.1, busy_s=0.05, busy_by_device={"a": 0.05, "b": 0.05},
            idle_share=0.5, device_ops=[], idle_gaps=[], span_counts={},
        ),
    )
    assert read("wf.step_roofline", out) == pytest.approx(100 * 4 * 0.02 / 0.1)
    assert read("wf.idle_share", out) == pytest.approx(50.0)
    assert read("wf.comms_per_instance", out) == pytest.approx(2.0)
    assert read("wf.fused_exec_share", out) == pytest.approx(40.0)
    assert read("train.mfu", out) == pytest.approx(100 * 1e14 / (2 * 197e12))
    assert read("train.plan_compile_ms", out) == 2.5
    # Nothing to read: no trace, no counters.
    bare = harness.Outcome(window_start=0.0, e2e={}, units=0, memory_peak_bytes=1, checks={})
    assert all(
        read(n, bare) is None
        for n in ("wf.step_roofline", "wf.idle_share", "wf.comms_per_instance",
                  "wf.fused_exec_share", "train.mfu", "train.idle_share")
    )
