"""The Mellum2 trainer cell at tiny widths on the CPU: the float32 reference
agrees with a sound run of the ``swirl_trainer_scopes`` runner, and each
fault the cell must catch, planted in the program, turns ``correct`` false;
so does the control.  Its counts match hand counts and the compiled grad
step, and the attribution of trace ops to named scopes works on a
synthetic trace and HLO text."""

from __future__ import annotations

import copy
import re

import jax
import jax.numpy as jnp
import pytest
from .chipbench_testing import make_cell

from chipbench import harness, planted, scopes, train_counts
from chipbench.harness import Outcome, passes
from chipbench.runners import swirl_trainer, swirl_trainer_scopes
from chipbench.xtrace import Event

CONFIG = "mellum2-12b-a2.5b.4l"
TRAFFIC = "pod1_b2x8192"
SEED = 2**31 + 11
# At these widths (bfloat16 weights, 96 tokens a step), over four seeds a
# sound run read loss gaps of 1.5e-4 to 7.3e-4, grad norm gaps of 2.6e-3
# to 1.5e-2 and change gaps of 5e-3 to 9.3e-3; the float8 control read
# 2e-3 to 4e-3, 5.8e-2 to 9.8e-2 and 1.9e-2 to 2.2e-2; each planted fault
# read grad norm gaps of 0.27 and more.  Each limit sits between.
TINY_LIMITS = {"loss_gap": 1.5e-3, "grad_norm_gap": 3e-2, "param_change_gap": 1.5e-2}


def tiny() -> tuple[dict, dict]:
    cfg = copy.deepcopy(harness.load_json(harness.config_path(CONFIG)))
    cfg["model"].update(
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, vocab=512,
        sliding_window=8, yarn_original_len=16,
    )
    cfg["model"]["moe"].update(n_experts=2, n_routed=8, first_expert=4, top_k=4, d_expert=32)
    cfg["limits"] = TINY_LIMITS
    job = copy.deepcopy(harness.load_json(harness.traffic_path(TRAFFIC)))
    job.update(global_batch=2, seq_len=48)
    return cfg, job


@pytest.fixture(scope="module")
def ref():
    """The reference module, its readings computed once per seed."""
    mod = harness.load_module(harness.reference_path(CONFIG), "chipbench_test_ref_mellum")
    readings = mod.reference_readings
    cache: dict = {}

    def cached(m, job, seed, **kw):
        key = (seed, str(kw.get("compute_dtype")))
        if key not in cache:
            cache[key] = readings(m, job, seed, **kw)
        return cache[key]

    mod.reference_readings = cached
    return mod


@pytest.fixture
def blocks_of_16(monkeypatch):
    """The program tiles the 48 positions as it tiles 8k: past a whole-chunk
    limit (32 here, 2048 there), blocks of 16 queries over their live keys."""
    import repro.models.layers as layers

    sdpa = layers.sdpa
    monkeypatch.setattr(layers, "LIVE_BLOCK", 16)
    monkeypatch.setattr(layers, "sdpa", lambda *a, q_chunk=32, **kw: sdpa(*a, q_chunk=q_chunk, **kw))


def run(ref, seed=SEED) -> Outcome:
    cfg, job = tiny()
    return swirl_trainer_scopes.run(make_cell(cfg, ref, job, seed=seed), trace_dir=None)


def test_sound_run_agrees_with_reference(ref, blocks_of_16):
    out = run(ref)
    assert passes(out.checks), out.checks
    assert set(out.checks) == set(TINY_LIMITS)
    assert out.units >= 1 and out.e2e["train_tokens_per_s"] > 0
    # 2 of 8 routed experts are held: about a quarter of the choices.
    assert 0.1 < out.counters["moe_held_share"] < 0.45
    assert out.counters["moe_load_max"] >= 1.0
    cfg, job = tiny()
    want = train_counts.model_flops_per_token(cfg["model"], job["seq_len"])
    assert out.cost["flops_per_token"] == want


@pytest.mark.parametrize("plant", sorted(planted.FAULTS))
def test_fault_turns_correct_false(ref, plant, blocks_of_16, monkeypatch):
    planted.FAULTS[plant](monkeypatch, tiny()[0]["model"])
    out = run(ref)
    assert not passes(out.checks), out.checks


def test_control_fails_the_limits(ref):
    cfg, job = tiny()
    with jax.default_matmul_precision("highest"):
        want = ref.reference_readings(cfg["model"], job, SEED)
        control = ref.reference_readings(
            cfg["model"], job, SEED, compute_dtype=jnp.float8_e4m3fn
        )
    gaps = swirl_trainer.compare(control, want)
    assert not passes({k: (gaps[k], v) for k, v in TINY_LIMITS.items()}), gaps


def test_load_probe_agrees_with_the_counters(ref):
    """``expert_loads.py``'s per-layer probe and the grad step's counters
    count the same choices: the busiest layer's load and the mean share."""
    from repro.models import Model

    cfg, job = tiny()
    m = cfg["model"]
    tool = harness.load_module(harness.ROOT / "benchmarks/chip/expert_loads.py", "t_mellum_loads")
    model = Model(swirl_trainer.model_config(cfg))
    params = jax.jit(lambda k: ref.init_params(m, k))(ref.seed_key_data(SEED))
    data = ref.synthetic_batch(
        vocab=m["vocab"], seq_len=job["seq_len"], global_batch=2, seed=SEED,
        step=0, shard=0, n_shards=1,
    )
    probe = tool.make_probe(model)
    layers = probe(params, data["tokens"])
    assert probe(params, data["tokens"]) == layers  # compiled once, read again
    assert len(layers) == 4
    _, metrics = jax.jit(model.loss)(params, data)
    assert max(s["load_max"] for s in layers) == pytest.approx(float(metrics["moe_load_max"]), rel=1e-6)
    assert sum(s["share"] for s in layers) / 4 == pytest.approx(float(metrics["moe_held_share"]), rel=1e-6)
    for s in layers:
        assert sum(s["held"]) == pytest.approx(s["share"] * 2 * job["seq_len"] * m["moe"]["top_k"])
        assert 0.0 <= s["common"] <= s["top"] + 1e-6 <= 1.0 + 1e-6
        assert s["load_max_centred"] >= 1.0


def test_weights_are_the_programs_layout(ref):
    from repro.models import Model

    cfg, _ = tiny()
    model = Model(swirl_trainer.model_config(cfg))
    swirl_trainer.check_layout(
        ref.param_layout(cfg["model"]), jax.eval_shape(model.init, jax.random.key(0))
    )
    full = harness.load_json(harness.config_path(CONFIG))
    swirl_trainer.check_layout(
        ref.param_layout(full["model"]),
        jax.eval_shape(Model(swirl_trainer.model_config(full)).init, jax.random.key(0)),
    )


def test_config_keeps_the_published_numbers():
    """Every published key is in the file; the changed ones are ``reduced``."""
    doc = harness.load_json(harness.config_path(CONFIG))
    m = doc["model"]
    published = {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert {k: v["published"] for k, v in doc["reduced"].items()} == published
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (
        m["n_layers"], m["moe"]["n_experts"], m["vocab"]
    )
    assert m["vocab"] * 8 == doc["reduced"]["vocab_size"]["published"]
    assert (m["d_model"], m["head_dim"], m["moe"]["d_expert"]) == (
        doc["hidden_size"], doc["head_dim"], doc["moe_intermediate_size"]
    )
    assert (m["moe"]["n_routed"], m["moe"]["top_k"]) == (
        doc["reduced"]["num_experts"]["published"], doc["num_experts_per_tok"]
    )
    kinds = {"sliding_attention": "attn_local", "full_attention": "attn"}
    assert [[kinds[k], "moe"] for k in doc["layer_types"][:4]] == m["pattern"]
    full = doc["rope_parameters"]["full_attention"]
    assert (m["yarn_factor"], m["yarn_original_len"], m["yarn_attention_factor"]) == (
        full["factor"], full["original_max_position_embeddings"], full["attention_factor"]
    )
    assert m["rope_local_theta"] == doc["rope_parameters"]["sliding_attention"]["rope_theta"]


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------


def test_live_blocks_by_hand():
    # 48 queries in blocks of 16, window 8: block 0 reads keys 0..15,
    # block 1 starts at 16 - 7 = 9, floored to 0, so 0..31; block 2 at 25,
    # floored to 16, so 16..47.
    assert train_counts.live_blocks(48, 16, 8) == [(16, 16), (16, 32), (16, 32)]
    assert train_counts.live_blocks(48, 16, 0) == [(16, 16), (16, 32), (16, 48)]
    assert train_counts.live_blocks(48, 12, 8) == [(12, 12), (12, 24), (12, 24), (12, 24)]
    assert train_counts.live_blocks(16, 16, 0) == [(16, 16)]
    # Mellum at 8k in blocks of 512: windowed blocks read 1,536 keys once
    # past the first two; full blocks their causal prefix.
    blocks = train_counts.live_blocks(8192, 512, 1024)
    assert blocks[:3] == [(512, 512), (512, 1024), (512, 1536)] and blocks[-1] == (512, 1536)
    assert sum(k for _, k in train_counts.live_blocks(8192, 512, 0)) == 512 * 16 * 17 // 2


def test_counts_tile_as_the_program():
    """The counts' query blocks are the program's: the whole sequence up to
    ``sdpa``'s ``q_chunk`` default, ``LIVE_BLOCK`` queries past it."""
    import inspect

    import repro.models.layers as layers

    assert train_counts.LIVE_BLOCK == layers.LIVE_BLOCK
    assert train_counts.WHOLE == inspect.signature(layers.sdpa).parameters["q_chunk"].default
    assert train_counts.query_block(1024) == 1024  # granite: one masked chunk
    assert train_counts.query_block(8192) == 512


def test_model_flops_by_hand():
    cfg, _ = tiny()
    m = cfg["model"]
    # mean keys: window 8 over 48 positions = (1+..+8 + 40*8)/48 = 356/48;
    # causal = 24.5
    assert train_counts.mean_keys(48, 8) == pytest.approx(356 / 48)
    assert train_counts.mean_keys(48, 0) == 24.5
    qkvo = 2 * 64 * (64 + 2 * 32 + 64)
    router = 2 * 64 * 8
    experts = (4 * 2 / 8) * 3 * 2 * 64 * 32
    attn = 2 * 2 * 64 * (3 * 356 / 48 + 24.5)
    want = 3 * (4 * (qkvo + router + experts) + attn + 2 * 64 * 512)
    assert train_counts.model_flops_per_token(m, 48) == pytest.approx(want)


def test_executed_work_by_hand():
    cfg, _ = tiny()
    m = cfg["model"]
    # full layer, batch 2: 10 products of 2 * B * Hq * D * q * k per block
    per = 2 * 2 * 4 * 16
    flops, _ = train_counts.attention_work(m, 2, 48, "attn", block=16)
    assert flops == 10 * per * 16 * (16 + 32 + 48)
    flops, _ = train_counts.attention_work(m, 2, 48, "attn_local", block=16)
    assert flops == 3 * 10 * per * 16 * (16 + 32 + 32)
    # one block over all keys: forward, the layer's recompute, backward
    flops, _ = train_counts.attention_work(m, 2, 48, "attn")
    assert flops == 8 * per * 48 * 48
    flops, bytes_ = train_counts.expert_work(m, 100.0)
    assert flops == 12 * 2 * 64 * 32 * 100.0
    assert bytes_ == 12 * ((100.0 * (64 + 32)) * 2 + 4 * 2 * 64 * 32 * 2)


def _products(m: dict, job: dict) -> dict[str, int]:
    """Matrix products per scope in the grad step as traced, every
    rematerialised copy included (the TPU's compiled step keeps them all)."""
    from jax.extend import core as jcore

    from repro.launch.steps import make_grad_step
    from repro.models import Model

    model = Model(swirl_trainer.model_config({"name": CONFIG, "model": m}))
    params = jax.eval_shape(model.init, jax.random.key(0))
    rows = job["global_batch"] // job["n_pods"]
    batch = {k: jax.ShapeDtypeStruct((rows, job["seq_len"]), jnp.int32) for k in ("tokens", "labels")}
    counts = dict.fromkeys(scopes.SCOPES, 0)

    def walk(jaxpr, stack):
        for e in jaxpr.eqns:
            names = f"{stack}/{e.source_info.name_stack}"
            if e.primitive.name in ("dot_general", "ragged_dot_general"):
                found = re.findall(r"(attn\.window|attn\.full|moe\.route|moe\.held)", names)
                if found:
                    counts[found[-1]] += 1
            for p in e.params.values():
                for sub in p if isinstance(p, (list, tuple)) else [p]:
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr, names)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub, names)

    walk(jax.make_jaxpr(make_grad_step(model))(params, batch).jaxpr, "")
    return counts


@pytest.mark.parametrize("key_block", [2048, 16])
def test_products_match_the_traced_step(key_block, blocks_of_16, monkeypatch):
    """10 products per block (and per piece of at most ``KEY_BLOCK`` keys),
    12 grouped products a layer, as the counts assume."""
    import repro.models.layers as layers

    monkeypatch.setattr(layers, "KEY_BLOCK", key_block)
    cfg, job = tiny()
    m = cfg["model"]
    counts = _products(m, job)
    pieces = lambda window: sum(  # noqa: E731
        -(-k // key_block) for _, k in train_counts.live_blocks(job["seq_len"], 16, window)
    )
    assert counts["attn.full"] == 10 * pieces(0)
    assert counts["attn.window"] == 3 * 10 * pieces(8)
    assert counts["moe.held"] == 12 * 4  # three projections, 12 products a layer


# ---------------------------------------------------------------------------
# Attribution of trace ops to scopes
# ---------------------------------------------------------------------------

HLO = """HloModule jit_grad_step, entry_computation_layout={()->()}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %exp.9 = f32[4]{0} exponential(%p), metadata={op_name="jit(grad_step)/attn.full/exp"}
}

ENTRY %main.1 () -> f32[4] {
  %convolution.3 = f32[4]{0} convolution(), metadata={op_name="jit(grad_step)/jit(main)/transpose(jvp(checkpoint))/rematted_computation/attn.window/bqhgd,bkhd->bqhgk/dot_general"}
  %fusion.1 = f32[4]{0} fusion(%convolution.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(grad_step)/jit(main)/attn.full/exp"}
  %fusion.2 = f32[4]{0} fusion(), kind=kLoop, metadata={op_name="jit(grad_step)/jit(main)/moe.route/argsort"}
  %ragged-dot-none.4 = bf16[8,4]{1,0} custom-call(), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.5 = f32[4]{0} copy(%fusion.1), metadata={op_name="jit(grad_step)/jit(main)/attn.fullish/copy"}
  ROOT %add.6 = f32[4]{0} add(%fusion.1, %copy.5)
}
"""


def test_op_scopes_from_hlo_text():
    got = scopes.op_scopes(HLO)
    assert got == {
        "exp.9": "attn.full",
        "convolution.3": "attn.window",
        "fusion.1": "attn.full",
        "fusion.2": "moe.route",
        "ragged-dot-none.4": "moe.held",
    }


def test_scope_seconds_on_a_synthetic_trace():
    dev, host = "/device:TPU:0", "/host:CPU"

    def ev(line, name, start, dur, plane=dev):
        return Event(plane, line, name, float(start), float(dur))

    def op(name, start, dur):
        return ev("XLA Ops", f"%{name} = f32[4]{{0}} op()", start, dur)

    events = [
        ev("python", "bench.window", 100, 1000, plane=host),
        # a grad step half outside the window is not counted
        ev("XLA Modules", "jit_grad_step(7)", 50, 100),
        op("convolution.3", 60, 50),
        # two whole grad steps inside
        ev("XLA Modules", "jit_grad_step(7)", 200, 300),
        op("convolution.3", 200, 100),
        op("fusion.1", 300, 40),
        op("ragged-dot-none.4", 340, 60),
        op("copy.5", 400, 100),
        ev("XLA Modules", "jit_grad_step(7)", 600, 200),
        op("convolution.3", 600, 100),
        op("fusion.2", 700, 100),
        # another program's ops are not the grad step's
        ev("XLA Modules", "jit_update(3)", 900, 100),
        op("fusion.1", 900, 100),
    ]
    t = scopes.scope_seconds(events, scopes.op_scopes(HLO), "jit_grad_step")
    assert t.runs == 2
    assert t.module_s == pytest.approx(500e-9)
    assert t.scope_s == pytest.approx(
        {"attn.window": 200e-9, "attn.full": 40e-9, "moe.route": 100e-9, "moe.held": 60e-9}
    )
    cfg, job = tiny()
    c = swirl_trainer_scopes.scope_counters(cfg["model"], job, t, 0.25)
    assert c["scope_s.attn.window"] == pytest.approx(100e-9)
    assert c["grad_step_s"] == pytest.approx(250e-9)
    assert c["scope_share"] == pytest.approx(400 / 500)
    assert c["attn.window_over_full"] == pytest.approx((100 / 3) / 20)
    flops, _ = train_counts.expert_work(cfg["model"], 0.25 * 2 * 48 * 4 * 4)
    assert c["experts.flops"] == flops


def test_metric_readers_on_counters():
    from chipbench import peaks

    out = Outcome(window_start=0.0, e2e={}, units=1, memory_peak_bytes=0, checks={})
    readers = {
        name: harness.load_module(harness.metric_path(name), f"t_mellum_{name}")
        for name in ("train.attn_roofline", "train.experts_roofline", "train.expert_load_max")
    }
    # Nothing to read: a program without the scopes or counters.
    assert all(r.read(out) is None for r in readers.values())
    out.peaks = peaks.peaks("TPU v5 lite")
    out.counters = {
        "scope_s.attn.window": 0.5, "scope_s.attn.full": 0.5, "scope_s.moe.held": 2.0,
        "attn.flops": 0.5 * 197e12, "attn.bytes": 1.0,
        "experts.flops": 1.0, "experts.bytes": 819e9, "moe_load_max": 1.5,
    }
    assert readers["train.attn_roofline"].read(out) == pytest.approx(50.0)
    assert readers["train.experts_roofline"].read(out) == pytest.approx(50.0)
    assert readers["train.expert_load_max"].read(out) == 1.5
