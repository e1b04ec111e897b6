"""Mellum2-12B-A2.5B at a small size on the CPU, against the plain float32
reference of the chip benchmark's configuration
(``benchmarks/chip/configs/mellum2-12b-a2.5b.4l.py``, which imports
nothing of the program).

The small model keeps every mechanism: 3 windowed layers and 1 full layer
(window 8 over 48 positions, attention in blocks of 16), YaRN on the full
layer with an original length of 16 so that its ramp is active, and a
dropless layer holding 2 of 8 routed experts, top-4.  Everything is
float32, so program and reference differ by summation order alone.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model, ModelConfig, MoECfg, smoke_variant
from repro.models import layers
from repro.models.moe import apply_moe_held, init_moe

REF = Path(__file__).resolve().parents[1] / "benchmarks/chip/configs/mellum2-12b-a2.5b.4l.py"
SEED = 2**33 + 7
# float32 on both sides: the logits agree to 6.3e-7 of their largest, the
# loss to 8e-8, the gradients to 1.5e-6 (worst leaf); the limits leave
# about fifteen times that.  bfloat16 arithmetic would miss them by orders
# of magnitude, and the planted faults must move the logits by 100 times
# the limit (offsetting the held experts by one moves them by 0.53).
LOGIT_TOL = 1e-5
GRAD_TOL = 2e-5


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("mellum_reference", REF)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def small(**moe) -> dict:
    """The model section of the benchmark's configuration, cut small."""
    return {
        "n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 32, "vocab": 256,
        "pattern": [["attn_local", "moe"]] * 3 + [["attn", "moe"]],
        "sliding_window": 8, "rope_theta": 500000.0, "rope_local_theta": 500000.0,
        "yarn_factor": 16.0, "yarn_original_len": 16, "yarn_beta_fast": 32.0,
        "yarn_beta_slow": 1.0, "yarn_attention_factor": 1.2772588722239782,
        "moe": {
            "n_experts": 2, "n_routed": 8, "first_expert": 4, "top_k": 4, "d_expert": 32,
            "n_shared": 0, "dropless": True, "router_aux_weight": 0.01, **moe,
        },
        "tied_embeddings": False, "norm": "rmsnorm", "activation": "silu_glu",
        "dtype": "float32", "remat": True,
    }


@pytest.fixture
def blocks_of_16(monkeypatch):
    """The program tiles the 48 positions as it tiles 8k: past a whole-chunk
    limit (32 here, 2048 there), blocks of 16 queries over their live keys."""
    sdpa = layers.sdpa
    monkeypatch.setattr(layers, "LIVE_BLOCK", 16)
    monkeypatch.setattr(layers, "sdpa", lambda *a, q_chunk=32, **kw: sdpa(*a, q_chunk=q_chunk, **kw))


def program_config(m: dict) -> ModelConfig:
    kw = dict(m)
    kw["pattern"] = tuple(tuple(k) for k in kw["pattern"])
    kw["moe"] = MoECfg(**kw["moe"])
    return ModelConfig(name="mellum-small", **kw)


def batch(m: dict, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, m["vocab"], size=(2, 49), dtype=np.int32)
    return {"tokens": jnp.asarray(tok[:, :-1]), "labels": jnp.asarray(tok[:, 1:])}


def reference_outputs(ref, m, params, b):
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: ref.forward(m, p, t))(params, b["tokens"])
        loss, grads = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(m, p, b)))(params, b)
    return logits, loss, grads


def program_outputs(model, m, params, b):
    logits, _ = jax.jit(model.forward)(params, b["tokens"])
    (loss, _), grads = jax.jit(jax.value_and_grad(model.loss, has_aux=True))(params, b)
    return logits[..., : m["vocab"]], loss, grads


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-12))


@pytest.fixture(scope="module")
def case(ref):
    """Seeded weights, a batch, and the reference's logits, loss and
    gradients on them."""
    m = small()
    params = jax.jit(lambda k: ref.init_params(m, k))(ref.seed_key_data(SEED))
    b = batch(m)
    return params, b, reference_outputs(ref, m, params, b)


def test_logits_loss_and_gradients_match_reference(case, blocks_of_16):
    m = small()
    params, b, want = case
    got = program_outputs(Model(program_config(m)), m, params, b)
    assert rel(got[0], want[0]) < LOGIT_TOL
    assert rel(got[1], want[1]) < LOGIT_TOL
    flat_want = jax.tree_util.tree_flatten_with_path(want[2])[0]
    for (path, g), w in zip(flat_want, jax.tree.leaves(got[2])):
        assert rel(w, g) < GRAD_TOL, jax.tree_util.keystr(path)


def _window_on_full(monkeypatch):
    sdpa = layers.sdpa
    monkeypatch.setattr(
        layers, "sdpa",
        lambda q, k, v, *, causal, window=0, **kw: sdpa(q, k, v, causal=causal, window=window or 8, **kw),
    )
    return {}


def _plain_rope_on_full(monkeypatch):
    monkeypatch.setattr(
        layers, "layer_rope",
        lambda cfg, local: (layers.rope_freqs(cfg.head_dim, cfg.rope_theta), 1.0),
    )
    return {}


def _held_offset_by_one(monkeypatch):
    return {"first_expert": 5}


@pytest.mark.parametrize(
    "plant", [_window_on_full, _plain_rope_on_full, _held_offset_by_one],
    ids=["window_on_full", "plain_rope_on_full", "held_offset_by_one"],
)
def test_planted_fault_fails(case, plant, blocks_of_16, monkeypatch):
    params, b, (want, _, _) = case
    model = Model(program_config(small(**plant(monkeypatch))))
    got, _ = jax.jit(model.forward)(params, b["tokens"])
    assert rel(got, want) > 100 * LOGIT_TOL


def test_shares_add_up_to_the_uncut_layer(ref):
    """The held parts of 4 chips, 2 experts each, add up to the whole layer
    of 8 experts: in the program, and against the reference's."""
    whole = program_config(small(n_experts=8, first_expert=0))
    p = init_moe(jax.random.key(3), whole, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (2, 24, 64), jnp.float32)
    y_whole, aux_whole, c = jax.jit(partial(apply_moe_held, whole))(p, x)
    assert float(c["moe_held_share"]) == 1.0
    total = jnp.zeros_like(y_whole)
    for share in range(4):
        cfg = program_config(small(n_experts=2, first_expert=2 * share))
        part = dict(p, **{k: p[k][2 * share : 2 * share + 2] for k in ("gate", "up", "down")})
        y, aux, _ = jax.jit(partial(apply_moe_held, cfg))(part, x)
        assert float(aux) == pytest.approx(float(aux_whole), rel=1e-6)
        total = total + y
    assert rel(total, y_whole) < 1e-5
    with jax.default_matmul_precision("highest"):
        y_ref, aux_ref = jax.jit(lambda p, x: ref._moe(small(n_experts=8, first_expert=0), p, x, None))(p, x)
    assert rel(y_whole, y_ref) < 1e-5
    assert float(aux_whole) == pytest.approx(float(aux_ref), rel=1e-5)


def test_rows_past_the_groups_are_never_read(monkeypatch):
    """A grouped product leaves the rows past its groups undefined (on the
    TPU they hold whatever was in memory): NaN there must reach neither
    the layer's output nor any gradient."""
    import repro.models.moe as moe

    grouped = jax.lax.ragged_dot

    def poison(a, sizes):
        past = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, a)

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return poison(grouped(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: grouped(a, b, sizes), lhs, rhs)[1](ct)
        return poison(d_lhs, sizes), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(moe.jax.lax, "ragged_dot", poisoned)
    cfg = program_config(small())
    p = init_moe(jax.random.key(5), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(6), (2, 24, 64), jnp.float32)

    def f(p, x):
        y, aux, _ = apply_moe_held(cfg, p, x)
        return jnp.sum(y * y) + aux

    value, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, x)
    assert bool(jnp.isfinite(value))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("key_block", [2048, 16], ids=["whole", "split"])
@pytest.mark.parametrize("window", [0, 8], ids=["causal", "window8"])
@pytest.mark.parametrize("block", [8, 12, 16, 24])
def test_blockwise_attention_matches_masked(window, block, key_block, monkeypatch):
    """Blocks that read only their live keys give the masked attention's
    output and gradients, for blocks larger, smaller and not a multiple of
    the window, with each block's softmax whole or split over keys."""
    monkeypatch.setattr(layers, "KEY_BLOCK", key_block)
    monkeypatch.setattr(layers, "LIVE_BLOCK", block)
    ks = jax.random.split(jax.random.key(block + window), 4)
    q = jax.random.normal(ks[0], (2, 48, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 48, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 48, 2, 16), jnp.float32)
    ct = jax.random.normal(ks[3], (2, 48, 4, 16), jnp.float32)

    @partial(jax.jit, static_argnums=0)
    def attend(whole):
        f = lambda q, k, v: layers.sdpa(q, k, v, causal=True, window=window, q_chunk=whole)  # noqa: E731
        out, vjp = jax.vjp(f, q, k, v)
        return out, vjp(ct)

    out, grads = attend(24)  # past 24 queries: blocks of LIVE_BLOCK
    want, want_grads = attend(48)  # one chunk: every key, masked
    assert rel(out, want) < 1e-5
    for g, w in zip(grads, want_grads):
        assert rel(g, w) < 1e-5


def _published_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """A transcription of the published YaRN rope initialisation."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    inv_freq_extrapolation_factor = 1 - np.clip(linear, 0, 1)
    inv = (
        inv_freq_interpolation * (1 - inv_freq_extrapolation_factor)
        + inv_freq_extrapolation * inv_freq_extrapolation_factor
    )
    return inv, (low, high)


def test_yarn_matches_the_published_formula(ref):
    cfg = get_config("mellum2-12b-a2.5b")
    want, (low, high) = _published_yarn(128, 500000.0, 16.0, 8192, 32.0, 1.0)
    assert (low, high) == (18, 35)  # Mellum's correction range
    inv, scale = layers.layer_rope(cfg, local=False)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0), want, rtol=1e-6)
    assert scale == 1.2772588722239782
    # Below the range the frequencies are kept, above it divided by 16.
    np.testing.assert_allclose(np.asarray(inv)[:18], 1.0 / 500000.0 ** (np.arange(0, 36, 2) / 128), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(inv)[35:], 1.0 / (16 * 500000.0 ** (np.arange(70, 128, 2) / 128)), rtol=1e-5)
    local, local_scale = layers.layer_rope(cfg, local=True)
    np.testing.assert_allclose(np.asarray(local), 1.0 / 500000.0 ** (np.arange(0, 128, 2) / 128), rtol=1e-5)
    assert local_scale == 1.0


def test_published_config_counts():
    cfg = get_config("mellum2-12b-a2.5b")
    assert [k for k, _ in cfg.layer_seq()[:4]] == ["attn_local"] * 3 + ["attn"]
    assert (cfg.moe.n_experts, cfg.moe.router_width, cfg.moe.top_k, cfg.moe.d_expert) == (64, 64, 8, 896)
    assert 11.9e9 < cfg.param_count() < 12.3e9  # "12B"
    assert 2.4e9 < cfg.active_param_count() < 2.6e9  # "A2.5B"
    # A chip's share: 8 of 64 experts held, so an eighth of the top-8
    # choices (one expert a token) is active here.
    share = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, n_experts=8, n_routed=64))
    d, de = cfg.d_model, cfg.moe.d_expert
    assert cfg.param_count() - share.param_count() == 28 * 56 * 3 * d * de
    assert share.param_count() - share.active_param_count() == 28 * 7 * 3 * d * de


def test_smoke_variant_keeps_the_share_and_rope():
    cfg = get_config("mellum2-12b-a2.5b")
    share = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=8, n_routed=64, first_expert=24)
    )
    s = smoke_variant(share)
    assert (s.moe.n_routed, s.moe.n_experts, s.moe.first_expert) == (8, 2, 3)
    assert s.moe.dropless and s.yarn_factor == 16.0 and s.rope_local_theta == 500000.0
    assert s.yarn_attention_factor == cfg.yarn_attention_factor


def test_trains_through_train_on_the_cpu():
    from repro.launch.train import train

    out = train(
        "mellum2-12b-a2.5b", smoke=True, steps=2, n_pods=1, global_batch=2,
        seq_len=32, ckpt_dir=None, compress_grads=False, log_every=1,
    )
    last = out["history"][-1]
    assert math.isfinite(last["loss"])
    assert last["moe_held_share"] == 1.0  # the registered layer holds all 64
    assert last["moe_load_max"] >= 1.0
