"""The trainer cell's run at tiny widths on the CPU: the float32 reference
agrees with a sound run, and each fault the cell can have, planted under
the timed path, turns ``correct`` false; so does the control, the
reference computed with float8 operands and live gradients."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from .chipbench_testing import TRAIN_CONFIG, load_ref, make_cell, tiny_train

from chipbench.runners import swirl_trainer
from chipbench.harness import passes

SEED = 2**31 + 5
# The cell's limits are set from readings at its own size.  At these widths
# a loss averages 64 times fewer tokens and a leaf holds far fewer
# elements, so bfloat16's noise reads several times higher (a sound run
# reads 2e-3 to 1.8e-2 and 5e-3 to 1e-2 over three seeds); the limits here
# scale with it and stay under what every fault reads.  At these widths
# the float8 control's gradient norms read no further from the reference
# than bfloat16's (1.6e-2 to 4e-2); its loss does (6.5e-3 to 1.1e-2
# against a sound run's 1.1e-3 to 2.3e-3), so here the loss gap carries
# the control's failure, as the gradient norms do at the cell's size.
TINY_LIMITS = {"loss_gap": 4e-3, "grad_norm_gap": 2e-2, "param_change_gap": 5e-2}


@pytest.fixture(scope="module")
def ref():
    """The reference module, its readings computed once per seed."""
    mod = load_ref(TRAIN_CONFIG)
    readings = mod.reference_readings
    cache: dict = {}

    def cached(m, job, seed, **kw):
        key = (seed, kw.get("steps"), str(kw.get("compute_dtype")))
        if key not in cache:
            cache[key] = readings(m, job, seed, **kw)
        return cache[key]

    mod.reference_readings = cached
    return mod


def run(ref):
    cfg, job = tiny_train()
    cfg["limits"] = TINY_LIMITS
    return swirl_trainer.run(make_cell(cfg, ref, job, seed=SEED), trace_dir=None)


def test_sound_run_agrees_with_reference(ref):
    out = run(ref)
    assert passes(out.checks), out.checks
    assert out.units >= 1 and out.e2e["train_tokens_per_s"] > 0
    assert out.host["plan_compile_ms"] > 0
    assert set(out.checks) == set(TINY_LIMITS)


def _state_unchanged(monkeypatch):
    import repro.optim.adamw as adamw

    def update(cfg, grads, state, params):
        zero = jnp.zeros((), jnp.float32)
        return params, state, {"grad_norm": zero, "lr": zero}

    monkeypatch.setattr(adamw, "update", update)


def _half_batch(monkeypatch):
    import repro.launch.steps as steps

    make = steps.make_grad_step

    def make_half(model):
        grad_step = make(model)
        return lambda params, batch: grad_step(
            params, jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
        )

    monkeypatch.setattr(steps, "make_grad_step", make_half)


def _no_exchange(monkeypatch):
    import repro.launch.train as train

    monkeypatch.setattr(train, "allreduce_mean", lambda parts: parts[0])


@pytest.mark.parametrize(
    "plant", [_state_unchanged, _half_batch, _no_exchange],
    ids=["state_unchanged", "half_batch", "no_exchange"],
)
def test_fault_turns_correct_false(ref, plant, monkeypatch):
    plant(monkeypatch)
    out = run(ref)
    assert not passes(out.checks), out.checks


def test_control_fails_the_limits(ref):
    cfg, job = tiny_train()
    with jax.default_matmul_precision("highest"):
        want = ref.reference_readings(cfg["model"], job, SEED, steps=3)
        control = ref.reference_readings(
            cfg["model"], job, SEED, steps=3, compute_dtype=jnp.float8_e4m3fn
        )
    gaps = swirl_trainer.compare(control, want)
    assert not passes({k: (gaps[k], v) for k, v in TINY_LIMITS.items()}), gaps


def test_control_keeps_gradients_alive(ref):
    # The rounding is straight through: the control's first gradient is
    # the reference's taken on rounded operands, not one flushed to zero.
    cfg, job = tiny_train()
    with jax.default_matmul_precision("highest"):
        want = ref.reference_readings(cfg["model"], job, SEED, steps=3)
        control = ref.reference_readings(
            cfg["model"], job, SEED, steps=3, compute_dtype=jnp.float8_e4m3fn
        )
    for name, norm in want["grad_norms"].items():
        assert control["grad_norms"][name] > 0.5 * norm, name
    assert control["losses"] != want["losses"]


def test_token_stream_is_the_trainers():
    from repro.data import SyntheticLM

    ref = load_ref(TRAIN_CONFIG)
    data = SyntheticLM(vocab=500, seq_len=16, global_batch=4, seed=SEED)
    for step, shard in ((0, 0), (3, 1)):
        want = data.batch(step, shard=shard, n_shards=2)
        got = ref.synthetic_batch(
            vocab=500, seq_len=16, global_batch=4, seed=SEED, step=step,
            shard=shard, n_shards=2,
        )
        for k in want:
            assert (got[k] == want[k]).all()


def test_weights_are_the_programs_layout():
    from repro.models import Model

    cfg, _ = tiny_train()
    ref = load_ref(TRAIN_CONFIG)
    model = Model(swirl_trainer.model_config(cfg))
    swirl_trainer.check_layout(
        ref.param_layout(cfg["model"]), jax.eval_shape(model.init, jax.random.key(0))
    )
    a = jax.jit(lambda k: ref.init_params(cfg["model"], k))(ref.seed_key_data(SEED))
    b = jax.jit(lambda k: ref.init_params(cfg["model"], k))(ref.seed_key_data(SEED))
    c = jax.jit(lambda k: ref.init_params(cfg["model"], k))(ref.seed_key_data(SEED + 1))
    assert all((x == y).all() for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not (a["embed"] == c["embed"]).all()
