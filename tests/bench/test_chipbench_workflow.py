"""The genomes cells' run at tiny sizes on the CPU: the plain reference
agrees with the SWIRL ``jax`` run, fused and not; each fault the cell can
have, planted under the timed path, turns ``correct`` false; so does the
control, the reference accumulating in bfloat16."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from .chipbench_testing import WF_CONFIG, load_ref, make_cell, tiny_genomes

from chipbench.runners import swirl_workflow
from chipbench.harness import passes

SEED = 2**31 + 11


@pytest.fixture
def wf_ref():
    return load_ref(WF_CONFIG)


def run(ref, *, fuse: bool = True, cfg: dict | None = None):
    traffic = {"runner": "swirl_workflow", "placement": "one_device", "fuse": fuse}
    cell = make_cell(cfg or tiny_genomes(), ref, traffic, seed=SEED)
    return swirl_workflow.run(cell, trace_dir=None)


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "op_by_op"])
def test_swirl_run_agrees_with_reference(wf_ref, fuse):
    out = run(wf_ref, fuse=fuse)
    assert passes(out.checks), out.checks
    assert set(out.checks) == {"overlap_gap", "count_gap", "histogram_gap"}
    assert out.failed == 0 and out.units >= 2
    # s0, 8 individuals, merge, sifting, 7 overlaps and 7 frequencies
    assert out.counters["execs"] == 25 * out.units
    assert out.counters["comms"] > 0
    assert out.e2e["wf_makespan_p95_ms"] > 0


def test_plan_adds_one_output_to_each_sink(wf_ref):
    from repro.core.translate import genomes_1000

    inst = swirl_workflow.with_sink_outputs(genomes_1000(n=8, m=7, a=2, b=2, c=2), wf_ref.out_name)
    sinks = {s for s in inst.workflow.steps if s.startswith(("sMO_", "sF_"))}
    assert len(sinks) == 14
    for s in sinks:
        assert inst.out_data(s) == frozenset({wf_ref.out_name(s)})
    assert len(inst.locations) == 9


def _marks_unchanged(ref, monkeypatch):
    monkeypatch.setattr(ref, "_mark", lambda genotypes: genotypes)


def _half_merged(ref, monkeypatch):
    merge = ref._merge

    def half(blocks):
        k = len(blocks) // 2
        return merge(blocks[:k] + [jnp.zeros_like(b) for b in blocks[k:]])

    monkeypatch.setattr(ref, "_merge", half)


def _no_exchange(ref, monkeypatch):
    # The merged marks never arrive where they are sent: the receiver
    # holds an empty buffer of the right shape.
    put = jax.device_put
    shape = (tiny_genomes()["sites"], tiny_genomes()["individuals"])

    def drop(x, *args, **kw):
        if getattr(x, "shape", None) == shape:
            return jnp.zeros_like(x)
        return put(x, *args, **kw)

    monkeypatch.setattr(jax, "device_put", drop)


def _answer_altered(ref, monkeypatch):
    overlap = ref._overlap
    monkeypatch.setattr(ref, "_overlap", lambda *a: overlap(*a).at[0, 0].add(1.0))


@pytest.mark.parametrize(
    "plant", [_marks_unchanged, _half_merged, _no_exchange, _answer_altered],
    ids=["state_unchanged", "half_batch", "no_exchange", "answer_altered"],
)
def test_fault_turns_correct_false(wf_ref, plant, monkeypatch):
    plant(wf_ref, monkeypatch)
    out = run(wf_ref)
    assert not passes(out.checks), out.checks
    assert out.failed > 0


def test_control_fails_the_limits(wf_ref):
    # Carrier counts pass 256 here, where bfloat16 stops counting exactly.
    cfg = tiny_genomes(individuals=640, sites=512, sifted=128)
    data = wf_ref.make_data(cfg, SEED)
    want = wf_ref.reference(cfg, data, 0)
    control = wf_ref.reference(cfg, data, 0, accumulate=jnp.bfloat16)
    gaps = wf_ref.compare(cfg, control, want)
    assert not passes({k: (v, cfg["limits"][k]) for k, v in gaps.items()}), gaps


def test_data_is_seeded_and_skewed(wf_ref):
    cfg = tiny_genomes(individuals=64, sites=2048)
    a, b = wf_ref.make_data(cfg, SEED), wf_ref.make_data(cfg, SEED)
    c = wf_ref.make_data(cfg, SEED + 1)
    assert all((x == y).all() for x, y in zip(a.blocks, b.blocks))
    assert not (a.blocks[0] == c.blocks[0]).all()
    g = np.concatenate([np.asarray(x) for x in a.blocks], axis=0)  # sites x individuals
    assert g.shape == (2048, 64)
    assert set(np.unique(g)) <= {0, 1, 2}
    carriers = (g > 0).mean(axis=1)
    assert np.median(carriers) < carriers.mean()  # most sites rare
    sizes = [len(p) for p in a.pops[0]]
    assert sizes == [n for _, n in cfg["populations"]]
    assert set(np.asarray(a.pops[0][5])) <= set(np.asarray(a.pops[0][4]))  # GBR in EUR
