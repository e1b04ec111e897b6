"""The ``multiprocess`` backend: real OS processes, typed failure, resume.

Covers the ISSUE-3 acceptance criteria: distinct PIDs per location group,
results identical to the other backends (including on the 1000 Genomes
workflow), no leaked worker processes after success *or* failure, a killed
worker surfacing as :class:`WorkerFailedError` naming the right location
and step, and checkpoint/restore resuming to the same result without
re-executing completed steps.
"""

from __future__ import annotations

import glob
import multiprocessing as mp
import os
import signal

import numpy as np
import pytest

from repro import swirl
from repro.backends import WorkerFailedError, available_backends, get_backend
from repro.backends.multiprocess import assign_workers
from repro.core.translate import genomes_1000

EDGES = {
    "preprocess": ["train_a", "train_b"],
    "train_a": ["evaluate"],
    "train_b": ["evaluate"],
    "evaluate": ["report"],
    "report": [],
}
MAPPING = {
    "preprocess": ("cpu0",),
    "train_a": ("gpu0",),
    "train_b": ("gpu1",),
    "evaluate": ("gpu0",),
    "report": ("cpu0",),
}


def quickstart_steps():
    return {
        "preprocess": lambda inp: {"d^preprocess": list(range(10))},
        "train_a": lambda inp: {"d^train_a": sum(inp["d^preprocess"])},
        "train_b": lambda inp: {"d^train_b": max(inp["d^preprocess"])},
        "evaluate": lambda inp: {
            "d^evaluate": inp["d^train_a"] + inp["d^train_b"]
        },
        "report": lambda inp: {},
    }


@pytest.fixture
def plan():
    return swirl.trace(EDGES, mapping=MAPPING).optimize()


def _pid_gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    except PermissionError:  # pragma: no cover - alive but not ours
        return False
    return False


def _assert_no_workers_left(program) -> None:
    assert not mp.active_children(), "worker processes were not reaped"
    assert program.last_pids, "run never recorded its worker pids"
    leaked = [pid for pid in program.last_pids.values() if not _pid_gone(pid)]
    if leaked:  # pragma: no cover - best-effort second opinion
        try:
            import psutil

            leaked = [
                p for p in leaked if psutil.pid_exists(p)
            ]
        except ModuleNotFoundError:
            pass
    assert not leaked, f"orphan worker processes: {leaked}"


# ---------------------------------------------------------------------------
# Real processes, correct results
# ---------------------------------------------------------------------------


class TestProcessIsolation:
    def test_registered_with_checkpoint_capability(self):
        b = get_backend("multiprocess")
        assert "multiprocess" in available_backends()
        assert "checkpoint" in b.capabilities

    def test_each_location_group_is_a_distinct_os_process(self, plan):
        exe = plan.lower("multiprocess").compile(quickstart_steps())
        result = exe.run()
        pids = result.stats["pids"]
        assert len(pids) == result.stats["workers"] == 3
        assert len(set(pids.values())) == 3, "workers shared a process"
        assert os.getpid() not in pids.values(), "a worker ran in-process"
        _assert_no_workers_left(exe.program)

    def test_identical_to_every_other_backend(self, plan):
        results = {
            b: plan.lower(b).compile(quickstart_steps()).run().data
            for b in available_backends()
        }
        reference = results.pop("multiprocess")
        for backend, data in results.items():
            assert data == reference, f"{backend} diverged from multiprocess"

    def test_identical_on_1000_genomes(self):
        inst = genomes_1000(n=2, m=2, a=1, b=1, c=1)
        plan = swirl.trace(inst).optimize()
        fns = {}
        for s in inst.workflow.steps:
            outs = inst.out_data(s)
            fns[s] = lambda i, s=s, outs=outs: {
                o: f"{s}({','.join(sorted(map(str, i)))})" for o in outs
            }
        init = {("l^d", d): f"raw:{d}" for d in inst.g("l^d")}
        results = {
            b: plan.lower(b, **({"timeout_s": 60} if b in ("threaded", "multiprocess") else {}))
            .compile(fns)
            .run(initial_payloads=dict(init))
            .data
            for b in available_backends()
        }
        reference = results.pop("multiprocess")
        for backend, data in results.items():
            assert data == reference, f"{backend} diverged on 1000 Genomes"

    def test_initial_payloads_reach_their_worker(self, plan):
        init = {("cpu0", "seed"): [5, 6, 7]}
        result = (
            plan.lower("multiprocess")
            .compile(quickstart_steps())
            .run(initial_payloads=dict(init))
        )
        threaded = (
            plan.lower("threaded")
            .compile(quickstart_steps())
            .run(initial_payloads=dict(init))
        )
        assert result.payload("cpu0", "seed") == [5, 6, 7]
        assert result.data == threaded.data


# ---------------------------------------------------------------------------
# Worker assignment: spatial constraints, workers=, schedule pinning
# ---------------------------------------------------------------------------


class TestWorkerAssignment:
    def test_default_one_process_per_location(self, plan):
        groups = assign_workers(plan.system)
        assert groups == [("cpu0",), ("gpu0",), ("gpu1",)]

    def test_spatial_constraint_locations_share_a_process(self):
        mapping = dict(MAPPING, evaluate=("gpu0", "gpu1"))
        plan = swirl.trace(EDGES, mapping=mapping).optimize()
        groups = assign_workers(plan.system)
        assert ("gpu0", "gpu1") in groups
        result = plan.lower("multiprocess").compile(quickstart_steps()).run()
        assert result.payload("cpu0", "d^evaluate") == 54
        assert result.stats["workers"] == 2

    def test_workers_option_packs_groups(self, plan):
        result = (
            plan.lower("multiprocess", workers=2)
            .compile(quickstart_steps())
            .run()
        )
        assert result.stats["workers"] == 2
        assert len(set(result.stats["pids"].values())) == 2
        assert result.payload("cpu0", "d^evaluate") == 54

    def test_workers_must_be_positive(self, plan):
        exe = plan.lower("multiprocess", workers=0).compile(
            quickstart_steps()
        )
        with pytest.raises(ValueError, match="workers"):
            exe.run()

    def test_schedule_pins_network_groups_to_processes(self):
        from repro.sched import NetworkModel

        inst = genomes_1000(n=2, m=2, a=1, b=1, c=1)
        net = NetworkModel.preset("two-rack").bind(sorted(inst.locations))
        plan = swirl.trace(inst).optimize().schedule(net)
        groups = assign_workers(
            plan.system, schedule=plan.schedule_report
        )
        # Every rack maps onto exactly one worker process.
        racks = {}
        for loc in plan.system.locations():
            racks.setdefault(net.group_of(loc), set()).add(loc)
        for members in racks.values():
            owners = {g for g in groups if members & set(g)}
            assert len(owners) == 1, f"rack {members} split across {owners}"

    def test_memory_transport_rejected(self, plan):
        exe = plan.lower("multiprocess", transport="memory").compile(
            quickstart_steps()
        )
        with pytest.raises(ValueError, match="cannot cross process"):
            exe.run()

    def test_unknown_option_rejected_at_lower_time(self, plan):
        with pytest.raises(TypeError, match="unknown options"):
            plan.lower("multiprocess", warp_speed=True)


class TestParentHoldingAccelerator:
    """A coordinator that holds a TPU never forks its workers."""

    def test_cpu_only_process_holds_no_accelerator(self):
        from repro.backends.multiprocess import held_accelerator

        assert held_accelerator() is None

    def test_fork_refused(self, plan, monkeypatch):
        from repro.backends import multiprocess as mpb

        monkeypatch.setattr(mpb, "held_accelerator", lambda: "tpu")
        exe = plan.lower("multiprocess", start_method="fork").compile(
            quickstart_steps()
        )
        with pytest.raises(mpb.AcceleratorHeldError, match="holds a tpu"):
            exe.run()
        assert not mp.active_children()

    def test_unpicklable_steps_refused_before_any_worker(
        self, plan, monkeypatch
    ):
        from repro.backends import multiprocess as mpb

        monkeypatch.setattr(mpb, "held_accelerator", lambda: "tpu")
        exe = plan.lower("multiprocess").compile(quickstart_steps())
        with pytest.raises(mpb.AcceleratorHeldError, match="cannot be pickled"):
            exe.run()
        assert not mp.active_children()


# ---------------------------------------------------------------------------
# Fault injection: worker death, orphan hygiene, checkpoint/restore
# ---------------------------------------------------------------------------


class TestWorkerFailure:
    def test_killed_worker_names_location_and_step(self, plan):
        exe = plan.lower(
            "multiprocess", _kill_at_step="evaluate", timeout_s=60
        ).compile(quickstart_steps())
        with pytest.raises(WorkerFailedError) as e:
            exe.run()
        assert e.value.location == "gpu0"  # evaluate's location
        assert e.value.step == "evaluate"
        assert e.value.exitcode == -signal.SIGKILL
        _assert_no_workers_left(exe.program)

    def test_step_exception_surfaces_as_worker_failed(self, plan):
        steps = quickstart_steps()
        steps["train_b"] = lambda inp: (_ for _ in ()).throw(
            ValueError("boom")
        )
        exe = plan.lower("multiprocess", timeout_s=60).compile(steps)
        with pytest.raises(WorkerFailedError) as e:
            exe.run()
        assert e.value.location == "gpu1"
        assert e.value.step == "train_b"
        assert "boom" in e.value.reason
        _assert_no_workers_left(exe.program)

    def test_checkpoint_restore_resumes_to_same_result(self, plan, tmp_path):
        log = tmp_path / "execs.log"

        def logged_steps():
            steps = {}
            for name, fn in quickstart_steps().items():

                def wrapper(inp, _name=name, _fn=fn):
                    with open(log, "a") as f:
                        f.write(f"{_name}\n")
                    return _fn(inp)

                steps[name] = wrapper
            return steps

        clean = plan.lower("multiprocess").compile(quickstart_steps()).run()

        exe = plan.lower(
            "multiprocess", _kill_at_step="evaluate", timeout_s=60
        ).compile(logged_steps())
        with pytest.raises(WorkerFailedError):
            exe.run()
        ckpt = exe.checkpoint()
        # The upstream steps' deltas were harvested before the crash.
        assert {"preprocess", "train_a", "train_b"} <= set(
            ckpt.completed_execs
        )
        assert "evaluate" not in ckpt.completed_execs

        log.write_text("")  # only the resumed run's executions from here
        restored = (
            plan.lower("multiprocess", timeout_s=60)
            .compile(logged_steps())
            .restore(ckpt)
            .run()
        )
        assert restored.data == clean.data
        rerun = set(log.read_text().split())
        assert "preprocess" not in rerun, "completed step was re-executed"
        assert "train_a" not in rerun and "train_b" not in rerun
        assert "evaluate" in rerun
        _assert_no_workers_left(restored and exe.program)

    def test_checkpoint_after_success_skips_everything(self, plan, tmp_path):
        log = tmp_path / "execs.log"
        steps = {}
        for name, fn in quickstart_steps().items():

            def wrapper(inp, _name=name, _fn=fn):
                with open(log, "a") as f:
                    f.write(f"{_name}\n")
                return _fn(inp)

            steps[name] = wrapper

        exe = plan.lower("multiprocess").compile(steps)
        first = exe.run()
        ckpt = exe.checkpoint()
        assert set(ckpt.completed_execs) == set(EDGES)
        log.write_text("")
        restored = (
            plan.lower("multiprocess").compile(steps).restore(ckpt).run()
        )
        assert restored.data == first.data
        assert log.read_text() == "", "restore re-executed completed steps"

    def test_cross_backend_checkpoint_restore(self, plan):
        """An inprocess snapshot resumes on multiprocess (same final data)."""
        inproc = plan.lower("inprocess").compile(quickstart_steps())
        done = inproc.run()
        ckpt = inproc.checkpoint()
        restored = (
            plan.lower("multiprocess")
            .compile(quickstart_steps())
            .restore(ckpt)
            .run()
        )
        assert restored.data == done.data


# ---------------------------------------------------------------------------
# Elastic recovery: SIGKILLed workers are renamed onto spares / folded onto
# survivors mid-run, without re-executing checkpointed steps
# ---------------------------------------------------------------------------


def _logged_steps(log):
    """Step bodies that append their name to ``log`` on every *execution*
    (a replayed recorded output writes nothing)."""
    steps = {}
    for name, fn in quickstart_steps().items():

        def wrapper(inp, _name=name, _fn=fn):
            with open(log, "a") as f:
                f.write(f"{_name}\n")
            return _fn(inp)

        steps[name] = wrapper
    return steps


class TestElasticRecovery:
    def test_spare_recovery_survives_sigkill(self, plan, tmp_path):
        log = tmp_path / "execs.log"
        clean = plan.lower("multiprocess", timeout_s=60).compile(
            quickstart_steps()
        ).run()
        exe = plan.lower(
            "multiprocess",
            timeout_s=60,
            _kill_at_step="evaluate",
            recover="spare",
            spares=["spare0"],
            trace=True,
        ).compile(_logged_steps(log))
        result = exe.run()

        recs = result.stats["recoveries"]
        assert len(recs) == 1
        rec = recs[0]
        assert rec["mode"] == "spare"
        assert rec["failed_step"] == "evaluate"
        assert rec["dead"] == ["gpu0"]
        assert rec["renaming"] == {"gpu0": "spare0"}
        # Same results as the unperturbed run, modulo the renaming.
        assert result.data == {
            ("spare0" if l == "gpu0" else l): d for l, d in clean.data.items()
        }
        # Checkpointed steps were replayed, never re-executed: every step
        # body ran exactly once across both fleets (`evaluate` was killed
        # *before* its body, so its single run is post-recovery).
        executed = log.read_text().split()
        assert sorted(executed) == sorted(quickstart_steps())
        # The recovery is visible as a phase span on the renamed location.
        spans = [
            s for s in result.profile.spans if s.name == "recover:spare"
        ]
        assert len(spans) == 1
        assert spans[0].kind == "phase"
        assert (spans[0].src, spans[0].dst) == ("gpu0", "spare0")
        _assert_no_workers_left(exe.program)

    def test_fold_recovery_merges_onto_survivor(self, plan, tmp_path):
        log = tmp_path / "execs.log"
        clean = plan.lower("multiprocess", timeout_s=60).compile(
            quickstart_steps()
        ).run()
        exe = plan.lower(
            "multiprocess",
            timeout_s=60,
            _kill_at_step="evaluate",
            recover="fold",
        ).compile(_logged_steps(log))
        result = exe.run()

        recs = result.stats["recoveries"]
        assert len(recs) == 1
        ren = recs[0]["renaming"]
        assert recs[0]["mode"] == "fold"
        assert set(ren) == {"gpu0"}
        target = ren["gpu0"]
        assert target in {"cpu0", "gpu1"}
        expected: dict = {}
        for l, d in clean.data.items():
            expected.setdefault(ren.get(l, l), {}).update(d)
        assert result.data == expected
        assert sorted(log.read_text().split()) == sorted(quickstart_steps())
        _assert_no_workers_left(exe.program)

    def test_error_failures_are_never_recovered(self, plan):
        # A deterministic step exception would just re-raise on the
        # replacement — only process *death* is recoverable.
        steps = quickstart_steps()
        steps["train_b"] = lambda inp: (_ for _ in ()).throw(
            ValueError("boom")
        )
        exe = plan.lower(
            "multiprocess",
            timeout_s=60,
            recover="spare",
            spares=["spare0"],
        ).compile(steps)
        with pytest.raises(WorkerFailedError) as e:
            exe.run()
        assert "boom" in e.value.reason
        _assert_no_workers_left(exe.program)

    def test_recovery_exhausted_spares_raises(self, plan):
        exe = plan.lower(
            "multiprocess",
            timeout_s=60,
            _kill_at_step="evaluate",
            recover="spare",
            spares=[],
            max_recoveries=0,
        ).compile(quickstart_steps())
        with pytest.raises(WorkerFailedError) as e:
            exe.run()
        assert e.value.exitcode == -signal.SIGKILL
        _assert_no_workers_left(exe.program)

    def test_bad_recover_mode_rejected(self, plan):
        exe = plan.lower(
            "multiprocess", recover="wishful"
        ).compile(quickstart_steps())
        with pytest.raises(ValueError, match="recover must be"):
            exe.run()

    def test_run_many_batch_keeps_draining_through_kills(self, plan):
        clean = plan.lower("multiprocess", timeout_s=60).compile(
            quickstart_steps()
        ).run()
        exe = plan.lower(
            "multiprocess",
            timeout_s=120,
            _kill_at_step="evaluate",
            recover="fold",
        ).compile(quickstart_steps())
        results = exe.run_many([None] * 3)
        assert len(results) == 3
        for r in results:
            assert len(r.stats["recoveries"]) == 1
            ren = r.stats["recoveries"][0]["renaming"]
            expected: dict = {}
            for l, d in clean.data.items():
                expected.setdefault(ren.get(l, l), {}).update(d)
            assert r.data == expected
        _assert_no_workers_left(exe.program)


# ---------------------------------------------------------------------------
# Elastic recovery over the zero-copy shared-memory transport
# ---------------------------------------------------------------------------


class TestZeroCopyElasticRecovery:
    """SIGKILL a worker that owns live /dev/shm arenas, then recover.

    ``preprocess`` on cpu0 broadcasts a 512KB array out of cpu0's shm
    arenas; ``report`` also runs on cpu0, so killing at ``report`` takes
    down a worker whose shared-memory segments are still on disk.  The
    recovery respawn must produce the clean run's arrays (modulo the
    renaming) and the coordinator's namespace sweep must leave nothing
    behind in /dev/shm.
    """

    @staticmethod
    def _array_steps():
        return {
            "preprocess": lambda inp: {
                "d^preprocess": np.arange(65536, dtype=np.float64)
            },
            "train_a": lambda inp: {"d^train_a": inp["d^preprocess"] * 2.0},
            "train_b": lambda inp: {"d^train_b": inp["d^preprocess"] + 1.0},
            "evaluate": lambda inp: {
                "d^evaluate": inp["d^train_a"] + inp["d^train_b"]
            },
            "report": lambda inp: {},
        }

    @staticmethod
    def _data_equal(got, want):
        if got.keys() != want.keys():
            return False
        for loc, payloads in want.items():
            if got[loc].keys() != payloads.keys():
                return False
            for d, v in payloads.items():
                if not np.array_equal(
                    np.asarray(got[loc][d]), np.asarray(v)
                ):
                    return False
        return True

    @pytest.mark.parametrize(
        "mode,opts",
        [
            ("spare", {"recover": "spare", "spares": ["spare0"]}),
            ("fold", {"recover": "fold"}),
        ],
    )
    def test_recovery_with_live_segments_leaves_no_shm(
        self, plan, mode, opts
    ):
        before = set(glob.glob("/dev/shm/swirl-*"))
        clean = (
            plan.lower("multiprocess", timeout_s=60, zero_copy=True)
            .compile(self._array_steps())
            .run()
        )
        exe = plan.lower(
            "multiprocess",
            timeout_s=120,
            zero_copy=True,
            _kill_at_step="report",
            **opts,
        ).compile(self._array_steps())
        result = exe.run()

        recs = result.stats["recoveries"]
        assert len(recs) == 1
        assert recs[0]["mode"] == mode
        ren = recs[0]["renaming"]
        assert set(ren) == {"cpu0"}
        expected: dict = {}
        for l, d in clean.data.items():
            expected.setdefault(ren.get(l, l), {}).update(d)
        assert self._data_equal(result.data, expected)
        _assert_no_workers_left(exe.program)
        assert set(glob.glob("/dev/shm/swirl-*")) == before
