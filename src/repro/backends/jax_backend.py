"""``jax`` backend — interpret location programs on a JAX host device mesh.

Each SWIRL location is pinned to a JAX device (round-robin over the host
mesh, or an explicit ``devices=`` option).  Compiling plans the
deterministic reducer's firing order over the per-location program IR once
(:func:`_plan`); every run replays it:

* an enabled ``ExecOp`` runs the step function with its inputs resident on
  the leader location's device and replicates ``Out^D(s)`` onto every
  device of ``M(s)`` — the (EXEC) rule's "add to every ``D_i``" becomes
  ``jax.device_put``;
* a matching ``SendOp``/``RecvOp`` pair moves the payload to the
  destination location's device — (COMM) as a device-to-device copy.

Only array payloads (``jax.Array`` / ``numpy.ndarray``) are staged through
the device API; plain Python payloads are copied by reference, so results
are bit-identical with the other backends on non-numeric workflows.  This is
the lowering the mesh trainer builds on: SWIRL send/recv pairs between
locations on one mesh axis are exactly what ``ppermute``-style collectives
implement at scale (see ``launch/sharding.py``).
"""

from __future__ import annotations

import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Mapping

from repro.core.compile import StepMeta
from repro.core.syntax import WorkflowSystem
from repro.exec.interp import (
    Cursor,
    Deadline,
    StepGuard,
    comm_span,
    enabled_exec_picks,
    exec_span,
    first_enabled_comm,
    record_policy_fire,
    run_span,
    segment_span,
)
from repro.exec.program import ExecProgram

from .base import Backend, BackendProgram, ExecutionResult, PayloadKey


def _is_array(x: Any) -> bool:
    import jax
    import numpy as np

    return isinstance(x, (jax.Array, np.ndarray))


def _buffer_ptrs(x: Any) -> frozenset[int]:
    """Addresses of the memory an array payload occupies."""
    import jax
    import numpy as np

    if isinstance(x, jax.Array):
        if x.is_deleted():
            return frozenset()
        return frozenset(
            s.data.unsafe_buffer_pointer() for s in x.addressable_shards
        )
    if isinstance(x, np.ndarray):
        return frozenset({x.ctypes.data})
    return frozenset()


def _plan(
    program: ExecProgram, *, fuse: bool
) -> tuple[tuple, WorkflowSystem | None]:
    """The deterministic reducer's firing order, simulated once.

    The order depends only on cursor states and data *names*, never on
    payload values, so it is fixed once the program is lowered: drain the
    enabled COMMs (locations scanned in sorted order), then fire the
    lowest-named enabled exec, until nothing is enabled.  No step body
    runs.  Returns ``(entries, remaining)`` where each entry is
    ``("comm", SendOp)``, ``("exec", ExecOp, locations)`` — the locations
    of ``M(s)`` that store ``Out^D(s)`` — or, when ``fuse``,
    ``("segment", ((ExecOp, locations), ...))`` for a straight-line run
    of at least two execs with no COMM between them and one leader (a
    fused program runs on one device).  ``remaining`` is the unreduced
    system, or ``None`` when the program terminates.
    """
    cursors = {lp.location: Cursor(lp) for lp in program.programs}
    data = {lp.location: set(lp.data) for lp in program.programs}
    order = sorted(cursors)
    entries: list = []
    run: list = []  # the straight-line exec run being collected

    def close_run() -> None:
        if fuse and len(run) >= 2:
            entries.append(("segment", tuple(run)))
        else:
            entries.extend(("exec", op, locs) for op, locs in run)
        run.clear()

    while True:
        while (hit := first_enabled_comm(cursors, data, order)) is not None:
            op, src, i, j = hit
            cursors[src].complete(i)
            cursors[op.dst].complete(j)
            data[op.dst].add(op.data)
            close_run()
            entries.append(("comm", op))
        execs = sorted(
            enabled_exec_picks(cursors, data, order),
            key=lambda pair: pair[0].step,
        )
        if not execs:
            break
        op, picks = execs[0]
        if run and min(op.locations) != min(run[-1][0].locations):
            close_run()
        run.append((op, tuple(loc for loc, _ in picks)))
        for loc, i in picks:
            cursors[loc].complete(i)
            data[loc].update(op.outputs)
    close_run()
    if all(c.finished() for c in cursors.values()):
        return tuple(entries), None
    return tuple(entries), program.remaining_system(
        {l: c.done_flags() for l, c in cursors.items()},
        {l: frozenset(d) for l, d in data.items()},
    )


class _FusedSegment:
    """One straight-line EXEC run compiled to a single jitted call.

    The segment function threads a data-name environment through the
    run's step bodies and returns every datum the run produces, so the
    per-location stores a fused run leaves behind are identical to the
    interpreted ones.  The env is split into ``(donated, kept)`` dicts:
    inputs the segment overwrites are donated so XLA can reuse their
    buffers in place, but only buffers the backend itself allocated and
    that no other store entry shares (see ``JaxMeshProgram.run``).
    """

    def __init__(self, acts: tuple, steps: Mapping[str, StepMeta]):
        import jax

        self.ops = ops = [op for op, _ in acts]  # in firing order
        self.leader = min(ops[0].locations)
        produced: set[str] = set()
        ext: list[str] = []
        for op in ops:
            for d in op.inputs:
                if d not in produced and d not in ext:
                    ext.append(d)
            produced.update(op.outputs)
        self.ext = ext
        self.produced = produced
        # Data overwritten by the segment may have its input buffer
        # donated; everything else must survive the call.
        self.donatable = [d for d in ext if d in produced]
        self.out_names: list[str] = []
        for op in ops:
            for d in op.outputs:
                if d not in self.out_names:
                    self.out_names.append(d)
        step_fns = {op.step: steps[op.step].fn for op in ops}
        seg_ops = list(ops)
        out_names = list(self.out_names)

        def seg_fn(donated: dict, kept: dict) -> dict:
            env = dict(donated)
            env.update(kept)
            for op in seg_ops:
                out = step_fns[op.step]({d: env[d] for d in op.inputs})
                for d in op.outputs:
                    env[d] = out[d]
            return {d: env[d] for d in out_names}

        self.fn = jax.jit(seg_fn, donate_argnums=(0,))
        self.traced = False
        self.arg_shapes: Any = None  # avals of the last call, for hlo()
        self.calls = 0

    def hlo(self) -> str:
        """Compiled HLO text of the segment for its last call's arguments."""
        return self.fn.lower(*self.arg_shapes).compile().as_text()


@dataclass
class JaxMeshProgram(BackendProgram):
    #: The firing order :func:`_plan` made at compile; every run replays it.
    plan: tuple = ()
    #: The system the plan leaves unreduced, or ``None`` if it terminates.
    remaining: WorkflowSystem | None = None
    #: Whether ``_plan`` fused (runs then report ``stats["fused"]``).
    fuse: bool = False
    #: Plan entry index -> its ``_FusedSegment``, or ``"eager"`` once it
    #: must stay interpreted; jitted segments live across ``run()`` calls
    #: so repeat runs hit XLA's cache.
    _segs: dict = field(
        default_factory=dict, init=False, repr=False, compare=False
    )

    def _device_map(self) -> dict[str, Any]:
        import jax

        devices = self.options.get("devices")
        if devices is None:
            platform = self.options.get("platform")
            devices = jax.devices(platform) if platform else jax.devices()
        locs = sorted(self.program.locations())
        schedule = self.options.get("schedule")
        if schedule is not None and getattr(schedule, "network", None):
            # Placement scheduler hand-down: keep each network group's
            # locations on one contiguous device block, so the cheap links
            # of the cost model map to intra-device placement.
            net = schedule.network
            locs.sort(key=lambda l: (net.group_of(l) or "", l))
            return {
                loc: devices[i * len(devices) // len(locs)]
                for i, loc in enumerate(locs)
            }
        return {loc: devices[i % len(devices)] for i, loc in enumerate(locs)}

    def run(
        self, initial_payloads: Mapping[PayloadKey, Any] | None = None
    ) -> ExecutionResult:
        with run_span("jax", next(self.run_ids)):
            return self._run(initial_payloads)

    def _run(
        self, initial_payloads: Mapping[PayloadKey, Any] | None
    ) -> ExecutionResult:
        import jax

        recorder = None
        if self.options.get("trace"):
            from repro.obs.events import TraceRecorder

            recorder = TraceRecorder()
        device_of = self._device_map()
        # ``bytes_moved`` counts every array placement, including the
        # same-device ``device_put``s that share their buffer and copy
        # nothing; ``comm_bytes`` counts only COMM payloads that landed in
        # new memory on another device.
        stats = {
            "execs": 0,
            "comms": 0,
            "device_puts": 0,
            "bytes_moved": 0,
            "comm_bytes": 0,
            "devices": {l: str(d) for l, d in device_of.items()},
        }
        # Uniform fault policy: each step fire goes through the shared
        # timeout + retry helper, and the run deadline is checked after
        # each exec or fused segment of the planned order.
        policy = self.options.get("policy")
        guard = None
        deadline = Deadline(None)
        if policy is not None:
            guard = StepGuard(
                policy,
                on_retry=lambda step, n, e: record_policy_fire(
                    recorder, "retry", "-", step,
                    time.monotonic(), time.monotonic(),
                ),
            )
            deadline = Deadline(policy.deadline_s)
            stats["policy"] = {"retries": 0, "timeouts": 0}
        if self.fuse:
            stats["fused"] = {
                "segments_planned": sum(
                    e[0] == "segment" for e in self.plan
                ),
                "fused_calls": 0,
                "fused_execs": 0,
                "fallbacks": 0,
                "donated": 0,
                "locations": {},
            }

        # Buffers this run allocated itself.  Only these may be donated:
        # a caller's initial payload, or an array a step body returned
        # (which it may still hold), must outlive the run.  ``device_put``
        # onto the array's own device shares its buffer, so a copy is
        # owned only if it landed in new memory.
        owned: weakref.WeakValueDictionary = weakref.WeakValueDictionary()

        def own(value: Any) -> Any:
            owned[id(value)] = value
            return value

        def place(loc: str, value: Any, *, comm: bool = False) -> Any:
            if not _is_array(value):
                return value
            nbytes = int(getattr(value, "nbytes", 0))
            stats["device_puts"] += 1
            stats["bytes_moved"] += nbytes
            out = jax.device_put(value, device_of[loc])
            if not _buffer_ptrs(out) & _buffer_ptrs(value):
                own(out)
                if comm:
                    stats["comm_bytes"] += nbytes
            return out

        payloads: dict[PayloadKey, Any] = {}
        for (loc, d), v in (initial_payloads or {}).items():
            payloads[(loc, d)] = place(loc, v)

        def store(op, locations, out: dict, on: Any = None) -> None:
            """Store ``Out^D(s)`` at every location of ``M(s)``; outputs
            already on device ``on`` (a fused call's) are not placed."""
            missing = set(op.outputs) - set(out)
            if missing:
                raise RuntimeError(
                    f"step {op.step!r} did not produce {sorted(missing)}"
                )
            for loc in locations:
                for d in op.outputs:
                    payloads[(loc, d)] = (
                        out[d] if device_of[loc] is on else place(loc, out[d])
                    )
            stats["execs"] += 1

        def fire(op, locations) -> None:
            leader = min(op.locations)
            inputs = {d: payloads[(leader, d)] for d in op.inputs}
            fn = self.steps[op.step].fn
            with exec_span(recorder, op, leader):
                out = (
                    fn(inputs) if guard is None
                    else guard.fire(op.step, lambda: fn(inputs))
                )
            store(op, locations, out)

        def donatable(seg: _FusedSegment, env: dict) -> dict[str, Any]:
            """Inputs whose buffers the segment may consume in place."""
            cand = {
                d: env[d] for d in seg.donatable
                if owned.get(id(env[d])) is env[d]
            }
            if not cand:
                return {}
            shared: dict[int, int] = {}
            for v in payloads.values():
                for ptr in _buffer_ptrs(v):
                    shared[ptr] = shared.get(ptr, 0) + 1
            # The candidate's own store entry is its one reference.
            return {
                d: v for d, v in cand.items()
                if all(shared.get(ptr, 0) == 1 for ptr in _buffer_ptrs(v))
            }

        def run_segment(index: int, acts: tuple) -> bool:
            """Fire a whole planned segment as one jitted call.

            Returns False (after caching the verdict) when the segment
            must stay interpreted — non-array inputs, or a step body
            that does not trace; the caller then fires its execs one by
            one.  An error while compiling or running a segment that did
            trace propagates.
            """
            seg = self._segs.get(index)
            if seg == "eager":
                return False
            if seg is None:
                seg = self._segs[index] = _FusedSegment(acts, self.steps)
            env = {d: payloads[(seg.leader, d)] for d in seg.ext}
            if not all(_is_array(v) for v in env.values()):
                self._segs[index] = "eager"
                stats["fused"]["fallbacks"] += 1
                return False
            donated = donatable(seg, env)
            kept = {d: v for d, v in env.items() if d not in donated}
            if not seg.traced:
                try:
                    seg.fn.trace(donated, kept)
                except Exception:  # a step body that does not trace
                    self._segs[index] = "eager"
                    stats["fused"]["fallbacks"] += 1
                    return False
                seg.traced = True
            stats["fused"]["donated"] += len(donated)
            seg.arg_shapes = jax.tree.map(
                lambda v: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=getattr(v, "sharding", None)
                ),
                (donated, kept),
            )
            with segment_span(recorder, seg.leader, seg.ops):
                out = jax.block_until_ready(seg.fn(donated, kept))
            for v in out.values():
                own(v)
            seg.calls += 1
            loc_stats = stats["fused"]["locations"].setdefault(
                seg.leader, {"calls": 0, "execs": 0}
            )
            loc_stats["calls"] += 1
            loc_stats["execs"] += len(acts)
            stats["fused"]["fused_calls"] += 1
            stats["fused"]["fused_execs"] += len(acts)
            # The values came from the fused call; the replication of
            # Out^D(s) onto every D_i is unchanged, and placement only
            # pays for locations on another device than the leader's.
            for op, locations in acts:
                store(op, locations, out, on=device_of[seg.leader])
            return True

        deadline.check()
        for index, entry in enumerate(self.plan):
            kind = entry[0]
            if kind == "comm":
                op = entry[1]
                payload = payloads[(op.src, op.data)]
                with comm_span(recorder, op, payload):
                    payloads[(op.dst, op.data)] = place(
                        op.dst, payload, comm=True
                    )
                stats["comms"] += 1
            elif kind == "exec":
                fire(entry[1], entry[2])
                deadline.check()
            elif run_segment(index, entry[1]):
                deadline.check()
            else:
                for op, locations in entry[1]:
                    fire(op, locations)
                    deadline.check()

        if guard is not None:
            stats["policy"] = guard.counts()
        if self.remaining is not None:
            raise RuntimeError(
                "jax backend: workflow did not terminate; remaining:\n"
                + self.remaining.pretty()
            )
        result: dict[str, dict[str, Any]] = {
            loc: {} for loc in self.program.locations()
        }
        for (loc, d), v in payloads.items():
            result.setdefault(loc, {})[d] = v
        profile = None
        if recorder is not None:
            from repro.obs.profile import RunProfile

            profile = RunProfile.from_recorder("jax", recorder)
        return ExecutionResult(
            backend="jax", data=result, stats=stats, profile=profile
        )

    def segment_hlo(self) -> dict[int, str]:
        """Compiled HLO text of every fused segment that has run.

        Keyed by the segment's index in the plan; shows which kernels
        the compiler emitted for the device the segment ran on (a Pallas
        kernel on a TPU is a ``tpu_custom_call``).
        """
        return {
            index: seg.hlo()
            for index, seg in sorted(self._segs.items())
            if isinstance(seg, _FusedSegment) and seg.calls
        }


class JaxBackend(Backend):
    name = "jax"
    capabilities = frozenset({"mesh", "device-placement"})

    def known_options(self) -> frozenset[str]:
        return super().known_options() | frozenset(
            {"devices", "platform", "fuse"}
        )

    def compile(
        self,
        program: ExecProgram | WorkflowSystem,
        steps: Mapping[str, StepMeta],
        options: Mapping[str, Any],
    ) -> JaxMeshProgram:
        program = self.lower(program, options)
        # A fault policy's guard wraps individual step fires, which a
        # fused call cannot honour, so a policy turns fusion off.
        fuse = bool(options.get("fuse")) and options.get("policy") is None
        plan, remaining = _plan(program, fuse=fuse)
        return JaxMeshProgram(
            program=program,
            steps=dict(steps),
            options=dict(options),
            plan=plan,
            remaining=remaining,
            fuse=fuse,
        )


def factory() -> Backend:
    return JaxBackend()
