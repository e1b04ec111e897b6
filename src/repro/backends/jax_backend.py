"""``jax`` backend — interpret location programs on a JAX host device mesh.

Each SWIRL location is pinned to a JAX device (round-robin over the host
mesh, or an explicit ``devices=`` option).  The compiled artifact then
interprets the per-location program IR deterministically:

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
from typing import Any, Mapping

from repro.core.compile import StepMeta
from repro.core.syntax import WorkflowSystem
from repro.exec.interp import (
    Cursor,
    Deadline,
    StepGuard,
    enabled_exec_picks,
    first_enabled_comm,
    record_comm_fire,
    record_exec_fire,
    record_policy_fire,
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


def _plan_segments(program, *, min_len: int = 2) -> dict[int, list]:
    """Partition the deterministic exec firing order into fusable runs.

    The reducer's firing order depends only on cursor states and data
    *names*, never on payload values, so it can be replayed statically:
    simulate the run loop (drain comms, fire the lowest-named enabled
    exec) without calling any step body and record where straight-line
    EXEC runs break — at a COMM boundary, or when the leader location
    changes (a fused program runs on one device).  Returns
    ``{start_exec_index: [(ExecOp, picks), ...]}`` for every run of at
    least ``min_len`` ops — the picks are recorded at plan time so the
    runtime replays cursor completions directly instead of re-scanning
    enabledness per op; the runtime counts fired execs and swaps in the
    jitted segment when the counter hits a start index.
    """
    cursors = {lp.location: Cursor(lp) for lp in program.programs}
    data = {lp.location: set(lp.data) for lp in program.programs}
    order = sorted(cursors)
    seq: list = []
    breaks: set[int] = set()
    while True:
        comm_fired = False
        while True:
            hit = first_enabled_comm(cursors, data, order)
            if hit is None:
                break
            op, src, i, j = hit
            cursors[src].complete(i)
            cursors[op.dst].complete(j)
            data[op.dst].add(op.data)
            comm_fired = True
        execs = sorted(
            enabled_exec_picks(cursors, data, order),
            key=lambda pair: pair[0].step,
        )
        if not execs:
            break
        op, picks = execs[0]
        if (
            not seq
            or comm_fired
            or min(op.locations) != min(seq[-1][0].locations)
        ):
            breaks.add(len(seq))
        seq.append((op, picks))
        for loc, i in picks:
            cursors[loc].complete(i)
            data[loc].update(op.outputs)
    segments: dict[int, list] = {}
    starts = sorted(breaks) + [len(seq)]
    for a, b in zip(starts, starts[1:]):
        if b - a >= min_len:
            segments[a] = seq[a:b]
    return segments


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

    def __init__(self, acts: list, steps: Mapping[str, StepMeta]):
        import jax

        self.acts = acts  # [(ExecOp, picks), ...] in firing order
        ops = [op for op, _ in acts]
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
        self.seconds = 0.0  # warm (post-compile) call time only
        self.bytes = 0

    def hlo(self) -> str:
        """Compiled HLO text of the segment for its last call's arguments."""
        return self.fn.lower(*self.arg_shapes).compile().as_text()


class JaxMeshProgram(BackendProgram):
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
        import jax

        recorder = None
        if self.options.get("trace"):
            from repro.obs.events import TraceRecorder

            recorder = TraceRecorder()
        device_of = self._device_map()
        stats = {
            "execs": 0,
            "comms": 0,
            "device_puts": 0,
            "bytes_moved": 0,
            "devices": {l: str(d) for l, d in device_of.items()},
        }
        # Uniform fault policy: the deterministic reducer guards each step
        # fire with the shared timeout + retry helper and checks the run
        # deadline once per reduction round.
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

        # Buffers this run allocated itself.  Only these may be donated:
        # a caller's initial payload, or an array a step body returned
        # (which it may still hold), must outlive the run.  ``device_put``
        # onto the array's own device shares its buffer, so a copy is
        # owned only if it landed in new memory.
        owned: weakref.WeakValueDictionary = weakref.WeakValueDictionary()

        def own(value: Any) -> Any:
            owned[id(value)] = value
            return value

        def place(loc: str, value: Any) -> Any:
            if not _is_array(value):
                return value
            stats["device_puts"] += 1
            stats["bytes_moved"] += int(getattr(value, "nbytes", 0))
            out = jax.device_put(value, device_of[loc])
            if not _buffer_ptrs(out) & _buffer_ptrs(value):
                own(out)
            return out

        payloads: dict[PayloadKey, Any] = {}
        for (loc, d), v in (initial_payloads or {}).items():
            payloads[(loc, d)] = place(loc, v)

        cursors = {
            lp.location: Cursor(lp) for lp in self.program.programs
        }
        data = {lp.location: set(lp.data) for lp in self.program.programs}
        order = sorted(cursors)

        def fire_one_comm() -> bool:
            hit = first_enabled_comm(cursors, data, order)
            if hit is None:
                return False
            op, src, i, j = hit
            cursors[src].complete(i)
            cursors[op.dst].complete(j)
            data[op.dst].add(op.data)
            if recorder is None:
                payloads[(op.dst, op.data)] = place(
                    op.dst, payloads[(op.src, op.data)]
                )
            else:
                payload = payloads[(op.src, op.data)]
                t0 = time.monotonic()
                payloads[(op.dst, op.data)] = place(op.dst, payload)
                record_comm_fire(
                    recorder, op, t0, time.monotonic(), payload
                )
            stats["comms"] += 1
            return True

        # Fused location programs: straight-line EXEC runs become single
        # jitted calls (segmented at COMM boundaries).  A fault policy
        # guard wraps individual step fires, which a fused call cannot
        # honour, so fusion is skipped when a guard is active.
        fuse = bool(self.options.get("fuse")) and guard is None
        if fuse and not hasattr(self, "_segments"):
            # Plan once per compiled program; jitted segment functions
            # live across run() calls so repeat runs hit XLA's cache
            # (and warm-call bandwidth is what roofline reports).
            self._segments = _plan_segments(self.program)
            self._seg_cache: dict[int, Any] = {}
        segments = self._segments if fuse else {}
        seg_cache = self._seg_cache if fuse else {}
        if fuse:
            stats["fused"] = {
                "segments_planned": len(segments),
                "fused_calls": 0,
                "fused_execs": 0,
                "fallbacks": 0,
                "donated": 0,
                "locations": {},
            }
        exec_count = 0

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

        def run_segment(start: int) -> bool:
            """Fire a whole planned segment as one jitted call.

            Returns False (after caching the verdict) when the segment
            must stay interpreted — non-array inputs, or a step body
            that does not trace; the caller then falls through to the
            op-by-op path for every op in the run.  An error while
            compiling or running a segment that did trace propagates.
            """
            import time as _time

            seg = seg_cache.get(start)
            if seg == "eager":
                return False
            acts = segments[start]
            if seg is None:
                seg = _FusedSegment(acts, self.steps)
                seg_cache[start] = seg
            env = {d: payloads[(seg.leader, d)] for d in seg.ext}
            if not all(_is_array(v) for v in env.values()):
                seg_cache[start] = "eager"
                stats["fused"]["fallbacks"] += 1
                return False
            donated = donatable(seg, env)
            kept = {d: v for d, v in env.items() if d not in donated}
            if not seg.traced:
                try:
                    seg.fn.trace(donated, kept)
                except Exception:  # a step body that does not trace
                    seg_cache[start] = "eager"
                    stats["fused"]["fallbacks"] += 1
                    return False
                seg.traced = True
            stats["fused"]["donated"] += len(donated)
            first_call = seg.calls == 0
            seg.arg_shapes = jax.tree.map(
                lambda v: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=getattr(v, "sharding", None)
                ),
                (donated, kept),
            )
            t0 = _time.perf_counter()
            out = jax.block_until_ready(seg.fn(donated, kept))
            dt = _time.perf_counter() - t0
            for v in out.values():
                own(v)
            seg.calls += 1
            moved = sum(
                int(getattr(v, "nbytes", 0)) for v in env.values()
            ) + sum(int(getattr(v, "nbytes", 0)) for v in out.values())
            if not first_call:
                # First call pays tracing + XLA compile; only warm calls
                # count toward achieved-bandwidth reporting.
                seg.seconds += dt
                seg.bytes += moved
            loc_stats = stats["fused"]["locations"].setdefault(
                seg.leader,
                {"calls": 0, "execs": 0, "bytes": 0, "seconds": 0.0},
            )
            loc_stats["calls"] += 1
            loc_stats["execs"] += len(acts)
            if not first_call:
                loc_stats["bytes"] += moved
                loc_stats["seconds"] += dt
            stats["fused"]["fused_calls"] += 1
            stats["fused"]["fused_execs"] += len(acts)
            # Replay the run's cursor/data effects from the recorded
            # plan — the values came from the fused call, the
            # bookkeeping (and the replication of Out^D(s) onto every
            # D_i) is unchanged.  Outputs already live on the leader's
            # device, so placement only pays for genuinely remote
            # locations.
            leader_dev = device_of[seg.leader]
            for op, picks in acts:
                if recorder is not None:
                    record_exec_fire(recorder, op, t0, t0 + dt)
                missing = set(op.outputs) - set(out)
                if missing:
                    raise RuntimeError(
                        f"step {op.step!r} did not produce "
                        f"{sorted(missing)}"
                    )
                for loc, i in picks:
                    cursors[loc].complete(i)
                    data[loc].update(op.outputs)
                    for d in op.outputs:
                        payloads[(loc, d)] = (
                            out[d]
                            if device_of[loc] is leader_dev
                            else place(loc, out[d])
                        )
                stats["execs"] += 1
            return True

        max_rounds = int(self.options.get("max_rounds", 1_000_000))
        for _ in range(max_rounds):
            deadline.check()
            progressed = False
            # Drain communications first (they are τ — silent, confluent).
            while fire_one_comm():
                progressed = True
            if fuse and exec_count in segments:
                if run_segment(exec_count):
                    exec_count += len(segments[exec_count])
                    progressed = True
                    continue
            # Deterministic firing order: lowest step name first.
            execs = sorted(
                enabled_exec_picks(cursors, data, order),
                key=lambda pair: pair[0].step,
            )
            if execs:
                op, picks = execs[0]
                leader = min(op.locations)
                inputs = {d: payloads[(leader, d)] for d in op.inputs}
                fn = self.steps[op.step].fn
                fire = (
                    (lambda: guard.fire(op.step, lambda: fn(inputs)))
                    if guard is not None
                    else (lambda: fn(inputs))
                )
                if recorder is None:
                    out = fire()
                else:
                    t0 = time.monotonic()
                    out = fire()
                    record_exec_fire(recorder, op, t0, time.monotonic())
                missing = set(op.outputs) - set(out)
                if missing:
                    raise RuntimeError(
                        f"step {op.step!r} did not produce {sorted(missing)}"
                    )
                for loc, i in picks:
                    cursors[loc].complete(i)
                    data[loc].update(op.outputs)
                    for d in op.outputs:
                        payloads[(loc, d)] = place(loc, out[d])
                stats["execs"] += 1
                exec_count += 1
                progressed = True
            if not progressed:
                break

        if fuse:
            from repro.roofline import device_peaks

            # Host-clock bandwidth of warm fused calls against the HBM
            # peak of the device that ran them; a device kind without a
            # published peak gets no entry.
            roofline = {}
            for loc, ls in stats["fused"]["locations"].items():
                peaks = device_peaks(
                    getattr(device_of[loc], "device_kind", None)
                )
                if peaks is None:
                    continue
                achieved = (
                    ls["bytes"] / ls["seconds"] if ls["seconds"] > 0 else 0.0
                )
                roofline[loc] = {
                    "device_kind": device_of[loc].device_kind,
                    "achieved_bytes_per_s": achieved,
                    "theoretical_bytes_per_s": peaks.hbm_bytes_per_s,
                    "fraction_of_roof": achieved / peaks.hbm_bytes_per_s,
                }
            stats["fused"]["roofline"] = roofline
        if guard is not None:
            stats["policy"] = guard.counts()
        if not all(c.finished() for c in cursors.values()):
            remaining = self.program.remaining_system(
                {l: c.done_flags() for l, c in cursors.items()},
                {l: frozenset(d) for l, d in data.items()},
            )
            raise RuntimeError(
                "jax backend: workflow did not terminate; remaining:\n"
                + remaining.pretty()
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

        Keyed by the segment's first exec index in the firing order;
        shows which kernels the compiler emitted for the device the
        segment ran on (a Pallas kernel on a TPU is a
        ``tpu_custom_call``).
        """
        cache = getattr(self, "_seg_cache", {})
        return {
            start: seg.hlo()
            for start, seg in sorted(cache.items())
            if isinstance(seg, _FusedSegment) and seg.calls
        }


class JaxBackend(Backend):
    name = "jax"
    capabilities = frozenset({"mesh", "device-placement"})

    def known_options(self) -> frozenset[str]:
        return super().known_options() | frozenset(
            {"devices", "platform", "max_rounds", "fuse"}
        )

    def compile(
        self,
        program: ExecProgram | WorkflowSystem,
        steps: Mapping[str, StepMeta],
        options: Mapping[str, Any],
    ) -> JaxMeshProgram:
        return JaxMeshProgram(
            program=self.lower(program, options),
            steps=dict(steps),
            options=dict(options),
        )


def factory() -> Backend:
    return JaxBackend()
