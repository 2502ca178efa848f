"""ChipServer: the thin composition of queue + policy + executor.

BinarEye's serving story (paper Sec. IV): frames stream in continuously
and the chip recombines its 16 sub-arrays across programmable network
widths S in {1, 2, 4} — several *programs* can stay resident (weights in
SRAM, instructions in the 16-slot program memory) and the array is
re-pointed per batch, trading energy for accuracy per task.  The serving
package is the TPU analogue of that controller, split mechanism/policy:

* :mod:`repro.serving.queue` — per-lane FIFOs + the round-robin pointer
  (who is next);
* :mod:`repro.serving.policy` — which program variant serves the lane:
  :class:`StaticPolicy` (each lane its own program, shared-array groups
  composite) or :class:`OperatingPointPolicy` (program families served
  at the operating point an energy budget and the backlog call for);
* :mod:`repro.serving.executor` — pad/dispatch/materialize + the depth-k
  prefetch pipeline;
* :class:`ChipServer` (this module) — wires them together and keeps the
  books (served/padded/energy billing via ``energy.serve_report``).

All pre-split behaviour is preserved: ``megakernel=True`` runs dispatches
through the whole-network resident kernel, ``prefetch=k`` pipelines
submission to depth k, ``shared=True`` forms shared-array composite
groups at admission, and a ``mesh`` replicates weights per device while
frames scatter on the batch axis.  New: ``families=`` registers program
families (variant sets of one task) behind a single queue lane and
serves them through the operating-point controller (``policy=`` /
``budget_uj_s=``).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core.chip import energy, interpreter, isa
from repro.serving import telemetry as telemetry_mod
from repro.serving.executor import Executor
from repro.serving.policy import (ContinuousPolicy, DispatchPolicy,
                                  OperatingPointPolicy, PolicyContext,
                                  StaticPolicy)
from repro.serving.queue import (FrameQueue, FrameRequest, FrameResult,
                                 plan_shared_groups)


@dataclasses.dataclass(frozen=True)
class ServeStats:
    """Host-side counters + the chip-model bill for what was served."""
    served: Dict[str, int]            # lane -> frames served
    padded: Dict[str, int]            # lane -> padding slots burned
    dispatches: int
    host_wall_s: float                # wall time inside dispatches
    host_frames_per_s: float
    chip: energy.ServeReport          # µJ/frame, frames/s, power analogue
    array_utilization: float = 0.0    # mean sum(1/S) of live sub-arrays
                                      # per dispatch (1.0 = full array)
    shared_dispatches: int = 0        # dispatches serving >= 2 programs
    policy: str = "static"
    variant_dispatches: Dict[str, int] = dataclasses.field(
        default_factory=dict)         # variant -> dispatches it ran
    energy_uj: float = 0.0            # chip-model energy billed, all lanes
    budget_uj_s: Optional[float] = None
    downshift_ratio: float = 0.0      # family dispatches served below the
                                      # top operating point
    p50_ms: float = 0.0               # input-to-label latency percentiles
    p95_ms: float = 0.0               # over timestamped frames (0.0 when
    p99_ms: float = 0.0               # nothing was stamped)
    padding_ratio: float = 0.0        # burned slots / billed slots

    @property
    def total_served(self) -> int:
        return sum(self.served.values())


class ChipServer:
    """Continuous static-batch serving of compiled ``InferencePlan``s.

    ``programs`` maps resident-program names to validated ISA programs;
    ``artifacts`` maps the same names to their packed deployment artifacts
    (``fold_params(..., packed=True)`` — float-folded artifacts are packed
    on admission).  ``batch`` is the static dispatch size; with a ``mesh``
    it must divide over the mesh's device count.  ``prefetch`` takes a
    pipeline depth (``True`` = 1); ``shared=True`` forms shared-array
    composite groups at admission.

    ``families`` maps a family (task) name to a sequence of resident
    program names that are variants of one task — same input geometry and
    class count, different operating points (see ``networks.FAMILIES``
    and ``interpreter.compile_family``).  Frames are submitted to the
    *family* name; the dispatch policy picks the served variant.  With
    ``families`` the policy defaults to the operating-point controller
    (``budget_uj_s`` caps the chip-model average power in uJ/s);
    ``policy`` accepts a :class:`DispatchPolicy` instance or the strings
    ``"static"`` / ``"operating-point"``.

    The server records its ``serve.*`` spans (``serving/telemetry.py``)
    under ``replica`` into ``telemetry``, by default the process-wide
    recorder, timed by ``clock``.
    """

    def __init__(self, programs: Mapping[str, isa.Program],
                 artifacts: Mapping[str, Any], *, batch: int = 8,
                 mesh=None, donate_frames: bool = False,
                 interpret: Optional[bool] = None,
                 megakernel: bool = False, prefetch: bool | int = False,
                 shared: bool = False,
                 policy: Optional[DispatchPolicy | str] = None,
                 families: Optional[Mapping[str, Sequence[str]]] = None,
                 budget_uj_s: Optional[float] = None,
                 f_hz: float = energy.F_EMIN,
                 slo_ms: float = 50.0,
                 warm_start: bool = True,
                 clock=time.perf_counter,
                 replica: str = "server",
                 telemetry: Optional[telemetry_mod.Recorder] = None):
        if set(programs) != set(artifacts):
            raise ValueError(
                f"programs {sorted(programs)} != artifacts {sorted(artifacts)}")
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        if int(prefetch) < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {prefetch}")
        ndev = mesh.devices.size if mesh is not None else 1
        if batch % ndev:
            raise ValueError(
                f"static batch {batch} must divide over the "
                f"{ndev}-device serving mesh")
        self.batch = batch
        self.mesh = mesh
        self.f_hz = f_hz
        self.prefetch = int(prefetch)        # pipeline depth, 0 = sync
        self.shared = shared
        self.slo_ms = slo_ms
        self.clock = clock                   # injectable for latency tests
        self.probe = (telemetry or telemetry_mod.RECORDER).probe(replica,
                                                                 clock)
        self.programs: Dict[str, isa.Program] = dict(programs)

        # -- lanes: families collapse their variants behind one lane -------
        self._families: Dict[str, Tuple[str, ...]] = {}
        if families:
            owned = {}
            for fam, members in families.items():
                members = tuple(members)
                if fam in self.programs:
                    raise ValueError(
                        f"family name {fam!r} collides with a resident "
                        "program name")
                missing = [m for m in members if m not in self.programs]
                if missing:
                    raise ValueError(
                        f"family {fam!r} members {missing} not resident")
                for m in members:
                    if m in owned:
                        raise ValueError(
                            f"program {m!r} belongs to families "
                            f"{owned[m]!r} and {fam!r}")
                    owned[m] = fam
                # validates shared geometry/classes across the variants
                interpreter.compile_family(
                    {m: self.programs[m] for m in members})
                self._families[fam] = members
        in_family = {m for ms in self._families.values() for m in ms}
        self._lanes: Tuple[str, ...] = tuple(self._families) + tuple(
            n for n in self.programs if n not in in_family)
        self._lane_variants: Dict[str, Tuple[str, ...]] = {
            **self._families,
            **{n: (n,) for n in self.programs if n not in in_family}}

        # -- mechanism ------------------------------------------------------
        self.executor = Executor(self.programs, artifacts, batch=batch,
                                 mesh=mesh, donate_frames=donate_frames,
                                 interpret=interpret, megakernel=megakernel,
                                 prefetch=self.prefetch,
                                 warm_start=warm_start, clock=clock,
                                 probe=self.probe)
        self.plans = self.executor.plans
        self.artifacts = self.executor.artifacts
        self.queue = FrameQueue(self._lanes)
        self._geom = {lane: self.executor.geometry(vs[0])
                      for lane, vs in self._lane_variants.items()}

        # -- policy ---------------------------------------------------------
        groups: Dict[str, Tuple[str, ...]] = {}
        self._groups_plan: Tuple[Tuple[str, ...], ...] = ()
        if shared:
            lane_progs = {n: self.programs[n] for n in self._lanes
                          if n in self.programs}
            self._groups_plan = plan_shared_groups(lane_progs)
            for members in self._groups_plan:
                for m in members:
                    groups[m] = members
            self.executor.warm_composites(self._groups_plan)
        self.policy = self._make_policy(policy, budget_uj_s)
        # static per-program chip reports: computed once, reused by stats()
        self._reports = {n: energy.analyze_net(p, f_hz)
                         for n, p in self.programs.items()}
        self.policy.bind(PolicyContext(
            batch=batch, lanes=self._lanes,
            variants=dict(self._lane_variants),
            programs=dict(self.programs), reports=dict(self._reports),
            groups=groups, quantum=ndev, clock=clock))

        # -- accounting -----------------------------------------------------
        self.failed = False                  # set by fail(); fleet skips us
        self.aborted_inflight = 0            # in-flight frames fail() dropped
        self._next_rid = 0
        self._dispatches = 0
        self._shared_dispatches = 0
        self._util_sum = 0.0
        self._served = {lane: 0 for lane in self._lanes}
        self._padded = {lane: 0 for lane in self._lanes}
        self._vserved = {name: 0 for name in self.programs}
        self._vpadded = {name: 0 for name in self.programs}
        self._billed = 0                     # frame slots launched (served
                                             # + padded, across all lanes)
        # per-frame latency books, one compact row per lane of a served
        # dispatch: (dispatch, t_done, lane, variant, rids, t_submits)
        self._done: List[tuple] = []

    def _make_policy(self, policy, budget_uj_s) -> DispatchPolicy:
        if isinstance(policy, DispatchPolicy):
            return policy
        if policy is None:
            policy = "operating-point" if self._families else "static"
        if policy == "static":
            if self._families:
                raise ValueError(
                    "families need a variant-choosing policy; use "
                    "policy='operating-point' (or drop families=)")
            return StaticPolicy()
        if policy == "operating-point":
            return OperatingPointPolicy(budget_uj_s=budget_uj_s,
                                        shared=self.shared)
        if policy == "continuous":
            inner = (OperatingPointPolicy(budget_uj_s=budget_uj_s,
                                          shared=self.shared)
                     if self._families else StaticPolicy())
            return ContinuousPolicy(slo_ms=self.slo_ms, inner=inner)
        raise ValueError(f"unknown policy {policy!r} (have 'static', "
                         "'operating-point', 'continuous', or a "
                         "DispatchPolicy)")

    @property
    def shared_groups(self) -> Tuple[Tuple[str, ...], ...]:
        """The compiled shared-array groups (empty unless ``shared=True``
        and some resident S-modes tile the array exactly)."""
        return self._groups_plan

    @property
    def families(self) -> Dict[str, Tuple[str, ...]]:
        return dict(self._families)

    # -- request side -------------------------------------------------------

    def submit(self, program: str, frame,
               t_submit: Optional[float] = None,
               rid: Optional[int] = None) -> int:
        """Enqueue one frame on a lane (program or family name); returns
        its request id (arrival order).  ``t_submit`` overrides the
        admission timestamp (trace replay stamps the trace's arrival
        time); by default the server clock stamps *now*.  ``rid``
        overrides the locally-assigned id — a fleet hands out globally
        unique ids so results from different replicas never collide."""
        t0 = self.clock()
        if program not in self._geom:
            raise KeyError(
                f"program {program!r} not resident "
                f"(have {sorted(self._geom)})")
        h, w, c = self._geom[program]
        frame = np.asarray(frame)
        if frame.shape != (h, w, c):
            raise ValueError(
                f"{program} expects frames of shape {(h, w, c)}, "
                f"got {frame.shape}")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            self._next_rid = max(self._next_rid, rid + 1)
        if t_submit is None:
            t_submit = t0
        self.queue.submit(FrameRequest(rid=rid, program=program, frame=frame,
                                       t_submit=t_submit))
        self.probe.add("serve.submit", self.clock() - t0)
        return rid

    def submit_many(self, program: str, frames) -> List[int]:
        return [self.submit(program, f) for f in frames]

    # -- dispatch side ------------------------------------------------------

    def _launch(self) -> Optional[Dict[str, Any]]:
        """Consult the policy for the next dispatch, run it, and bill it.
        Serving counters are billed at launch — the energy is burned the
        moment the batch hits the array, synced or not."""
        index = self._dispatches
        with self.probe.span("serve.select", dispatch=index):
            dispatch = self.policy.select(self.queue)
        if dispatch is None:
            return None
        self._dispatches += 1
        handle = self.executor.launch(dispatch, index)
        with self.probe.span("serve.finish", dispatch=index):
            size = (dispatch.batch if dispatch.batch is not None
                    else self.batch)
            live = []
            for ld in dispatch.lanes:
                n = len(ld.requests)
                self._served[ld.lane] += n
                self._padded[ld.lane] += size - n
                self._vserved[ld.variant] += n
                self._vpadded[ld.variant] += size - n
                self._billed += size
                if n:
                    live.append(self.programs[ld.variant])
            if dispatch.composite:
                self._shared_dispatches += 1
                self._util_sum += energy.array_occupancy(live)
            else:
                self._util_sum += 1.0 / self.programs[
                    dispatch.lanes[0].variant].s
        return handle

    def step(self) -> List[FrameResult]:
        """One dispatch: pull a static batch, run its program(s), return
        results for the real (non-padding) frames.  [] once drained.

        With ``prefetch=k`` up to k batches are staged and dispatched
        *before* blocking on the oldest one, and finished results are
        pulled to the host by a background thread; batches still leave
        the queue in exactly the synchronous order, so fairness is
        untouched.

        All timing goes through ``self.clock`` — the injected clock is
        the server's single time domain (``host_wall_s``, ``t_submit``,
        ``t_done`` and the latency trace all share it), so a
        ``VirtualClock`` replay never silently mixes in wall time.  The
        step is the ``serve.step`` span; ``host_wall_s`` is their sum.
        """
        with self.probe.span("serve.step", dispatch=self._dispatches):
            results = self.executor.step(self._launch)
            if results:
                with self.probe.span("serve.finish",
                                     dispatch=results[0].dispatch):
                    self._book(results)
        return results

    def _book(self, results: List[FrameResult]) -> None:
        """One latency row per lane of the dispatch these results came
        from (a composite's lanes are contiguous in ``results``)."""
        first, last = results[0], results[-1]
        if (first.program, first.variant) == (last.program, last.variant):
            groups = [results]
        else:
            groups = [list(g) for _k, g in itertools.groupby(
                results, key=lambda r: (r.program, r.variant))]
        for g in groups:
            n = len(g)
            self._done.append((
                g[0].dispatch, g[0].t_done, g[0].program, g[0].variant,
                np.fromiter((r.rid for r in g), np.int64, n),
                np.fromiter((r.t_submit for r in g), np.float64, n)))

    @property
    def _host_wall_s(self) -> float:
        """Seconds inside this server's steps since the last reset (the
        sum of its ``serve.step`` spans)."""
        return self.probe.seconds.get("serve.step", 0.0)

    def _latency_s(self) -> np.ndarray:
        """Input-to-label seconds of every stamped frame, completion
        order."""
        lats = [t_done - t_sub[t_sub > 0.0]
                for _d, t_done, _l, _v, _r, t_sub in self._done
                if t_done > 0.0]
        return np.concatenate(lats) if lats else np.zeros(0)

    def drain(self) -> List[FrameResult]:
        """Serve until the queue is empty; results in dispatch order.
        The policy is flushed for the duration: a continuous policy's
        admission window never holds the final ragged batches back."""
        out: List[FrameResult] = []
        self.policy.set_flush(True)
        try:
            while True:
                got = self.step()
                if not got and not len(self.queue):
                    return out
                out.extend(got)
        finally:
            self.policy.set_flush(False)

    def close(self) -> None:
        """Release the background fetch thread, syncing (and discarding —
        ``drain()`` first to collect them) any in-flight dispatches.  The
        server keeps working afterwards with prefetch degraded to
        synchronous fetch; safe to call more than once."""
        self.executor.close()

    def fail(self) -> Dict[str, List[FrameRequest]]:
        """Simulated host loss: kill this replica and hand back every
        frame it had not finished serving, grouped by lane with order
        preserved (in-flight dispatches oldest-first, then the queued
        FIFO).  The energy already billed for abandoned in-flight
        dispatches stays billed — it was burned the moment the batch hit
        the array — so this replica's ``billed == served + padded``
        ledger stays consistent; the migrated frames are re-billed by
        whoever serves them.  The server is unusable afterwards."""
        orphans: Dict[str, List[FrameRequest]] = {
            lane: [] for lane in self._lanes}
        inflight = self.executor.abort()        # in-flight, oldest first
        self.aborted_inflight = len(inflight)   # fleet's refired count
        for req in inflight:
            orphans[req.program].append(req)
        for lane in self._lanes:                # then the queued backlog
            while True:
                got = self.queue.take(lane, self.batch)
                if not got:
                    break
                orphans[lane].extend(got)
        self.failed = True
        return {lane: reqs for lane, reqs in orphans.items() if reqs}

    # -- accounting ---------------------------------------------------------

    def reset_stats(self) -> None:
        """Zero the serving counters and latency books, keeping all
        compiled state — benches warm the jit caches through the real
        serve path, then measure from a clean ledger."""
        self._dispatches = 0
        self._shared_dispatches = 0
        self._util_sum = 0.0
        self._served = {lane: 0 for lane in self._lanes}
        self._padded = {lane: 0 for lane in self._lanes}
        self._vserved = {name: 0 for name in self.programs}
        self._vpadded = {name: 0 for name in self.programs}
        self.probe.clear()
        self._billed = 0
        self._done = []
        for v in self.policy.variant_dispatches:
            self.policy.variant_dispatches[v] = 0

    def latency_trace(self) -> List[Dict[str, Any]]:
        """Per-frame admission-to-label records (stamped frames only), in
        completion order, built from the per-dispatch rows when read.
        Read by ``ServeFleet.latency_trace`` (merged over replicas), by
        ``launch/chip_serve.py`` (the share within the SLO) and by
        ``benchmarks/kernel_microbench.py`` (its latency trace file)."""
        out: List[Dict[str, Any]] = []
        for disp, t_done, lane, variant, rids, t_sub in self._done:
            if t_done <= 0.0:
                continue
            for rid, ts in zip(rids.tolist(), t_sub.tolist()):
                if ts > 0.0:
                    out.append(dict(rid=rid, lane=lane, variant=variant,
                                    dispatch=disp, t_submit=ts,
                                    t_done=t_done,
                                    latency_ms=(t_done - ts) * 1e3))
        return out

    def stats(self) -> ServeStats:
        chip = energy.serve_report(self.programs, self._vserved,
                                   self._vpadded, f_hz=self.f_hz,
                                   reports=self._reports,
                                   billed=self._billed)
        total = sum(self._served.values())
        fps = total / self._host_wall_s if self._host_wall_s else 0.0
        util = self._util_sum / self._dispatches if self._dispatches else 0.0
        energy_uj = sum(
            (self._vserved[v] + self._vpadded[v])
            * self._reports[v].i2l_energy_per_inference * 1e6
            for v in self.programs)
        budget = getattr(self.policy, "budget_uj_s", None)
        vd = dict(self.policy.variant_dispatches)
        lats = self._latency_s()
        if len(lats):
            p50, p95, p99 = np.percentile(lats, [50, 95, 99])
        else:
            p50 = p95 = p99 = 0.0
        padded = sum(self._padded.values())
        ratio = padded / self._billed if self._billed else 0.0
        return ServeStats(served=dict(self._served),
                          padded=dict(self._padded),
                          dispatches=self._dispatches,
                          host_wall_s=self._host_wall_s,
                          host_frames_per_s=fps,
                          chip=chip,
                          array_utilization=util,
                          shared_dispatches=self._shared_dispatches,
                          policy=self.policy.name,
                          variant_dispatches=vd,
                          energy_uj=energy_uj,
                          budget_uj_s=budget,
                          downshift_ratio=self.policy.downshift_ratio(),
                          p50_ms=float(p50) * 1e3,
                          p95_ms=float(p95) * 1e3,
                          p99_ms=float(p99) * 1e3,
                          padding_ratio=ratio)
