"""Dispatch executor: the mechanism that runs a policy's decisions.

This module owns everything between a :class:`~repro.serving.policy.
Dispatch` decision and host-side results — no scheduling choices live
here:

* **launch** — pad each member lane's pull to the static batch (the
  always-on pipeline never idles; short lanes pad with the last real
  frame, empty lanes with zeros), scatter over the serving mesh if one
  is bound, and run the member's jit'd serve function, then ask for
  every output's device-to-host copy at once (``copy_to_host_async``):
  the runtime starts each copy as soon as the kernel that writes it
  ends, so the host finds it done or under way when it comes back for
  the results, instead of starting it then.  A multi-lane
  dispatch runs as ONE shared-array composite ``pallas_call``
  (``interpreter.pack_programs``): composites are compiled lazily per
  ordered variant tuple and cached, so both admission-time groups
  (static policy) and per-dispatch tilings (operating-point controller
  downshifts) hit the same compile cache.
* **materialize / finish** — sync a dispatch's device arrays to host
  numpy and unpack them into per-request :class:`FrameResult`s.  A
  dispatch whose outputs are all computed when the host comes to
  finish it counts ``serve.ready``: its copies could run wholly while
  the host did other work.
* **depth-k prefetch pipeline** — :meth:`step` keeps up to ``prefetch``
  dispatches in flight before blocking on the oldest one; the policy is
  still consulted in exactly the synchronous order, so pipelining never
  changes the schedule (property-tested).  Depth 1, the server's
  default, launches the next ready dispatch before blocking on the
  current one, so the host path of dispatch N+1 runs while the device
  computes N; with nothing else ready the step is synchronous.  Every
  dispatch is materialized on the caller's thread: the copies started
  at launch are what overlaps the host, so the executor runs no thread
  of its own.  Each launch made while an earlier dispatch is still in
  flight counts ``serve.ahead``.

Dispatches carry their own pad target (``Dispatch.batch``): a continuous
policy's early-and-small launches pad only to their bucket size, not the
full static batch, so the burned-slot bill shrinks with the window.

A dispatch with a ``cascade`` route is the third kind beside single and
composite: one detector batch through the fused cascade unit
(:meth:`Executor.cascade_for`) with the route's margin as the kernel's
control word.  It pipelines like the others; its bill depends on the
counts the kernel returns, so :meth:`Executor.finish` hands them to the
server's ``on_cascade`` books and counts ``cascade.escalated`` and
``cascade.rec_slots``.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chip import interpreter, isa
from repro.distributed import sharding
from repro.kernels import cache as warmcache
from repro.serving import telemetry
from repro.serving.policy import Dispatch
from repro.serving.queue import (DetectorAnswer, FrameRequest, FrameResult,
                                 margins_of)


class Executor:
    """Launch/materialize/finish + the prefetch pipeline for one server.

    ``programs``/``artifacts`` are keyed by resident *variant* name (for
    a static server that is just the lane name).  ``artifacts`` holds the
    raw admission-time artifacts (any form); per-variant device operands
    and jit'd serve functions are built here.
    """

    def __init__(self, programs: Mapping[str, isa.Program],
                 artifacts: Mapping[str, Any], *, batch: int,
                 mesh=None, donate_frames: bool = False,
                 interpret: Optional[bool] = None,
                 megakernel: bool = False, prefetch: int = 1,
                 warm_start: bool = True,
                 clock: Callable[[], float] = time.perf_counter,
                 probe: Optional[telemetry.Probe] = None,
                 on_cascade: Optional[Callable[[Dispatch, int, int],
                                               None]] = None):
        self.batch = batch
        # the owner's books for a finished cascade dispatch:
        # (dispatch, escalated frames, recognizer slots computed)
        self.on_cascade = on_cascade
        self.mesh = mesh
        self.prefetch = prefetch
        self.clock = clock
        # the owning server's telemetry handle (serve.* stage spans)
        self.probe = probe or telemetry.RECORDER.probe("executor", clock)
        self._launched: set = set()      # (variants, size) run so far
        self._donate = donate_frames
        self._interpret = interpret
        self._megakernel = megakernel
        # warm_start routes serve-fn builds through the keyed warm-start
        # cache (kernels/cache.py): a second executor asking for the same
        # (programs, mesh, options, backend) shares the already-jitted
        # function — and its compiled shapes — so a replacement fleet
        # replica skips trace+compile entirely.  Sharing is safe because
        # serve fns are pure of weights (the artifact is an argument).
        self._warm_start = warm_start
        self.programs: Dict[str, isa.Program] = dict(programs)
        self._raw_artifacts: Dict[str, Any] = dict(artifacts)
        self.plans: Dict[str, interpreter.InferencePlan] = {}
        self.artifacts: Dict[str, Any] = {}
        self._fns: Dict[str, Any] = {}
        self._geom: Dict[str, Tuple[int, int, int]] = {}
        for name, prog in self.programs.items():
            isa.validate(prog)
            plan = interpreter.compile_plan(prog)
            if megakernel:
                art = interpreter.ensure_image(artifacts[name], prog)
            else:
                art = interpreter.ensure_packed(artifacts[name])
            if mesh is not None:
                art = sharding.replicate_artifact(mesh, art)
            io = prog.instrs[0]
            self.plans[name] = plan
            self.artifacts[name] = art
            self._geom[name] = (io.height, io.width, io.in_channels)
            self._fns[name] = self._serve_fn(plan, (prog,))
        self._composites: Dict[Tuple[str, ...], Dict[str, Any]] = {}
        self._cascades: Dict[Tuple[str, str, int], Dict[str, Any]] = {}
        self._deltas: Dict[Tuple[str, Optional[int], int], Dict[str, Any]] = {}
        self._inflight: collections.deque = collections.deque()

    def _serve_fn(self, plan, progs: Tuple[isa.Program, ...],
                  kind: str = "serve", **extra):
        """Build (or warm-start) the jit'd serve fn for ``plan``.
        ``extra`` kwargs pass through to ``plan.make_serve_fn`` — the
        caller must fold them into ``kind`` so the warm-start key
        distinguishes them."""
        # CompositePlan.make_serve_fn has no megakernel knob (a composite
        # IS one fused pallas_call already) — only single-program plans
        # take it.
        kw: Dict[str, Any] = dict(mesh=self.mesh,
                                  donate_frames=self._donate,
                                  interpret=self._interpret)
        if kind == "serve":
            kw["megakernel"] = self._megakernel
        kw.update(extra)
        build = lambda: plan.make_serve_fn(**kw)
        if not self._warm_start:
            return build()
        key = warmcache.serve_fn_key(
            progs, mesh=self.mesh,
            megakernel=self._megakernel and kind == "serve",
            donate_frames=self._donate, interpret=self._interpret,
            kind=kind)
        return warmcache.get_or_build(key, build)

    def geometry(self, variant: str) -> Tuple[int, int, int]:
        return self._geom[variant]

    def calibration_inputs(self, variant: str) -> Tuple[Any, Optional[bool]]:
        """What an offline calibration of ``variant`` runs on: its
        admission-time artifact and this executor's interpret mode."""
        return self._raw_artifacts[variant], self._interpret

    # -- composite compilation ---------------------------------------------

    def composite_for(self, variants: Tuple[str, ...]) -> Dict[str, Any]:
        """The compiled shared-array composite for an ordered variant
        tuple (lazy; cached — admission-time groups and on-the-fly
        controller tilings share the cache)."""
        comp = self._composites.get(variants)
        if comp is None:
            cplan, cimage = interpreter.pack_programs(
                {v: self.programs[v] for v in variants},
                {v: self._raw_artifacts[v] for v in variants})
            if self.mesh is not None:
                cimage = sharding.replicate_artifact(self.mesh, cimage)
            cfn = self._serve_fn(
                cplan, tuple(self.programs[v] for v in variants),
                kind="composite")
            comp = dict(plan=cplan, image=cimage, fn=cfn)
            self._composites[variants] = comp
        return comp

    def cascade_for(self, detector: str, recognizer: str, *,
                    positive_class: int = 1) -> Dict[str, Any]:
        """The compiled fused detector->recognizer cascade for a variant
        pair (lazy; cached like :meth:`composite_for`).  The serve fn
        routes through the warm-start cache with the positive class in
        the key — cascades of the same pair at different positive
        classes trace different escalation masks."""
        key = (detector, recognizer, positive_class)
        casc = self._cascades.get(key)
        if casc is None:
            cplan, cimage = interpreter.pack_cascade(
                {v: self.programs[v] for v in (detector, recognizer)},
                {v: self._raw_artifacts[v] for v in (detector, recognizer)},
                detector=detector, recognizer=recognizer,
                positive_class=positive_class)
            if self.mesh is not None:
                cimage = sharding.replicate_artifact(self.mesh, cimage)
            cfn = self._serve_fn(
                cplan, (self.programs[detector], self.programs[recognizer]),
                kind=f"cascade.p{positive_class}")
            casc = dict(plan=cplan, image=cimage, fn=cfn)
            self._cascades[key] = casc
        return casc

    def delta_for(self, variant: str, *, rb: Optional[int] = None,
                  check_every: int = 1) -> Dict[str, Any]:
        """The compiled delta-gated serving unit for one resident
        variant (lazy; cached like :meth:`composite_for`): the variant's
        ``DeltaPlan`` + megakernel weight image + jit'd stateful serve
        fn ``(image, frames, last, llog, ctrl) -> gated outputs``.
        ``rb``/``check_every`` tune the recompute-drain chunking and are
        part of the cache key (distinct knobs -> distinct compiles)."""
        key = (variant, rb, check_every)
        dl = self._deltas.get(key)
        if dl is None:
            dplan, dimage = interpreter.pack_delta(
                self.programs[variant], self._raw_artifacts[variant],
                name=variant)
            if self.mesh is not None:
                dimage = sharding.replicate_artifact(self.mesh, dimage)
            dfn = self._serve_fn(
                dplan, (self.programs[variant],),
                kind="delta.r%s.c%d" % (rb or 0, check_every),
                rb=rb, check_every=check_every)
            dl = dict(plan=dplan, image=dimage, fn=dfn)
            self._deltas[key] = dl
        return dl

    def warm_composites(self, groups) -> None:
        """Precompile composites for admission-time groups (static
        shared serving compiles its groups up front, like the chip
        loading every resident program's weights before serving)."""
        for members in groups:
            self.composite_for(tuple(members))

    @property
    def compiled_composites(self) -> Tuple[Tuple[str, ...], ...]:
        return tuple(self._composites)

    # -- launch / materialize / finish --------------------------------------

    def pad_frames(self, reqs: List[FrameRequest],
                   geom: Tuple[int, int, int],
                   size: Optional[int] = None, dispatch: int = -1):
        """Stack a lane's pull into a batch of ``size`` (default: the
        static batch — the always-on pipeline doesn't idle: short lanes
        pad with the last real frame, empty lanes with zeros; the burned
        slots are billed).  ``dispatch`` is the span's id."""
        size = self.batch if size is None else size
        with self.probe.span("serve.stack", dispatch=dispatch):
            if reqs:
                frames = np.stack([r.frame for r in reqs])
                if len(reqs) < size:
                    pad = np.broadcast_to(
                        frames[-1], (size - len(reqs),) + frames.shape[1:])
                    frames = np.concatenate([frames, pad])
            else:
                frames = np.zeros((size,) + geom, dtype=np.int32)
        return frames

    def launch(self, dispatch: Dispatch, index: int) -> Dict[str, Any]:
        """Run one policy decision on the device; returns the in-flight
        handle (device arrays, not yet synced)."""
        size = dispatch.batch if dispatch.batch is not None else self.batch
        variants = tuple(ld.variant for ld in dispatch.lanes)
        route = dispatch.cascade
        if route is not None:
            unit = self.cascade_for(variants[0], route.rec_variant,
                                    positive_class=route.positive_class)
            fn, art = unit["fn"], unit["image"]
            variants += (route.rec_variant,)
        elif dispatch.composite:
            comp = self.composite_for(variants)
            fn, art = comp["fn"], comp["image"]
        else:
            fn, art = self._fns[variants[0]], self.artifacts[variants[0]]
        host = [self.pad_frames(list(ld.requests), self._geom[ld.variant],
                                size, index) for ld in dispatch.lanes]
        probe = self.probe
        with probe.span("serve.put", dispatch=index):
            frames = [jnp.asarray(f) for f in host]
            if self.mesh is not None:
                frames = [sharding.scatter_frames(self.mesh, f)
                          for f in frames]
            if route is not None:
                ctrl = interpreter.CascadePlan.margin_ctrl(
                    route.margin, len(dispatch.lanes[0].requests))
        if (variants, size) not in self._launched:
            self._launched.add((variants, size))
            probe.count("serve.compile", dispatch=index,
                        variants=",".join(variants), batch=size)
        with probe.span("serve.launch", dispatch=index):
            if route is not None:
                dl, dlab, rl, rlab, queue, counts = fn(art, frames[0], ctrl)
                logits, labels = (dl, rl), (dlab, rlab, queue, counts)
            else:
                logits, labels = fn(art, tuple(frames) if dispatch.composite
                                    else frames[0])
            for out in jax.tree_util.tree_leaves((logits, labels)):
                out.copy_to_host_async()
        # the launch stamp: how long the oldest frame of the batch queued
        t_launch = probe.clock()
        oldest = min((ld.requests[0].t_submit for ld in dispatch.lanes
                      if ld.requests), default=0.0)
        if oldest > 0.0:
            probe.stamp("queue_wait", t_launch - oldest)
        return dict(dispatch=dispatch, index=index, logits=logits,
                    labels=labels)

    def materialize(self, handle: Dict[str, Any]):
        """Sync an in-flight dispatch's device arrays to host numpy: the
        labels (``serve.wait``: what is left of the device's time and of
        their transfer), then the logits (``serve.fetch``: what is left
        of theirs).  Both copies were asked for at launch.  A cascade's
        "labels" are its labels, escalation queue and counts; its
        "logits" both stages' logits."""
        dispatch = handle["dispatch"]
        many = dispatch.composite or dispatch.cascade is not None
        with self.probe.span("serve.wait", dispatch=handle["index"]):
            labels = jax.block_until_ready(handle["labels"])
            labels = (tuple(jax.device_get(labels)) if many
                      else np.asarray(labels))
        with self.probe.span("serve.fetch", dispatch=handle["index"]):
            logits = (tuple(jax.device_get(handle["logits"])) if many
                      else np.asarray(handle["logits"]))
        return logits, labels

    def finish(self, handle: Dict[str, Any]) -> List[FrameResult]:
        """Block on an in-flight dispatch and materialize its results.
        Counts ``serve.ready`` first if the device has already computed
        every output of the dispatch."""
        if all(out.is_ready() for out in jax.tree_util.tree_leaves(
                (handle["logits"], handle["labels"]))):
            self.probe.count("serve.ready", dispatch=handle["index"])
        logits, labels = self.materialize(handle)
        with self.probe.span("serve.finish", dispatch=handle["index"]):
            if handle["dispatch"].cascade is not None:
                return self._cascade_results(handle, logits, labels)
            return self._results(handle, logits, labels)

    def _cascade_results(self, handle, logits, labels) -> List[FrameResult]:
        """A finished cascade dispatch: its books (from the kernel's
        counts) and one result per real frame, answered by the
        recognizer where the frame escalated, else by the detector."""
        dispatch: Dispatch = handle["dispatch"]
        index = handle["index"]
        t_done = self.clock()
        dl, rl = logits
        dlab, rlab, queue, counts = labels
        esc, slots = int(counts[0]), int(counts[1])
        self.probe.count("cascade.escalated", esc, dispatch=index)
        self.probe.count("cascade.rec_slots", slots, dispatch=index)
        if self.on_cascade is not None:
            self.on_cascade(dispatch, esc, slots)
        rank = {p: k for k, p in enumerate(queue[:esc].tolist())}
        margins = margins_of(dl, dispatch.cascade.positive_class).tolist()
        dlab, rlab = dlab.tolist(), rlab.tolist()
        drows, rrows = list(dl), list(rl)
        ld, = dispatch.lanes
        out = []
        for i, r in enumerate(ld.requests):
            k = rank.get(i)
            det = DetectorAnswer(dlab[i], drows[i], margins[i], k is not None)
            label, lg = ((dlab[i], drows[i]) if k is None
                         else (rlab[k], rrows[k]))
            out.append(FrameResult(r.rid, ld.lane, label, lg, index,
                                   ld.variant, r.t_submit, t_done, det))
        return out

    def _results(self, handle, logits, labels) -> List[FrameResult]:
        dispatch: Dispatch = handle["dispatch"]
        t_done = self.clock()        # label available on the host, now
        if dispatch.composite:
            out = []
            for mi, ld in enumerate(dispatch.lanes):
                out.extend(
                    FrameResult(rid=r.rid, program=ld.lane,
                                label=int(labels[mi][i]),
                                logits=logits[mi][i],
                                dispatch=handle["index"],
                                variant=ld.variant,
                                t_submit=r.t_submit, t_done=t_done)
                    for i, r in enumerate(ld.requests))
            return out
        ld, = dispatch.lanes
        return [FrameResult(rid=r.rid, program=ld.lane, label=int(labels[i]),
                            logits=logits[i], dispatch=handle["index"],
                            variant=ld.variant,
                            t_submit=r.t_submit, t_done=t_done)
                for i, r in enumerate(ld.requests)]

    # -- the prefetch pipeline ----------------------------------------------

    def inflight_frames(self) -> int:
        """Real frames of the dispatches launched and not yet finished."""
        return sum(len(ld.requests) for handle in self._inflight
                   for ld in handle["dispatch"].lanes)

    def _fill(self, launch_fn: Callable[[], Optional[Dict[str, Any]]],
              busy: bool = False) -> None:
        """Launch dispatches until ``prefetch`` are in flight (or the
        policy has nothing ready).  ``busy``: the caller holds a popped
        dispatch that is still running, so every launch here runs ahead
        of it."""
        while len(self._inflight) < self.prefetch:
            ahead = busy or bool(self._inflight)
            handle = launch_fn()
            if handle is None:
                return
            if ahead:
                self.probe.count("serve.ahead")
            self._inflight.append(handle)

    def step(self, launch_fn: Callable[[], Optional[Dict[str, Any]]]
             ) -> List[FrameResult]:
        """One dispatch through the pipeline: synchronous when
        ``prefetch == 0``, else keep the pipeline filled and block only
        on the oldest in-flight dispatch."""
        if not self.prefetch:
            cur = launch_fn()
            return [] if cur is None else self.finish(cur)
        self._fill(launch_fn)
        if not self._inflight:
            return []
        cur = self._inflight.popleft()
        self._fill(launch_fn, busy=True)       # launch N+1.. while N runs
        return self.finish(cur)

    def abort(self) -> List[FrameRequest]:
        """Simulated host loss: drop every in-flight dispatch WITHOUT
        materializing results and hand back the orphaned requests,
        oldest dispatch first (the fleet re-enqueues them, in order, at
        the front of a survivor's lanes).  Device work already launched
        is abandoned; what the server billed for it at launch stays
        billed, exactly like a chip losing power mid-frame (a cascade,
        billed only when it finishes, stays unbilled)."""
        orphans: List[FrameRequest] = []
        while self._inflight:
            for ld in self._inflight.popleft()["dispatch"].lanes:
                orphans.extend(ld.requests)
        return orphans

    def close(self) -> None:
        """Sync (and discard) any in-flight dispatches; safe to call
        more than once."""
        while self._inflight:
            self.finish(self._inflight.popleft())
