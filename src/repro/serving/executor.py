"""Dispatch executor: the mechanism that runs a policy's decisions.

This module owns everything between a :class:`~repro.serving.policy.
Dispatch` decision and host-side results — no scheduling choices live
here:

* **launch** — pad each member lane's pull to the static batch (the
  always-on pipeline never idles; short lanes pad with the last real
  frame, empty lanes with zeros), scatter over the serving mesh if one
  is bound, and run the member's jit'd serve function.  A multi-lane
  dispatch runs as ONE shared-array composite ``pallas_call``
  (``interpreter.pack_programs``): composites are compiled lazily per
  ordered variant tuple and cached, so both admission-time groups
  (static policy) and per-dispatch tilings (operating-point controller
  downshifts) hit the same compile cache.
* **materialize / finish** — sync a dispatch's device arrays to host
  numpy and unpack them into per-request :class:`FrameResult`s.
* **depth-k prefetch pipeline** — :meth:`step` keeps up to ``prefetch``
  dispatches in flight before blocking on the oldest one, with finished
  results fetched to host memory by a background thread; the policy is
  still consulted in exactly the synchronous order, so pipelining never
  changes the schedule (property-tested).  At depth 1 the background
  thread is skipped: with a single in-flight handle the consumer pops it
  immediately, so a fetch thread adds handoff overhead without any
  overlap to win (the BENCH prefetch-anomaly fix).

Dispatches carry their own pad target (``Dispatch.batch``): a continuous
policy's early-and-small launches pad only to their bucket size, not the
full static batch, so the burned-slot bill shrinks with the window.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
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
from repro.serving.queue import FrameRequest, FrameResult


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
                 megakernel: bool = False, prefetch: int = 0,
                 warm_start: bool = True,
                 clock: Callable[[], float] = time.perf_counter,
                 probe: Optional[telemetry.Probe] = None):
        self.batch = batch
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
        # background fetch only pays off at depth >= 2: with one handle
        # in flight the consumer blocks on it immediately, so a thread
        # handoff is pure overhead (see module docstring)
        self._fetch_pool: Optional[concurrent.futures.ThreadPoolExecutor] = (
            concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="serve-fetch")
            if self.prefetch >= 2 else None)

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
        if dispatch.composite:
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
        if (variants, size) not in self._launched:
            self._launched.add((variants, size))
            probe.count("serve.compile", dispatch=index,
                        variants=",".join(variants), batch=size)
        with probe.span("serve.launch", dispatch=index):
            logits, labels = fn(art, tuple(frames) if dispatch.composite
                                else frames[0])
        # the launch stamp: how long the oldest frame of the batch queued
        t_launch = probe.clock()
        oldest = min((ld.requests[0].t_submit for ld in dispatch.lanes
                      if ld.requests), default=0.0)
        if oldest > 0.0:
            probe.stamp("queue_wait", t_launch - oldest)
        return dict(dispatch=dispatch, index=index, logits=logits,
                    labels=labels)

    @staticmethod
    def materialize(handle: Dict[str, Any], probe=None):
        """Sync an in-flight dispatch's device arrays to host numpy: the
        labels (``serve.wait``: the device's time and their transfer),
        then the logits (``serve.fetch``).  The background fetch thread
        calls it without a probe and records nothing."""
        many = handle["dispatch"].composite
        with (probe.span("serve.wait", dispatch=handle["index"])
              if probe is not None else contextlib.nullcontext()):
            labels = jax.block_until_ready(handle["labels"])
            labels = (tuple(np.asarray(l) for l in labels) if many
                      else np.asarray(labels))
        with (probe.span("serve.fetch", dispatch=handle["index"])
              if probe is not None else contextlib.nullcontext()):
            logits = (tuple(np.asarray(l) for l in handle["logits"]) if many
                      else np.asarray(handle["logits"]))
        return logits, labels

    def finish(self, handle: Dict[str, Any]) -> List[FrameResult]:
        """Block on an in-flight dispatch and materialize its results."""
        if "future" in handle:
            with self.probe.span("serve.wait", dispatch=handle["index"]):
                logits, labels = handle["future"].result()
        else:
            logits, labels = self.materialize(handle, self.probe)
        with self.probe.span("serve.finish", dispatch=handle["index"]):
            return self._results(handle, logits, labels)

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

    def _fill(self, launch_fn: Callable[[], Optional[Dict[str, Any]]]) -> None:
        """Launch dispatches until ``prefetch`` are in flight (or the
        queue drains), handing each to the background fetch thread."""
        while len(self._inflight) < self.prefetch:
            handle = launch_fn()
            if handle is None:
                return
            if self._fetch_pool is not None:
                handle["future"] = self._fetch_pool.submit(
                    self.materialize, handle)
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
        self._fill(launch_fn)                  # stage N+1.. while N runs
        return self.finish(cur)

    def abort(self) -> List[FrameRequest]:
        """Simulated host loss: drop every in-flight dispatch WITHOUT
        materializing results and hand back the orphaned requests,
        oldest dispatch first (the fleet re-enqueues them, in order, at
        the front of a survivor's lanes).  Device work already launched
        is abandoned — its energy was billed at launch and is genuinely
        burned, exactly like a chip losing power mid-frame."""
        orphans: List[FrameRequest] = []
        while self._inflight:
            handle = self._inflight.popleft()
            fut = handle.get("future")
            if fut is not None:
                fut.cancel()
            for ld in handle["dispatch"].lanes:
                orphans.extend(ld.requests)
        if self._fetch_pool is not None:
            self._fetch_pool.shutdown(wait=False, cancel_futures=True)
            self._fetch_pool = None
        return orphans

    def close(self) -> None:
        """Release the background fetch thread, syncing (and discarding)
        any in-flight dispatches; safe to call more than once."""
        while self._inflight:
            self.finish(self._inflight.popleft())
        if self._fetch_pool is not None:
            self._fetch_pool.shutdown(wait=True)
            self._fetch_pool = None

    def __del__(self):  # pragma: no cover - interpreter-exit ordering
        try:
            if getattr(self, "_fetch_pool", None) is not None:
                self._fetch_pool.shutdown(wait=False)
        except Exception:
            pass
