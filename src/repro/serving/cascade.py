"""Cascaded always-on pipelines: cheap detector -> expensive recognizer.

The paper's flagship deployment (Sec. IV / Table 1): an always-on chip
runs the 0.92 uJ/frame S=4 face *detector* on every frame and only wakes
the 14.4 uJ/frame S=1 owner *recognizer* when a face is actually there —
the energy-accuracy hierarchy that makes an always-on budget feasible.
:class:`CascadePipeline` is that runtime on top of :class:`ChipServer`:

* every submitted frame enters the **detector** lane;
* a detector result whose logit margin (positive-class logit minus the
  best other logit) reaches ``margin`` **escalates**: the frame is
  resubmitted to the **recognizer** lane, whose label becomes the
  cascade's final answer (bit-exact vs running the recognizer offline
  on that frame — tested).  At the default ``margin=0.0`` this is
  exactly "the detector said ``positive_class``"; raising the margin
  trades recognizer energy for recall, lowering it (down to ``-inf`` =
  recognize everything) trades the other way;
* everything else finalizes with the detector's (negative) label.

Both stages run through the ordinary serving mechanism, so they batch,
pad, bill, prefetch and (when their S-modes allow) share the array like
any other lanes.  Escalations are **deferred**: promoted frames buffer
inside the pipeline until a full recognizer batch accumulates (the
trailing remainder flushes at drain) — without this, escalations drip
into the recognizer lane one or two per detector dispatch and static-
batch padding burns most of the expensive stage's energy; with it the
recognizer wakes only for (almost) full batches, which is exactly how a
real always-on hierarchy amortizes its wake-ups.
:meth:`CascadePipeline.report` bills the whole cascade with
:func:`energy.cascade_report`: detector energy on every frame plus
recognizer energy on the escalated fraction — strictly below running the
recognizer on every frame whenever the escalation rate is under
``1 - det_uj/rec_uj`` (~94% for the paper's 0.92 -> 14.4 uJ pair).

**Fused mode** (``CascadePipeline(..., fused=True)``) moves the whole
hierarchy into the kernel tier: detector + recognizer share ONE
composite SRAM image (``interpreter.pack_cascade``), the escalation
decision is made *inside* the kernel, and the recognizer drains the
in-kernel escalation queue through bounded-iteration control flow —
one dispatch per detector batch, no host round-trip, no deferred
buffering, no recognizer re-submission.  Labels are bit-exact vs the
host cascade for every margin (the kernel compares the integer logit
margin against ``ceil(margin)`` — equivalent for integer logits — see
``CascadePlan.margin_ctrl``); the energy bill is identical in shape
(detector on every slot, recognizer on the escalated count the kernel
reports back, plus its drain-chunk padding).  Fused dispatches are
compiled lazily through :meth:`Executor.cascade_for` and the warm-start
cache, like any composite.

**Margin calibration** (:func:`calibrate_margin`): instead of picking
the escalation margin by eyeball, run the detector offline on a
held-out labelled split and choose the *cheapest* (highest) margin
whose escalations still capture ``target_recall`` of the positive
frames — the margin becomes a recall contract, and energy-vs-recall is
a tunable curve.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np

from repro.core.chip import energy, interpreter
from repro.serving.queue import FrameResult
from repro.serving.server import ChipServer


def margins_of(logits, positive_class: int = 1) -> np.ndarray:
    """Vectorized escalation margins: positive-class logit minus the best
    competing logit, float64, one per row of ``logits``."""
    lg = np.asarray(logits, dtype=np.float64)
    pos = lg[:, positive_class]
    rest = np.delete(lg, positive_class, axis=1).max(axis=1)
    return pos - rest


def margin_for_recall(margins, labels, target_recall: float) -> float:
    """The cheapest escalation margin meeting a recall target.

    ``margins`` are detector logit margins on a held-out split,
    ``labels`` boolean "this frame must escalate" ground truth.  Returns
    the largest threshold ``thr`` such that at least
    ``ceil(target_recall * P)`` of the ``P`` positive frames satisfy
    ``margin >= thr`` — highest threshold = fewest escalations = the
    cheapest operating point on the energy-vs-recall curve.  With no
    positives (or a zero target) every threshold meets the target, so
    the cheapest is ``+inf`` (escalate nothing).
    """
    m = np.asarray(margins, dtype=np.float64)
    y = np.asarray(labels, dtype=bool)
    if m.shape != y.shape:
        raise ValueError(f"margins {m.shape} and labels {y.shape} disagree")
    pos = np.sort(m[y])[::-1]
    k = int(math.ceil(target_recall * len(pos)))
    if k <= 0:
        return float("inf")
    if k > len(pos):
        raise ValueError(
            f"target_recall {target_recall} asks for {k} of "
            f"{len(pos)} positive frames")
    return float(pos[k - 1])


def calibrate_margin(frames, labels, target_recall: float = 0.95, *,
                     detector, artifact, positive_class: int = 1,
                     interpret: Optional[bool] = None) -> float:
    """Calibrate the escalation margin on a held-out split.

    Runs ``detector`` (an ISA program, with its deployment ``artifact``)
    offline over ``frames``, computes the logit margins, and returns the
    cheapest margin capturing ``target_recall`` of the frames whose
    ``labels`` mark them positive (:func:`margin_for_recall`).  Replaces
    margin-by-heuristic (e.g. the bench's old median margin): the chosen
    margin carries a recall guarantee *on the calibration split*.
    """
    frames = np.asarray(frames)
    labels = np.asarray(labels, dtype=bool)
    if len(frames) != len(labels):
        raise ValueError(f"{len(frames)} frames vs {len(labels)} labels")
    plan = interpreter.compile_plan(detector)
    logits, _ = plan.forward(interpreter.ensure_packed(artifact), frames,
                             interpret=interpret)
    return margin_for_recall(margins_of(np.asarray(logits), positive_class),
                             labels, target_recall)


@dataclasses.dataclass(frozen=True)
class CascadeResult:
    """The cascade's final answer for one submitted frame."""
    rid: int                    # cascade-level request id (arrival order)
    label: int                  # recognizer label if escalated, else the
                                # detector's negative label
    escalated: bool
    detector_label: int
    detector_margin: float      # positive logit - best other logit
    logits: np.ndarray          # logits of the stage that produced label


class CascadePipeline:
    """Two-stage always-on cascade over a :class:`ChipServer`.

    ``detector`` and ``recognizer`` are resident lane names on
    ``server``; both must accept the same frame geometry.  ``margin``
    is the escalation threshold on the detector's logit margin (0.0 =
    escalate every positive-labelled frame).

    ``fused=True`` serves the hierarchy as ONE kernel dispatch per
    detector batch: frames still enqueue on the detector lane, but each
    step pulls a batch and runs it through the fused cascade kernel
    (``Executor.cascade_for``) — detector, in-kernel escalation mask,
    and recognizer-over-escalated-lanes in a single ``pallas_call``.
    Labels are bit-exact vs the host path for every margin; results
    finalize in the same step (no deferred recognizer batches).  Lanes
    outside the cascade still serve through the ordinary server path in
    either mode.
    """

    def __init__(self, server: ChipServer, detector: str, recognizer: str,
                 *, positive_class: int = 1, margin: float = 0.0,
                 fused: bool = False):
        for lane in (detector, recognizer):
            if lane not in server.queue.lanes:
                raise KeyError(f"lane {lane!r} not resident on the server "
                               f"(have {sorted(server.queue.lanes)})")
            if len(server._lane_variants[lane]) > 1:
                raise ValueError(
                    f"cascade stage {lane!r} is a program family; cascade "
                    "stages must be single-variant lanes (the energy bill "
                    "is per stage program)")
        if detector == recognizer:
            raise ValueError("detector and recognizer must be distinct lanes")
        gd = server._geom[detector]
        gr = server._geom[recognizer]
        if gd != gr:
            raise ValueError(
                f"cascade stages disagree on frame geometry: "
                f"detector {gd} vs recognizer {gr}")
        self.server = server
        self.detector = detector
        self.recognizer = recognizer
        self.positive_class = positive_class
        self.margin = margin
        self.fused = fused
        self._det_variant = server._lane_variants[detector][0]
        self._rec_variant = server._lane_variants[recognizer][0]
        # the fused dispatch unit compiles eagerly (like warm_composites:
        # resident programs load their weights before serving) and routes
        # through the executor's warm-start cache
        self._fused = (server.executor.cascade_for(
            self._det_variant, self._rec_variant,
            positive_class=positive_class) if fused else None)
        self.fused_dispatches = 0
        self._next_rid = 0
        self._frames: Dict[int, np.ndarray] = {}   # srid -> frame (det stage)
        self._det_rid: Dict[int, int] = {}         # det srid -> cascade rid
        self._rec_rid: Dict[int, int] = {}         # rec srid -> cascade rid
        self._det_info: Dict[int, tuple] = {}      # crid -> (label, margin)
        self._deferred: List[tuple] = []           # (crid, frame) awaiting a
                                                   # full recognizer batch
        self.other_results: List[FrameResult] = []  # results of server lanes
                                                    # outside the cascade
        self._submitted = 0
        self._escalated = 0

    # -- request side -------------------------------------------------------

    def submit(self, frame) -> int:
        """Enqueue one frame on the detector stage; returns its cascade
        request id (arrival order)."""
        rid = self._next_rid
        self._next_rid += 1
        srid = self.server.submit(self.detector, frame)
        self._det_rid[srid] = rid
        if not self.fused:       # fused dispatches gather frames in-kernel
            self._frames[srid] = np.asarray(frame)
        self._submitted += 1
        return rid

    def submit_many(self, frames) -> List[int]:
        return [self.submit(f) for f in frames]

    # -- dispatch side ------------------------------------------------------

    def _margin(self, logits: np.ndarray) -> float:
        """Positive-class logit minus the best competing logit."""
        pos = float(logits[self.positive_class])
        rest = np.delete(np.asarray(logits, dtype=np.float64),
                         self.positive_class)
        return pos - float(rest.max())

    def _route(self, r: FrameResult) -> Optional[CascadeResult]:
        """Process one server result: finalize, or escalate and return
        ``None`` (the recognizer's result will finalize later).  Results
        of lanes outside the cascade — the server may host other
        resident programs — pass through to :attr:`other_results`."""
        if r.rid not in self._det_rid and r.rid not in self._rec_rid:
            self.other_results.append(r)
            return None
        if r.rid in self._det_rid:
            crid = self._det_rid.pop(r.rid)
            frame = self._frames.pop(r.rid)
            m = self._margin(r.logits)
            if m >= self.margin:
                self._deferred.append((crid, frame))
                self._det_info[crid] = (r.label, m)
                self._escalated += 1
                self._flush(full_only=True)
                return None
            return CascadeResult(rid=crid, label=int(r.label),
                                 escalated=False, detector_label=int(r.label),
                                 detector_margin=m, logits=r.logits)
        crid = self._rec_rid.pop(r.rid)
        det_label, det_margin = self._det_info.pop(crid)
        return CascadeResult(rid=crid, label=int(r.label), escalated=True,
                             detector_label=det_label,
                             detector_margin=det_margin, logits=r.logits)

    def _flush(self, full_only: bool = False) -> None:
        """Submit deferred escalations to the recognizer lane — whole
        static batches only when ``full_only`` (the steady-state rule),
        everything when draining (the trailing partial batch)."""
        while len(self._deferred) >= self.server.batch or (
                self._deferred and not full_only):
            take = self._deferred[:self.server.batch]
            del self._deferred[:self.server.batch]
            for crid, frame in take:
                srid = self.server.submit(self.recognizer, frame)
                self._rec_rid[srid] = crid

    def _step_fused(self, reqs) -> List[CascadeResult]:
        """One fused dispatch: a detector batch through the in-kernel
        cascade; every frame in it finalizes immediately (escalated
        frames carry the recognizer's answer from the same kernel).
        The dispatch is the server's ``serve.step`` span."""
        srv = self.server
        with srv.probe.span("serve.step", dispatch=srv._dispatches):
            return self._run_fused(reqs)

    def _run_fused(self, reqs) -> List[CascadeResult]:
        srv = self.server
        size = srv.batch
        frames = srv.executor.pad_frames(reqs, srv._geom[self.detector],
                                         size, srv._dispatches)
        ctrl = interpreter.CascadePlan.margin_ctrl(self.margin, len(reqs))
        dl, dlab, rl, rlab, queue, counts = self._fused["fn"](
            self._fused["image"], frames, ctrl)
        dl, dlab = np.asarray(dl), np.asarray(dlab)
        rl, rlab = np.asarray(rl), np.asarray(rlab)
        queue, counts = np.asarray(queue), np.asarray(counts)
        esc, slots = int(counts[0]), int(counts[1])
        # bill both phases at launch like ChipServer._launch: detector
        # on every batch slot, recognizer on the slots the kernel
        # actually computed (escalated + drain-chunk padding, from the
        # kernel's own scalar report)
        n = len(reqs)
        srv._served[self.detector] += n
        srv._padded[self.detector] += size - n
        srv._vserved[self._det_variant] += n
        srv._vpadded[self._det_variant] += size - n
        srv._served[self.recognizer] += esc
        srv._padded[self.recognizer] += slots - esc
        srv._vserved[self._rec_variant] += esc
        srv._vpadded[self._rec_variant] += slots - esc
        srv._billed += size + slots
        srv._dispatches += 1
        # sequential phases: slot-weighted mean of the two occupancies
        sd = srv.programs[self._det_variant].s
        sr = srv.programs[self._rec_variant].s
        srv._util_sum += (size / sd + slots / sr) / (size + slots)
        self.fused_dispatches += 1
        self._escalated += esc
        rank = {int(p): k for k, p in enumerate(queue[:esc])}
        out = []
        for i, r in enumerate(reqs):
            crid = self._det_rid.pop(r.rid)
            m = self._margin(dl[i])
            k = rank.get(i)
            if k is None:
                out.append(CascadeResult(
                    rid=crid, label=int(dlab[i]), escalated=False,
                    detector_label=int(dlab[i]), detector_margin=m,
                    logits=dl[i]))
            else:
                out.append(CascadeResult(
                    rid=crid, label=int(rlab[k]), escalated=True,
                    detector_label=int(dlab[i]), detector_margin=m,
                    logits=rl[k]))
        return out

    def step(self) -> List[CascadeResult]:
        """One dispatch; returns any cascade results it finalized.

        Host mode: one server dispatch (escalating detector hits
        finalize on a later recognizer dispatch).  Fused mode: one
        detector batch through the in-kernel cascade, every frame in it
        final; the server only steps for lanes outside the cascade.
        [] when there was nothing to run."""
        if self.fused:
            reqs = self.server.queue.take(self.detector, self.server.batch)
            if reqs:
                return self._step_fused(reqs)
            got = self.server.step()      # lanes outside the cascade
            return [c for c in map(self._route, got) if c is not None]
        got = self.server.step()
        if not got and self._deferred:
            self._flush()                  # trailing partial batch
            got = self.server.step()
        return [c for c in map(self._route, got) if c is not None]

    def drain(self) -> List[CascadeResult]:
        """Serve until every submitted frame (including frames escalated
        along the way) has a final answer; results in finalization
        order."""
        out: List[CascadeResult] = []
        if self.fused:
            self.server.policy.set_flush(True)   # non-cascade lanes too
            try:
                while True:
                    got = self.step()
                    out.extend(got)
                    if not got and self.server.queue.pending() == 0:
                        return out
            finally:
                self.server.policy.set_flush(False)
        while True:
            got = self.server.step()
            if not got:
                if self._deferred:
                    self._flush()          # trailing partial batch
                    continue
                if self.server.queue.pending() == 0:
                    return out
                continue
            out.extend(c for c in map(self._route, got) if c is not None)

    # -- accounting ---------------------------------------------------------

    @property
    def submitted(self) -> int:
        return self._submitted

    @property
    def escalated(self) -> int:
        return self._escalated

    def calibrate(self, frames, labels,
                  target_recall: float = 0.95) -> float:
        """Calibrate ``self.margin`` on a held-out labelled split via
        :func:`calibrate_margin` (the pipeline's own detector program
        and artifact); returns — and adopts — the chosen margin."""
        ex = self.server.executor
        self.margin = calibrate_margin(
            frames, labels, target_recall,
            detector=self.server.programs[self._det_variant],
            artifact=ex._raw_artifacts[self._det_variant],
            positive_class=self.positive_class,
            interpret=ex._interpret)
        return self.margin

    def report(self, include_padding: bool = True) -> energy.CascadeReport:
        """The chip-model energy bill for everything this cascade served
        so far (see :func:`energy.cascade_report`).  ``include_padding``
        bills the static-batch padding slots each stage actually burned
        on the server (the honest deployment figure).

        All four figures come from the server's *launch ledger* (billed
        at dispatch, ``billed == served + padded`` per stage): detector
        frames and escalations that actually hit the array.  A
        mid-stream report therefore never bills frames still queued or
        deferred, and the drain-time recognizer remainder's padding is
        billed exactly once — the escalation rate's denominator is the
        detector frames served, not the padded slot count."""
        det_prog = self.server.programs[self._det_variant]
        rec_prog = self.server.programs[self._rec_variant]
        stats = self.server.stats()
        frames = stats.served.get(self.detector, 0)
        escalated = stats.served.get(self.recognizer, 0)
        padded_det = stats.padded.get(self.detector, 0)
        padded_rec = stats.padded.get(self.recognizer, 0)
        if not include_padding:
            padded_det = padded_rec = 0
        return energy.cascade_report(
            det_prog, rec_prog, frames=frames,
            escalated=escalated, detector_padded=padded_det,
            recognizer_padded=padded_rec, f_hz=self.server.f_hz)
