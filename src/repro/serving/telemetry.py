"""Serving telemetry: host spans on the device trace's clock, counters and
a per-step ring, kept in memory by one process-wide recorder.

A span is two things at once:

* a ``jax.profiler.TraceAnnotation`` carrying its ids (the dispatch
  index, the replica): while a profiler trace is being taken it lands on
  the trace's host plane, on the same clock as the device ops, so the
  spans of one dispatch line up with its kernels; with no trace running
  it is a no-op in C++;
* an in-memory timer on its owner's injected clock (a server's
  ``clock``), so a ``VirtualClock`` replay records exactly 0.

Spans nest per thread: each records its parent, so a span's self time is
its duration less its children's.  A ``serve.step`` span opens one row
of the per-step ring: the replica, the dispatch index, the queue wait of
the dispatch it launched and the self seconds of every stage span
(:data:`STAGES`) closed inside it.

The recorder is process-wide, like a metrics registry: every
``ChipServer`` and ``ServeFleet`` records into :data:`RECORDER` under its
replica name unless it is handed its own (``telemetry=``).  There is no
switch: it is cheap enough to stay on (about ten spans and a row a
dispatch, one timed call a submitted frame).  :func:`snapshot` returns a
plain dict; :func:`reset` zeroes it.

Names (``docs/serving.md``, "Telemetry"):

* ``serve.submit`` — ``ChipServer.submit`` (timing and count only, no
  profiler span: it runs once per frame);
* ``serve.step`` — one ``ChipServer.step`` as its caller sees it, and
  inside it ``serve.select`` (policy and queue take), ``serve.stack``
  (stacking and padding the batch), ``serve.put`` (host-to-device copy),
  ``serve.launch`` (the jitted call and the request for its outputs'
  host copies; a compile shows here), ``serve.wait`` (what remains of
  the device time and of the label transfer), ``serve.fetch`` (what
  remains of the logits' transfer) and ``serve.finish`` (results and
  the server's books);
* ``serve.compile`` (counter, with the dispatch index) — launches of a
  (variant, batch size) the executor has not run before;
* ``serve.ahead`` (counter, no events) — dispatches launched while an
  earlier dispatch of the same server was still in flight: over the
  ``serve.launch`` count, the share of dispatches the pipeline overlapped;
* ``serve.ready`` (counter, with the dispatch index) — dispatches whose
  outputs the device had all computed when the host came to finish
  them: over the ``serve.launch`` count, the share whose transfers,
  started at launch, could run wholly while the host did other work;
* ``cascade.escalated``, ``cascade.rec_slots`` (counters, with the
  dispatch index) — per finished fused cascade dispatch, the frames the
  kernel escalated and the recognizer slots it computed (the excess over
  the escalated frames is drain-chunk padding);
* ``fleet.step``, ``fleet.fail``, ``fleet.replace`` — one fleet tick; a
  kill's harvest and requeue; building the replacement replica.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, Optional

import numpy as np
from jax import profiler

STEP = "serve.step"
STAGES = ("select", "stack", "put", "launch", "wait", "fetch", "finish")
COLUMNS = ("replica", "dispatch", "queue_wait", "step") + STAGES
RING_ROWS = 65536
EVENT_LIMIT = 1024           # counter events kept with their ids

_COL = {name: i for i, name in enumerate(COLUMNS)}
_STAGE_COL = {"serve." + s: _COL[s] for s in STAGES}


class Recorder:
    """Cumulative span totals, counters and a ring of per-step rows."""

    def __init__(self, rows: int = RING_ROWS):
        self._ring = np.zeros((rows, len(COLUMNS)))
        self._local = _Local()
        self._spans: Dict[tuple, list] = {}      # (name, replica) ->
        self.reset()                             # [count, s, self s]

    def reset(self) -> None:
        """Zero every total, counter and row."""
        self._n = 0                              # rows ever written
        for tot in self._spans.values():         # in place: probes hold
            tot[:] = [0, 0.0, 0.0]               # their own entries
        self._counters: Dict[str, int] = {}
        self._events = collections.deque(maxlen=EVENT_LIMIT)
        self._replica_ids: Dict[str, int] = {}

    def probe(self, replica: str,
              clock: Callable[[], float] = time.perf_counter) -> "Probe":
        """An owner's handle: spans under ``replica``, timed by ``clock``."""
        return Probe(self, replica, clock)

    # -- recording -------------------------------------------------------------

    def _total(self, name: str, replica: str) -> list:
        """The running [count, seconds, self seconds] of a span name
        under a replica."""
        tot = self._spans.get((name, replica))
        if tot is None:
            tot = self._spans[(name, replica)] = [0, 0.0, 0.0]
        return tot

    def count(self, name: str, n: int = 1, **ids) -> None:
        """Add ``n`` to a counter; with ids, also keep the event (the
        newest :data:`EVENT_LIMIT` of them)."""
        self._counters[name] = self._counters.get(name, 0) + n
        if ids:
            self._events.append(dict(ids, name=name))

    def _commit(self, replica: str, row: list) -> None:
        rid = self._replica_ids.setdefault(replica, len(self._replica_ids))
        row[0] = rid
        self._ring[self._n % len(self._ring)] = row
        self._n += 1

    # -- reading ---------------------------------------------------------------

    def rows(self) -> Dict[str, Any]:
        """The rows the ring holds, oldest first: one array per column,
        with ``replica`` as names."""
        k = min(self._n, len(self._ring))
        start = self._n - k
        idx = (np.arange(k) + start) % len(self._ring)
        block = self._ring[idx]
        names = {i: n for n, i in self._replica_ids.items()}
        out = {c: block[:, i] for i, c in enumerate(COLUMNS)}
        out["replica"] = np.array([names[int(i)] for i in out["replica"]],
                                  dtype=object)
        out["dispatch"] = out["dispatch"].astype(np.int64)
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Totals per span name (and per replica), counters, counter
        events, and p50/p95 over the held rows of each stage, the queue
        wait, the step and the step less its wait (``host``).  Seconds."""
        spans: Dict[str, Dict[str, Any]] = {}
        for (name, replica), (n, s, own) in sorted(self._spans.items()):
            if not n:
                continue
            agg = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                          "self_seconds": 0.0,
                                          "replicas": {}})
            agg["count"] += n
            agg["seconds"] += s
            agg["self_seconds"] += own
            agg["replicas"][replica] = {"count": n, "seconds": s}
        k = min(self._n, len(self._ring))
        steps: Dict[str, Any] = {"rows": k, "written": self._n,
                                 "p50": {}, "p95": {}}
        if k:
            block = self._ring[:k] if self._n <= len(self._ring) \
                else self._ring
            cols = {c: block[:, _COL[c]]
                    for c in ("queue_wait", "step") + STAGES}
            cols["host"] = cols["step"] - cols["wait"]
            for c, v in cols.items():
                p50, p95 = np.percentile(v, [50, 95])
                steps["p50"][c] = float(p50)
                steps["p95"][c] = float(p95)
        return {"spans": spans, "counters": dict(self._counters),
                "events": list(self._events), "steps": steps}


class Probe:
    """One owner's view of a recorder: its replica name, its clock, and
    its own span totals since :meth:`clear` (a server's books)."""

    __slots__ = ("recorder", "replica", "clock", "seconds", "_tots")

    def __init__(self, recorder: Recorder, replica: str,
                 clock: Callable[[], float]):
        self.recorder = recorder
        self.replica = replica
        self.clock = clock
        self.seconds: Dict[str, float] = {}
        self._tots: Dict[str, list] = {}

    def _total(self, name: str, replica: str) -> list:
        if replica != self.replica:
            return self.recorder._total(name, replica)
        tot = self._tots.get(name)
        if tot is None:
            tot = self._tots[name] = self.recorder._total(name, replica)
        return tot

    def span(self, name: str, **ids) -> "_Span":
        """A timed span and profiler annotation; ``ids`` (the dispatch
        index; ``replica`` defaults to the probe's) go on both."""
        ids.setdefault("replica", self.replica)
        return _Span(self, name, ids)

    def add(self, name: str, seconds: float, n: int = 1) -> None:
        """Time taken outside a span (no profiler event, not in
        :attr:`seconds`), e.g. once per submitted frame."""
        tot = self._tots.get(name) or self._total(name, self.replica)
        tot[0] += n
        tot[1] += seconds
        tot[2] += seconds

    def count(self, name: str, n: int = 1, **ids) -> None:
        """Add ``n`` to a counter; with ids, also keep the event, under
        this probe's replica (a per-dispatch counter passes none)."""
        if ids:
            ids.setdefault("replica", self.replica)
        self.recorder.count(name, n, **ids)

    def stamp(self, column: str, value: float) -> None:
        """Set a column of the step row open on this thread, if any."""
        step = self.recorder._local.step
        if step is not None:
            step.row[_COL[column]] = value

    def clear(self) -> None:
        self.seconds = {}


class _Local(threading.local):
    """Per thread: the innermost open span and the open step."""

    def __init__(self):
        self.top: Optional[_Span] = None
        self.step: Optional[_Span] = None


# is a profiler trace being taken?  (a static check in C++)
_annotating = profiler.TraceAnnotation.is_enabled
_SELECT = _COL["select"]
_STEP_COL = _COL["step"]


class _Span:
    __slots__ = ("probe", "name", "ids", "ann", "t0", "child", "parent",
                 "row", "live", "outer")

    def __init__(self, probe: Probe, name: str, ids: Dict[str, Any]):
        self.probe, self.name, self.ids = probe, name, ids
        self.child = 0.0
        self.ann = None

    def __enter__(self) -> "_Span":
        if _annotating():
            self.ann = profiler.TraceAnnotation(self.name, **self.ids)
            self.ann.__enter__()
        local = self.probe.recorder._local
        self.parent = local.top
        local.top = self
        if self.name == STEP:
            self.row = [0.0] * len(COLUMNS)
            self.row[1] = self.ids.get("dispatch", -1)
            self.live = False
            self.outer = local.step
            local.step = self
        self.t0 = self.probe.clock()
        return self

    def __exit__(self, *exc) -> None:
        probe = self.probe
        dur = probe.clock() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        rec = probe.recorder
        local = rec._local
        local.top = self.parent
        if self.parent is not None:
            self.parent.child += dur
        own = dur - self.child
        name = self.name
        replica = self.ids["replica"]
        tot = probe._total(name, replica)
        tot[0] += 1
        tot[1] += dur
        tot[2] += own
        seconds = probe.seconds
        seconds[name] = seconds.get(name, 0.0) + dur
        col = _STAGE_COL.get(name)
        if col is not None:
            step = local.step
            if step is not None:
                step.row[col] += own
                if col != _SELECT:
                    step.live = True
        elif name == STEP:
            local.step = self.outer
            if self.live:
                self.row[_STEP_COL] = dur
                rec._commit(replica, self.row)


RECORDER = Recorder()


def snapshot() -> Dict[str, Any]:
    """The process-wide recorder's :meth:`Recorder.snapshot`."""
    return RECORDER.snapshot()


def reset() -> None:
    """Zero the process-wide recorder."""
    RECORDER.reset()
