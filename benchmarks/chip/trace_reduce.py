"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` alone.  What it gives:

* the traced window: the harness's ``bench.window`` host span (the whole
  trace where that span is absent);
* per device, busy time: the union of the intervals in which an
  operation ran on it, clipped to the window; idle is the rest;
* Mosaic kernel time: the summed device time of the Pallas kernels
  (``tpu_custom_call`` events), over all devices;
* the device operations that took most time, summed by op name;
* idle time by what the host was doing: each gap between busy intervals
  goes to the innermost harness span (``bench.*``) open at its middle.

Device planes are ``/device:TPU:<n>``; their operations sit on the
``XLA Ops`` line.  Host spans sit on the ``/host:CPU`` plane.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def _stats(ev) -> Dict[str, str]:
    out = {}
    for item in ev.stats:
        try:
            k, v = item
        except (TypeError, ValueError):
            continue
        out[str(k)] = str(v)
    return out


def is_kernel(name: str, stats: Dict[str, str]) -> bool:
    """A Pallas (Mosaic) kernel: its HLO is a ``tpu_custom_call``."""
    text = " ".join([name] + list(stats.values()))
    return "tpu_custom_call" in text or "custom-call" in text


def op_name(name: str) -> str:
    """An HLO op's name without its text and instance number:
    ``%binary_conv2x2_block.11 = u32[...] custom-call(...)`` ->
    ``binary_conv2x2_block``."""
    base = name.split(" = ", 1)[0].strip().lstrip("%")
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def union_ns(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, w0, w1):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def device_planes(pd) -> list:
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1])
                  if p.name.rsplit(":", 1)[1].isdigit() else 0)


def host_spans(pd) -> List[Tuple[float, float, str]]:
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


class Spans:
    """Harness spans sorted by start, for attributing an instant: spans
    nest, so the innermost one open at ``t`` is the latest-starting one
    that covers it."""

    def __init__(self, spans):
        self.spans = sorted(sp for sp in spans if sp[2] != WINDOW_SPAN)
        self.starts = [sp[0] for sp in self.spans]

    def at(self, t: float, reach: int = 16) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(i - reach, -1), -1):
            s, e, name = self.spans[j]
            if e >= t:
                return name[len(SPAN_PREFIX):]
        return "none"


def reduce_profile(pd, devices: int = 1) -> dict:
    spans = host_spans(pd)
    open_at = Spans(spans)
    planes = device_planes(pd)[:devices]
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane")
    ops_by_plane = []
    lo, hi = float("inf"), float("-inf")
    for p in planes:
        evs = []
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name, _stats(ev)))
                lo, hi = min(lo, ev.start_ns), max(hi, evs[-1][1])
        ops_by_plane.append(evs)
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    w0, w1 = win[0] if win else (lo, hi)
    window_ns = max(w1 - w0, 0.0)

    busy, kernel_ns, totals = [], 0.0, {}
    gaps: Dict[str, float] = {}
    for evs in ops_by_plane:
        iv = []
        for s, e, name, st in evs:
            c = _clip(s, e, w0, w1)
            if c is None:
                continue
            iv.append(c)
            d = c[1] - c[0]
            key = op_name(name)
            totals[key] = totals.get(key, 0.0) + d
            if is_kernel(name, st):
                kernel_ns += d
        u = union_ns(iv)
        busy.append(sum(e - s for s, e in u))
        edges = [w0] + [x for se in u for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                who = open_at.at((a + b) / 2)
                gaps[who] = gaps.get(who, 0.0) + (b - a)
    n = len(busy)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "kernel_s": kernel_ns / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(totals.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])],
    }


def reduce(path: str, devices: int = 1) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), devices)
