"""The program's own telemetry (``repro.serving.telemetry``), read after
a run by the metrics that come from its spans and per-step rows.

``snapshot()`` returns ``None`` where the program has no such module
(a checkout from before it), so those metrics are left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def snapshot() -> Optional[Dict[str, Any]]:
    try:
        from repro.serving import telemetry
    except ImportError:
        return None
    return telemetry.snapshot()


def step_p50(stage: str) -> Optional[float]:
    """p50 over the recorded steps of one column, in seconds; ``None``
    without telemetry or rows."""
    snap = snapshot()
    if not snap or not snap["steps"]["rows"]:
        return None
    return snap["steps"]["p50"][stage]


def span_total(name: str) -> Optional[Dict[str, Any]]:
    """A span's totals (``count``, ``seconds``, ``self_seconds``);
    ``None`` without telemetry or without such a span."""
    snap = snapshot()
    if not snap:
        return None
    tot = snap["spans"].get(name)
    return tot if tot and tot["count"] else None
