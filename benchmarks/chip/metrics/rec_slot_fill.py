"""Cascade drain: escalated frames over the recognizer slots the fused
cascade kernel computed (the rest is drain-chunk padding), over the run,
in %: the program's ``cascade.escalated`` and ``cascade.rec_slots``
counters (``repro.serving.telemetry``)."""

import program_telemetry


def read(rec):
    snap = program_telemetry.snapshot()
    if not snap:
        return None
    counters = snap["counters"]
    slots = counters.get("cascade.rec_slots")
    if not slots:
        return None
    return 100.0 * counters.get("cascade.escalated", 0) / slots
