"""Fleet: milliseconds to build a replacement replica after a kill, the
program's ``fleet.replace`` span (mean over the run's replacements,
``repro.serving.telemetry``).  Part of ``fleet_recovery_ms``, which runs
on to the replacement's first served frame."""

import program_telemetry


def read(rec):
    tot = program_telemetry.span_total("fleet.replace")
    return None if tot is None else 1e3 * tot["seconds"] / tot["count"]
