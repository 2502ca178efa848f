"""Executor: milliseconds a dispatch waits for its labels (device time
plus the label transfer), the p50 over the program's recorded steps of
``serve.wait`` (``repro.serving.telemetry``)."""

import program_telemetry


def read(rec):
    s = program_telemetry.step_p50("wait")
    return None if s is None else 1e3 * s
