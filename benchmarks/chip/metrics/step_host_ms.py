"""Server, queue, policy, executor, plans: host milliseconds of one
dispatch, the p50 over the program's recorded steps of ``serve.step``
less its ``serve.wait`` (``repro.serving.telemetry``).  Most steps come
from the untraced part of the window, so this describes the unslowed
host."""

import program_telemetry


def read(rec):
    s = program_telemetry.step_p50("host")
    return None if s is None else 1e3 * s
