"""Server admission: mean microseconds per submitted frame inside
``ChipServer.submit`` (the program's ``serve.submit`` timing,
``repro.serving.telemetry``)."""

import program_telemetry


def read(rec):
    tot = program_telemetry.span_total("serve.submit")
    return None if tot is None else 1e6 * tot["seconds"] / tot["count"]
