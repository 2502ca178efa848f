#!/usr/bin/env python3
"""What the serving telemetry costs the host, in ns per call.

    PYTHONPATH=src python benchmarks/telemetry_cost.py [--n 200000]

Times a stage span (``Probe.span``: the profiler annotation and the
in-memory timing), a ``serve.step`` span that writes a per-step row, and
the per-frame ``Probe.add`` of ``serve.submit``, each with no profiler
trace running and with one running (host tracer at level 1, the chip
benchmark's setting).  Prints one JSON line.  A host-CPU figure: it says
what a dispatch pays in host time, not what the device does.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax

from repro.serving import telemetry


def _ns(fn, n: int) -> float:
    t0 = time.perf_counter()
    fn(n)
    return (time.perf_counter() - t0) / n * 1e9


def measure(n: int) -> dict:
    rec = telemetry.Recorder()
    p = rec.probe("bench")

    def stage(n):
        for i in range(n):
            with p.span("serve.put", dispatch=i):
                pass

    def step(n):
        for i in range(n):
            with p.span("serve.step", dispatch=i):
                with p.span("serve.wait", dispatch=i):
                    pass

    def submit(n):
        for _ in range(n):
            p.add("serve.submit", 1e-6)

    def run():
        return {"stage_span_ns": _ns(stage, n),
                "step_with_wait_ns": _ns(step, n // 2),
                "submit_add_ns": _ns(submit, n)}

    out = {"backend": jax.default_backend(), "profiler_off": run()}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["profiler_on"] = run()
        finally:
            jax.profiler.stop_trace()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200000)
    args = ap.parse_args(argv)
    print(json.dumps(measure(args.n)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
