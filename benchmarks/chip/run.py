#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``workloads/<cell>.json``) names its configuration, its driver
and its traffic.  The run builds seeded weights and a seeded frame bank,
warms every shape the cell's traffic uses (set-up), measures for
``--seconds``, then checks every answer against the plain reference and
prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, (traced runs)
``breakdown``, and last ``check``: each number compared with its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, as ``BENCHMARK.json`` lists them.
A traced run profiles only the window's last quarter (the profiler
slows the host): its host-clock metrics come from the part before it.

Exits non-zero, printing no result, unless JAX's first device is a TPU
and there are as many chips as the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def fail(msg: str, code: int = 2):
    print(f"benchmarks/chip/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench_file = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(bench_file):
        fail(f"no BENCHMARK.json at {ROOT}")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail("the system under test (src/repro) is not in this checkout")
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    bench = harness.load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r} (have {sorted(cells)})")
    cell = harness.cell_file(args.workload)
    config = harness.config_file(cell["config"])

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform!r}; "
             "this benchmark measures the chip and never falls back", 3)
    chips = int(cell["chips"])
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devs)}", 3)
    import work
    peaks = work.peaks_for(devs[0].device_kind)
    from repro.kernels import cache as compile_cache
    compile_cache.enable_persistent()

    result = measure(cell, config, bench, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     devices=devs[:chips], peaks=peaks, t_start=T_START)
    for name, c in result["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def measure(cell, config, bench, *, seed, seconds, trace, devices, peaks,
            t_start, interpret=None, target_hook=None) -> dict:
    """One run of one cell on ``devices``: set-up, window, check, metrics.
    ``interpret`` and ``target_hook`` (which may break the system under
    test) are for the benchmark's own tests."""
    import harness
    import numpy as np
    import work

    last = [t_start]

    def phase(name):
        t = time.perf_counter()
        print(f"set-up: {name} {t - last[0]:.3f} s", file=sys.stderr)
        last[0] = t

    phase("process start to harness")
    h = harness.Harness(cell, config, seed=seed, seconds=seconds,
                        trace=trace, devices=devices, interpret=interpret)
    h.build()
    phase("weights and frame bank")
    driver = harness.driver_module(cell["driver"])
    target = driver.Target(h)
    if target_hook is not None:
        target_hook(target)
    phase("system under test built")
    target.warm(h)
    gc.collect()           # set-up's garbage is not collected in the window,
    gc.freeze()            # nor are set-up's objects scanned there
    phase("warm-up")
    traffic = cell["traffic"]
    setup_s = time.perf_counter() - t_start
    log = harness.LOOPS[traffic["loop"]](h, target, traffic)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    t0, t_end = h.window
    t_ans = np.asarray(log.t_answer)
    in_window = [a for a, t in zip(log.answers, t_ans) if t <= t_end]
    untraced, served = [], in_window
    disp = log.counters.get("dispatches", 0)
    if h.traced:
        # the part of the window before the traced slice, and the slice
        call, s0, s1 = h.traced
        untraced = [a for a, t in zip(log.answers, t_ans) if t < call]
        served = [a for a, t in zip(log.answers, t_ans) if s0 <= t <= s1]
        disp -= log.counters_traced.get("dispatches", 0)
    work_done = target.work(h, served)
    untraced_work = target.work(h, untraced)
    target.close()                     # the program's state is freed here
    gc.collect()

    reduced = None
    if log.trace_file:
        import trace_reduce
        reduced = trace_reduce.reduce(log.trace_file, devices=len(devices))
    h.drop_trace()

    t_check = time.perf_counter()
    checks = harness.check(h, target, log)
    print(f"check: {time.perf_counter() - t_check:.3f} s over "
          f"{len(log.answers)} answers; programs compiled or loaded inside "
          f"the window: {log.counters['window_compiles']}", file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    def ops_of(done):
        return sum(work.frame_ops(h.layers[n]) * k for n, k in done.items())

    ops = ops_of(work_done)
    nbytes = sum(work.frame_bytes(h.layers[n]) * k
                 for n, k in work_done.items())
    nbytes += disp * sum(work.weight_bytes(h.layers[n]) for n in work_done)
    rec = {
        "cell": cell, "config": config, "chips": len(devices),
        "window_s": t_end - t0, "setup_s": setup_s,
        "frames_done": len(in_window),
        # traced runs: the traced slice's frames, work and seconds, and
        # the untraced part of the window before it
        "ops": ops, "bytes": nbytes,
        "traced_frames": len(served) if h.traced else 0,
        "traced_s": (h.traced[2] - h.traced[1]) if h.traced else 0.0,
        "untraced_frames": len(untraced), "untraced_ops": ops_of(untraced_work),
        "untraced_s": (h.traced[0] - t0) if h.traced else 0.0,
        "counters": log.counters, "trace": reduced, "peaks": peaks,
    }
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[group]:
        if not harness.applies(m, cell["name"]):
            continue
        value = harness.metric_reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(log.index),
           "failed": int(checks["missing"]["value"]),
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                            "idle_gaps": reduced["idle_gaps"][:10]}
    out["check"] = checks
    return out


if __name__ == "__main__":
    sys.exit(main())
