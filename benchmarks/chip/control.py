#!/usr/bin/env python3
"""The control: the plain reference put in the system's place, its sums
computed in a lower precision, read by the cell's own comparison.

    python3 benchmarks/chip/control.py --workload cifar9_s1.backlog \
        --seeds 1,2,3 [--acc int8,bfloat16]

For each seed it builds the cell's seeded weights and frame bank (the
working set the window serves), runs the reference over the whole bank
at float32 and at each lower precision, and prints each number the
cell's check compares.  A control that reads above the limits shows the
comparison can fail.  Needs the chip, like ``run.py``; the benchmark's
runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def control(cell, config, *, seed, accs, devices, interpret=None) -> dict:
    import harness
    h = harness.Harness(cell, config, seed=seed, seconds=0.0, trace=False,
                        devices=devices, interpret=interpret)
    h.build()
    target = harness.driver_module(cell["driver"]).Target(h, system=False)
    out = {}
    for acc in accs:
        cols, idx = target.control_columns(h, acc)
        out[acc] = {k: v for k, (v, _lim) in
                    target.compare(h, cols, idx).items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--acc", default="int8,bfloat16")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    import jax
    cell = harness.cell_file(args.workload)
    config = harness.config_file(cell["config"])
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("control.py: needs the chip", file=sys.stderr)
        return 3
    from repro.kernels import cache as compile_cache
    compile_cache.enable_persistent()
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = control(cell, config, seed=seed, accs=args.acc.split(","),
                      devices=devs[:1])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
