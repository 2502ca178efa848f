"""Benchmark harness: one entry per paper table/figure + system reports.

  fig4_layer_perf    Fig. 4  per-layer core GOPS / TOPS/W
  fig5_i2l           Fig. 5  I2L energy/throughput/power vs S
  table1_comparison  Table 1 cross-chip comparison + advantage ratios
  roofline_report    40-cell dry-run roofline table (needs dryrun JSONs)

Each prints human tables plus a ``CSV,name,us_per_call,derived`` line.
Exit code 0 iff every anchor check passes.
"""

from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (fig4_layer_perf, fig5_i2l, roofline_report,
                            table1_comparison)
    results = {}
    for name, mod in [("fig4_layer_perf", fig4_layer_perf),
                      ("fig5_i2l", fig5_i2l),
                      ("table1_comparison", table1_comparison),
                      ("roofline_report", roofline_report)]:
        try:
            results[name] = bool(mod.run())
        except Exception:  # noqa: BLE001 — report, keep going
            import traceback
            traceback.print_exc()
            results[name] = False
    print("\n== benchmark summary ==")
    for name, ok in results.items():
        print(f"  [{'OK' if ok else 'FAIL'}] {name}")
    sys.exit(0 if all(results.values()) else 1)


if __name__ == "__main__":
    main()
