"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME]``
Prints ``name,us_per_call,derived`` CSV lines; writes per-table CSVs to
experiments/bench/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (table2_runtime, fig3_breakdown, fig45_k_sweep,
                        fig6_eps_sweep, fig7_density, fig8_tuning,
                        table3_mrim, perf_im_engines)

ALL = [
    ("table2_runtime", table2_runtime.main),
    ("fig3_breakdown", fig3_breakdown.main),
    ("fig45_k_sweep", fig45_k_sweep.main),
    ("fig6_eps_sweep", fig6_eps_sweep.main),
    ("fig7_density", fig7_density.main),
    ("fig8_tuning", fig8_tuning.main),
    ("table3_mrim", table3_mrim.main),
    ("perf_im_engines", perf_im_engines.main),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    failures = []
    for name, fn in ALL:
        if args.only and args.only != name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
