"""Shared benchmark helpers.

Scaling note: the paper's experiments use SNAP graphs with up to 1e8 edges
and eps=0.05 on a V100.  These benchmarks replay its figures on the CPU at
reduced n/eps, keeping the *methodology* (same machinery, same sweeps) and
recording the configuration next to each number.  Their timings are CPU
wall clocks, never device metrics; the TPU record is PERF.md, and the
chip itself is exercised by chip_smoke.py at the repository root.
"""
from __future__ import annotations

import csv
import os
import time

import numpy as np

from repro.graph import csr as csr_mod
from repro.graph import generators, weights

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "bench")


def ba_graph(n: int, r: int, seed: int = 0):
    src, dst = generators.barabasi_albert(n, r, seed=seed)
    return weights.wc_weights(csr_mod.from_edges(src, dst, n))


def timed(fn, *args, repeat: int = 1, **kw):
    fn(*args, **kw)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt


def write_csv(name: str, header: list[str], rows: list[list]):
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def report(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
