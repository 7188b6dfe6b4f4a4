"""Controls and planted faults: runs of a cell whose ``correct`` must come
out false.  No benchmark run uses them.

The control puts the plain reference in the program's place with one
guarantee of the configuration broken: the seeds are the k nodes of
highest occurrence in the pool (overlap-blind, no marginal gains), not the
exact greedy — the shortcut that would tempt a later change to selection.
The faults break the timed path: the reverse BFS takes no step past the
root (``roots_only``), the answer is computed on half of the pool with its
share taken over that half (``half_pool``), or one seed of the answer is
altered where it is produced (``altered_answer``).  Every cell runs on one
chip, so no exchange between chips can be left out.

    python3 chipbench/drills.py --workload ba-r4-75k.solve --drill control \
        --seeds 11,12,13 --seconds 40
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Callable, NamedTuple, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Drill(NamedTuple):
    after_setup: Optional[Callable]
    alter: Optional[Callable]


def _frac(gains, n_rows) -> float:
    return float(np.float32(sum(gains)) / np.float32(max(n_rows, 1)))


def overlap_blind(pool, k: int):
    """The k nodes of highest occurrence (ties to the lowest id), each
    credited with its whole occurrence."""
    occur = np.bincount(pool.flat[(pool.flat >= 0) & (pool.flat < pool.n)],
                        minlength=pool.n)
    seeds = np.argsort(-occur, kind="stable")[:k]
    return [int(s) for s in seeds], [int(occur[s]) for s in seeds]


def _solve_answer(out: dict, seeds, gains, n_rows, n) -> dict:
    frac = _frac(gains, n_rows)
    return {**out, "seeds": seeds, "gains": gains, "frac": frac,
            "spread": n * frac}


def _control_solve(out, pool):
    seeds, gains = overlap_blind(pool, len(out["seeds"]))
    return _solve_answer(out, seeds, gains, pool.n_rows, pool.n)


def _sub_pool(pool, rows: int):
    from chipbench.reference import Pool
    end = pool.row_ptr[rows]
    return Pool(pool.flat[:end], pool.ids[:end], rows, pool.n)


def _half_solve(out, pool):
    half = _sub_pool(pool, max(pool.n_rows // 2, 1))
    seeds, gains = half.greedy(len(out["seeds"]))
    return _solve_answer(out, seeds, gains, half.n_rows, pool.n)


def _altered_solve(out, pool):
    seeds = list(out["seeds"])
    seeds[-1] = (seeds[-1] + 1) % pool.n
    return {**out, "seeds": seeds}


def _roots_only(state):
    """Every BFS step past the root returns the lanes' state unchanged: the
    engine's batches keep only their roots."""
    import jax
    import jax.numpy as jnp
    trunc = jax.jit(lambda lens: jnp.minimum(lens, 1))
    engine = state.new_solver(0).engine
    for name in ("sample", "sample_device"):
        fn = getattr(engine, name, None)
        if fn is None:
            continue

        def broken(key, fn=fn):
            b = fn(key)
            return b._replace(lengths=trunc(b.lengths))
        setattr(engine, name, broken)


def drill(kind: str, name: str) -> Drill:
    """The drill ``name`` for a cell of ``kind`` (``solve``)."""
    table = {
        ("solve", "control"): Drill(None, _control_solve),
        ("solve", "roots_only"): Drill(_roots_only, None),
        ("solve", "half_pool"): Drill(None, _half_solve),
        ("solve", "altered_answer"): Drill(None, _altered_solve),
    }
    if (kind, name) not in table:
        raise KeyError(f"no drill {name!r} for a {kind} cell")
    return table[(kind, name)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--drill", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if __package__ in (None, ""):
        sys.path.insert(0, os.path.dirname(HERE))
    from chipbench import run, spec
    run.prepare_process()
    import jax
    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("drills: needs the cell's TPU chips", file=sys.stderr)
        return 2
    d = drill(cell.traffic["kind"], args.drill)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.execute(cell, seed, args.seconds, False,
                          devices[:cell.chips], t0=time.perf_counter(),
                          drill=d)
        run.emit({"drill": args.drill, "seed": seed,
                  "correct": res["correct"], "checked": res["checked"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
