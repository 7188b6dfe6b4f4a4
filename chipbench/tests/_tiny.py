"""A cell cut to a size the CPU runs in seconds, for the drill tests: the
harness without its look for a chip."""
import time

import jax

from chipbench import drills, run, spec

SOLVE = "ba-r4-75k.solve"


def run_tiny(workload: str, traffic: dict, drill: str | None, *,
             n: int = 3000, seconds: float = 1.0, seed: int = 2 ** 31 + 11,
             limits: dict | None = None) -> dict:
    cell = spec.cell(workload)
    cell = cell._replace(config={**cell.config, "n_nodes": n},
                         traffic={**cell.traffic, **traffic},
                         limits={**cell.limits, **(limits or {})})
    d = (None if drill is None
         else drills.drill(cell.traffic["kind"], drill))
    return run.execute(cell, seed, seconds, False, jax.devices()[:1],
                       t0=time.perf_counter(), drill=d)
