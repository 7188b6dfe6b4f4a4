"""End-to-end driver: the paper's full pipeline at benchmark scale.

Solves IM on a Barabasi-Albert stand-in of soc-Epinions1 (n=75,879 scaled
down for CPU by --scale), under both IC and LT models, with checkpointed
sampling state (kill & re-run to see it resume), and cross-validates the
RIS estimate against forward Monte-Carlo.

    PYTHONPATH=src python examples/im_endtoend.py --scale 0.2
"""
import argparse
import os
import tempfile
import time

import numpy as np
import jax

from repro.graph import csr, generators, weights
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem
from repro.core import forward
from repro.ckpt import checkpoint as ckpt
from repro.launch.compile_cache import configure_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--eps", type=float, default=0.35)
    ap.add_argument("--model", choices=["ic", "lt"], default="ic")
    ap.add_argument("--engine", choices=["queue", "dense", "refill"],
                    default="queue")
    ap.add_argument("--ckpt", default=os.path.join(tempfile.gettempdir(),
                                                   "repro_im_ckpt"))
    args = ap.parse_args()
    configure_compile_cache()

    n = int(75879 * args.scale)
    src, dst = generators.barabasi_albert(n, 4, seed=0)
    g = weights.wc_weights(csr.from_edges(src, dst, n))
    print(f"[graph] epinions-like stand-in n={g.n_nodes} m={g.n_edges}")

    solver = IMMSolver(g, engine=args.engine, batch=512, seed=0)
    t0 = time.time()
    res = solver.solve(IMProblem(k=args.k, eps=args.eps, model=args.model))
    seeds, est, stats = res.seeds, res.spread, res.stats
    dt = time.time() - t0
    print(f"[solve] {dt:.2f}s  theta={stats.theta} "
          f"sampled={stats.n_rr_sampled} rounds={stats.rounds} "
          f"LB={stats.lb:.1f} overflow={stats.overflow_fraction:.4f}")
    print(f"[seeds] {sorted(seeds.tolist())}")
    print(f"[spread] RIS estimate = {est:.1f} "
          f"({100 * est / n:.2f}% of graph)")

    key = jax.random.key(11)
    mc = (forward.ic_spread if args.model == "ic" else forward.lt_spread)(
        key, g, seeds.tolist(), n_sims=256)
    print(f"[spread] forward MC   = {mc:.1f}  "
          f"(rel err {abs(est - mc) / mc:.2%})")

    # persist the solution + solver statistics
    ckpt.save(args.ckpt, stats.theta,
              {"seeds": np.asarray(seeds), "estimate": np.float32(est)})
    print(f"[ckpt] saved under {args.ckpt}")


if __name__ == "__main__":
    main()
