"""Quickstart: solve influence maximization on a small social graph.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax

from repro.graph import csr, generators, weights
from repro.core.imm import imm
from repro.core import forward
from repro.core.engine import list_engines, make_engine
from repro.launch.compile_cache import configure_compile_cache


def main():
    configure_compile_cache()
    # 1. build a scale-free social graph with weighted-cascade probabilities
    src, dst = generators.barabasi_albert(2000, 4, seed=0)
    g = weights.wc_weights(csr.from_edges(src, dst, 2000))
    print(f"graph: n={g.n_nodes} m={g.n_edges}")

    # 2. run gIM (IMM accelerated by the batched queue engine).  Any name
    #    from the engine registry works here — see DESIGN.md §3.
    print(f"registered engines: {list_engines()}")
    seeds, spread_est, stats = imm(g, k=10, eps=0.35, engine="queue",
                                   batch=512, seed=0)
    print(f"seeds: {sorted(seeds.tolist())}")
    print(f"RIS spread estimate:  {spread_est:8.1f} "
          f"(theta={stats.theta}, rounds={stats.rounds})")

    # 2b. the engine protocol directly: sample one canonical RRBatch
    eng = make_engine("queue", csr.reverse(g), batch=8)
    batch = eng.sample(jax.random.key(0))
    print(f"one RRBatch: {batch.n_sets} sets, "
          f"max size {int(np.asarray(batch.lengths).max())}, "
          f"{int(batch.steps)} micro-steps")

    # 3. validate with forward Monte-Carlo (Kempe-style simulation)
    mc = forward.ic_spread(jax.random.key(7), g, seeds.tolist(), n_sims=512)
    print(f"forward MC spread:    {mc:8.1f}")
    # 4. compare against random seeds
    rnd = np.random.default_rng(0).choice(2000, size=10, replace=False)
    mc_rnd = forward.ic_spread(jax.random.key(8), g, rnd.tolist(),
                               n_sims=512)
    print(f"random-seed spread:   {mc_rnd:8.1f}  "
          f"(gIM advantage {mc / mc_rnd:.2f}x)")


if __name__ == "__main__":
    main()
