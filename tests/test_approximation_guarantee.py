"""The paper's core contract: IMM returns a (1-1/e-ε)-approximate seed set.

On a brute-force-solvable graph we enumerate all size-k seed sets, estimate
each spread by forward MC, and check every engine's solution clears the
bound (with MC slack).  This validates the full estimator chain
(θ math + sampling + greedy), not just its pieces.
"""
import itertools

import numpy as np
import jax
import pytest

from repro.graph import csr, generators, weights
from repro.core.imm import imm
from repro.core import forward, oracle

N, K, EPS = 24, 2, 0.3


def _graph():
    src, dst = generators.erdos_renyi(N, 96, seed=5)
    return weights.wc_weights(csr.from_edges(src, dst, N))


@pytest.mark.parametrize("engine", ["queue", "dense", "refill"])
def test_imm_clears_approximation_bound(engine):
    g = _graph()
    # brute force: spread of every 2-subset by forward MC
    best, best_set = -1.0, None
    for i, pair in enumerate(itertools.combinations(range(N), K)):
        s = forward.ic_spread(jax.random.key(1000 + i), g, list(pair),
                              n_sims=192)
        if s > best:
            best, best_set = s, pair
    seeds, est, _ = imm(g, K, EPS, engine=engine, batch=128, seed=3)
    got = forward.ic_spread(jax.random.key(7), g, seeds.tolist(),
                            n_sims=2048)
    bound = (1.0 - 1.0 / np.e - EPS) * best
    # 10% slack absorbs the MC noise of `best` and `got`
    assert got >= bound * 0.9, (engine, got, bound, best, best_set)


# ------------------------------------ the selection scan, every variant

def _oracle_rows(g_rev, count, rng, t_rounds=1):
    """``count`` numpy-oracle RR samples as item lists (``t_rounds``
    tagged sets ``t * n + v`` from one root, as the MRIM sampler draws
    them), with their roots."""
    n = g_rev.n_nodes
    offs, idx, w = (np.asarray(a) for a in
                    (g_rev.offsets, g_rev.indices, g_rev.weights))
    rows, roots = [], rng.integers(0, n, count)
    for root in roots:
        rows.append([t * n + v for t in range(t_rounds)
                     for v in oracle.rr_set_ic(offs, idx, w, int(root), rng)])
    return rows, roots


def _quota_greedy(rows, n_items, n_group, quota, k_steps):
    """Exact greedy with at most ``quota`` picks per item group (ties ->
    lowest id): the MRIM reference."""
    occur = np.zeros(n_items, np.int64)
    for r in rows:
        occur[r] += 1
    covered = np.zeros(len(rows), bool)
    left = np.full(-(-n_items // n_group), quota)
    seeds = []
    for _ in range(k_steps):
        u = int(np.argmax(np.where(left[np.arange(n_items) // n_group] > 0,
                                   occur, -1)))
        seeds.append(u)
        left[u // n_group] -= 1
        for i, r in enumerate(rows):
            if not covered[i] and u in r:
                covered[i] = True
                occur[r] -= 1
    return seeds


def _objective(seeds, rows, row_w, n):
    """RIS estimate of a seed set's objective on independent oracle rows:
    n · mean(row weight · [the row holds a seed])."""
    s = set(int(u) for u in seeds)
    hit = np.array([bool(s.intersection(r)) for r in rows])
    return n * float((row_w * hit).sum()) / len(rows)


@pytest.mark.parametrize("variant", ("plain", "weighted", "budgeted",
                                     "candidates", "mrim"))
def test_seed_quality_within_guarantee_vs_oracle(variant):
    """The selection scan's seeds clear (1 - 1/e - eps) of the numpy
    oracle's on each variant's own objective, judged on 8,192 independent
    oracle RR samples (10% slack absorbs the estimate's noise).  The
    weighted problem runs on an engine instance with uniform roots, so the
    row-weighted scan does the selecting."""
    from repro.core.engine import make_engine
    from repro.core.imm import IMMSolver
    from repro.core.problem import IMProblem
    n, k, eps = 30, 3, 0.3
    src, dst = generators.erdos_renyi(n, 150, seed=12)
    g = weights.wc_weights(csr.from_edges(src, dst, n))
    g_rev = csr.reverse(g)
    w = (np.arange(n) % 4 + 1).astype(np.float32)
    costs = (np.arange(n) % 3 + 1).astype(np.float32)
    cand = np.arange(0, n, 2)
    t_rounds = 2 if variant == "mrim" else 1
    pool, roots = _oracle_rows(g_rev, 2048, np.random.default_rng(0),
                               t_rounds)
    engine = "queue"
    if variant == "plain":
        problem = IMProblem(k=k, eps=eps, max_theta=2048)
        ref = oracle.imm_oracle(
            np.asarray(g_rev.offsets), np.asarray(g_rev.indices),
            np.asarray(g_rev.weights), n, k, eps, seed=0,
            max_theta=2048)[0]
    elif variant == "weighted":
        problem = IMProblem(k=k, eps=eps, max_theta=2048, node_weights=w)
        engine = make_engine("queue", g_rev, batch=64)
        ref = oracle.greedy_max_coverage_weighted(pool, n, k, w[roots])[0]
    elif variant == "budgeted":
        problem = IMProblem(eps=eps, max_theta=2048, costs=costs, budget=4.0)
        ref = oracle.budgeted_greedy_cost_ratio(pool, n, costs, 4.0)[0]
    elif variant == "candidates":
        problem = IMProblem(k=k, eps=eps, max_theta=2048, candidates=cand)
        mask = np.isin(np.arange(n), cand)
        ref = oracle.budgeted_greedy_cost_ratio(
            pool, n, np.ones(n), float(k), candidates=mask)[0]
    else:
        problem = IMProblem(k=2, t_rounds=t_rounds, theta=2048)
        ref = _quota_greedy(pool, n * t_rounds, n, 2, 2 * t_rounds)
    if isinstance(engine, str):
        solver = IMMSolver(g, engine=engine, batch=64, seed=2)
    else:
        solver = IMMSolver(g, engine=engine, seed=2)
    res = solver.solve(problem)
    if variant == "weighted":
        assert solver._row_weight_mode
    if variant == "budgeted":
        assert res.cost <= 4.0 + 1e-6
    if variant == "candidates":
        assert set(res.seeds.tolist()) <= set(cand.tolist())
    rows, roots = _oracle_rows(g_rev, 8192, np.random.default_rng(1),
                               t_rounds)
    row_w = w[roots] if variant == "weighted" else np.ones(len(rows))
    got = _objective(res.seeds, rows, row_w, n)
    want = _objective(ref, rows, row_w, n)
    bound = (1.0 - 1.0 / np.e - eps) * want
    assert got >= bound * 0.9, (variant, got, want)
