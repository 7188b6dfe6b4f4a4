"""Multi-round influence maximization (paper §4.8; CR-NAIMM of Sun et al.'18).

Influence propagates over T independent rounds; we pick k seeds *per round*
to maximize the number of nodes influenced at least once.  Per the paper:
"after selecting a random node, we initiate a random BFS originating from
the selected node as many times as the number of rounds.  Each element in a
random RR set is a tuple of node-id and round number."

Implementation: the T per-round BFS of one RR sample run as T adjacent lanes
of the queue engine sharing one root; elements are encoded as
``round * n + node`` so the whole coverage machinery (occur histogram,
membership scan, decrement) is reused verbatim on an item space of size n·T.
The cross-round greedy of CR-NAIMM — mask rounds whose per-round budget k is
exhausted — is a *group budget* on the selection scan
(``SelectionSpec(n_group=n, n_groups=T, group_quota=k)``), so MRIM is just
``IMMSolver.solve(IMProblem(k=k, t_rounds=T, ...))``: the dedicated
``_greedy_mrim`` scan of earlier revisions is gone, and the selection scan
solves MRIM on any mesh.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from repro.graph.csr import CSRGraph
from repro.core import rrset as rr_queue
from repro.core.engine import MRIMEngine
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem


def sample_mrim_round(key, g_rev: CSRGraph, batch: int, t_rounds: int,
                      qcap: int, ec: int = rr_queue.EC_DEFAULT):
    """Sample ``batch`` MRIM RR sets (each = T tagged BFS from a shared root).

    Thin compatibility wrapper over :class:`~repro.core.engine.MRIMEngine`.
    Returns (nodes (B, W) encoded ids, lengths (B,), overflowed (B,)).
    """
    eng = MRIMEngine(g_rev, MRIMEngine.Config(batch=batch, t_rounds=t_rounds,
                                              qcap=qcap, ec=ec))
    b = eng.sample(key)
    return np.asarray(b.nodes), np.asarray(b.lengths), np.asarray(b.overflowed)


class MRIMResult(NamedTuple):
    seeds_per_round: list    # T lists of k node ids
    spread_estimate: float
    n_rr: int


def solve_mrim(g: CSRGraph, k: int, t_rounds: int, n_rr: int, *,
               qcap: int | None = None, batch: int = 64, seed: int = 0
               ) -> MRIMResult:
    """Fixed-θ MRIM solve — a thin wrapper over the unified problem API:
    ``IMMSolver(g, engine=...).solve(IMProblem(k=k, t_rounds=T, theta=n_rr))``
    (the paper's Table-3 experiment uses fixed ε; the IMM θ machinery
    composes identically — drop ``theta=`` from the problem to run the full
    Alg. 2 schedule)."""
    solver = IMMSolver(g, batch=batch, qcap=qcap, seed=seed)
    res = solver.solve(IMProblem(k=k, t_rounds=t_rounds, theta=n_rr))
    frac = res.frac
    return MRIMResult(seeds_per_round=res.seeds_per_round(),
                      spread_estimate=g.n_nodes * frac,
                      n_rr=res.stats.n_rr_sampled)
