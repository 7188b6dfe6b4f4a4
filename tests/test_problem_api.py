"""IMProblem variant spec: one solve(problem) API for plain / weighted /
budgeted / candidate-restricted / MRIM influence maximization.

Contracts under test (ISSUE acceptance criteria):
* plain problems through ``solve(IMProblem(...))`` match ``solve_problem``
  bit-identically on every sampler engine;
* the removed ``solve(k, eps)`` shim raises TypeError (never warns, never
  samples);
* ``imm()`` raises TypeError on unknown kwargs (the old whitelist filter
  silently swallowed typos), the removed ``selection=`` / ``eval_batch=``
  knobs included;
* variant solves are deterministic conformant with the numpy references
  (weighted greedy, budgeted cost-ratio greedy) on the *same* RR pool;
* candidate restriction and budgets are honored;
* MRIM routes through the selection scan (``_greedy_mrim`` is gone) with
  per-round quotas;
* the sketch-driven θ early exit provably never changes seeds/θ;
* variant solves run under ``jax.transfer_guard("disallow")``.
"""
import numpy as np
import jax
import pytest

from repro.graph import csr as csr_mod
from repro.graph import generators, weights
from repro.core import coverage as cov, mrim, oracle
from repro.core.engine import make_engine
from repro.core.imm import IMMSolver, imm, imm_result
from repro.core.problem import IMProblem, IMResult


def _wc_graph(n=50, m=250, seed=0):
    src, dst = generators.erdos_renyi(n, m, seed=seed)
    return weights.wc_weights(csr_mod.from_edges(src, dst, n))


def _pool_lists(store):
    """Reconstruct python RR-set lists from a store snapshot (conformance
    references run on the exact pool the solver selected from)."""
    snap = store.snapshot()
    flat = np.asarray(snap.rr_flat)[np.asarray(snap.valid)]
    ids = np.asarray(snap.rr_ids)[np.asarray(snap.valid)]
    return [flat[ids == i].tolist() for i in range(snap.n_rr)]


# ------------------------------------------------------------- validation

def test_improblem_validation():
    with pytest.raises(ValueError, match="exactly one of"):
        IMProblem()                                  # neither k nor budget
    with pytest.raises(ValueError, match="exactly one of"):
        IMProblem(k=3, budget=2.0)                   # both
    with pytest.raises(ValueError, match="costs= requires budget="):
        IMProblem(k=3, costs=np.ones(5))
    with pytest.raises(ValueError, match="budgeted MRIM"):
        IMProblem(budget=2.0, t_rounds=2)
    with pytest.raises(ValueError, match="IC-only"):
        IMProblem(k=2, t_rounds=2, model="lt")
    with pytest.raises(ValueError, match="positive int"):
        IMProblem(k=0)
    p = IMProblem(k=3, node_weights=[1, 2], candidates=[0])
    with pytest.raises(ValueError, match="node_weights"):
        p.resolve(5)                                 # wrong weight length
    with pytest.raises(ValueError, match="candidate ids"):
        IMProblem(k=2, candidates=[7]).resolve(5)
    with pytest.raises(ValueError, match="affordable"):
        IMProblem(budget=1.0, costs=np.full(5, 9.0)).resolve(5)
    assert IMProblem(k=2).variant == "plain"
    assert IMProblem(budget=1.0, node_weights=np.ones(3)).variant == \
        "weighted+budgeted"


# --------------------------------------- plain parity + shim removal

@pytest.mark.parametrize("engine", ("queue", "refill", "lt"))
def test_plain_problem_solve_and_solve_problem_agree(engine):
    g = _wc_graph()
    res = IMMSolver(g, engine=engine, batch=64, seed=3).solve(
        IMProblem(k=4, eps=0.5, max_theta=256))
    res2 = IMMSolver(g, engine=engine, batch=64, seed=3).solve_problem(
        IMProblem(k=4, eps=0.5, max_theta=256))
    assert isinstance(res, IMResult)
    np.testing.assert_array_equal(res.seeds, res2.seeds)
    assert res.spread == res2.spread
    assert res.stats.theta == res2.stats.theta
    assert res.stats.variant == "plain"
    assert res.stats.selection == "fused"


def test_removed_solve_k_eps_form_raises_typeerror():
    """The solve(k, eps) deprecation shim is gone: the positional/kwarg
    forms raise a TypeError that points at IMProblem, never warn, and
    never run a solve."""
    g = _wc_graph()
    solver = IMMSolver(g, batch=64, seed=0)
    with pytest.raises(TypeError, match="IMProblem"):
        solver.solve(2, 0.5)
    with pytest.raises(TypeError, match="removed"):
        solver.solve(2, 0.5, max_theta=64)
    with pytest.raises(TypeError, match="IMProblem"):
        solver.solve(k=2, eps=0.5)
    assert solver._stats.rounds == 0    # the shim path never sampled


def test_solve_rejects_extra_args():
    g = _wc_graph()
    with pytest.raises(TypeError, match="IMProblem"):
        IMMSolver(g, batch=64).solve(IMProblem(k=2, eps=0.5), 0.4)
    with pytest.raises(TypeError, match="IMProblem"):
        IMMSolver(g, batch=64).solve(IMProblem(k=2, eps=0.5), k=5)


def test_tagged_engine_instance_solves_matching_t_rounds():
    """A tagged (MRIM) engine *instance* defers the item-space check to the
    first solve, which must carry the matching t_rounds; a plain solve on
    it still raises."""
    from repro.core.engine import MRIMEngine
    g = _wc_graph(seed=4)
    eng = MRIMEngine(csr_mod.reverse(g),
                     MRIMEngine.Config(batch=16, t_rounds=3))
    res = IMMSolver(g, engine=eng, seed=1).solve(
        IMProblem(k=2, t_rounds=3, theta=128))
    assert len(res.seeds_per_round()) == 3
    with pytest.raises(ValueError, match="item space"):
        IMMSolver(g, engine=eng, seed=1).solve(IMProblem(k=2, eps=0.5))


def test_imm_unknown_kwargs_raise_typeerror():
    """Regression: the old whitelist filter silently dropped typos like
    ``sketchk=64`` (the user thought they had configured the sketch)."""
    g = _wc_graph()
    with pytest.raises(TypeError, match="sketchk"):
        imm(g, 3, 0.5, sketchk=64)
    with pytest.raises(TypeError, match="slection"):
        imm(g, 3, 0.5, slection="fused")
    with pytest.raises(TypeError, match="foo"):
        imm_result(g, IMProblem(k=2, eps=0.5), foo=1)
    # known keys still work end to end
    seeds, est, st = imm(g, 3, 0.5, engine="queue", batch=64, max_theta=128,
                         sketch_k=64)
    assert len(seeds) == 3 and est > 0


@pytest.mark.parametrize("keyword,value", [("selection", "fused"),
                                           ("eval_batch", 32)],
                         ids=("selection", "eval_batch"))
def test_removed_selection_keywords_raise(keyword, value):
    """Selection has no options left: the removed ``selection=`` and
    ``eval_batch=`` knobs raise TypeError like any unknown keyword, on the
    solver, the one-shot wrappers and the serving registry's solver
    options, before anything is built or sampled."""
    from repro.serve.registry import WarmSolverRegistry
    g = _wc_graph()
    with pytest.raises(TypeError, match=keyword):
        IMMSolver(g, **{keyword: value})
    with pytest.raises(TypeError, match=keyword):
        imm(g, 3, 0.5, **{keyword: value})
    with pytest.raises(TypeError, match=keyword):
        imm_result(g, IMProblem(k=2, eps=0.5), **{keyword: value})
    with pytest.raises(TypeError, match=keyword):
        WarmSolverRegistry(solver_opts={"batch": 64, keyword: value})


# ------------------------------------------------------------- variants

def test_candidate_restriction_honored_all_backends():
    g = _wc_graph(seed=1)
    cand = np.arange(0, 50, 3)
    solver = IMMSolver(g, batch=64, seed=2)
    res = solver.solve(IMProblem(k=4, eps=0.5, max_theta=256,
                                 candidates=cand))
    assert set(res.seeds.tolist()) <= set(cand.tolist())
    # the candidate-masked greedy on the pool the solver selected from
    mask = np.isin(np.arange(50), cand)
    ref_seeds, ref_frac, _ = oracle.budgeted_greedy_cost_ratio(
        _pool_lists(solver.store), 50, np.ones(50), 4.0, candidates=mask)
    assert res.seeds.tolist() == ref_seeds
    assert res.frac == pytest.approx(ref_frac, abs=1e-6)


def test_candidate_exhaustion_never_duplicates_seeds():
    """Regression: with fewer productive candidates than k, the variant
    greedy must stop (trimmed sentinels), never pad the result by
    re-picking an already-selected seed at zero gain."""
    g = _wc_graph(seed=1)
    cand = [7, 9]
    res = IMMSolver(g, batch=64, seed=2).solve(
        IMProblem(k=5, eps=0.5, theta=256, candidates=cand))
    s = res.seeds.tolist()
    assert len(s) == len(set(s)), s
    assert set(s) <= set(cand) and len(s) <= len(cand)


def test_problem_model_overrides_solver_default():
    """Regression: an explicit model="ic" on the problem must override a
    solver constructed with model="lt" (None inherits)."""
    g = _wc_graph(seed=2)
    solver = IMMSolver(g, model="lt", batch=64, seed=0)
    solver.solve(IMProblem(k=2, eps=0.5, theta=128, model="ic"))
    assert solver.engine_name == "queue"
    solver.solve(IMProblem(k=2, eps=0.5, theta=128))   # None -> inherit lt
    assert solver.engine_name == "lt"
    with pytest.raises(ValueError, match="IC-only"):
        solver.solve(IMProblem(k=2, t_rounds=2, theta=128))


def test_budgeted_solve_honors_budget_and_matches_reference():
    g = _wc_graph(seed=2)
    rng = np.random.default_rng(5)
    costs = rng.integers(1, 5, 50).astype(np.float32)
    budget = 7.0
    solver = IMMSolver(g, batch=64, seed=4)
    res = solver.solve(IMProblem(eps=0.5, theta=512, costs=costs,
                                 budget=budget))
    assert res.cost <= budget + 1e-6
    assert res.cost == pytest.approx(float(costs[res.seeds].sum()))
    # deterministic conformance: numpy cost-ratio greedy on the exact pool
    # the solver selected from
    ref_seeds, ref_frac, ref_spent = oracle.budgeted_greedy_cost_ratio(
        _pool_lists(solver.store), 50, costs, budget)
    assert res.seeds.tolist() == ref_seeds
    assert res.frac == pytest.approx(ref_frac, abs=1e-6)
    assert res.cost == pytest.approx(ref_spent)


def test_weighted_row_estimator_matches_numpy_reference():
    """Row-weighted (importance-weighted) selection — the fallback for
    engines without weighted-root sampling — equals the weighted numpy
    greedy on the same pool."""
    g = _wc_graph(seed=3)
    w = (np.arange(50) % 7 + 1).astype(np.float32)
    eng = make_engine("queue", csr_mod.reverse(g), batch=64)  # uniform roots
    solver = IMMSolver(g, engine=eng, seed=6)
    res = solver.solve(IMProblem(k=4, eps=0.5, theta=512, node_weights=w))
    assert solver._row_weight_mode            # fallback estimator engaged
    rr = _pool_lists(solver.store)
    roww = w[[r[0] for r in rr]]              # queue rows are root-first
    ref_seeds, ref_frac = oracle.greedy_max_coverage_weighted(rr, 50, 4, roww)
    assert res.seeds.tolist() == ref_seeds
    assert res.frac == pytest.approx(ref_frac, rel=1e-5)
    assert res.spread == pytest.approx(float(w.sum()) * ref_frac, rel=1e-5)


def test_plain_problem_on_weighted_engine_instance_raises():
    """Regression: a weighted-root engine instance under a plain problem
    would silently estimate the weighted objective on the uniform scale —
    the solver must refuse instead (and accept the matching weighted
    problem in weight-proportional mode)."""
    g = _wc_graph(seed=3)
    w = (np.arange(50) % 3 + 1).astype(np.float32)
    eng = make_engine("queue", csr_mod.reverse(g), batch=32, root_weights=w)
    solver = IMMSolver(g, engine=eng, seed=0)    # deferred prepare
    with pytest.raises(ValueError, match="no node_weights"):
        solver.solve(IMProblem(k=2, eps=0.5, theta=128))
    res = IMMSolver(g, engine=eng, seed=0).solve(
        IMProblem(k=2, eps=0.5, theta=128, node_weights=w))
    assert len(res.seeds) == 2
    assert not np.asarray(res.gains).sum() == 0


def test_weighted_solve_uses_weight_proportional_roots():
    """Named engines get the alias table: the solver samples roots ∝ w and
    selection stays the plain (row-unweighted) program."""
    g = _wc_graph(seed=4)
    w = np.zeros(50, np.float32)
    w[:10] = 1.0                               # only nodes 0..9 draw roots
    solver = IMMSolver(g, batch=64, seed=1)
    res = solver.solve(IMProblem(k=3, eps=0.5, theta=256, node_weights=w))
    assert not solver._row_weight_mode
    assert solver.engine.root_weights is not None
    rr = _pool_lists(solver.store)
    assert all(r[0] < 10 for r in rr)          # every root came from support
    assert res.spread <= float(w.sum()) + 1e-6  # scale is Σw, frac <= 1


# ----------------------------------------------------------------- MRIM

def test_mrim_routes_through_unified_backends():
    assert not hasattr(mrim, "_greedy_mrim")   # dedicated scan deleted
    g = _wc_graph(seed=8)
    res = IMMSolver(g, seed=0, batch=32).solve(
        IMProblem(k=2, t_rounds=3, theta=512))
    per_round = res.seeds_per_round()
    assert len(per_round) == 3
    assert all(len(s) == 2 for s in per_round)       # per-round quota
    # the wrapper is a thin IMProblem(t_rounds=T) shim over the same path
    wrapped = mrim.solve_mrim(g, k=2, t_rounds=3, n_rr=512, batch=32, seed=0)
    assert wrapped.seeds_per_round == \
        IMMSolver(g, seed=0, batch=32).solve(
            IMProblem(k=2, t_rounds=3, theta=512)).seeds_per_round()


# ------------------------------------------------------- θ early exit

def test_early_exit_preserves_seeds_and_theta():
    g = _wc_graph(n=60, m=180, seed=1)
    base = IMMSolver(g, batch=64, seed=5).solve(IMProblem(k=3, eps=0.5))
    gated = IMMSolver(g, batch=64, seed=5).solve(
        IMProblem(k=3, eps=0.5, early_exit=True))
    np.testing.assert_array_equal(base.seeds, gated.seeds)
    assert base.stats.theta == gated.stats.theta
    assert base.spread == gated.spread
    assert gated.stats.early_exit_skips > 0    # the gate actually fired
    skips = [h for h in gated.stats.history if h[0] == "lb_skip"]
    assert len(skips) == gated.stats.early_exit_skips


def test_early_exit_noop_outside_exact_safe_regime():
    """With a sketch smaller than θ_1 the gate must stand down (occupancy
    is no longer the exact count, so the bound would be unsound)."""
    g = _wc_graph(n=60, m=180, seed=1)
    base = IMMSolver(g, batch=64, seed=5).solve(IMProblem(k=3, eps=0.5))
    gated = IMMSolver(g, batch=64, seed=5, sketch_k=32).solve(
        IMProblem(k=3, eps=0.5, early_exit=True))
    np.testing.assert_array_equal(base.seeds, gated.seeds)
    assert base.stats.theta == gated.stats.theta


# ------------------------------------------------- transfer-guard hygiene

@pytest.mark.parametrize("variant", ("weighted", "budgeted", "candidates",
                                     "mrim"))
def test_variant_solve_under_transfer_guard(variant):
    g = _wc_graph(seed=9)
    w = (np.arange(50) % 5 + 1).astype(np.float32)
    problem = {
        "weighted": IMProblem(k=3, eps=0.5, max_theta=256, node_weights=w),
        "budgeted": IMProblem(eps=0.5, max_theta=256,
                              costs=np.ones(50, np.float32), budget=3.0),
        "candidates": IMProblem(k=3, eps=0.5, max_theta=256,
                                candidates=np.arange(25)),
        "mrim": IMProblem(k=2, t_rounds=2, theta=256),
    }[variant]
    solver = IMMSolver(g, batch=64, seed=7)
    solver.prepare(problem)    # host-side construction outside the guard
    with jax.transfer_guard("disallow"):
        res = solver.solve(problem)
    assert len(res.seeds) >= 1


def test_prepare_reuses_pool_for_same_signature():
    g = _wc_graph(seed=9)
    solver = IMMSolver(g, batch=64, seed=7)
    r1 = solver.solve(IMProblem(k=2, eps=0.5, max_theta=128))
    pool = solver.store.n_rr
    r2 = solver.solve(IMProblem(k=3, eps=0.5, max_theta=128))
    assert solver.store.n_rr >= pool           # pool reused, not reset
    w = np.ones(50, np.float32)
    solver.solve(IMProblem(k=2, eps=0.5, max_theta=128, node_weights=w))
    # weights change the engine signature -> fresh pool
    assert solver.engine.root_weights is not None
