"""Fault-tolerant solve & serve (DESIGN.md §8).

Contracts under test (ISSUE acceptance criteria):
* durable pool checkpoints: ``save_pool`` → process restart →
  ``restore_pool`` → the continued solve is bit-identical to an
  uninterrupted one, on mesh=1 AND an 8-fake-device mesh, with the
  restore + solve legal under ``jax.transfer_guard("disallow")``;
* resumable sampling: injected faults at the sample/append/grow/select
  boundaries are retried by ``FaultPolicy`` and the result stream stays
  bit-identical (transactional RNG cursor: a retried round replays the
  same subkey against unchanged buffers);
* growth-allocation failure recovery: ``on_oom`` hooks run, the append
  retries its exact-need allocation, and the solve completes
  bit-identically;
* ε-driven LB-loop crash/resume: the checkpoint's ``lb_completed``
  watermark + ``active_solve`` digest let a restarted process skip
  completed LB iterations instead of re-running them over a larger pool
  (which would fork the stream);
* serving failure isolation: one poisoned request among healthy
  batch-mates fails alone with a typed error (satellite regression), the
  executing entry is quarantined and never serves again, spill-on-evict
  rehydrates bit-identically, the per-key circuit breaker walks
  closed → open → half-open → closed, and degraded answers carry certified
  bounds and are never cached.
"""
import asyncio
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.graph import csr as csr_mod
from repro.graph import generators, weights
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem
from repro.ft.failures import (DeadlineExceeded, FaultInjector, FaultPolicy,
                               InjectedFailure, PoolAllocError, is_transient)
from repro.serve import (CircuitOpenError, ServeConfig, SolverFailedError,
                         WarmSolverRegistry, build_service, execute_batch)

OPTS = {"batch": 32, "seed": 7}
THETA = 1024


def _wc_graph(n=60, m=300, seed=0):
    src, dst = generators.erdos_renyi(n, m, seed=seed)
    return weights.wc_weights(csr_mod.from_edges(src, dst, n))


@pytest.fixture(scope="module")
def g():
    return _wc_graph()


@pytest.fixture(scope="module")
def ref(g):
    """Uninterrupted fixed-θ baseline every bit-identity test compares to."""
    return IMMSolver(g, **OPTS).solve(IMProblem(k=3, theta=THETA))


def _same(a, b):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(a.gains, b.gains)
    assert a.frac == b.frac and a.spread == b.spread


# ------------------------------------------------ fault taxonomy / policy

def test_is_transient_classification():
    assert is_transient(InjectedFailure("x"))
    assert is_transient(PoolAllocError("x"))
    assert not is_transient(ValueError("x"))
    assert not is_transient(DeadlineExceeded("x"))

    assert is_transient(jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert not is_transient(
        jax.errors.JaxRuntimeError("INTERNAL: device lost"))


def test_is_transient_requires_the_runtime_error_type():
    """Only the runtime's own error class counts as allocator pressure: a
    look-alike carrying the same message (the old name-based match) does
    not, and neither does a plain RuntimeError."""
    class XlaRuntimeError(RuntimeError):
        pass
    assert not is_transient(XlaRuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert not is_transient(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert is_transient(jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 17179869184 bytes"))


def test_injector_validates_sites_and_counts():
    with pytest.raises(ValueError):
        FaultInjector(fail_at={"bogus": {1}})
    inj = FaultInjector(fail_at={"sample": {2}})
    inj.check("sample")                      # crossing 1: clean
    with pytest.raises(InjectedFailure):
        inj.check("sample")                  # crossing 2 fires exactly once
    inj.check("sample")
    assert inj.fires == 1 and inj.fired_log == [("sample", 2)]


def test_policy_backoff_capped_and_gives_up():
    sleeps = []
    pol = FaultPolicy(injector=FaultInjector(rate=1.0), max_retries=3,
                      backoff_base_s=0.01, backoff_cap_s=0.02,
                      sleep=sleeps.append)
    with pytest.raises(InjectedFailure):
        pol.run(lambda: 1, "sample")
    assert pol.gave_up == 1
    assert pol.retries == 4                  # 3 retried + the final attempt
    assert sleeps == [0.01, 0.02, 0.02]      # 0.01·2^i capped at 0.02


# ------------------------------------- resumable sampling (tentpole, §8)

def test_injected_faults_retry_bit_identical(g, ref):
    pol = FaultPolicy(injector=FaultInjector(
        fail_at={"sample": {2, 5}, "append": {4}, "select": {1}}),
        sleep=lambda s: None)
    got = IMMSolver(g, fault_policy=pol, **OPTS).solve(
        IMProblem(k=3, theta=THETA))
    _same(ref, got)
    assert pol.injector.fires == 4 and pol.retries == 4 and pol.gave_up == 0


def test_growth_fault_recovers_bit_identical(g):
    """Allocation failures during capacity doubling (the store grows to the
    exact footprint of the batch) escalate to the policy, whose on_oom
    hooks run before the append retries — and the solve still matches the
    fault-free stream.  θ is set well past the store's initial element
    capacity so growth genuinely happens."""
    p = IMProblem(k=3, theta=8192)
    clean = IMMSolver(g, batch=256, seed=7).solve(p)
    freed = []
    pol = FaultPolicy(injector=FaultInjector(fail_at={"grow": {1, 2}}),
                      sleep=lambda s: None)
    pol.on_oom.append(lambda: freed.append(1) or 1)
    s = IMMSolver(g, batch=256, seed=7, fault_policy=pol)
    _same(clean, s.solve(p))
    assert pol.injector.fires == 2
    assert freed and pol.oom_recoveries >= 1
    assert pol.injector.counts["grow"] >= 3      # the retried alloc passed


def test_midstream_checkpoint_restore_bit_identical(g, ref, tmp_path):
    """Same-process restart drill: sample partway, save_pool, rebuild a
    fresh solver, restore_pool, finish — bit-identical result and the
    RNG cursor positions match the uninterrupted solver's."""
    d = str(tmp_path / "ck")
    s1 = IMMSolver(g, **OPTS)
    s1.prepare(IMProblem(k=3, theta=THETA))
    s1.sample_until(THETA // 2)
    step = int(s1.stats.rounds)
    s1.save_pool(d)
    s2 = IMMSolver(g, **OPTS)
    assert s2.restore_pool(d) == step
    assert np.array_equal(np.asarray(jax.random.key_data(s1.key)),
                          np.asarray(jax.random.key_data(s2.key)))
    got = s2.solve(IMProblem(k=3, theta=THETA))
    _same(ref, got)


def test_restore_pool_rejects_foreign_and_missing_checkpoints(g, tmp_path):
    from repro.ckpt import checkpoint as ckpt_mod
    s = IMMSolver(g, **OPTS)
    with pytest.raises(FileNotFoundError):
        s.restore_pool(str(tmp_path / "nope"))
    # a train-loop checkpoint is not an im-pool checkpoint
    d = str(tmp_path / "train")
    ckpt_mod.save(d, 1, {"w": np.zeros(3)}, meta={"format": "train"})
    with pytest.raises(ValueError, match="im-pool"):
        s.restore_pool(d)


def test_eps_lb_loop_crash_resume_bit_identical(g, tmp_path):
    """ε-driven solve killed mid-LB-loop resumes from the checkpoint's
    lb_completed watermark + active_solve digest and lands bit-identical
    to the uninterrupted run (theta, rounds, seeds, spread)."""
    d = str(tmp_path / "ck")
    p = IMProblem(k=3, eps=0.4, max_theta=2048)
    clean = IMMSolver(g, **OPTS).solve(p)

    inj = FaultInjector(fail_at={"sample": {9}})
    pol = FaultPolicy(injector=inj, max_retries=0, sleep=lambda s: None)
    s1 = IMMSolver(g, fault_policy=pol, checkpoint_dir=d,
                   checkpoint_every=1, **OPTS)
    with pytest.raises(InjectedFailure):
        s1.solve_problem(p)

    s2 = IMMSolver(g, checkpoint_dir=d, checkpoint_every=1, **OPTS)
    s2.restore_pool(d)
    assert s2._active_solve == p.signature_digest()   # in-flight marker
    got = s2.solve_problem(p)
    assert s2._active_solve is None                   # cleared on success
    _same(clean, got)
    assert clean.stats.theta == got.stats.theta
    assert clean.stats.rounds == got.stats.rounds


def test_resilient_solve_eps_driven(g, tmp_path):
    from repro.ft.runner import resilient_solve
    p = IMProblem(k=3, eps=0.4, max_theta=2048)
    clean = IMMSolver(g, **OPTS).solve(p)
    d = str(tmp_path / "ck")
    inj = FaultInjector(fail_at={"sample": {6}, "select": {2}})

    def make_solver():
        pol = FaultPolicy(injector=inj, max_retries=0, sleep=lambda s: None)
        return IMMSolver(g, fault_policy=pol, checkpoint_dir=d,
                         checkpoint_every=2, **OPTS)

    got, report = resilient_solve(make_solver, p, d)
    assert report.completed and report.restarts == 2
    _same(clean, got)


# ------------------------------------ subprocess restart (satellite c)

RESTART_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import numpy as np, jax
from jax.sharding import Mesh
from repro.graph import csr as csr_mod, generators, weights
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem
from repro.ft.elastic import pool_restore_mesh

assert len(jax.devices()) == {ndev}
src, dst = generators.erdos_renyi(60, 300, seed=0)
g = weights.wc_weights(csr_mod.from_edges(src, dst, 60))
mesh = None if {ndev} == 1 else pool_restore_mesh({ndev})
opts = dict(engine="queue", batch=64, seed=3, mesh=mesh)
p = IMProblem(k=4, theta=2048)
if {save}:
    ref = IMMSolver(g, **opts).solve(p)
    print("RESULT", ref.seeds.tolist(), ref.gains.tolist(), repr(ref.frac),
          repr(ref.spread))
    s = IMMSolver(g, **opts)
    s.prepare(p)
    with jax.transfer_guard("disallow"):
        s.sample_until(700)
    s.save_pool(r"{d}")
    print("SAVED", s.stats.rounds, s.store.n_rr)
else:
    s = IMMSolver(g, **opts)
    # restore_pool = prepare(): host-side engine construction, run outside
    # the guard like any cold prepare; the continued sample/select rounds
    # must then be transfer-guard legal
    step = s.restore_pool(r"{d}")
    with jax.transfer_guard("disallow"):
        got = s.solve_problem(p)
    print("RESUMED", step)
    print("RESULT", got.seeds.tolist(), got.gains.tolist(), repr(got.frac),
          repr(got.spread))
"""


def _run_restart(ndev, save, d):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c",
         RESTART_SCRIPT.format(ndev=ndev, save=save, d=d)],
        env=env, capture_output=True, text=True, cwd="/root/repo",
        timeout=600)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    return r.stdout


@pytest.mark.parametrize("ndev", [1, 8])
def test_save_restart_restore_bit_identical_across_processes(ndev, tmp_path):
    """The durable-checkpoint contract across a REAL process boundary:
    process A solves (reference) and saves a mid-sampling checkpoint;
    process B — a fresh interpreter — restores it and finishes the solve
    under ``transfer_guard("disallow")``, bit-identical to A's reference.
    ndev=8 runs the whole drill on a forced 8-device mesh (sharded store
    rows restored onto the device that owned them)."""
    d = str(tmp_path / "ck")
    out_a = _run_restart(ndev, 1, d)
    out_b = _run_restart(ndev, 0, d)
    res_a = [l for l in out_a.splitlines() if l.startswith("RESULT")]
    res_b = [l for l in out_b.splitlines() if l.startswith("RESULT")]
    assert "RESUMED" in out_b
    assert res_a == res_b, (res_a, res_b)


# -------------------------------------------- degraded answers (§8)

def test_degraded_result_bounds_certify_returned_seed_set(g):
    """The degraded answer's ``spread_bounds`` certify the *returned* seed
    set: its exact union coverage over the pool lies inside [lo, hi], the
    estimate is clamped into the bounds, and the exact greedy answer (a
    no-worse seed set) is at least the certified lower bound."""
    solver = IMMSolver(g, sketch_k=64, **OPTS)
    exact, deg = execute_batch(
        solver, [IMProblem(k=3, theta=THETA), IMProblem(k=3, theta=THETA)],
        deadlines=[None, 0.0])
    assert not exact.degraded and deg.degraded
    lo, hi = deg.spread_bounds
    assert lo <= deg.spread <= hi
    assert exact.spread >= lo
    assert len(deg.seeds) == 3
    # recompute the degraded set's exact coverage host-side (mesh=1: the
    # store's row ids are global) and check the certificate
    st = solver.store.state()
    flat, ids = st["flat"].reshape(-1), st["ids"].reshape(-1)
    valid = st["valid"].reshape(-1)
    covered = np.unique(ids[valid & np.isin(flat, deg.seeds)]).size
    cov_spread = g.n_nodes * covered / solver.store.n_rr
    assert lo <= cov_spread <= hi + 1e-9, (lo, cov_spread, hi)


def test_degraded_without_sketch_falls_back_to_occur(g):
    solver = IMMSolver(g, **OPTS)            # no sketch configured
    _, deg = execute_batch(
        solver, [IMProblem(k=2, theta=THETA), IMProblem(k=2, theta=THETA)],
        deadlines=[None, 0.0])
    assert deg.degraded and deg.spread_bounds[0] > 0


def test_degraded_ineligible_objective_raises_typed(g):
    """Budgeted objectives have no certified sketch answer: an expired
    deadline surfaces as DeadlineExceeded, not a silent wrong result."""
    solver = IMMSolver(g, **OPTS)
    solver.solve(IMProblem(k=2, theta=THETA))        # pool is warm
    costs = np.ones(g.n_nodes, np.float32)
    with pytest.raises(DeadlineExceeded):
        solver.solve_problem(IMProblem(theta=THETA, costs=costs, budget=3.0),
                             deadline_s=0.0)


# ---------------------------------- serving isolation (satellite a)

def _poison_k9():
    """Policy whose injector kills any solve of a k=9 problem at its
    selection — the 'poisoned request' of the isolation tests."""
    return FaultPolicy(injector=FaultInjector(
        rate=1.0,
        match=lambda site, ctx: (site == "select" and isinstance(ctx, dict)
                                 and getattr(ctx.get("problem"), "k", None)
                                 == 9)),
        max_retries=0, sleep=lambda s: None)


def test_poisoned_request_fails_alone_batchmates_served(g):
    """Blast-radius regression: one poisoned problem in a batch of three
    compatible requests fails with a typed error by itself; the healthy
    batch-mates are re-run in isolation and served bit-identically."""
    opts = {**OPTS, "fault_policy": _poison_k9()}

    async def run():
        svc = build_service({"g": g}, ServeConfig(
            max_batch=8, batch_window_s=0.02, solver_opts=opts,
            breaker_threshold=100))
        async with svc:
            return await asyncio.gather(
                svc.submit("g", IMProblem(k=2, theta=THETA)),
                svc.submit("g", IMProblem(k=9, theta=THETA)),
                svc.submit("g", IMProblem(k=3, theta=THETA)),
                return_exceptions=True), svc.stats()
    results, st = asyncio.run(run())
    ok = [r for r in results if not isinstance(r, BaseException)]
    bad = [r for r in results if isinstance(r, BaseException)]
    assert len(ok) == 2 and len(bad) == 1
    assert isinstance(bad[0], SolverFailedError)
    assert "InjectedFailure" in str(bad[0])
    assert st.served == 2 and st.failed == 1
    assert st.quarantines >= 1 and st.isolated_retries >= 1
    for r, k in zip(ok, (2, 3)):
        fresh = IMMSolver(g, **OPTS).solve(IMProblem(k=k, theta=THETA))
        _same(fresh, r.result)


def test_breaker_opens_then_halfopen_probe_heals(g):
    async def run():
        svc = build_service({"g": g}, ServeConfig(
            solver_opts={**OPTS, "fault_policy": _poison_k9()},
            breaker_threshold=2, breaker_cooldown_s=0.2))
        outcomes = []
        async with svc:
            for _ in range(2):
                try:
                    await svc.submit("g", IMProblem(k=9, theta=THETA))
                    outcomes.append("served")
                except Exception as e:
                    outcomes.append(type(e).__name__)
            # same registry key: the open breaker rejects healthy work too
            try:
                await svc.submit("g", IMProblem(k=2, theta=THETA))
                outcomes.append("served")
            except Exception as e:
                outcomes.append(type(e).__name__)
            mid = svc.stats()
            await asyncio.sleep(0.25)        # cooldown -> half-open probe
            await svc.submit("g", IMProblem(k=2, theta=THETA))
            outcomes.append("served")
            return outcomes, mid, svc.stats()
    outcomes, mid, end = asyncio.run(run())
    assert outcomes[0] == "SolverFailedError"
    assert "CircuitOpenError" in outcomes[1:3]
    assert outcomes[-1] == "served"
    assert mid.breakers_open >= 1 and mid.breaker_trips >= 1
    assert end.breakers_open == 0            # probe success closed it


def test_spill_on_evict_rehydrate_on_miss_bit_identical(g, tmp_path):
    reg = WarmSolverRegistry(solver_opts=OPTS, spill_dir=str(tmp_path))
    reg.add_graph("g", g)
    p = IMProblem(k=2, theta=THETA)
    e1 = reg.get("g", p)
    e1.solver.solve(p)
    reg.account(e1)
    reg.evict(reg.solver_key("g", p))
    assert reg.snapshot().spills == 1
    # uninterrupted reference: warm solver continuing 1024 -> 2048
    s_ref = IMMSolver(g, **OPTS)
    s_ref.solve(p)
    ref2 = s_ref.solve(IMProblem(k=2, theta=2 * THETA))
    # miss -> rehydrate instead of resample; continuation bit-identical
    e2 = reg.get("g", p)
    assert reg.snapshot().rehydrations == 1 and e2.bytes > 0
    _same(ref2, e2.solver.solve(IMProblem(k=2, theta=2 * THETA)))


def test_quarantine_drops_without_spilling(g, tmp_path):
    reg = WarmSolverRegistry(solver_opts=OPTS, spill_dir=str(tmp_path))
    reg.add_graph("g", g)
    p = IMProblem(k=2, theta=THETA)
    entry = reg.get("g", p)
    entry.solver.solve(p)
    reg.account(entry)
    key = reg.solver_key("g", p)
    freed = reg.quarantine(key)
    assert freed > 0 and key not in reg.entries
    st = reg.snapshot()
    assert st.quarantined == 1 and st.spills == 0    # never spilled
    assert reg.quarantine(key) == 0                  # unknown key: no-op
    # the next miss cold-starts (no snapshot exists) and still serves the
    # canonical answer
    fresh = reg.get("g", p)
    assert fresh.solver is not entry.solver
    _same(IMMSolver(g, **OPTS).solve(p), fresh.solver.solve(p))


def test_degraded_response_never_cached(g):
    async def run():
        svc = build_service({"g": g}, ServeConfig(
            solver_opts={**OPTS, "sketch_k": 64}))
        async with svc:
            r1 = await svc.submit("g", IMProblem(k=3, theta=1 << 16),
                                  deadline_s=0.05)
            # same problem, no deadline: must recompute exactly, not
            # replay the degraded answer from the cache
            r2 = await svc.submit("g", IMProblem(k=3, theta=1 << 16))
        return r1, r2, svc.stats()
    r1, r2, st = asyncio.run(run())
    assert r1.degraded and not r2.degraded and not r2.cached
    assert st.degraded == 1
    lo, hi = r1.result.spread_bounds
    assert lo <= r1.result.spread <= hi
    # the exact greedy set can only cover more than the degraded set's
    # certified floor (its UB certifies the degraded set, not the optimum)
    assert r2.result.spread >= lo
