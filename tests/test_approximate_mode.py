"""mode="approximate" (pool-free DiFuseR mode) conformance suite.

Four contracts, per DESIGN.md §10:

* **Validation** — the mode rejects every pool-needing feature
  (node_weights / budget / t_rounds) at the problem layer, and
  ``resolve_incremental`` refuses to patch a pool that doesn't exist.
* **Saturation** — a fully-occupied linear-counting row carries no
  information beyond its k·ln(k) ceiling: the estimate is clamped + flagged
  and ``IMResult.spread_bounds`` widens to the trivial upper bound instead
  of reporting a silently-finite number.
* **Exact regime** — while ``n_rr <= sketch_k`` under "mod" bucketing the
  bucketing is injective and Δocc == exact marginal gain, so the
  approximate path must be *bit-identical* to the fused exact scan (store
  level and end-to-end, where the FusedSketchEngine wrapper must also
  preserve the sampling RNG stream).
* **Quality** — MC-evaluated seed quality clears
  ``(1 − 1/e − ε − ε_cert)·OPT_oracle`` where ε_cert is the realized
  certified relative error from the returned bounds; and the certified
  interval itself brackets the forward-MC spread (with MC slack).

Plus the durability and distribution legs: im-pool v2 sketch checkpoints
round-trip bit-identically, and an 8-fake-device subprocess pins mesh
bit-identity of the fold + selection (devices are locked at first jax init,
so that check runs out of process like test_sharded_store's).
"""
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.graph import csr as csr_mod, generators, weights
from repro.core import coverage as cov
from repro.core import forward
from repro.core import oracle
from repro.core import sketch as sketch_mod
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem


def _graph(n=60, m=300, seed=6):
    src, dst = generators.erdos_renyi(n, m, seed=seed)
    return weights.wc_weights(csr_mod.from_edges(src, dst, n))


def _batches(n=50, rounds=4, seed=7):
    r = np.random.default_rng(seed)
    out = []
    for _ in range(rounds):
        lens = r.integers(0, 8, 61)              # empty rows + odd count
        w = max(int(lens.max()), 1)
        nodes = np.zeros((61, w), np.int64)
        for i, ln in enumerate(lens):
            if ln:
                nodes[i, :ln] = r.choice(n, size=ln, replace=False)
        out.append((nodes, lens))
    return out


# --------------------------------------------------------------- validation

def test_mode_validation():
    with pytest.raises(ValueError, match="unknown mode"):
        IMProblem(k=2, eps=0.5, mode="sketchy")
    with pytest.raises(ValueError, match="node_weights"):
        IMProblem(k=2, eps=0.5, mode="approximate",
                  node_weights=np.ones(8, np.float32))
    with pytest.raises(ValueError, match="budget"):
        IMProblem(k=2, eps=0.5, mode="approximate",
                  costs=np.ones(8, np.float32), budget=3.0)
    with pytest.raises(ValueError, match="t_rounds"):
        IMProblem(k=2, eps=0.5, mode="approximate", t_rounds=3)


def test_mode_keys_the_pool_signature():
    # "mode" is a pool field: approximate requests must never share a
    # warm pool (or a serving batch) with exact ones
    a = IMProblem(k=2, eps=0.5).pool_digest(model="ic")
    b = IMProblem(k=2, eps=0.5, mode="approximate").pool_digest(model="ic")
    assert a != b


def test_resolve_incremental_rejects_approximate():
    g = _graph()
    from repro.core import stream as stream_mod
    s = IMMSolver(g, engine="queue", batch=64, seed=0)
    s.solve(IMProblem(k=2, theta=256, mode="approximate"))
    deltas = stream_mod.EdgeDeltas(
        add_src=np.asarray([0], np.int32),
        add_dst=np.asarray([1], np.int32),
        add_p=np.asarray([0.5], np.float32),
        rm_src=np.asarray([], np.int32), rm_dst=np.asarray([], np.int32))
    with pytest.raises(ValueError, match="approximate"):
        s.resolve_incremental(
            IMProblem(k=2, theta=256, mode="approximate"), deltas)


def test_occur_fastpath_excludes_approximate():
    from repro.serve.batching import occur_fastpath_eligible
    g = _graph()
    s = IMMSolver(g, engine="queue", batch=64, seed=0)
    assert occur_fastpath_eligible(s, IMProblem(k=1, theta=64))
    assert not occur_fastpath_eligible(
        s, IMProblem(k=1, theta=64, mode="approximate"))


# --------------------------------------------------------------- saturation

def test_linear_count_saturation_clamped_and_flagged():
    k = 128
    est, sat = sketch_mod.linear_count_saturated([0, 64, k, k + 5], k)
    assert not sat[0] and not sat[1] and sat[2] and sat[3]
    assert est[0] == 0.0
    assert np.all(np.isfinite(est))
    assert est[2] == pytest.approx(k * np.log(k))  # the clamp, not inf
    assert est[3] == est[2]
    # certified error stays finite at the ceiling too
    assert np.all(np.isfinite(sketch_mod.linear_count_rel_error(est, k)))


def test_auto_sketch_k_sizing():
    with pytest.raises(ValueError):
        sketch_mod.auto_sketch_k(0.0, 100)
    with pytest.raises(ValueError):
        sketch_mod.auto_sketch_k(1.5, 100)
    k1 = sketch_mod.auto_sketch_k(0.5, 10**6)
    k2 = sketch_mod.auto_sketch_k(0.1, 10**6)
    assert k2 > k1                       # tighter eps -> bigger sketch
    assert k1 % 32 == 0 and k2 % 32 == 0
    assert sketch_mod.auto_sketch_k(0.01, 100) <= 128  # clamped near n


def test_saturation_widens_spread_bounds():
    # theta >> sketch_k saturates the union row: the result must flag the
    # widened (trivial) upper bound rather than a silently-finite estimate
    g = _graph()
    n = g.n_nodes
    s = IMMSolver(g, engine="queue", batch=64, seed=0, sketch_k=64)
    res = s.solve(IMProblem(k=4, theta=4096, mode="approximate"))
    assert res.spread_bounds is not None
    lo, hi = res.spread_bounds
    assert s._sketch_info["saturated"]
    assert hi == pytest.approx(float(n))  # widened to scale * n_rr/n_rr
    assert 0.0 < lo <= res.spread <= hi


# ------------------------------------------------------------- exact regime

def test_exact_regime_store_level_identity():
    # n_rr <= sketch_k under "mod": Δocc is the exact marginal, so greedy
    # on sketches must match the fused flat scan seed-for-seed/gain-for-gain
    n, k = 50, 6
    exact = cov.ShardedDeviceRRStore(n, capacity=8)
    sk = cov.SketchRRStore(n, sketch_k=256)
    for b in _batches(n=n):
        exact.append_batch(b)
        sk.append_batch(b)
    assert exact.n_rr == sk.n_rr and sk.n_rr <= sk.sketch_k
    r_exact = exact.select(k)
    info = {}
    r_sk = cov.select_seeds_sketch(sk, k, info_out=info)
    a, b_ = jax.device_get(((r_exact.seeds, r_exact.gains, r_exact.frac),
                            (r_sk.seeds, r_sk.gains, r_sk.frac)))
    assert info["exact_regime"] and info["rel_error"] == 0.0
    assert np.array_equal(np.asarray(a[0]), np.asarray(b_[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b_[1]))
    assert float(a[2]) == pytest.approx(float(b_[2]), rel=1e-6)
    assert info["lo_rows"] == info["hi_rows"] == info["occ_union"]


def test_exact_regime_end_to_end_identity():
    # same theta, same seed: the FusedSketchEngine preserves the sampling
    # RNG stream, and with theta <= sketch_k the selection is injective —
    # the whole approximate solve is bit-identical to fused exact
    g = _graph()
    theta = 192
    se = IMMSolver(g, engine="queue", batch=64, seed=3)
    re_ = se.solve(IMProblem(k=4, theta=theta))
    sa = IMMSolver(g, engine="queue", batch=64, seed=3, sketch_k=256)
    ra = sa.solve(IMProblem(k=4, theta=theta, mode="approximate"))
    assert np.array_equal(np.asarray(re_.seeds), np.asarray(ra.seeds))
    assert re_.spread == pytest.approx(ra.spread, rel=1e-6)
    lo, hi = ra.spread_bounds
    assert lo == pytest.approx(ra.spread, rel=1e-6)
    assert hi == pytest.approx(ra.spread, rel=1e-6)
    assert sa.store.per_device_pool_bytes() == 0


def test_candidate_mask_and_degenerate_k():
    g = _graph()
    cand = np.zeros(g.n_nodes, bool)
    cand[:3] = True
    s = IMMSolver(g, engine="queue", batch=64, seed=0, sketch_k=256)
    res = s.solve(IMProblem(k=5, theta=192, mode="approximate",
                            candidates=np.flatnonzero(cand)))
    seeds = np.asarray(res.seeds)
    assert len(seeds) <= 3 and set(seeds.tolist()) <= {0, 1, 2}


# ------------------------------------------------------------------ quality

def test_mc_quality_clears_certified_bound():
    # genuine approximation regime (n_rr > sketch_k, unsaturated): seeds
    # must clear (1 - 1/e - eps - eps_cert) x oracle quality under MC, and
    # the certified interval must bracket the MC spread
    g = _graph()
    n, k, eps = g.n_nodes, 4, 0.3
    s = IMMSolver(g, engine="queue", batch=64, seed=3, sketch_k=1024)
    res = s.solve(IMProblem(k=k, eps=eps, max_theta=4096,
                            mode="approximate"))
    assert s.store.n_rr > 1024, "params must exercise the estimate regime"
    assert not s._sketch_info["saturated"]
    lo, hi = res.spread_bounds
    assert lo <= res.spread <= hi

    g_fwd = g  # forward.ic_spread wants the forward graph
    got = forward.ic_spread(jax.random.key(7), g_fwd,
                            np.asarray(res.seeds).tolist(), n_sims=2048)
    rev = csr_mod.reverse(g)
    o_seeds, _, _ = oracle.imm_oracle(
        np.asarray(rev.offsets), np.asarray(rev.indices),
        np.asarray(rev.weights), n, k, eps, seed=11, max_theta=4096)
    best = forward.ic_spread(jax.random.key(8), g_fwd, list(o_seeds),
                             n_sims=2048)
    eps_cert = (res.spread - lo) / max(res.spread, 1e-9)
    bound = (1.0 - 1.0 / np.e - eps - eps_cert) * best
    assert got >= bound * 0.9, (got, bound, best, eps_cert)
    # the certificate brackets the MC spread (30% slack for MC noise)
    assert lo * 0.7 <= got <= hi * 1.3, (lo, got, hi)


# --------------------------------------------------------------- durability

def test_pool_checkpoint_v2_roundtrip(tmp_path):
    from repro.ckpt import checkpoint as ckpt_mod
    g = _graph()
    d = str(tmp_path / "pool")
    p = IMProblem(k=4, theta=1024, mode="approximate")
    s1 = IMMSolver(g, engine="queue", batch=64, seed=5, sketch_k=128)
    s1.prepare(p)
    s1.sample_until(400)
    s1.save_pool(d)
    meta = ckpt_mod.load_manifest(d, ckpt_mod.latest_step(d))["meta"]
    assert meta["version"] == IMMSolver.POOL_CKPT_VERSION_SKETCH
    assert meta["store"]["kind"] == "sketch"

    s2 = IMMSolver(g, engine="queue", batch=64, seed=5, sketch_k=128)
    s2.restore_pool(d)
    assert isinstance(s2.store, cov.SketchRRStore)
    r1 = s1.solve_problem(p)
    r2 = s2.solve_problem(p)
    assert np.array_equal(np.asarray(r1.seeds), np.asarray(r2.seeds))
    assert r1.spread == pytest.approx(r2.spread, rel=1e-7)
    assert r1.spread_bounds == pytest.approx(r2.spread_bounds, rel=1e-7)


def test_restore_rejects_sketch_size_mismatch(tmp_path):
    # a differently-sized sketch is a different estimator: restoring it
    # into a solver configured for another sketch_k must refuse, not
    # silently serve looser (or phantom-tighter) bounds
    g = _graph()
    d = str(tmp_path / "pool")
    s1 = IMMSolver(g, engine="queue", batch=64, seed=5, sketch_k=128)
    s1.prepare(IMProblem(k=4, theta=512, mode="approximate"))
    s1.sample_until(128)
    s1.save_pool(d)
    s2 = IMMSolver(g, engine="queue", batch=64, seed=5, sketch_k=256)
    with pytest.raises(ValueError, match="signature"):
        s2.restore_pool(d)


# -------------------------------------- 8-way mesh bit-identity (subprocess)

MESH8_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import coverage as cov
from repro.graph import csr as csr_mod, generators, weights
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem

assert len(jax.devices()) == 8
mesh8 = Mesh(np.asarray(jax.devices()), ("samples",))
n, k = 50, 6

def batches():
    r = np.random.default_rng(7)
    out = []
    for _ in range(4):
        lens = r.integers(0, 8, 61)
        w = max(int(lens.max()), 1)
        nodes = np.zeros((61, w), np.int64)
        for i, ln in enumerate(lens):
            if ln:
                nodes[i, :ln] = r.choice(n, size=ln, replace=False)
        out.append((nodes, lens))
    return out

# store level: fold + selection bit-identical on 1-dev vs 8-dev meshes,
# in and out of the exact regime, all under the transfer guard
for sketch_k in (64, 256):
    d1 = cov.SketchRRStore(n, sketch_k=sketch_k)
    d8 = cov.SketchRRStore(n, sketch_k=sketch_k, mesh=mesh8)
    with jax.transfer_guard("disallow"):
        for b in batches():
            d1.append_batch(b)
            d8.append_batch(b)
        assert d1.n_rr == d8.n_rr and d1.n_elems == d8.n_elems
        s1, s8 = jax.device_get((d1.sketch_words(), d8.sketch_words()))
        assert np.array_equal(np.asarray(s1), np.asarray(s8)), \
            ("frontier fold diverged across mesh sizes", sketch_k)
        i1, i8 = {}, {}
        r1 = cov.select_seeds_sketch(d1, k, info_out=i1)
        r8 = cov.select_seeds_sketch(d8, k, info_out=i8)
        a, b_ = jax.device_get(((r1.seeds, r1.gains, r1.frac),
                                (r8.seeds, r8.gains, r8.frac)))
        assert np.array_equal(a[0], b_[0]), (sketch_k, a[0], b_[0])
        assert np.array_equal(a[1], b_[1]) and a[2] == b_[2]
        assert i1 == i8, (i1, i8)

# end to end: same engine stream, pool-free solve, 1-dev vs 8-dev
src, dst = generators.erdos_renyi(60, 300, seed=6)
g = weights.wc_weights(csr_mod.from_edges(src, dst, 60))
res = {}
p = IMProblem(k=4, theta=1024, mode="approximate")
for mesh in (None, mesh8):
    solver = IMMSolver(g, engine="queue", batch=64, seed=3, sketch_k=128,
                       mesh=mesh)
    solver.prepare(p)   # host-side construction outside the guard
    with jax.transfer_guard("disallow"):
        r = solver.solve(p)
    res[r.stats.pool_sharding] = (r.seeds.tolist(), round(r.spread, 6),
                                  tuple(round(b, 6) for b in r.spread_bounds))
assert res["samples:1"] == res["samples:8"], res
print("OK", res["samples:8"])
"""


def test_approximate_bit_identical_across_mesh_sizes():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", MESH8_SCRIPT], env=env,
                       capture_output=True, text=True, cwd="/root/repo",
                       timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "OK" in r.stdout
