"""Device-resident RR pipeline: DeviceRRStore equivalence with the host
compaction, fused-selection parity with the numpy oracle, and the
transfer-guard regression over a full IMM solve."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graph import csr as csr_mod
from repro.graph import generators, weights
from repro.core import coverage as cov, oracle
from repro.core.engine import make_engine
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem


def _wc_graph(n=40, m=200, seed=0):
    src, dst = generators.erdos_renyi(n, m, seed=seed)
    return weights.wc_weights(csr_mod.from_edges(src, dst, n))


def _random_batch(rng, n, count, max_len=8, allow_empty=False):
    lens = rng.integers(0 if allow_empty else 1, max_len, count)
    w = max(int(lens.max()), 1)
    nodes = np.zeros((count, w), np.int64)
    for i, ln in enumerate(lens):
        nodes[i, :ln] = rng.choice(n, size=ln, replace=False)
    return nodes, lens


# --------------------------------------------------- store equivalence

def test_device_store_matches_host_store_random_batches():
    """Device rank-scatter compaction == host numpy compaction, element for
    element, across doubling growth, empty rows, and varying widths."""
    rng = np.random.default_rng(0)
    n = 37
    dev = cov.DeviceRRStore(n, capacity=4)       # force repeated doubling
    host = cov.IncrementalRRStore(n, capacity=4)
    rr_all = []
    for i in range(6):
        nodes, lens = _random_batch(rng, n, int(rng.integers(1, 24)),
                                    allow_empty=(i % 2 == 0))
        dev.append_batch((nodes, lens))
        host.append_batch((nodes, lens))
        rr_all += [nodes[j, :lens[j]].tolist()
                   for j in range(len(lens)) if lens[j]]
    ds, hs = dev.snapshot(), host.snapshot()
    assert ds.n_rr == hs.n_rr == len(rr_all) == dev.n_rr
    np.testing.assert_array_equal(np.asarray(ds.rr_flat),
                                  np.asarray(hs.rr_flat))
    np.testing.assert_array_equal(np.asarray(ds.rr_ids),
                                  np.asarray(hs.rr_ids))
    assert np.asarray(ds.valid).all()
    # the buffers beyond the live extent stay sentinel/invalid (the pool
    # buffers carry a leading shard dim; this store is the mesh=1 case)
    assert dev.capacity >= dev.n_elems
    assert not np.asarray(dev._valid)[0, dev.n_elems:].any()


def test_device_store_matches_build_store_single_batch():
    rng = np.random.default_rng(1)
    n = 29
    nodes, lens = _random_batch(rng, n, 17)
    dev = cov.DeviceRRStore(n)
    dev.append_batch((nodes, lens))
    ref = cov.build_store((nodes, lens), n)
    snap = dev.snapshot()
    assert snap.n_rr == ref.n_rr
    np.testing.assert_array_equal(np.asarray(snap.rr_flat),
                                  np.asarray(ref.rr_flat))
    np.testing.assert_array_equal(np.asarray(snap.rr_ids),
                                  np.asarray(ref.rr_ids))


def test_store_no_mirror_drift_when_every_row_overflowed():
    """Regression: a batch whose *every* row overflowed may report lengths
    beyond the materialized width (truncated nodes, true pre-truncation
    length).  The device store clamps to the width; the host compaction
    previously repeated row ids by the raw length while masking elements by
    width — the counts drifted apart and ``IncrementalRRStore.append_batch``
    crashed with a broadcast error.  Both stores must clamp identically."""
    rng = np.random.default_rng(11)
    n = 40
    nodes = rng.integers(0, n, (6, 4))
    lens = np.full(6, 9)                    # every row overflowed: 9 > width 4
    dev = cov.DeviceRRStore(n, capacity=4)
    dev.append_batch((nodes, lens))
    host = cov.IncrementalRRStore(n, capacity=4)
    host.append_batch((nodes, lens))        # used to raise ValueError
    td, nd = (int(x.sum()) for x in jax.device_get((dev._t_dev,
                                                    dev._nrr_dev)))
    assert (dev.n_elems, dev.n_rr) == (td, nd) == (24, 6)
    assert (host._t, host.n_rr) == (24, 6)
    np.testing.assert_array_equal(np.asarray(dev.snapshot().rr_flat),
                                  np.asarray(host.snapshot().rr_flat))
    np.testing.assert_array_equal(np.asarray(dev.snapshot().rr_ids),
                                  np.asarray(host.snapshot().rr_ids))
    # build_store shares the compaction; its counts must agree too
    ref = cov.build_store((nodes, lens), n)
    assert ref.n_rr == 6 and int(ref.rr_flat.shape[0]) == 24


def test_device_store_accepts_overflowed_truncated_rows():
    """Overflowed lanes deliver truncated rows (length == qcap); the store
    must take them verbatim like the host path does."""
    g = _wc_graph(n=30, m=300, seed=2)
    g_rev = csr_mod.reverse(g)
    eng = make_engine("queue", g_rev, batch=32, qcap=2)   # force overflow
    b = eng.sample(jax.random.key(0))
    assert bool(np.asarray(b.overflowed).any())
    dev = cov.DeviceRRStore(30)
    host = cov.IncrementalRRStore(30)
    dev.append_batch(b)
    host.append_batch((np.asarray(b.nodes), np.asarray(b.lengths)))
    np.testing.assert_array_equal(np.asarray(dev.snapshot().rr_flat),
                                  np.asarray(host.snapshot().rr_flat))
    assert dev.n_rr == host.n_rr


# ----------------------------------------------- fused selection parity

def _pool_batches(rng, density):
    """(n, k, batches) of a random RR pool.  ``sparse``: rows of 1-7 nodes
    of 50.  ``dense``: rows of 128-1023 nodes of 4,096, a mean RR size
    past n/32; ``full``: every row holds all 256 nodes.  A bit-matrix scan
    over (rows, ⌈n/32⌉) words is no larger than the flat pool on the dense
    and full pools; the flat scan serves them all."""
    if density == "sparse":
        return 50, 6, [_random_batch(rng, 50, 60) for _ in range(4)]
    if density == "dense":
        n, rows, lens = 4096, 96, rng.integers(128, 1024, 96)
    else:
        n, rows = 256, 40
        lens = np.full(rows, n)
    nodes = np.zeros((rows, int(lens.max())), np.int64)
    for i, ln in enumerate(lens):
        nodes[i, :ln] = rng.choice(n, size=ln, replace=False)
    return n, 6, [(nodes, lens)]


def _store_and_rows(n, batches, **kw):
    dev = cov.DeviceRRStore(n, capacity=8, **kw)
    rr_all = []
    for nodes, lens in batches:
        dev.append_batch((nodes, lens))
        rr_all += [nodes[j, :lens[j]].tolist() for j in range(len(lens))]
    return dev, rr_all


@pytest.mark.parametrize("density", ("sparse", "dense", "full"))
def test_fused_selection_matches_oracle(density):
    n, k, batches = _pool_batches(np.random.default_rng(3), density)
    dev, rr_all = _store_and_rows(n, batches)
    if density != "sparse":
        assert dev.n_elems >= dev.n_rr * n / 32
    res = dev.select(k)
    seeds_o, frac_o = oracle.greedy_max_coverage(rr_all, n, k)
    assert np.asarray(res.seeds).tolist() == seeds_o
    assert float(res.frac) == pytest.approx(frac_o, abs=1e-6)


def test_fused_selection_matches_oracle_on_random_graph_batches():
    g = _wc_graph(n=45, m=220, seed=4)
    g_rev = csr_mod.reverse(g)
    eng = make_engine("queue", g_rev, batch=48)
    dev = cov.DeviceRRStore(45)
    rr_all = []
    for i in range(3):
        b = eng.sample(jax.random.key(i))
        dev.append_batch(b)
        nodes, lens = np.asarray(b.nodes), np.asarray(b.lengths)
        rr_all += [nodes[j, :lens[j]].tolist() for j in range(b.n_sets)]
    res = dev.select(5)
    seeds_o, frac_o = oracle.greedy_max_coverage(rr_all, 45, 5)
    assert np.asarray(res.seeds).tolist() == seeds_o
    assert float(res.frac) == pytest.approx(frac_o, abs=1e-6)


@pytest.mark.parametrize("sketch_k,mode", [
    pytest.param(32, "mod", id="32-mod"),
    pytest.param(64, "mod", id="64-mod"),
    pytest.param(None, "mod", id="None"),
    pytest.param(64, "mix", id="64-mix")])
def test_fused_selection_unchanged_by_store_sketch(sketch_k, mode):
    """A store that keeps a coverage sketch (for the θ early exit and
    degraded results) selects bit for bit what a store without one does,
    plain and candidate-masked, also after its sketch has been read (built
    on demand when the store keeps none)."""
    n, k, batches = _pool_batches(np.random.default_rng(7), "sparse")
    plain, _ = _store_and_rows(n, batches)
    sketched, _ = _store_and_rows(n, batches, sketch_k=sketch_k,
                                  sketch_mode=mode)
    assert sketched.sketch_k == sketch_k
    jax.block_until_ready(sketched.sketch_words())
    spec = cov.SelectionSpec(k_steps=k, n_group=n, group_quota=k,
                             cand=np.arange(n) % 3 != 0)
    for a, b in ((plain.select(k), sketched.select(k)),
                 (plain.select(k, spec=spec), sketched.select(k, spec=spec))):
        a, b = jax.device_get((tuple(a), tuple(b)))
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("variant", ("weighted", "budgeted", "candidates"))
def test_variant_selection_matches_oracle_on_dense_pool(variant):
    """The variant scan on a dense pool (mean RR size past n/32) equals the
    numpy reference greedy of its variant on the same rows."""
    rng = np.random.default_rng(11)
    n, k, batches = _pool_batches(rng, "dense")
    nodes, lens = batches[0]
    rr_all = [nodes[j, :lens[j]].tolist() for j in range(len(lens))]
    if variant == "weighted":
        roww = rng.integers(1, 9, len(lens)).astype(np.float32)
        dev = cov.DeviceRRStore(n, capacity=8, row_weighted=True)
        dev.append_batch((nodes, lens), row_w=roww)
        spec = cov.SelectionSpec(k_steps=k, n_group=n, group_quota=k,
                                 weighted=True)
        seeds_o, frac_o = oracle.greedy_max_coverage_weighted(
            rr_all, n, k, roww)
    else:
        dev, _ = _store_and_rows(n, batches)
        if variant == "budgeted":
            costs = rng.integers(1, 5, n).astype(np.float32)
            budget = 9.0
            spec = cov.SelectionSpec(k_steps=9, n_group=n, group_quota=9,
                                     costs=costs, budget=budget)
            seeds_o, frac_o, spent_o = oracle.budgeted_greedy_cost_ratio(
                rr_all, n, costs, budget)
        else:
            cand = np.arange(n) % 3 == 1
            spec = cov.SelectionSpec(k_steps=k, n_group=n, group_quota=k,
                                     cand=cand)
            seeds_o, frac_o, _ = oracle.budgeted_greedy_cost_ratio(
                rr_all, n, np.ones(n), float(k), candidates=cand)
            assert len(seeds_o) == k          # every pick gains: no ties at 0
    res = dev.select(k, spec=spec)
    seeds, frac, spent = jax.device_get((res.seeds, res.frac, res.spent))
    seeds = np.asarray(seeds)
    assert seeds[seeds < n].tolist() == seeds_o
    assert float(frac) == pytest.approx(frac_o, rel=1e-6)
    if variant == "budgeted":
        assert float(spent) == pytest.approx(spent_o) and spent_o <= budget


# --------------------------------------------- transfer-guard regression

@pytest.mark.parametrize("engine", ("queue", "refill"))
def test_solve_runs_under_transfer_guard(engine):
    """The whole sampling+selection loop must be device-resident: an outer
    ``transfer_guard("disallow")`` held over solve() raises on any implicit
    host↔device transfer (the old pipeline bounced the pool through numpy
    every round)."""
    g = _wc_graph(n=50, m=250, seed=5)
    solver = IMMSolver(g, engine=engine, batch=64, seed=0)
    with jax.transfer_guard("disallow"):
        res = solver.solve(IMProblem(k=3, eps=0.5, max_theta=256))
    seeds, est, stats = res.seeds, res.spread, res.stats
    assert len(set(seeds.tolist())) == 3
    assert est > 0 and stats.theta > 0
    assert stats.n_rr_sampled >= min(stats.theta, 256)


def test_solve_quality_unchanged_vs_oracle_greedy():
    """End-to-end: fused device pipeline and the plain select_seeds on the
    final snapshot agree on the same pool."""
    g = _wc_graph(n=60, m=300, seed=6)
    solver = IMMSolver(g, engine="queue", batch=64, seed=3)
    res = solver.solve(IMProblem(k=4, eps=0.5))
    seeds, est = res.seeds, res.spread
    snap = solver.store.snapshot()
    ref = cov.select_seeds(snap, 4)
    assert seeds.tolist() == np.asarray(ref.seeds).tolist()
    assert est == pytest.approx(g.n_nodes * float(ref.frac), rel=1e-5)


def test_refill_sample_device_padding_rows():
    """sample_device returns fixed-shape batches whose zero-length rows are
    dropped by the store; real sets match the host unpack exactly."""
    g = _wc_graph(n=40, m=200, seed=7)
    g_rev = csr_mod.reverse(g)
    eng = make_engine("refill", g_rev, batch=32)
    bd = eng.sample_device(jax.random.key(5))
    bh = eng.sample(jax.random.key(5))
    lens_d = np.asarray(bd.lengths)
    dev = cov.DeviceRRStore(40)
    dev.append_batch(bd)
    assert dev.n_rr == int((lens_d > 0).sum()) == bh.n_sets
    host = cov.IncrementalRRStore(40)
    host.append_batch((np.asarray(bh.nodes), np.asarray(bh.lengths)))
    np.testing.assert_array_equal(np.asarray(dev.snapshot().rr_flat),
                                  np.asarray(host.snapshot().rr_flat))
    np.testing.assert_array_equal(np.asarray(dev.snapshot().rr_ids),
                                  np.asarray(host.snapshot().rr_ids))


# ------------------------------------------------------ satellite bits

def test_interpret_defaults_to_backend(monkeypatch):
    from repro.kernels import ops
    monkeypatch.delenv(ops._ENV_FLAG, raising=False)
    assert ops.INTERPRET is None                 # auto, no import side effect
    assert ops.resolve_interpret() == (jax.default_backend() == "cpu")
    assert ops.resolve_interpret(True) is True   # per-call wins
    try:
        ops.INTERPRET = False                    # module override for tests
        assert ops.resolve_interpret() is False
        assert ops.resolve_interpret(True) is True
    finally:
        ops.INTERPRET = None
    # env override (the CI interpret-mode job): below the module override,
    # above the backend default
    monkeypatch.setenv(ops._ENV_FLAG, "1")
    assert ops.resolve_interpret() is True
    monkeypatch.setenv(ops._ENV_FLAG, "false")
    assert ops.resolve_interpret() is False
    assert ops.resolve_interpret(True) is True
    try:
        ops.INTERPRET = True
        monkeypatch.setenv(ops._ENV_FLAG, "0")
        assert ops.resolve_interpret() is True   # module override wins
    finally:
        ops.INTERPRET = None


def test_masked_occur_kernel():
    from repro.kernels import ops as kops
    rng = np.random.default_rng(8)
    rows, n_words = 16, 3
    words = jnp.asarray(rng.integers(0, 2**32, (rows, n_words),
                                     dtype=np.uint64).astype(np.uint32))
    mask = jnp.asarray(rng.random(rows) < 0.5)
    got = np.asarray(kops.occur_from_bitset_masked(words, mask))
    bits = np.unpackbits(
        np.asarray(words).view(np.uint8).reshape(rows, -1),
        axis=1, bitorder="little")
    expect = (bits * np.asarray(mask)[:, None]).sum(axis=0)
    np.testing.assert_array_equal(got, expect)


def test_coalesce_ic_merges_parallel_edges_exactly():
    src = np.array([0, 0, 0, 1, 1, 2])
    dst = np.array([1, 1, 2, 2, 2, 0])
    w = np.array([0.5, 0.5, 0.3, 1.0, 0.2, 0.4], np.float32)
    g = csr_mod.from_edges(src, dst, 3, weights=w)
    gc = csr_mod.coalesce_ic(g)
    s2, d2, w2 = csr_mod.to_edges(gc)
    ew = dict(zip(zip(s2.tolist(), d2.tolist()), w2.tolist()))
    assert len(s2) == 4
    assert ew[(0, 1)] == pytest.approx(0.75)      # 1 - (1-0.5)^2
    assert ew[(0, 2)] == pytest.approx(0.3)
    assert ew[(1, 2)] == 1.0                      # contains a p=1 edge
    assert ew[(2, 0)] == pytest.approx(0.4)
    # simple sorted graphs come back unchanged (same object)
    assert csr_mod.coalesce_ic(gc) is gc


def test_dedup_mode_detection():
    from repro.core.rrset import detect_dedup_mode
    src, dst = generators.erdos_renyi(40, 200, seed=1)
    g_rev = csr_mod.reverse(weights.wc_weights(
        csr_mod.from_edges(src, dst, 40)))
    assert csr_mod.rows_dst_sorted(g_rev)
    mode = detect_dedup_mode(g_rev)
    assert mode in ("none", "segmented")
    # coalescing always yields a simple graph -> no dedup needed
    assert detect_dedup_mode(csr_mod.coalesce_ic(g_rev)) == "none"
    # unsorted multigraph -> sort fallback
    gm = csr_mod.from_edges(np.array([0, 0, 0]), np.array([2, 1, 2]), 3,
                            sort=False)
    assert detect_dedup_mode(gm) == "sort"


def test_queue_chunk_dedup_no_duplicates_on_multigraph():
    """Multi-edges within one EC chunk must still enqueue a node once
    (sort-based first-occurrence dedup, paper §3.1)."""
    src = np.repeat(np.arange(1, 20), 6)         # 6 parallel edges each
    dst = np.tile([0], src.shape[0])
    src = np.concatenate([src, np.zeros(19, np.int64)])
    dst = np.concatenate([dst, np.arange(1, 20)])
    g = weights.uniform_weights(csr_mod.from_edges(src, dst, 20), p=1.0)
    g_rev = csr_mod.reverse(g)
    from repro.core import rrset
    s = rrset.sample_rrsets_queue(jax.random.key(0), g_rev, batch=16,
                                  qcap=20, ec=8)
    nodes, lens = np.asarray(s.nodes), np.asarray(s.lengths)
    for i in range(16):
        row = nodes[i, :lens[i]].tolist()
        assert len(set(row)) == len(row)
