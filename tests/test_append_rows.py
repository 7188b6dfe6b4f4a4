"""The pool append driven by row offsets (``coverage._append_rows_local``)
and the sampling round's unsliced output, against what they replace: the
former packed append (mask, prefix sum and binary search over all R·W
slots) and the rank-scatter, bit for bit; the queue and MRIM engines'
RR sets as the sliced round drew them; and a solve through both against
the numpy greedy on its own pool."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coverage as cov, oracle
from repro.core.engine import make_engine
from repro.core.imm import IMMSolver
from repro.core.packing import PACK_WINDOW
from repro.core.problem import IMProblem
from repro.graph import csr as csr_mod
from repro.graph import generators, weights


def _rank_positions(csum, width, size):
    """Lower-bound binary search for the 1st..``width``-th set slot of a
    flat mask, from its inclusive prefix sum."""
    tgt = jnp.arange(1, width + 1, dtype=jnp.int32)
    lo = jnp.zeros((width,), jnp.int32)
    hi = jnp.full((width,), size - 1, jnp.int32)
    for _ in range(max(int(np.ceil(np.log2(max(size, 2)))), 1)):
        mid = (lo + hi) >> 1
        go_right = csum[mid] < tgt
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return jnp.clip(lo, 0, size - 1)


def _former_packed_append(flat, ids, valid, t, n_rr, nodes, lens, *, pack,
                          n, ew=None, wsum=None, roww=None):
    """The packed append that the row append replaced: an R·W mask and
    prefix sum, a binary search for each of ``pack`` slots, one window."""
    r, w = nodes.shape
    lens = jnp.minimum(jnp.maximum(lens.astype(jnp.int32), 0), w)
    fm = (jnp.arange(w, dtype=jnp.int32)[None, :] < lens[:, None]).reshape(-1)
    csum = jnp.cumsum(fm.astype(jnp.int32))
    total = csum[-1]
    src = _rank_positions(csum, pack, r * w)
    jvalid = jnp.arange(1, pack + 1, dtype=jnp.int32) <= total
    fnodes = nodes.reshape(-1).astype(jnp.int32)[src]
    row_valid = lens > 0
    rid = n_rr + jnp.cumsum(row_valid.astype(jnp.int32)) - 1
    flat = jax.lax.dynamic_update_slice(flat, jnp.where(jvalid, fnodes, n),
                                        (t,))
    ids = jax.lax.dynamic_update_slice(
        ids, jnp.where(jvalid, rid[src // w], 0), (t,))
    valid = jax.lax.dynamic_update_slice(valid, jvalid, (t,))
    t_out, nrr_out = t + total, n_rr + row_valid.sum(dtype=jnp.int32)
    if ew is None:
        return flat, ids, valid, t_out, nrr_out
    roww = roww.astype(jnp.float32)
    ew = jax.lax.dynamic_update_slice(
        ew, jnp.where(jvalid, roww[src // w], 0.0), (t,))
    wsum = wsum + jnp.where(row_valid, roww, 0.0).sum(dtype=jnp.float32)
    return flat, ids, valid, ew, t_out, nrr_out, wsum


def _rank_scatter_append(flat, ids, valid, t, n_rr, nodes, lens, ew=None,
                         wsum=None, roww=None):
    """The rank-scatter append that the row append replaced: every one of
    the R·W slots scattered to its rank in a row-major prefix sum of the
    validity mask (out-of-range ranks dropped)."""
    cap = flat.shape[0]
    r, w = nodes.shape
    lens = jnp.minimum(jnp.maximum(lens.astype(jnp.int32), 0), w)
    fm = (jnp.arange(w, dtype=jnp.int32)[None, :] < lens[:, None]).reshape(-1)
    dest = jnp.where(fm, t + jnp.cumsum(fm, dtype=jnp.int32) - 1, cap)
    flat = flat.at[dest].set(nodes.reshape(-1).astype(jnp.int32), mode="drop")
    valid = valid.at[dest].set(True, mode="drop")
    row_valid = lens > 0
    rid = n_rr + jnp.cumsum(row_valid, dtype=jnp.int32) - 1
    ids = ids.at[dest].set(
        jnp.broadcast_to(rid[:, None], (r, w)).reshape(-1), mode="drop")
    t_out = t + fm.sum(dtype=jnp.int32)
    nrr_out = n_rr + row_valid.sum(dtype=jnp.int32)
    if ew is None:
        return flat, ids, valid, t_out, nrr_out
    roww = roww.astype(jnp.float32)
    ew = ew.at[dest].set(
        jnp.broadcast_to(roww[:, None], (r, w)).reshape(-1), mode="drop")
    wsum = wsum + jnp.where(row_valid, roww, 0.0).sum(dtype=jnp.float32)
    return flat, ids, valid, ew, t_out, nrr_out, wsum


N = 500
# name: (rows, width, pack, t, n_rr, lengths rule, capacity past t + elems)
CASES = {
    "empty_rows": (24, 40, 512, 0, 0, "half_empty", 512),
    "full_row": (16, 64, 1024, 0, 0, "one_full", 1024),
    "windows": (32, 48, 32, 0, 0, "random", 64),
    "offset": (20, 30, 256, 37, 5, "random", 256),
    # the buffer ends at t + elements: a window longer than that starts
    # before t (and keeps those slots), the last of several starts early
    "tight": (20, 30, 512, 37, 5, "random", 0),
    "tight_windows": (20, 30, 64, 37, 5, "random", 0),
    "no_elements": (12, 30, 64, 9, 2, "all_empty", 64),
}


def _case(name, seed):
    r, w, pack, t, n_rr, rule, room = CASES[name]
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, w + 1, r)
    if rule == "half_empty":
        lens[rng.permutation(r)[:r // 2]] = 0
    elif rule == "one_full":
        lens[r // 3] = w
    elif rule == "all_empty":
        lens[:] = 0
    nodes = rng.integers(0, N, (r, w)).astype(np.int32)   # junk past lens
    cap = t + int(lens.sum()) + room
    flat = np.full(cap, N, np.int32)
    ids = np.zeros(cap, np.int32)
    valid = np.zeros(cap, bool)
    ew = np.zeros(cap, np.float32)
    flat[:t] = rng.integers(0, N, t)            # an earlier append's rows
    ids[:t] = np.sort(rng.integers(0, max(n_rr, 1), t))
    valid[:t] = True
    ew[:t] = rng.random(t)
    roww = rng.random(r).astype(np.float32)
    bufs = (flat, ids, valid, np.int32(t), np.int32(n_rr))
    return bufs, ew, np.float32(0.25), nodes, lens.astype(np.int32), roww, \
        pack, int(lens.sum())


def _run(fn, bufs, ew, wsum, nodes, lens, roww, pack, weighted):
    flat, ids, valid, t, n_rr = (jnp.asarray(x) for x in bufs)
    f = jax.jit(fn)
    if not weighted:
        return f(flat, ids, valid, t, n_rr, jnp.asarray(nodes),
                 jnp.asarray(lens))
    return f(flat, ids, valid, t, n_rr, jnp.asarray(nodes),
             jnp.asarray(lens), ew=jnp.asarray(ew), wsum=jnp.asarray(wsum),
             roww=jnp.asarray(roww))


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("name", sorted(CASES))
def test_row_append_matches_the_former_appends(name, weighted):
    """Every buffer and counter after the row append equals the
    rank-scatter's, and the former packed append's wherever its one window
    held the batch and fitted the buffer: the rows, their ids and weights,
    the virgin slots past the count and the earlier rows before ``t``."""
    bufs, ew, wsum, nodes, lens, roww, pack, elems = _case(name, 7)
    args = (bufs, ew, wsum, nodes, lens, roww, pack, weighted)
    got = _run(lambda *a, **k: cov._append_rows_local(*a, pack=pack, n=N,
                                                      **k), *args)
    want = _run(_rank_scatter_append, *args)
    assert len(got) == len(want)
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
    assert int(got[-2 if weighted else -1]) == bufs[4] + (lens > 0).sum()
    if elems <= pack and bufs[0].shape[0] >= int(bufs[3]) + pack:
        former = _run(lambda *a, **k: _former_packed_append(
            *a, pack=pack, n=N, **k), *args)
        for g_, w_ in zip(got, former):
            np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))


def test_store_appends_wide_batches_as_the_host_compaction():
    """A batch wider than one window with more elements than one window
    (several windows) lands as the host compaction does."""
    rng = np.random.default_rng(3)
    n, r = 40_000, 8
    lens = rng.integers(2_000, 9_000, r)
    lens[2] = 0
    nodes = rng.integers(0, n, (r, 10_000))
    assert r * nodes.shape[1] > PACK_WINDOW and lens.sum() > PACK_WINDOW
    dev = cov.ShardedDeviceRRStore(n, capacity=8)
    host = cov.IncrementalRRStore(n, capacity=8)
    for batch in ((nodes[:, :5], np.minimum(lens, 5)), (nodes, lens)):
        dev.append_batch(batch)
        host.append_batch(batch)
    a, b = dev.snapshot(), host.snapshot()
    assert a.n_rr == b.n_rr == 2 * (r - 1)
    np.testing.assert_array_equal(np.asarray(a.rr_flat),
                                  np.asarray(b.rr_flat))
    np.testing.assert_array_equal(np.asarray(a.rr_ids), np.asarray(b.rr_ids))


def _digest(nodes, lens):
    nodes, lens = np.asarray(nodes), np.asarray(lens)
    h = hashlib.sha256()
    for b in range(nodes.shape[0]):
        h.update(np.asarray(nodes[b, :lens[b]], np.int64).tobytes())
        h.update(np.int64(lens[b]).tobytes())
    return h.hexdigest()[:16]


def _graph(kind):
    if kind == "wc":
        src, dst = generators.erdos_renyi(120, 600, seed=8)
        return weights.wc_weights(csr_mod.from_edges(src, dst, 120))
    src, dst = generators.erdos_renyi(50, 400, seed=7)
    return weights.uniform_weights(csr_mod.from_edges(src, dst, 50), p=1.0)


# the RR sets of keys 0, 1, 2 as the round drew them when it returned the
# queue sliced to its first qcap columns (digests of each row within its
# length); the p=1 graph overflows its small qcap
SLICED_ROUNDS = {
    ("queue", "wc", (("batch", 32),)):
        ("fdf78fde9555b368", "80f068864f4b17a9", "681f33a4b856e156"),
    ("queue", "p1", (("batch", 32), ("qcap", 5))):
        ("6e26776645e08ef4", "d10564ab09757a4c", "870045010e96bc88"),
    ("mrim", "wc", (("batch", 16), ("t_rounds", 2))):
        ("6635178f22f61e52", "31aeaf8c0c2d0ee0", "accc0f0d52527b7c"),
    ("mrim", "p1", (("batch", 8), ("t_rounds", 3), ("qcap", 7))):
        ("16a1af3f1dcc8604", "2d863bdb4b7193da", "778703acf0e48512"),
}


@pytest.mark.parametrize("case", sorted(SLICED_ROUNDS), ids=str)
def test_unsliced_round_draws_the_sliced_rounds_sets(case):
    engine, kind, opts = case
    g = _graph(kind)
    e = make_engine(engine, csr_mod.reverse(g), **dict(opts))
    got = []
    for i in range(3):
        b = e.sample(jax.random.key(i))
        got.append(_digest(b.nodes, b.lengths))
    assert tuple(got) == SLICED_ROUNDS[case]


def test_round_returns_its_queue_with_the_pad_tail():
    g = _graph("wc")
    e = make_engine("queue", csr_mod.reverse(g), batch=8)
    b = e.sample(jax.random.key(0))
    assert b.nodes.shape == (8, g.n_nodes + e.config.ec)
    assert int(np.asarray(b.lengths).max()) <= g.n_nodes


def test_solve_through_the_row_append_matches_the_oracle_greedy():
    """A solve whose rounds take the row append (512 lanes of n + EC
    slots span many windows) picks the numpy greedy's seeds on its own
    pool, and counts the pool's elements."""
    n = 300
    src, dst = generators.barabasi_albert(n, 3, seed=2)
    g = weights.wc_weights(csr_mod.from_edges(src, dst, n))
    solver = IMMSolver(g, engine="queue", batch=512, seed=5)
    assert 512 * (n + 128) > PACK_WINDOW
    res = solver.solve(IMProblem(k=5, theta=2048))
    snap = solver.store.snapshot()
    flat = np.asarray(snap.rr_flat)[np.asarray(snap.valid)]
    ids = np.asarray(snap.rr_ids)[np.asarray(snap.valid)]
    rows = [flat[ids == i].tolist() for i in range(snap.n_rr)]
    seeds, frac = oracle.greedy_max_coverage(rows, n, 5)
    assert [int(s) for s in res.seeds] == seeds
    assert float(res.frac) == pytest.approx(frac, abs=1e-6)
    assert res.stats.pool_elements == flat.size == solver.store.n_elems
