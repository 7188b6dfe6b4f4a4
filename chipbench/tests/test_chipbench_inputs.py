"""The benchmark's inputs and yardsticks: graphs and traffic made from the
seed, the plain references, and the byte count of the sampling roofline."""
import numpy as np

from chipbench import graphs, reference, roofline
from chipbench.spec import cell


def test_graph_is_a_faithful_copy_and_follows_the_seed():
    from repro.graph import generators, weights, csr
    src, dst = graphs.barabasi_albert(2000, 4, seed=7)
    psrc, pdst = generators.barabasi_albert(2000, 4, seed=7)
    np.testing.assert_array_equal(src, psrc)
    np.testing.assert_array_equal(dst, pdst)
    g = graphs.wc_graph(src, dst, 2000)
    pg = weights.wc_weights(csr.from_edges(src, dst, 2000))
    np.testing.assert_array_equal(g.offsets, np.asarray(pg.offsets))
    np.testing.assert_array_equal(g.indices, np.asarray(pg.indices))
    np.testing.assert_array_equal(g.weights, np.asarray(pg.weights))
    prog = graphs.to_program(g)
    np.testing.assert_array_equal(np.asarray(prog.indices), g.indices)
    other = graphs.barabasi_albert(2000, 4, seed=8)[1]
    assert not np.array_equal(other, dst)


def test_graph_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setattr(graphs, "CACHE", str(tmp_path))
    conf = {**cell("ba-r4-75k.solve").config, "n_nodes": 1500}
    a = graphs.make(conf)
    b = graphs.make(conf)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert len(list(tmp_path.rglob("*.npz"))) == 1
    other = graphs.make({**conf, "graph_seed": conf["graph_seed"] + 1})
    assert not np.array_equal(other.indices, a.indices)


def test_solve_keys_are_fixed_by_the_mix():
    from chipbench import solve
    mix = cell("ba-r4-75k.solve").traffic
    a = solve.window_keys(mix)
    assert a == solve.window_keys(dict(mix))
    assert len(a) == mix["solve_keys"] == len(set(a))
    assert all(0 <= k < 2 ** 31 for k in a)
    assert a != solve.window_keys({**mix, "key_seed": mix["key_seed"] + 1})


def _tiny():
    # 0 -> 1, 0 -> 2, 1 -> 2, 2 -> 3, 3 -> 0; p = 1 everywhere
    src = np.array([0, 0, 1, 2, 3])
    dst = np.array([1, 2, 2, 3, 0])
    offsets = np.array([0, 2, 3, 4, 5])
    return graphs.HostGraph(offsets, dst.astype(np.int32),
                            np.ones(5, np.float32)), src, dst


def test_roofline_bytes_by_hand():
    g, _, _ = _tiny()
    # in-degrees: node0 1 (from 3), node1 1, node2 2, node3 1
    np.testing.assert_array_equal(roofline.distinct_in_degree(g),
                                  [1, 1, 2, 1])
    # expanding nodes 2 and 0: 2 x 8 B of offsets + (2 + 1) x 8 B of edges
    assert roofline.bfs_bytes(np.array([2, 0]), roofline.distinct_in_degree(
        g)) == 2 * 8 + 3 * 8


def test_reference_rr_sets_and_pool_checks():
    g, _, _ = _tiny()
    rev = graphs.reverse(g)
    rng = np.random.default_rng(0)
    # p = 1: the RR set of 2 is everything that reaches 2
    assert sorted(reference.rr_set_ic(rev, 2, rng)) == [0, 1, 2, 3]
    rows = [[2, 0, 1, 3], [1, 0, 3, 2], [3, 2, 1, 0]]
    flat = np.concatenate(rows)
    ids = np.repeat(np.arange(3), 4)
    pool = reference.Pool(flat, ids, 3, 4)
    assert pool.bad_rows(g) == 0
    bad = [[2, 3, 1, 0],      # 3 -> 2 fine, 1 has no edge into {2, 3}
           [1, 1, 0, 3],      # a node twice
           [0, 9, 1, 2]]      # out of range
    bflat = np.concatenate(bad + rows)
    bids = np.repeat(np.arange(6), 4)
    assert reference.Pool(bflat, bids, 6, 4).bad_rows(g) == 3


def test_reference_greedy_matches_the_serial_oracle():
    from repro.core.oracle import greedy_max_coverage
    rng = np.random.default_rng(4)
    n = 60
    rows = [rng.choice(n, size=rng.integers(1, 6), replace=False).tolist()
            for _ in range(300)]
    pool = reference.Pool(np.concatenate(rows),
                          np.repeat(np.arange(300), [len(r) for r in rows]),
                          300, n)
    seeds, gains = pool.greedy(8)
    want, frac = greedy_max_coverage(rows, n, 8)
    assert seeds == want
    assert sum(gains) == round(frac * 300)
    cand = np.zeros(n, bool)
    cand[::3] = True
    cs, cg = pool.greedy(80, cand)
    assert len(cs) == cand.sum() and set(cs) == set(np.flatnonzero(cand))
    assert cg == sorted(cg, reverse=True)
