import numpy as np
import jax
import jax.numpy as jnp
import networkx as nx
import pytest

from repro.graph import csr as csr_mod
from repro.graph import generators, weights, sampler


def _random_edges(n=50, m=200, seed=0):
    return generators.erdos_renyi(n, m, seed=seed)


def test_csr_roundtrip():
    src, dst = _random_edges()
    g = csr_mod.from_edges(src, dst, 50)
    s2, d2, _ = csr_mod.to_edges(g)
    assert sorted(zip(s2.tolist(), d2.tolist())) == sorted(zip(src.tolist(), dst.tolist()))


def test_csr_rows_match_adjacency():
    src, dst = _random_edges(seed=3)
    g = csr_mod.from_edges(src, dst, 50)
    offs = np.asarray(g.offsets)
    idx = np.asarray(g.indices)
    adj = {}
    for s, d in zip(src, dst):
        adj.setdefault(int(s), []).append(int(d))
    for u in range(50):
        row = sorted(idx[offs[u]:offs[u + 1]].tolist())
        assert row == sorted(adj.get(u, []))


def test_reverse_is_transpose():
    src, dst = _random_edges(seed=1)
    g = csr_mod.from_edges(src, dst, 50, weights=np.arange(len(src), dtype=np.float32))
    gt = csr_mod.reverse(g)
    s, d, w = csr_mod.to_edges(g)
    s2, d2, w2 = csr_mod.to_edges(gt)
    fwd = sorted(zip(s.tolist(), d.tolist(), w.tolist()))
    rev = sorted(zip(d2.tolist(), s2.tolist(), w2.tolist()))
    assert fwd == rev


def test_wc_weights_sum_to_one_per_node():
    src, dst = _random_edges(seed=2)
    g = weights.wc_weights(csr_mod.from_edges(src, dst, 50))
    s, d, w = csr_mod.to_edges(g)
    sums = np.zeros(50)
    np.add.at(sums, d, w)
    indeg = np.bincount(d, minlength=50)
    np.testing.assert_allclose(sums[indeg > 0], 1.0, rtol=1e-5)


def test_barabasi_albert_properties():
    src, dst = generators.barabasi_albert(2000, 3, seed=0)
    assert np.all(src != dst)
    # symmetric edge set
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert all((d, s) in fwd for s, d in list(fwd)[:500])
    # power-law-ish: max degree much larger than mean, but of BA's order
    # r·sqrt(n), not a handful of hubs holding every early node
    deg = np.bincount(src, minlength=2000)
    assert deg.max() > 5 * deg.mean()
    assert deg.max() < 4 * 3 * np.sqrt(2000)


def _barabasi_albert_sequential(n, r, seed):
    """Node-by-node preferential attachment over a growing endpoint pool,
    drawing the same uniforms as the vectorized generator."""
    rng = np.random.default_rng(seed)
    r0 = r + 1
    a, b = np.triu_indices(r0, k=1)
    pool = list(np.concatenate([a, b]))
    u = rng.random((n - r0, r))
    src, dst = list(a), list(b)
    for t in range(n - r0):
        size = len(pool)
        picks = [pool[min(int(u[t, i] * size), size - 1)] for i in range(r)]
        pool += [r0 + t] * r + picks
        src += [r0 + t] * r
        dst += picks
    src, dst = np.asarray(src), np.asarray(dst)
    return np.concatenate([src, dst]), np.concatenate([dst, src])


@pytest.mark.parametrize("n,r,seed", [(40, 3, 7), (600, 4, 0),
                                      (5000, 6, 1), (70_000, 2, 3)])
def test_barabasi_albert_matches_sequential_attachment(n, r, seed):
    got = generators.barabasi_albert(n, r, seed=seed)
    want = _barabasi_albert_sequential(n, r, seed)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_icosahedral_multimesh_counts():
    verts, src, dst = generators.icosahedral_multimesh(2)
    # 10*4^R + 2 vertices
    assert verts.shape == (162, 3)
    np.testing.assert_allclose(np.linalg.norm(verts, axis=1), 1.0, rtol=1e-5)
    # symmetric, no self loops
    assert np.all(src != dst)
    e = set(zip(src.tolist(), dst.tolist()))
    assert all((d, s) in e for s, d in list(e)[:200])


def test_two_tier_reachability():
    src, dst, n = generators.two_tier_social(4, 2)
    g = csr_mod.from_edges(src, dst, n)
    G = nx.DiGraph(list(zip(src.tolist(), dst.tolist())))
    # every leaf reachable from core 0 through the ring
    reach = nx.descendants(G, 0) | {0}
    assert len(reach) == n


def test_neighbor_sampler_shapes_and_validity():
    src, dst = _random_edges(n=30, m=120, seed=5)
    g = csr_mod.from_edges(src, dst, 30)
    seeds = jnp.asarray([0, 3, 7, 11], dtype=jnp.int32)
    sub = sampler.sample_subgraph(jax.random.key(0), g, seeds, (5, 3))
    b1, b2 = sub.blocks
    assert b1.nodes.shape == (4 * 5,)
    assert b2.nodes.shape == (4 * 5 * 3,)
    offs = np.asarray(g.offsets); idx = np.asarray(g.indices)
    nodes1 = np.asarray(b1.nodes); mask1 = np.asarray(b1.mask)
    parents = np.asarray(seeds)[np.asarray(b1.parent_idx)]
    for nb, p, mk in zip(nodes1, parents, mask1):
        if mk:
            assert nb in idx[offs[p]:offs[p + 1]]
        else:
            assert nb == p  # self-loop padding


def test_partition_edges_covers_all():
    from repro.graph import partition
    src, dst = _random_edges(seed=7)
    g = csr_mod.from_edges(src, dst, 50, weights=np.arange(len(src), dtype=np.float32))
    sh = partition.partition_edges(g, 8)
    assert sh.src.shape[0] == 8
    m = len(src)
    assert int(sh.mask.sum()) == m
    flat = sorted(zip(np.asarray(sh.src).ravel()[np.asarray(sh.mask).ravel()].tolist(),
                      np.asarray(sh.dst).ravel()[np.asarray(sh.mask).ravel()].tolist()))
    assert flat == sorted(zip(src.tolist(), dst.tolist()))


def test_from_edges_sort_false_requires_grouped_input():
    # grouped (src non-decreasing) input builds the same CSR as sort=True
    src = np.array([0, 0, 1, 3]); dst = np.array([2, 1, 3, 0])
    g = csr_mod.from_edges(src, dst, 4, sort=False)
    g2 = csr_mod.from_edges(src, dst, 4, sort=True)
    assert np.array_equal(np.asarray(g.offsets), np.asarray(g2.offsets))
    assert np.array_equal(np.asarray(g.indices), np.asarray(g2.indices))
    # ungrouped input used to produce a silently corrupt CSR (bincount
    # offsets paired with input-order indices); now it raises
    with pytest.raises(ValueError, match="source-grouped"):
        csr_mod.from_edges([2, 0, 1], [0, 1, 2], 3, sort=False)
    # the graph/weights.py callers feed to_edges output (grouped by
    # construction) into sort=False — they must keep passing
    rs, rd = _random_edges(seed=11)
    wg = weights.wc_weights(csr_mod.from_edges(rs, rd, 50))
    assert wg.n_edges == len(rs)


def test_graph_digest_content_identity():
    src, dst = _random_edges(seed=4)
    w = np.random.default_rng(0).random(len(src)).astype(np.float32)
    g = csr_mod.from_edges(src, dst, 50, weights=w)
    g_same = csr_mod.from_edges(src.copy(), dst.copy(), 50, weights=w.copy())
    assert csr_mod.graph_digest(g) == csr_mod.graph_digest(g_same)
    # any content change — weights or topology — changes the digest
    w2 = w.copy(); w2[0] += 0.25
    g_w = csr_mod.from_edges(src, dst, 50, weights=w2)
    assert csr_mod.graph_digest(g_w) != csr_mod.graph_digest(g)
    g_t = csr_mod.from_edges(src[:-1], dst[:-1], 50, weights=w[:-1])
    assert csr_mod.graph_digest(g_t) != csr_mod.graph_digest(g)
