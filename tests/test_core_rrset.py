"""RR-set engine correctness: deterministic, structural, and statistical."""
import numpy as np
import jax
import jax.numpy as jnp
import networkx as nx
import pytest

from repro.graph import csr as csr_mod
from repro.graph import generators, weights
from repro.core import rrset, dense, packing, coverage as cov
from repro.core import oracle


def _wc_graph(n=60, m=240, seed=0):
    src, dst = generators.erdos_renyi(n, m, seed=seed)
    return weights.wc_weights(csr_mod.from_edges(src, dst, n))


def _det_graph(p, n=40, m=160, seed=1):
    src, dst = generators.erdos_renyi(n, m, seed=seed)
    return weights.uniform_weights(csr_mod.from_edges(src, dst, n), p=p)


def _nx_reverse_reach(g, root):
    src, dst, _ = csr_mod.to_edges(g)
    G = nx.DiGraph()
    G.add_nodes_from(range(g.n_nodes))
    G.add_edges_from(zip(src.tolist(), dst.tolist()))
    return nx.ancestors(G, root) | {root}


@pytest.mark.parametrize("engine", ["queue", "dense"])
def test_p1_rrset_equals_reverse_reachability(engine):
    """With p=1 every edge survives: RR set == exact reverse-reachable set."""
    g = _det_graph(p=1.0)
    g_rev = csr_mod.reverse(g)
    key = jax.random.key(0)
    if engine == "queue":
        s = rrset.sample_rrsets_queue(key, g_rev, batch=16, qcap=g.n_nodes)
        rr = rrset.to_lists(s)
        roots = np.asarray(s.roots)
        assert not bool(np.asarray(s.overflowed).any())
    else:
        s = dense.sample_rrsets_dense(key, g_rev, batch=16)
        rr = dense.membership_to_lists(s.membership)
        roots = np.asarray(s.roots)
    for row, root in zip(rr, roots):
        assert set(row) == _nx_reverse_reach(g, int(root))


@pytest.mark.parametrize("engine", ["queue", "dense"])
def test_p0_rrset_is_singleton(engine):
    g = _det_graph(p=0.0)
    g_rev = csr_mod.reverse(g)
    key = jax.random.key(1)
    if engine == "queue":
        s = rrset.sample_rrsets_queue(key, g_rev, batch=8, qcap=g.n_nodes)
        rr = rrset.to_lists(s)
        roots = np.asarray(s.roots)
    else:
        s = dense.sample_rrsets_dense(key, g_rev, batch=8)
        rr = dense.membership_to_lists(s.membership)
        roots = np.asarray(s.roots)
    for row, root in zip(rr, roots):
        assert row == [int(root)]


def test_queue_rrsets_are_valid_and_unique():
    """Structural invariants: root first, no duplicates, all reverse-reachable."""
    g = _wc_graph()
    g_rev = csr_mod.reverse(g)
    s = rrset.sample_rrsets_queue(jax.random.key(2), g_rev, batch=64,
                                  qcap=g.n_nodes)
    rr = rrset.to_lists(s)
    roots = np.asarray(s.roots)
    for row, root in zip(rr, roots):
        assert row[0] == int(root)
        assert len(set(row)) == len(row)
        reach = _nx_reverse_reach(g, int(root))
        assert set(row) <= reach


def test_queue_small_chunk_matches_structure():
    """EC smaller than degrees exercises the multi-chunk path."""
    g = _det_graph(p=1.0, n=30, m=300, seed=3)
    g_rev = csr_mod.reverse(g)
    s = rrset.sample_rrsets_queue(jax.random.key(3), g_rev, batch=8,
                                  qcap=g.n_nodes, ec=4)
    rr = rrset.to_lists(s)
    for row, root in zip(rr, np.asarray(s.roots)):
        assert set(row) == _nx_reverse_reach(g, int(root))


def test_engines_agree_statistically():
    """Occur rates of both engines agree within CLT tolerance (same dist)."""
    g = _wc_graph(n=40, m=200, seed=5)
    g_rev = csr_mod.reverse(g)
    B, R = 128, 8
    occ_q = np.zeros(g.n_nodes)
    occ_d = np.zeros(g.n_nodes)
    for i in range(R):
        sq = rrset.sample_rrsets_queue(jax.random.key(10 + i), g_rev, B,
                                       qcap=g.n_nodes)
        for row in rrset.to_lists(sq):
            occ_q[row] += 1
        sd = dense.sample_rrsets_dense(jax.random.key(100 + i), g_rev, B)
        occ_d += np.asarray(sd.membership).sum(axis=0)
    total = B * R
    p_q, p_d = occ_q / total, occ_d / total
    se = np.sqrt((p_q * (1 - p_q) + p_d * (1 - p_d)) / total) + 1e-9
    z = np.abs(p_q - p_d) / se
    # 40 comparisons; allow 4.5 sigma
    assert z.max() < 4.5, f"max z={z.max():.2f}"


def test_queue_engine_matches_oracle_statistically():
    g = _wc_graph(n=40, m=200, seed=6)
    g_rev = csr_mod.reverse(g)
    offs = np.asarray(g_rev.offsets); idx = np.asarray(g_rev.indices)
    w = np.asarray(g_rev.weights)
    rng = np.random.default_rng(0)
    total = 1024
    occ_o = np.zeros(g.n_nodes)
    for _ in range(total):
        for v in oracle.rr_set_ic(offs, idx, w, int(rng.integers(g.n_nodes)), rng):
            occ_o[v] += 1
    occ_q = np.zeros(g.n_nodes)
    for i in range(total // 128):
        s = rrset.sample_rrsets_queue(jax.random.key(i), g_rev, 128,
                                      qcap=g.n_nodes)
        for row in rrset.to_lists(s):
            occ_q[row] += 1
    p_o, p_q = occ_o / total, occ_q / total
    se = np.sqrt((p_o * (1 - p_o) + p_q * (1 - p_q)) / total) + 1e-9
    z = np.abs(p_o - p_q) / se
    assert z.max() < 4.5, f"max z={z.max():.2f}"


def test_overflow_flag_set_when_qcap_too_small():
    g = _det_graph(p=1.0, n=50, m=400, seed=7)
    g_rev = csr_mod.reverse(g)
    s = rrset.sample_rrsets_queue(jax.random.key(4), g_rev, batch=32, qcap=2)
    rr = rrset.to_lists(s)
    # every produced row still fits the cap and is duplicate-free
    for row in rr:
        assert len(row) <= 2
        assert len(set(row)) == len(row)
    assert bool(np.asarray(s.overflowed).any())


def test_multi_edges_single_enqueue():
    """Parallel edges to one node: p=1 must not enqueue the node twice."""
    src = np.asarray([0, 0, 0, 0, 1, 1])
    dst = np.asarray([1, 1, 1, 2, 2, 2])
    g = csr_mod.from_edges(src, dst, 3,
                           weights=np.ones(6, dtype=np.float32))
    g_rev = csr_mod.reverse(g)
    # root=2 in reverse graph reaches 0 and 1 through parallel edges
    nodes, lengths, overflow, _ = rrset._sample_queue(
        jax.random.key(0), g_rev.offsets,
        rrset.edge_row_table(g_rev.indices, g_rev.weights, ec=8),
        jnp.asarray([2, 2, 2, 2], jnp.int32), batch=4, qcap=3, n=3, m=6)
    for b in range(4):
        row = np.asarray(nodes[b, :int(lengths[b])])
        assert sorted(row.tolist()) == [0, 1, 2]
    assert not bool(np.asarray(overflow).any())


@pytest.mark.parametrize("ec", [8, 32, 128])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5, 1.0])
def test_pack_accepted_matches_numpy_left_pack(density, ec):
    """The chunk pack is a left-pack in column order, sentinel ``n`` beyond
    each lane's count; lane 0 accepts every slot and lane 1 none."""
    n = 75_879
    rng = np.random.default_rng(ec)
    accept = rng.random((16, ec)) < density
    accept[0], accept[1] = True, False
    nbr = rng.integers(0, n, size=(16, ec)).astype(np.int32)
    got = np.asarray(rrset._pack_accepted(jnp.asarray(accept),
                                          jnp.asarray(nbr), n, ec))
    rows, lens = packing.pack_rows(nbr, accept)
    want = np.full((16, ec), n, np.int32)
    for b in range(16):
        want[b, :lens[b]] = rows[b, :lens[b]]
    np.testing.assert_array_equal(got, want)


def _rank_positions(csum, width, size):
    """Positions of the 1st..``width``-th set elements of a mask, from its
    inclusive prefix sum: a lower-bound binary search (the chunk pack's
    former search)."""
    tgt = jnp.arange(1, width + 1, dtype=jnp.int32)
    lo = jnp.zeros((width,), jnp.int32)
    hi = jnp.full((width,), size - 1, jnp.int32)
    for _ in range(max(int(np.ceil(np.log2(max(size, 2)))), 1)):
        mid = (lo + hi) >> 1
        go_right = csum[mid] < tgt
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return jnp.clip(lo, 0, size - 1)


def _binary_search_pack(accept, nbr, n, width):
    """The former chunk pack: binary search over the accept prefix sum."""
    csum = jnp.cumsum(accept.astype(jnp.int32), axis=1)
    pos = jax.vmap(
        lambda c: _rank_positions(c, width, accept.shape[1]))(csum)
    packed = jnp.take_along_axis(nbr, pos, axis=1)
    tgt = jnp.arange(1, width + 1, dtype=jnp.int32)[None, :]
    return jnp.where(tgt <= csum[:, -1:], packed, n)


@pytest.mark.parametrize("graph", ["wc", "p1_dense"])
@pytest.mark.parametrize("sampler", ["queue", "refill"])
def test_pack_keeps_the_draw_stream(sampler, graph, monkeypatch):
    """Both samplers draw the same RR sets, in the same order and steps,
    with the binary-search pack in place of the compare-reduce one.  The
    p=1 graph's in-degrees exceed ACCEPT_CAP, so chunks take the
    full-width visited write."""
    if graph == "wc":
        g = _wc_graph(n=120, m=600, seed=8)
    else:
        g = _det_graph(p=1.0, n=100, m=5000, seed=9)
        assert np.diff(np.asarray(csr_mod.reverse(g).offsets)).max() > (
            rrset.ACCEPT_CAP + 1)
    g_rev = csr_mod.reverse(g)

    def draw():
        jax.clear_caches()
        if sampler == "queue":
            s = rrset.sample_rrsets_queue(jax.random.key(21), g_rev, 32,
                                          qcap=g.n_nodes)
            out = (s.nodes, s.lengths, s.steps)
        else:
            s = rrset.sample_rrsets_refill(jax.random.key(22), g_rev, 16,
                                           quota=48, out_cap=8 * g.n_nodes)
            out = (s.flat, s.lengths, s.n_done, s.steps)
        return [np.asarray(x) for x in out]

    packed_by_reduce = draw()
    traced = []

    def reference(*args):
        traced.append(1)
        return _binary_search_pack(*args)

    monkeypatch.setattr(rrset, "_pack_accepted", reference)
    packed_by_search = draw()
    jax.clear_caches()
    assert traced
    for a, b in zip(packed_by_reduce, packed_by_search):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("m,ec", [(1, 128), (127, 128), (128, 128),
                                  (129, 128), (5_000, 128), (1_000, 100),
                                  (37, 8), (50, 1)])
def test_edge_row_fetch_matches_clipped_element_reads(m, ec):
    """The row fetch returns ``indices[clip(start + c, 0, m - 1)]`` and the
    weights alike, bit for bit, for windows starting at 0, at m - 1, at m
    (a degree-0 node after the last edge), on and across row boundaries,
    and anywhere else."""
    rng = np.random.default_rng(m + ec)
    idx = rng.integers(0, 1 << 30, m).astype(np.int32)
    w = rng.random(m).astype(np.float32)
    w[::7] = np.float32(1.0)
    table = rrset.edge_row_table(jnp.asarray(idx), jnp.asarray(w), ec=ec)
    assert table.shape == (-(-m // ec) + 2, 2, ec)
    starts = np.r_[0, m - 1, m, ec - 1, ec, ec + 1, 2 * ec - 1,
                   rng.integers(0, m + 1, 64)]
    starts = np.clip(starts, 0, m).astype(np.int32)
    nbr, pw = rrset._fetch_edges(table, jnp.asarray(starts), m)
    eidx = np.clip(starts[:, None] + np.arange(ec), 0, m - 1)
    np.testing.assert_array_equal(np.asarray(nbr), idx[eidx])
    np.testing.assert_array_equal(np.asarray(pw).view(np.int32),
                                  w[eidx].view(np.int32))


def _multi_edge_graph(n=160, seed=3):
    """Reverse graph with parallel edges, a hub whose row spans many
    chunks, and degree-0 nodes (the last 10 have no in-edges)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, 700)
    dst = rng.integers(0, n - 10, 700)
    hub = np.r_[rng.integers(0, n, 90), np.repeat(src[:5], 3)]
    src, dst = np.r_[src, hub], np.r_[dst, np.zeros(len(hub), np.int64)]
    return csr_mod.from_edges(src, dst, n,
                              weights=rng.random(len(src)).astype(np.float32)
                              * 0.6)


@pytest.mark.parametrize("sampler,dedup", [("queue", "none"),
                                           ("queue", "segmented"),
                                           ("queue", "sort"),
                                           ("refill", "segmented")])
def test_row_fetch_keeps_the_draw_stream(sampler, dedup, monkeypatch):
    """Both samplers draw the same RR sets, lengths and steps with the
    former per-element fetch (``indices[eidx]``, ``weights[eidx]``) in
    place of the row fetch, at a chunk width whose windows cross row
    boundaries on most steps."""
    g = _multi_edge_graph()
    g_rev = csr_mod.reverse(g)                       # rows dst-sorted
    if dedup == "none":
        g_rev = csr_mod.coalesce_ic(g_rev)
    elif dedup == "sort":
        src, dst, w = csr_mod.to_edges(g_rev)
        order = np.random.default_rng(0).permutation(len(src))
        g_rev = csr_mod.from_edges(src[order], dst[order], g.n_nodes,
                                   weights=w[order])
    assert rrset.detect_dedup_mode(g_rev) == dedup
    ec = 8
    assert np.diff(np.asarray(g_rev.offsets)).max() > 4 * ec
    indices, weights_ = g_rev.indices, g_rev.weights

    def draw():
        jax.clear_caches()
        if sampler == "queue":
            s = rrset.sample_rrsets_queue(jax.random.key(31), g_rev, 32,
                                          qcap=g.n_nodes, ec=ec, dedup=dedup)
            out = (s.nodes, s.lengths, s.steps)
        else:
            s = rrset.sample_rrsets_refill(jax.random.key(32), g_rev, 16,
                                           quota=48, out_cap=8 * g.n_nodes,
                                           ec=ec, dedup=dedup)
            out = (s.flat, s.lengths, s.n_done, s.steps)
        return [np.asarray(x) for x in out]

    by_rows = draw()
    traced = []

    def element_fetch(edge_rows, start, m):
        traced.append(1)
        eidx = jnp.clip(start[:, None] + jnp.arange(edge_rows.shape[2]),
                        0, m - 1)
        return indices[eidx], weights_[eidx]

    monkeypatch.setattr(rrset, "_fetch_edges", element_fetch)
    by_elements = draw()
    jax.clear_caches()
    assert traced
    assert by_rows[-1] > 0
    for a, b in zip(by_rows, by_elements):
        np.testing.assert_array_equal(a, b)
