"""Host-side graph generators (numpy).

* :func:`barabasi_albert` — the paper's §4.6 scalability workload (n=1e6,
  r=2..32).  Implemented with the repeated-endpoints trick so attachment is
  proportional to degree, O(n·r).
* :func:`erdos_renyi` — fixed edge-count G(n, m) for tests/benchmarks.
* :func:`icosahedral_multimesh` — GraphCast's refined icosahedron mesh
  (refinement R => 10·4^R + 2 nodes), multimesh = union of all levels' edges.
* :func:`two_tier_social` — small directed "core-periphery" graph with known
  structure, used by unit tests.
"""
from __future__ import annotations

import numpy as np


def barabasi_albert(n: int, r: int, seed: int = 0):
    """Undirected BA preferential-attachment graph -> directed both ways.

    Returns (src, dst) with both edge directions, as the paper treats the BA
    graphs as undirected social graphs.
    """
    if r < 1 or n <= r:
        raise ValueError("need n > r >= 1")
    rng = np.random.default_rng(seed)
    # initial clique of r0 = r+1 nodes
    r0 = r + 1
    init_src, init_dst = np.triu_indices(r0, k=1)
    # repeated-endpoint pool: node id appears once per incident edge end.
    # Layout: the clique's ends, then per new node t its r own ends followed
    # by its r picks.  Node t draws its r picks uniformly from the pool as
    # it stands before t, i.e. from its first p0 + 2r·t entries, so the
    # attachment law is exactly degree-proportional at every step.
    pool0 = np.concatenate([init_src, init_dst]).astype(np.int64)
    p0, m = pool0.shape[0], n - r0
    t = np.arange(m, dtype=np.int64)
    pos = (rng.random((m, r)) * (p0 + 2 * r * t)[:, None]).astype(np.int64)
    pos = np.minimum(pos, (p0 + 2 * r * t - 1)[:, None]).ravel()
    off = pos - p0
    owner, j = off // (2 * r), off % (2 * r)
    # a pick that lands on an earlier node's own end is that node; one that
    # lands on an earlier pick is whatever that pick resolved to
    dst = np.where(off < 0, pool0[np.minimum(pos, p0 - 1)], r0 + owner)
    ref = np.where((off >= 0) & (j >= r), owner * r + j - r, -1)
    todo = np.flatnonzero(ref >= 0)
    while todo.size:                 # pointer jumping: chains halve per pass
        nxt = ref[todo]
        jump = ref[nxt]
        done = jump < 0
        dst[todo[done]] = dst[nxt[done]]
        ref[todo] = jump
        todo = todo[~done]
    src = np.concatenate([init_src, np.repeat(r0 + t, r)]).astype(np.int64)
    dst = np.concatenate([init_dst, dst]).astype(np.int64)
    return np.concatenate([src, dst]), np.concatenate([dst, src])


def erdos_renyi(n: int, m: int, seed: int = 0, directed: bool = True):
    """G(n, m): m directed edges sampled uniformly (self-loops removed)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=int(m * 1.1) + 8)
    dst = rng.integers(0, n, size=src.shape[0])
    keep = src != dst
    src, dst = src[keep][:m], dst[keep][:m]
    if not directed:
        return np.concatenate([src, dst]), np.concatenate([dst, src])
    return src.astype(np.int64), dst.astype(np.int64)


def two_tier_social(n_core: int = 8, n_leaf_per_core: int = 4):
    """Directed test graph: a core ring + leaves pointing into their core node.

    Every leaf l of core c has edge (c -> l); ring edges (c -> c+1).  Known
    reachability structure for unit tests.
    """
    src, dst = [], []
    n = n_core * (1 + n_leaf_per_core)
    for c in range(n_core):
        src.append(c)
        dst.append((c + 1) % n_core)
        for j in range(n_leaf_per_core):
            leaf = n_core + c * n_leaf_per_core + j
            src.append(c)
            dst.append(leaf)
    return np.asarray(src, dtype=np.int64), np.asarray(dst, dtype=np.int64), n


def icosahedral_multimesh(refinement: int):
    """GraphCast-style icosphere multimesh.

    Returns (vertices (V,3) float32, src, dst) where the edge set is the
    union of the refined mesh edges at every level 0..refinement, both
    directions (GraphCast processor operates on the symmetric multimesh).
    """
    phi = (1.0 + np.sqrt(5.0)) / 2.0
    verts = np.asarray(
        [(-1, phi, 0), (1, phi, 0), (-1, -phi, 0), (1, -phi, 0),
         (0, -1, phi), (0, 1, phi), (0, -1, -phi), (0, 1, -phi),
         (phi, 0, -1), (phi, 0, 1), (-phi, 0, -1), (-phi, 0, 1)],
        dtype=np.float64,
    )
    verts /= np.linalg.norm(verts, axis=1, keepdims=True)
    faces = np.asarray(
        [(0, 11, 5), (0, 5, 1), (0, 1, 7), (0, 7, 10), (0, 10, 11),
         (1, 5, 9), (5, 11, 4), (11, 10, 2), (10, 7, 6), (7, 1, 8),
         (3, 9, 4), (3, 4, 2), (3, 2, 6), (3, 6, 8), (3, 8, 9),
         (4, 9, 5), (2, 4, 11), (6, 2, 10), (8, 6, 7), (9, 8, 1)],
        dtype=np.int64,
    )
    verts_list = [v for v in verts]
    all_edges = set()

    def face_edges(fs):
        e = set()
        for a, b, c in fs:
            for u, v in ((a, b), (b, c), (c, a)):
                e.add((min(u, v), max(u, v)))
        return e

    all_edges |= face_edges(faces)
    midpoint_cache: dict[tuple[int, int], int] = {}

    def midpoint(a: int, b: int) -> int:
        key = (min(a, b), max(a, b))
        if key in midpoint_cache:
            return midpoint_cache[key]
        mid = verts_list[a] + verts_list[b]
        mid /= np.linalg.norm(mid)
        verts_list.append(mid)
        idx = len(verts_list) - 1
        midpoint_cache[key] = idx
        return idx

    for _ in range(refinement):
        new_faces = []
        for a, b, c in faces:
            ab, bc, ca = midpoint(a, b), midpoint(b, c), midpoint(c, a)
            new_faces += [(a, ab, ca), (b, bc, ab), (c, ca, bc), (ab, bc, ca)]
        faces = np.asarray(new_faces, dtype=np.int64)
        all_edges |= face_edges(faces)

    und = np.asarray(sorted(all_edges), dtype=np.int64)
    src = np.concatenate([und[:, 0], und[:, 1]])
    dst = np.concatenate([und[:, 1], und[:, 0]])
    return np.asarray(verts_list, dtype=np.float32), src, dst
