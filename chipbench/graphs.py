"""The benchmark's own graph data: a Barabasi-Albert stand-in with
weighted-cascade probabilities, made on the host from the configuration's
fixed ``graph_seed``: a deployment is one graph, as a dataset is one file.

The generator and the weighting are copies, kept here so that a change to
the program cannot change the graph the benchmark measures.  The graph
enters the program through ``repro.graph.csr.from_edges``.  A generated
graph is cached under ``chipbench/.cache/graphs/``, keyed by its recipe.
"""
from __future__ import annotations

import os
from typing import NamedTuple

import numpy as np

CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")


class HostGraph(NamedTuple):
    """Forward CSR on the host: ``offsets[u]:offsets[u+1]`` are u's
    out-edges, ``weights`` the IC probability of each edge."""
    offsets: np.ndarray   # (n+1,) int64
    indices: np.ndarray   # (m,) int32
    weights: np.ndarray   # (m,) float32

    @property
    def n(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def m(self) -> int:
        return self.indices.shape[0]


def barabasi_albert(n: int, r: int, seed: int):
    """Undirected BA preferential attachment, both directions as directed
    edges: node t draws its r picks from the repeated-endpoint pool as it
    stands before t (a copy of the program's generator)."""
    if r < 1 or n <= r:
        raise ValueError("need n > r >= 1")
    rng = np.random.default_rng(seed)
    r0 = r + 1
    init_src, init_dst = np.triu_indices(r0, k=1)
    pool0 = np.concatenate([init_src, init_dst]).astype(np.int64)
    p0, m = pool0.shape[0], n - r0
    t = np.arange(m, dtype=np.int64)
    pos = (rng.random((m, r)) * (p0 + 2 * r * t)[:, None]).astype(np.int64)
    pos = np.minimum(pos, (p0 + 2 * r * t - 1)[:, None]).ravel()
    off = pos - p0
    owner, j = off // (2 * r), off % (2 * r)
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


def wc_graph(src, dst, n: int) -> HostGraph:
    """Forward CSR grouped by source (stable), p_uv = 1/indeg(v)."""
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    indeg = np.bincount(dst, minlength=n).astype(np.float64)
    w = (1.0 / indeg[dst]).astype(np.float32)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=offsets[1:])
    return HostGraph(offsets, dst.astype(np.int32), w)


def make(config: dict) -> HostGraph:
    """The configuration's graph, from the cache when it is there."""
    if config["generator"] != "barabasi_albert" or config["weights"] != "wc":
        raise ValueError(f"unknown graph recipe in {config['name']!r}")
    n, r = int(config["n_nodes"]), int(config["ba_r"])
    seed = int(config["graph_seed"])
    path = os.path.join(CACHE, "graphs", f"{config['name']}-n{n}-r{r}-"
                        f"s{seed}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return HostGraph(z["offsets"], z["indices"], z["weights"])
    g = wc_graph(*barabasi_albert(n, r, seed), n)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, offsets=g.offsets, indices=g.indices, weights=g.weights)
    os.replace(tmp, path)
    return g


def to_program(g: HostGraph):
    """The program's ``CSRGraph`` of ``g``, placed on the default device."""
    from repro.graph import csr
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.offsets))
    return csr.from_edges(src, g.indices, g.n, weights=g.weights, sort=False)


def reverse(g: HostGraph) -> HostGraph:
    """Transpose: edge (u, v, p) becomes (v, u, p)."""
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.offsets))
    order = np.argsort(g.indices, kind="stable")
    offsets = np.zeros(g.n + 1, np.int64)
    np.cumsum(np.bincount(g.indices, minlength=g.n), out=offsets[1:])
    return HostGraph(offsets, src[order].astype(np.int32),
                     g.weights[order])
