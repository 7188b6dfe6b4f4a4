"""Plain references for the comparison that decides ``correct``.

Straightforward numpy, independent of the program: a reverse BFS that
draws IC RR sets, the exact greedy maximum coverage (Alg. 1 lines 6-10,
ties to the lowest node id), and a check that a pool row is a
reverse-reachable set of its root.  They read only the benchmark's own
graph and what the program returned.
"""
from __future__ import annotations

import numpy as np

from chipbench.graphs import HostGraph


def rr_set_ic(rev: HostGraph, root: int, rng: np.random.Generator):
    """One RR set under IC: randomized BFS on the reverse graph from
    ``root``; the visit order, root first."""
    visited = {root}
    queue = [root]
    head = 0
    while head < len(queue):
        u = queue[head]
        head += 1
        s, e = rev.offsets[u], rev.offsets[u + 1]
        if e > s:
            keep = rng.random(e - s) < rev.weights[s:e]
            for v in rev.indices[s:e][keep].tolist():
                if v not in visited:
                    visited.add(v)
                    queue.append(v)
    return queue


def ris_share(rev: HostGraph, nodes, count: int, seed: int) -> float:
    """Share of ``count`` fresh reference RR sets (uniform roots) that
    contain one of ``nodes``: the RIS estimate of their spread over n."""
    rng = np.random.default_rng(seed)
    hit_set = set(int(s) for s in nodes)
    hits = 0
    for root in rng.integers(0, rev.n, size=count).tolist():
        hits += not hit_set.isdisjoint(rr_set_ic(rev, root, rng))
    return hits / count


def hubs(fwd: HostGraph, count: int) -> np.ndarray:
    """The ``count`` nodes of highest out-degree (ties to the lowest id): a
    probe set fixed by the graph alone, so that its share of a pool is an
    unbiased estimate of its spread over n."""
    return np.argsort(-np.diff(fwd.offsets), kind="stable")[:count]


class Pool:
    """A program's pool fetched to the host: ``flat`` holds the nodes of
    every row, ``ids`` the row of each element (non-decreasing)."""

    def __init__(self, flat, ids, n_rows: int, n: int):
        self.flat = np.asarray(flat, np.int64)
        self.ids = np.asarray(ids, np.int64)
        self.n_rows, self.n = int(n_rows), int(n)
        counts = np.bincount(self.ids, minlength=self.n_rows)
        self.row_ptr = np.zeros(self.n_rows + 1, np.int64)
        np.cumsum(counts[:self.n_rows], out=self.row_ptr[1:])
        inside = (self.flat >= 0) & (self.flat < self.n)
        self._node = np.where(inside, self.flat, self.n)
        order = np.argsort(self._node, kind="stable")
        self._rows_by_node = self.ids[order]
        self.node_ptr = np.zeros(self.n + 2, np.int64)
        np.cumsum(np.bincount(self._node, minlength=self.n + 1),
                  out=self.node_ptr[1:])

    def rows_hit(self, nodes) -> int:
        """Rows that contain one of ``nodes``."""
        return int(np.unique(self.ids[np.isin(self.flat, nodes)]).size)

    def greedy(self, k: int, cand=None):
        """Exact greedy: ``cand=None`` is the plain scan (argmax over every
        node, a picked node may come again with gain 0); with a candidate
        mask a node is picked once, only among the candidates, and the scan
        stops when none is left.  Returns (seeds, gains)."""
        occur = np.bincount(self._node, minlength=self.n + 1)[:self.n]
        covered = np.zeros(self.n_rows, bool)
        feas = None if cand is None else np.asarray(cand, bool).copy()
        seeds, gains = [], []
        for _ in range(k):
            if feas is None:
                u = int(np.argmax(occur))
            else:
                masked = np.where(feas, occur, -1)
                u = int(np.argmax(masked))
                if masked[u] < 0:
                    break
                feas[u] = False
            rows = self._rows_by_node[self.node_ptr[u]:self.node_ptr[u + 1]]
            rows = np.unique(rows[~covered[rows]])
            covered[rows] = True
            seeds.append(u)
            gains.append(int(rows.size))
            if rows.size:
                starts, ends = self.row_ptr[rows], self.row_ptr[rows + 1]
                lens = ends - starts
                idx = np.repeat(starts - np.cumsum(lens) + lens, lens) \
                    + np.arange(lens.sum())
                occur -= np.bincount(self._node[idx],
                                     minlength=self.n + 1)[:self.n]
        return seeds, gains

    def bad_rows(self, fwd: HostGraph) -> int:
        """Rows that are not a reverse-reachable set of their first node:
        an empty row, a node out of range or twice in a row, or a node
        after the first with no forward edge to a node before it in the
        row (the reverse BFS reached it through such an edge)."""
        n = self.n
        bad = np.zeros(self.n_rows, bool)
        bad[np.diff(self.row_ptr) == 0] = True
        if self.flat.size == 0:
            return int(bad.sum())
        rows = self.ids
        pos = np.arange(self.flat.size) - self.row_ptr[rows]
        out_of_range = self._node >= n
        bad[rows[out_of_range]] = True
        key = rows * (n + 1) + self._node
        order = np.argsort(key, kind="stable")
        dup = np.zeros(key.size, bool)
        dup[order[1:]] = key[order[1:]] == key[order[:-1]]
        bad[rows[dup]] = True
        # each later element needs an out-edge into an earlier element
        later = np.flatnonzero((pos > 0) & ~out_of_range)
        v = self._node[later]
        starts, ends = fwd.offsets[v], fwd.offsets[v + 1]
        lens = ends - starts
        owner = np.repeat(later, lens)
        nbr = fwd.indices[np.repeat(starts - np.cumsum(lens) + lens, lens)
                          + np.arange(lens.sum())].astype(np.int64)
        skey = key[order]
        want = rows[owner] * (n + 1) + nbr
        at = np.minimum(np.searchsorted(skey, want), skey.size - 1)
        found = skey[at] == want
        parent_ok = found & (pos[order[at]] < pos[owner])
        reached = np.zeros(self.flat.size, bool)
        reached[owner[parent_ok]] = True
        orphan = later[~reached[later]]
        bad[rows[orphan]] = True
        return int(bad.sum())
