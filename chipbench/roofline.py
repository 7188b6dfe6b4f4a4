"""The least memory traffic of reverse IC sampling, from the graph and the
RR sets drawn.

Expanding a node in the reverse BFS reads its two CSR offsets (8 B) and,
for each distinct in-neighbour, the neighbour's id and the edge's
probability (8 B).  Summed over every node of every RR set drawn, that is
the same work whatever sampler draws the sets.
"""
from __future__ import annotations

import numpy as np

from chipbench.graphs import HostGraph

OFFSET_BYTES = 8     # two int32 offsets per expanded node
EDGE_BYTES = 8       # int32 neighbour id + float32 probability per in-edge


def distinct_in_degree(fwd: HostGraph) -> np.ndarray:
    """In-degree of every node counting parallel edges once."""
    src = np.repeat(np.arange(fwd.n, dtype=np.int64), np.diff(fwd.offsets))
    key = np.unique(fwd.indices.astype(np.int64) * fwd.n + src)
    return np.bincount(key // fwd.n, minlength=fwd.n)


def bfs_bytes(nodes: np.ndarray, in_degree: np.ndarray) -> int:
    """Bytes the reverse BFS must read to expand ``nodes`` (every element
    of the RR sets drawn)."""
    nodes = np.asarray(nodes, np.int64)
    return int(nodes.size * OFFSET_BYTES
               + EDGE_BYTES * in_degree[nodes].sum())
