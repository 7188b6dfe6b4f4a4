"""Vectorized padded-row packing shared by the engine adapters.

``pack_rows`` is the host (numpy) variant with a data-dependent output
width; ``pack_rows_device`` is its jit-safe twin with a *static* width (the
mask's column count), used on the device-resident engine paths where shape
stability matters more than trailing padding.  Low-level (imports nothing
from core) so both the samplers and the engine layer can use it without
cycles.  ``row_window`` is the device pool's append and the sketch fold's
element pack: a window of a batch's packed elements, read through the
batch's row offsets.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def pack_rows(values: np.ndarray, mask: np.ndarray):
    """Left-compact masked elements of each row into a padded matrix.

    values, mask: (B, C).  Returns (rows (B, W), lengths (B,)) where W is the
    max per-row count; column order is preserved.  Fully vectorized: rank =
    prefix count of the mask, then one scatter.
    """
    mask = np.asarray(mask, bool)
    values = np.asarray(values)
    lens = mask.sum(axis=1).astype(np.int64)
    width = max(int(lens.max()) if lens.size else 0, 1)
    out = np.zeros((mask.shape[0], width), values.dtype)
    rank = mask.cumsum(axis=1) - 1
    r, c = np.nonzero(mask)
    out[r, rank[r, c]] = values[r, c]
    return out, lens


PACK_WINDOW = 1 << 15   # elements per window of a row-offset pack


def row_window(nodes, lens, ends, j):
    """Slots ``j`` of the row-major left-pack of a padded batch, found from
    its row offsets alone: no (R, W) mask, prefix sum or scatter.

    ``nodes`` (R, W), ``lens`` (R,) clamped to ``[0, W]``, ``ends`` their
    inclusive prefix sum (row r's elements are ranks ``ends[r] - lens[r]``
    to ``ends[r] - 1``), ``j`` element ranks.  Slot j's row is the first
    whose end passes j (a binary search over the R ends), and its node is
    ``nodes[row, j - start[row]]`` (one gather).  Returns (values, row,
    is_elem), ``is_elem`` false where j is not a rank of the batch; values
    and rows there are in range and meaningless.  Work and memory are
    O(len(j) · log R), whatever W is.
    """
    r, w = nodes.shape
    row = jnp.clip(jnp.searchsorted(ends, j, side="right"), 0, r - 1)
    col = jnp.clip(j - (ends[row] - lens[row]), 0, w - 1)
    is_elem = (j >= 0) & (j < ends[-1])
    return nodes[row, col], row, is_elem


def pack_rows_device(values, mask):
    """jnp twin of :func:`pack_rows` (traceable, device-resident).

    Output width is static (= ``mask.shape[1]``); rows are left-compacted in
    column order, the tail is zero padding.  Returns (rows (B, C), lengths
    (B,) int32).
    """
    b, c = mask.shape
    lens = mask.sum(axis=1, dtype=jnp.int32)
    rank = jnp.cumsum(mask, axis=1) - 1
    dest = jnp.where(mask, rank, c)                  # OOB -> dropped
    rows_idx = jnp.arange(b)[:, None]
    out = jnp.zeros((b, c), values.dtype).at[rows_idx, dest].set(
        values, mode="drop")
    return out, lens
