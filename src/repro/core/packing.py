"""Vectorized padded-row packing shared by the engine adapters.

``pack_rows`` is the host (numpy) variant with a data-dependent output
width; ``pack_rows_device`` is its jit-safe twin with a *static* width (the
mask's column count), used on the device-resident engine paths where shape
stability matters more than trailing padding.  Low-level (imports nothing
from core) so both the samplers and the engine layer can use it without
cycles.
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


def rank_positions(csum, width: int, size: int):
    """Positions of the 1st..``width``-th set elements of a flat mask, given
    its inclusive prefix sum ``csum`` (length ``size``).

    Vectorized lower-bound binary search — log(size) gather steps, no
    scatter (XLA:CPU lowers scatter to a serial per-update loop).  Entries
    beyond the true count converge to ``size - 1``; callers mask by count.
    Used by the device store's packed append (coverage).
    """
    tgt = jnp.arange(1, width + 1, dtype=jnp.int32)
    lo = jnp.zeros((width,), jnp.int32)
    hi = jnp.full((width,), size - 1, jnp.int32)
    for _ in range(max(int(np.ceil(np.log2(max(size, 2)))), 1)):
        mid = (lo + hi) >> 1
        go_right = csum[mid] < tgt
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return jnp.clip(lo, 0, size - 1)


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
