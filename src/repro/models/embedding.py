"""Embedding lookup library for recsys-scale sparse tables.

JAX has no native EmbeddingBag or CSR sparse — per the assignment this IS
part of the system:

* :func:`embedding_bag` — gather (``jnp.take``) + ``jax.ops.segment_sum``
  (sum/mean modes) over a flat multi-hot id list with offsets-style segments.
* :func:`sharded_lookup` — mod/row-sharded tables: each device holds a
  contiguous row slice; lookup = masked local gather + ``psum`` over the
  table axis (DLRM-style model-parallel embeddings).  Used inside
  ``jax.shard_map``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def embedding_bag(table, ids, segment_ids, num_segments, *, mode="sum",
                  valid=None):
    """table (R, D); ids (N,) int32; segment_ids (N,) sorted int32.
    Returns (num_segments, D)."""
    vals = jnp.take(table, ids, axis=0)
    if valid is not None:
        vals = jnp.where(valid[:, None], vals, 0)
    out = jax.ops.segment_sum(vals, segment_ids, num_segments=num_segments,
                              indices_are_sorted=True)
    if mode == "mean":
        ones = (valid.astype(table.dtype) if valid is not None
                else jnp.ones(ids.shape[0], table.dtype))
        cnt = jax.ops.segment_sum(ones, segment_ids,
                                  num_segments=num_segments,
                                  indices_are_sorted=True)
        out = out / jnp.maximum(cnt, 1)[:, None]
    return out


def sharded_lookup(table_local, ids, axis_name: str):
    """Row-sharded lookup inside shard_map.

    table_local: (R/D, dim) this device's contiguous row slice;
    ids: (..., ) global row ids (replicated across the table axis).
    Returns (..., dim) — psum-combined; cost = one psum(batch·dim) per call.
    """
    shard = jax.lax.axis_index(axis_name)
    rows_local = table_local.shape[0]
    local = ids - shard * rows_local
    mine = (local >= 0) & (local < rows_local)
    vals = jnp.take(table_local, jnp.clip(local, 0, rows_local - 1), axis=0)
    vals = jnp.where(mine[..., None], vals, 0)
    return jax.lax.psum(vals, axis_name)
