"""Pallas kernel for the bottom-k / one-permutation coverage sketches.

The sketch subsystem (``core/sketch.py``) summarises, for every node v, the
set of RR rows containing v as a k-bit hashed occupancy bitmap packed into
``k/32`` uint32 words — the same packed-bitset layout the Visited structures
use (``kernels/bitset.py``).

:func:`sketch_union_popcount` computes per-node ``popcount(sketch[v] |
covered)``, the inner product of the CELF sketch estimate: the
union-cardinality proxy for ``|rows(v) ∪ rows(S)|`` evaluated for *all*
nodes in one popcount sweep.  The sketch matrix is (R, k/32) with k/32
narrow, which XLA lays out node-minor; the kernel reads it through the
free transposed (k/32, R) view, so each block is a (k/32, BB) tile whose
per-node sums are a sublane reduction landing lane-major in the output.

The sketch *fold* (scatter-OR of (row, bucket) pairs) has no kernel: the
sort-dedup fold ``core/sketch.scatter_or_bits`` is the one path on every
backend.  The matching ``popcount(covered)`` baseline is one
:func:`_popcount` call on a (W,) vector — not worth a kernel.  Estimation
(linear counting) happens in ``core/sketch.py``; the kernel only produces
occupancy counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitset import _popcount


def _union_popcount_kernel(words_ref, cov_ref, out_ref):
    words = words_ref[...]                        # (W, BB) uint32, node-minor
    cov = cov_ref[...]                            # (W, 1) uint32, replicated
    out_ref[...] = _popcount(words | cov).astype(jnp.int32).sum(
        axis=0, keepdims=True)


def _resolve(interpret: bool | None) -> bool:
    # defer to the shared kernel dispatch policy (per-call > module override
    # > env > backend default).  Resolution happens *here*, outside the
    # jitted implementations: ``interpret`` is a static argname, so the
    # concrete bool is the jit cache key — a later env/override change gets
    # a fresh resolution instead of a stale cached trace.  Lazy import: ops
    # imports this module back (lazily) for its public wrappers.
    from repro.kernels.ops import resolve_interpret
    return resolve_interpret(interpret)


def sketch_union_popcount(words, cov, *, block_b: int = 2048,
                          interpret: bool | None = None):
    """``out[v] = popcount(words[v] | cov)`` for every sketch row.

    ``words``: (R, W) uint32 packed per-node sketches; ``cov``: (W,) uint32
    packed union sketch of the selected seed set.  Returns (R,) int32 —
    the occupancy of each candidate union, from which the CELF path derives
    estimated marginal coverage (see ``core/sketch.py``).

    ``interpret=None`` (default) defers to ``ops.resolve_interpret`` like
    every other kernel: interpret mode on CPU, compiled Mosaic on an
    accelerator backend.
    """
    return _union_popcount(words, cov, block_b=block_b,
                           interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _union_popcount(words, cov, *, block_b: int, interpret: bool):
    r, w = words.shape
    if cov.shape != (w,):
        raise ValueError("cov must be a (W,) vector matching the sketch "
                         "word width")
    bb = min(block_b, r)
    return pl.pallas_call(
        _union_popcount_kernel,
        grid=(pl.cdiv(r, bb),),
        in_specs=[pl.BlockSpec((w, bb), lambda i: (0, i)),
                  pl.BlockSpec((w, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, bb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, r), jnp.int32),
        interpret=interpret,
    )(words.T, cov.reshape(w, 1)).reshape(r)
