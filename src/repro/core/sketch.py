"""Sketch-based coverage estimation (Cohen et al., sketch-based IM).

The fused Alg. 7 greedy recomputes marginal coverage over the *full* RR pool
every round — O(elements) per seed.  A bottom-k-style sketch answers the
same "how many uncovered RR rows does candidate v hit?" question from a
fixed-size summary:

For every node v we keep a **hashed one-permutation occupancy sketch**: a
k-bucket bitmap where bucket ``h(row_id) mod k`` is set iff some RR row
containing v hashed there.  Unions are bitwise OR, cardinality proxies are
popcounts — exactly the packed-bitset plumbing of ``kernels/bitset.py``, so
the per-candidate union estimate over all n nodes is one Pallas popcount
sweep (``kernels/sketch.py``).

The occupancy is maintained **directly as packed uint32 words** — an
(R, k/32) uint32 matrix, never an (R, k) bool one.  Scatter-OR into packed
words is not a plain scatter (two bits landing in one word must combine,
and a bit already present must not carry), so the fold
(:func:`scatter_or_bits`) lexsorts the batch's (row, bucket) pairs, keeps
first occurrences, masks bits already set in the live words, and commits
the survivors with one scatter-*add* — which at that point is exactly
scatter-OR.  This is the ~8× sketch-memory cut over the historical bool
occupancy (deleted), and the one fold on every backend.

Properties the sketch readers (``coverage.select_seeds_sketch`` in
approximate mode, the θ early exit, deadline-degraded results) rely on:

* **Lower bound** — new occupied buckets require new rows, so
  ``Δocc(v | S) = occ(sketch_v | sketch_S) − occ(sketch_S)`` never exceeds
  the exact marginal coverage of v: summed Δocc gains certify a lower
  bound on the rows a seed set covers.
* **Exact-safe regime** — with the default ``"mod"`` bucketing
  (``bucket = row_id % k``) the map is injective while ``n_rr <= k``, so
  Δocc *equals* the exact marginal gain and the sketch greedy returns the
  exact greedy's seeds.  Past k rows the sketch degrades gracefully into a
  uniform hash (sequential row ids stride the buckets perfectly).
* **Incremental** — ``ShardedDeviceRRStore.append_batch`` folds each batch
  into the packed words with one jit'd sort+scatter (O(batch elements
  · log), no rebuild); on a multi-device mesh the fold runs replicated
  (every device folds the identical full batch — cheaper than any
  cross-device OR of sketch deltas, see DESIGN.md §5).

Cardinality estimation for consumers that want absolute counts (benchmarks,
tests) is classic linear counting: ``n̂ = k · ln(k / (k − occ))``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PACK_WINDOW, row_window
from repro.kernels.bitset import _popcount


def resolve_sketch_k(k: int) -> int:
    """Round the bucket count up to a whole number of uint32 words."""
    if k <= 0:
        raise ValueError("sketch_k must be positive")
    return ((k + 31) // 32) * 32


def bucket_of(row_ids, k: int, mode: str = "mod"):
    """Bucket index of each RR row id (jit-traceable).

    ``"mod"`` — identity modulo k: injective (exact) while ids < k, a
    perfect stride afterwards.  ``"mix"`` — Knuth multiplicative hash then
    modulo, for adversarial id patterns.
    """
    rid = row_ids.astype(jnp.uint32)
    if mode == "mix":
        rid = rid * jnp.uint32(2654435761)
    elif mode != "mod":
        raise ValueError(f"unknown sketch hash mode {mode!r}")
    return (rid % jnp.uint32(k)).astype(jnp.int32)


def scatter_or_bits(words, v, b):
    """Scatter-OR bucket bits into packed words: ``words[v] |= 1 << b``.

    ``words`` (R, W) uint32, ``v``/``b`` (E,) int32 flat (row, bucket)
    pairs; entries with ``v >= R`` are dropped (sentinels).  Duplicate
    pairs and bits already present are handled exactly: pairs are lexsorted
    and deduplicated, surviving bits are masked against the current words
    (one gather), and the remainder — now provably absent and pairwise
    distinct — commits via scatter-add, which equals scatter-OR on disjoint
    bits.  O(E log E) work, no bool buffer of any size.
    """
    n_rows = words.shape[0]
    order = jnp.lexsort((b, v))
    vs, bs = v[order], b[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (vs[1:] != vs[:-1]) | (bs[1:] != bs[:-1])])
    wi = bs >> 5
    bit = jnp.uint32(1) << (bs & 31).astype(jnp.uint32)
    cur = words[jnp.clip(vs, 0, n_rows - 1), jnp.clip(wi, 0, words.shape[1] - 1)]
    new = jnp.where(first & (vs < n_rows) & ((cur & bit) == 0),
                    bit, jnp.uint32(0))
    return words.at[vs, wi].add(new, mode="drop")


def fold_batch_packed(words, nodes, lens, row_base, *, k, mode):
    """Fold one padded batch into the packed (R, k/32) occupancy words.

    ``row_base`` is the pool's *global* row count before this batch (device
    scalar), so bucketing matches the canonical batch-order row numbering
    regardless of how the pool itself is sharded.  Rows with length 0 are
    padding and contribute nothing.  Plain traceable function — the store
    jits it directly (single device) or per shard inside ``shard_map``
    (every device folds the identical replicated batch).
    """
    row_valid = lens.astype(jnp.int32) > 0
    rid = row_base + jnp.cumsum(row_valid, dtype=jnp.int32) - 1
    return fold_frontier_rows(words, nodes, lens, rid, k=k, mode=mode)


def fold_frontier_rows(words, nodes, lens, row_ids, *, k, mode):
    """:func:`fold_batch_packed` with caller-supplied per-row global RR ids
    — the pool-free hot path (``mode="approximate"``), where sharded
    callers number rows over the full batch before slicing their block.
    Rows with length 0 are padding.

    The batch's elements are read window by window through its row offsets
    (:func:`~repro.core.packing.row_window`), so work and memory follow the
    elements, not R·W.  A pair that a later window folds again finds its
    bit already set, so the windows give the one-shot fold's words bit for
    bit.
    """
    r, w = nodes.shape
    if r * w == 0:
        return words
    n_rows = words.shape[0]
    win = min(PACK_WINDOW, r * w)
    lens = jnp.minimum(jnp.maximum(lens.astype(jnp.int32), 0), w)
    ends = jnp.cumsum(lens, dtype=jnp.int32)
    bucket = bucket_of(row_ids, k, mode)
    slot = jnp.arange(win, dtype=jnp.int32)

    def window(i, words):
        node, row, is_elem = row_window(nodes, lens, ends, i * win + slot)
        v = jnp.where(is_elem, node.astype(jnp.int32), n_rows)
        return scatter_or_bits(words, v, bucket[row])

    return jax.lax.fori_loop(0, (ends[-1] + win - 1) // win, window, words)


def flat_to_packed_bits(flat, ids, valid, *, n_rows, k, mode):
    """(flat pool → (v, b) pairs) for :func:`scatter_or_bits`."""
    b = bucket_of(ids, k, mode)
    v = jnp.where(valid, flat.astype(jnp.int32), n_rows)
    return v, b


@functools.partial(jax.jit, static_argnames=("n_rows", "k", "mode"))
def sketch_packed_from_flat(flat, ids, valid, *, n_rows, k, mode):
    """Build packed (n_rows, k/32) occupancy words from an existing flat
    pool (stores created without an incremental sketch).

    Also the windowed-eviction rebuild path (DESIGN.md §9.3):
    ``ShardedDeviceRRStore._rewrite`` re-derives the sketch from the
    surviving flat pool with shard-major renumbered row ids.  Bucketing
    reads only row ids — never pool positions — so any injective
    renumbering composes bit-identically with later ``append_batch``
    folds (pinned by the sketch-rebuild conformance test).
    """
    v, b = flat_to_packed_bits(flat, ids, valid, n_rows=n_rows, k=k,
                               mode=mode)
    return scatter_or_bits(jnp.zeros((n_rows, k // 32), jnp.uint32), v, b)


@functools.partial(jax.jit, static_argnames=("n", "k", "mode"))
def sketch_from_flat(flat, ids, valid, *, n, k, mode):
    """Bool (n+1, k) occupancy from a flat pool — the PR-3 reference fold.

    Kept as the *test oracle* for the packed-word fold (the property suite
    asserts ``pack_sketch(sketch_from_flat(...)) == packed fold`` bit for
    bit); no production path materializes this buffer anymore.
    """
    b = bucket_of(ids, k, mode)
    v = jnp.where(valid, flat, n + 1)            # OOB -> dropped
    return jnp.zeros((n + 1, k), bool).at[v, b].set(True, mode="drop")


def pack_sketch(occ, *, words):
    """(R, k) bool occupancy -> (R, k/32) uint32 packed words, via the
    Pallas ``pack_bits`` kernel (same LSB-first bit order as the Covered
    bitset and the Visited structures)."""
    from repro.kernels import ops as kops
    if occ.shape[1] != words * 32:
        raise ValueError("occupancy width must be words * 32")
    return kops.pack_bits(occ)


@jax.jit
def union_row(cov_words, sk_words, u):
    """``cov | sketch[u]`` — fold one selected seed into the union sketch."""
    return cov_words | sk_words[u]


@jax.jit
def _minus_base(union_occ, cov_words):
    return union_occ - _popcount(cov_words).sum(dtype=jnp.int32)


def _union_popcount_rows(rows, cov_words):
    """``popcount(rows[v] | cov)`` per row, SWAR-vectorised — the interpret
    fallback for the union-popcount kernel.  Under interpret mode the Pallas
    per-block loop degrades to full-array copies, so the sweep runs this
    elementwise form instead; integer arithmetic makes it bit-identical to
    the kernel output.
    """
    u = rows | cov_words[None, :]
    return _popcount(u).astype(jnp.int32).sum(axis=1, dtype=jnp.int32)


def union_gains(sk_words, cov_words, *, interpret=None):
    """Estimated marginal occupancy Δocc(v | S) for every node, in one
    kernel sweep: ``popcount(sketch[v] | cov) − popcount(cov)``.

    Returns a device (R,) int32 vector (R = sketch rows; callers slice off
    the sentinel row).  Δocc is a certified lower bound on the exact
    marginal coverage (see module docstring).  ``interpret`` picks the
    algorithm (kernel vs SWAR fallback); jitted callers must resolve it
    outside their trace (it picks the algorithm, so a baked-in stale
    choice would survive jit caching).
    """
    from repro.kernels import ops as kops
    if kops.resolve_interpret(interpret):
        return _minus_base(_union_popcount_rows(sk_words, cov_words),
                           cov_words)
    return _minus_base(
        kops.sketch_union_popcount(sk_words, cov_words, interpret=False),
        cov_words)


def union_gains_stripe(sk_words, cov_words, stripe_start, stripe_rows: int,
                       *, interpret=None):
    """Δocc for one contiguous stripe of sketch rows — the shard-local body
    of the mesh-parallel sweep (each device scores its stripe of candidates
    against its sketch replica; a psum of the disjoint stripes yields the
    full replicated vector).  On compiled backends the stripe runs through
    the Pallas union-popcount kernel, so the mesh=1 sweep is exactly the
    historical single-device kernel sweep; under interpret mode it takes
    the bit-identical SWAR fallback (see :func:`_union_popcount_rows`).
    """
    from repro.kernels import ops as kops
    rows = jax.lax.dynamic_slice(
        sk_words, (stripe_start, 0), (stripe_rows, sk_words.shape[1]))
    if kops.resolve_interpret(interpret):
        occ = _union_popcount_rows(rows, cov_words)
    else:
        occ = kops.sketch_union_popcount(rows, cov_words, interpret=False)
    return occ - _popcount(cov_words).sum(dtype=jnp.int32)


def linear_count(occupied, k: int):
    """Linear-counting cardinality estimate from bucket occupancy.

    Exact while the bucketing is injective (``occupied`` distinct rows all
    landed in distinct buckets); otherwise ``k·ln(k/(k−occ))`` corrects for
    collisions (capped at full occupancy).
    """
    occ = np.asarray(occupied, dtype=np.float64)
    occ = np.clip(occ, 0.0, k - 1.0)
    est = k * np.log(k / (k - occ))
    return np.where(np.asarray(occupied) >= k, k * np.log(k), est)


def linear_count_saturated(occupied, k: int):
    """:func:`linear_count` plus a per-entry ``saturated`` flag.

    A fully-occupied row (``occ >= k``) carries no cardinality information
    beyond "at least ~k·ln(k)": the raw formula diverges, so the estimate is
    clamped to that ceiling and flagged.  Consumers that surface estimates
    to users (approximate-mode selection, ``IMResult.spread_bounds``) MUST
    widen their upper bound on saturation instead of reporting the clamp as
    a finite estimate.
    """
    sat = np.asarray(occupied) >= k
    return linear_count(occupied, k), sat


def linear_count_rel_error(est, k: int, *, z: float = 3.0):
    """Certified relative standard-error bound of the linear-counting
    estimate, scaled to ``z`` standard deviations.

    Whang et al.: with load ``t = n/k``, the estimator's relative StdErr is
    ``sqrt(e^t − t − 1) / (t · sqrt(k))`` (asymptotically normal), so a
    z-sigma relative bound is ``z ×`` that.  ``est`` is used as the plug-in
    for n.  Saturated rows (``t`` at the ln(k) ceiling) get whatever the
    formula yields there — callers widen separately via the flag.
    """
    t = np.maximum(np.asarray(est, dtype=np.float64) / k, 1e-9)
    se = np.sqrt(np.maximum(np.expm1(t) - t, 0.0)) / (t * np.sqrt(k))
    return z * se


def auto_sketch_k(eps: float, n: int, *, z: float = 3.0) -> int:
    """Bucket count sized so the certified z-sigma relative error of the
    linear-counting estimate stays within ``eps/2`` at moderate load.

    At the design load ``t = 1`` (n ≈ k rows folded per bucket row) the
    relative StdErr coefficient is ``c = sqrt(e − 2)``; solving
    ``z·c/sqrt(k) <= eps/2`` gives ``k >= (2·z·c/eps)²``.  Clamped to
    ``[64, n]`` (below 64 the normal approximation is junk; above n the
    sketch would outweigh an exact Occur) and rounded to whole uint32
    words.  Higher loads degrade gracefully — the *reported* bound on
    ``spread_bounds`` always uses the realized load via
    :func:`linear_count_rel_error`, never this design point.
    """
    if not (0.0 < eps < 1.0):
        raise ValueError("eps must lie in (0, 1)")
    c = math.sqrt(math.e - 2.0)
    k = math.ceil((2.0 * z * c / eps) ** 2)
    k = max(64, min(k, max(int(n), 64)))
    return resolve_sketch_k(k)
