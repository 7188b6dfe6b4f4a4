"""Queue-based RR-set engine — the gIM decomposition (paper Alg. 3/6), TPU-adapted.

Parallel decomposition (see DESIGN.md §2):

* gIM block  -> *lane*:    B RR sets sampled concurrently (vectorized batch dim)
* gIM warp   -> *chunk*:   the current node's CSR row is processed EC edges per
                           micro-step (EC=128 = VPU lane width; the paper's
                           ``for i = tx; i < deg; i += N_th`` loop, Alg. 3 L16)
* Q_shr+RR_tmp -> queue row: one fixed (Qcap,) row per lane.  In BFS the
  dequeued prefix *is* the RR set, so gIM's three structures (shared queue,
  reservoir, RR_tmp) collapse into one array + (head, tail) cursors.  Overflow
  (paper Alg. 4's reservoir trigger) is counted, not spilled: `overflowed`
  lanes are reported so callers can resample at larger Qcap (0 on all
  benchmark workloads at the default Qcap).
* Visited[n] byte array -> bit-packed (B, ceil(n/32)) uint32 (32x smaller).
* atomic_enqueue -> in-chunk left-pack (prefix-sum rank + log-step binary
  search gather) + one contiguous dynamic_update_slice per lane.  XLA:CPU
  lowers scatter to a serial per-update loop, so the former (B, EC) masked
  scatters dominated the micro-step; the packed append writes a contiguous
  window into an EC-padded queue row instead, and the visited-bit update
  scatters only the first ACCEPT_CAP packed columns (full-width fallback
  via lax.cond when a chunk accepts more — e.g. p=1.0 stress graphs).
* curand        -> threefry key folded per micro-step (replay-deterministic).

Intra-chunk duplicate hazard (paper §3.1): within one EC chunk the same
destination may appear on several edges (multi-edges).  Each *edge* must get an
independent Bernoulli trial, but the node must be enqueued at most once.  We
accept only the first successful occurrence per node per chunk
(:func:`_first_occurrence`, O(EC log EC) per lane): on the
destination-sorted rows :func:`repro.graph.csr.reverse` produces, duplicates
are adjacent and the check is a segmented prefix-OR in log-step shifts; on
arbitrary row order it falls back to a stable sort + neighbour-difference
scan.  The earlier implementation materialized a dense ``(B, EC, EC)``
first-occurrence mask — O(EC^2) work *and* memory per micro-step; both new
paths keep the accept set (and accepted positions) bit-identical.  This
composes with the visited-bit test-and-set across chunks.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph
from repro.core.packing import rank_positions
from repro.core.roots import draw_roots, roots_from_uniform

EC_DEFAULT = 128  # edge-chunk width (the paper's N_th=32, scaled to VPU lanes)


class QueueSample(NamedTuple):
    nodes: jnp.ndarray       # (B, Qcap) int32 — visit-order node ids per lane
    lengths: jnp.ndarray     # (B,) int32 — RR-set sizes
    roots: jnp.ndarray       # (B,) int32
    overflowed: jnp.ndarray  # (B,) bool — lane hit Qcap (RR set truncated)
    steps: jnp.ndarray       # () int32 — micro-steps executed


def _bit_test(words, nodes):
    """words: (B, W) uint32; nodes: (B, EC) int32 -> (B, EC) bool (bit set?)."""
    w = nodes >> 5
    b = (nodes & 31).astype(jnp.uint32)
    got = jnp.take_along_axis(words, w, axis=1)
    return ((got >> b) & jnp.uint32(1)) != 0


ACCEPT_CAP = 32  # fast-path width of the per-chunk enqueue (pack + scatter)


def detect_dedup_mode(g_rev: CSRGraph) -> str:
    """Host preprocessing (engines run it once at construction): which chunk
    dedup the sampler needs for this graph.

    * ``"none"`` — no duplicate (u, v) edges anywhere: within a chunk all
      destinations are distinct, so accept == candidate and the dedup
      disappears from the micro-step entirely (the common case).
    * ``"segmented"`` — multi-edges exist but rows are destination-sorted
      (the :func:`repro.graph.csr.reverse` layout): duplicates are adjacent
      and first-occurrence is a segmented prefix-OR.
    * ``"sort"`` — multi-edges on arbitrarily ordered rows: stable in-chunk
      sort.
    """
    from repro.graph.csr import rows_dst_sorted
    offs = np.asarray(g_rev.offsets, dtype=np.int64)
    idx = np.asarray(g_rev.indices, dtype=np.int64)
    if idx.size <= 1:
        return "none"
    if rows_dst_sorted(g_rev):
        eq = np.diff(idx) == 0
        inner = offs[1:-1]
        inner = inner[(inner > 0) & (inner < idx.size)]
        eq[inner - 1] = False                    # row boundaries don't count
        return "segmented" if eq.any() else "none"
    row_of = np.repeat(np.arange(len(offs) - 1), np.diff(offs))
    order = np.lexsort((idx, row_of))
    si, sr = idx[order], row_of[order]
    dup = (np.diff(si) == 0) & (np.diff(sr) == 0)
    return "sort" if dup.any() else "none"


def _first_occurrence(nbr, cand, arange_ec, *, mode: str):
    """accept[b, j]: is j the first chunk position among the lane's candidates
    carrying destination ``nbr[b, j]``?  (paper §3.1 duplicate hazard.)

    ``mode`` comes from :func:`detect_dedup_mode`.  ``"segmented"``:
    duplicates are adjacent (destination-sorted rows), so first-occurrence
    is a segmented prefix-OR over equal-value runs — O(EC log EC) per lane
    in log-step shifts, no sort, no gather.  ``"sort"``: stable sort of
    (destination, position) + neighbour-difference scan, also O(EC log EC).
    Every path is bit-identical to the dense (EC, EC) first-occurrence mask
    this replaces.
    """
    if mode == "none":
        return cand
    if mode == "segmented":
        runhead = jnp.concatenate(
            [jnp.ones_like(nbr[:, :1], dtype=bool),
             nbr[:, 1:] != nbr[:, :-1]], axis=1)
        # segmented inclusive prefix-OR of `cand` (Hillis-Steele)
        val, seg = cand, runhead
        d = 1
        ec = nbr.shape[1]
        while d < ec:
            val = val | (jnp.pad(val[:, :-d], ((0, 0), (d, 0))) & ~seg)
            seg = seg | jnp.pad(seg[:, :-d], ((0, 0), (d, 0)),
                                constant_values=True)
            d *= 2
        prev = jnp.pad(val[:, :-1], ((0, 0), (1, 0)))   # OR up to j-1
        return cand & (runhead | ~prev)
    if mode != "sort":
        raise ValueError(f"unknown dedup mode {mode!r}")
    sentinel = jnp.iinfo(jnp.int32).max
    key = jnp.where(cand, nbr, sentinel)
    pos = jnp.broadcast_to(arange_ec[None, :], nbr.shape)
    skey, spos = jax.lax.sort_key_val(key, pos, dimension=1, is_stable=True)
    first = jnp.concatenate(
        [jnp.ones_like(skey[:, :1], dtype=bool),
         skey[:, 1:] != skey[:, :-1]], axis=1)
    accept_sorted = first & (skey != sentinel)
    rows = jnp.arange(nbr.shape[0], dtype=jnp.int32)[:, None]
    return jnp.zeros_like(cand).at[rows, spos].set(accept_sorted)


def _pack_accepted(accept, nbr, n, ec, width):
    """Left-pack each lane's first ``width`` accepted destinations, order
    preserved.

    Returns packed (B, width) int32 — sentinel ``n`` beyond each lane's
    count.  The j-th accepted position is found by a vectorized binary
    search over the accept prefix sum (log EC gather steps), so the pack
    needs no scatter — XLA:CPU lowers scatter to a serial per-update loop,
    which made the old per-chunk scatters the dominant micro-step cost.
    """
    csum = jnp.cumsum(accept.astype(jnp.int32), axis=1)
    cnt = csum[:, -1]
    pos = jax.vmap(lambda c: rank_positions(c, width, ec))(csum)
    packed = jnp.take_along_axis(nbr, pos, axis=1)
    tgt = jnp.arange(1, width + 1, dtype=jnp.int32)[None, :]
    return jnp.where(tgt <= cnt[:, None], packed, n)


def _bits_write(visited, packed, n, n_words):
    """Set the visited bits of packed destinations (sentinel ``n`` rows are
    dropped).  Chunk-unique + previously-unseen nodes ⇒ all bits distinct ⇒
    scatter-add == scatter-or."""
    lane = jnp.arange(visited.shape[0], dtype=jnp.int32)
    valid = packed < n
    w = jnp.where(valid, packed >> 5, n_words)
    bit = jnp.where(
        valid,
        jnp.left_shift(jnp.uint32(1), (packed & 31).astype(jnp.uint32)),
        jnp.uint32(0))
    return visited.at[lane[:, None], w].add(bit, mode="drop")


def _rows_append(buf, packed, start):
    """Contiguous per-lane append: one dynamic_update_slice per lane instead
    of a scatter.  ``buf`` carries an EC-wide pad tail, so the slice window
    beyond a lane's accept count lands in scratch space that the next append
    (or the length mask) overwrites/ignores."""
    return jax.vmap(
        lambda row, upd, st: jax.lax.dynamic_update_slice(row, upd, (st,))
    )(buf, packed, start)


def _enqueue_chunk(buf, visited, accept, nbr, tail, cap, ec, n, n_words,
                   arange_ec):
    """The paper's atomic_enqueue (Alg. 3 L21) for one chunk: left-pack the
    accepted destinations, append them contiguously into each lane's row at
    ``tail``, and mark their visited bits.

    Fast path works at ACCEPT_CAP width — it covers every chunk whose
    accept count fits (the overwhelming case under sub-critical IC
    weights); a full-EC pass runs only when some lane accepted more (e.g.
    p=1.0 stress graphs), via ``lax.cond``.  Capacity: the first
    ``cap - tail`` accepted fit, exactly the old per-slot rule; the rest
    land in the pad tail and are dropped (overflow is flagged by the
    caller from the returned ``cnt``).

    Returns (buf, visited, cnt, take).
    """
    kacc = min(ACCEPT_CAP, ec)
    with jax.named_scope("rr.append"):
        cnt = accept.sum(axis=1, dtype=jnp.int32)
        take = jnp.minimum(cnt, jnp.maximum(cap - tail, 0))
        packed = _pack_accepted(accept, nbr, n, ec, ec)
        # buffer append is a cheap contiguous write — always full width,
        # and crucially NOT routed through lax.cond: conditionals break
        # XLA's in-place buffer aliasing and would copy the whole row
        # buffer per step
        buf = _rows_append(buf, packed, tail)
    with jax.named_scope("rr.visited_write"):
        vis_src = jnp.where(arange_ec[None, :] < take[:, None], packed, n)
        if kacc == ec:
            visited = _bits_write(visited, vis_src, n, n_words)
        else:
            # the visited scatter cost is per update entry, so cap its
            # width; only `visited` (small) crosses the cond boundary
            visited = jax.lax.cond(
                (cnt > kacc).any(),
                lambda v: _bits_write(v, vis_src, n, n_words),
                lambda v: _bits_write(v, vis_src[:, :kacc], n, n_words),
                visited)
    return buf, visited, cnt, take


@functools.partial(jax.jit,
                   static_argnames=("batch", "qcap", "ec", "n", "m",
                                    "dedup"))
def _sample_queue(key, offsets, indices, weights, roots, *,
                  batch, qcap, ec, n, m, dedup="sort"):
    n_words = (n + 31) // 32
    lane = jnp.arange(batch, dtype=jnp.int32)
    # EC-wide pad tail absorbs the contiguous-append slice windows
    queue = jnp.zeros((batch, qcap + ec), dtype=jnp.int32)
    queue = queue.at[:, 0].set(roots)
    visited = jnp.zeros((batch, n_words), dtype=jnp.uint32)
    visited = visited.at[lane, roots >> 5].set(
        jnp.left_shift(jnp.uint32(1), (roots & 31).astype(jnp.uint32)))
    # init derived from `roots` so device-varying types propagate when the
    # sampler runs inside shard_map (one lane batch per device)
    qhead = jnp.zeros_like(roots)
    qtail = jnp.ones_like(roots)
    ecur = jnp.zeros_like(roots)
    overflow = roots < 0
    arange_ec = jnp.arange(ec, dtype=jnp.int32)

    # every op of a micro-step sits in one ``rr.*`` named scope: the
    # scope lands in the op's metadata, which the device trace keeps
    def cond(st):
        _, _, qhead, qtail, _, _, _, _ = st
        with jax.named_scope("rr.advance"):
            return (qhead < qtail).any()

    def body(st):
        queue, visited, qhead, qtail, ecur, overflow, key, step = st
        with jax.named_scope("rr.edges"):
            active = qhead < qtail
            u = queue[lane, jnp.clip(qhead, 0, qcap - 1)]        # current node
            s = offsets[u]
            deg = offsets[u + 1] - s
            pos = ecur[:, None] + arange_ec[None, :]             # (B, EC)
            valid = (pos < deg[:, None]) & active[:, None]
            eidx = jnp.clip(s[:, None] + pos, 0, m - 1)
            nbr = indices[eidx]                                  # (B, EC)
            pw = weights[eidx]
        with jax.named_scope("rr.draw"):
            key, sub = jax.random.split(key)
            urand = jax.random.uniform(sub, (batch, ec))
            keep = (urand < pw) & valid                          # traversed
        with jax.named_scope("rr.visited_test"):
            unseen = ~_bit_test(visited, nbr)
            cand = keep & unseen
        with jax.named_scope("rr.dedup"):
            # first successful occurrence per destination within the chunk
            accept = _first_occurrence(nbr, cand, arange_ec, mode=dedup)
        queue, visited, cnt, take = _enqueue_chunk(
            queue, visited, accept, nbr, qtail, qcap, ec, n, n_words,
            arange_ec)
        with jax.named_scope("rr.advance"):
            overflow = overflow | (cnt > take)
            qtail = qtail + take
            # advance the edge cursor / pop the node (Alg. 3 L12)
            ecur2 = ecur + ec
            row_done = ecur2 >= deg
            qhead = jnp.where(active & row_done, qhead + 1, qhead)
            ecur = jnp.where(active & ~row_done, ecur2, 0)
            step = step + 1
        return queue, visited, qhead, qtail, ecur, overflow, key, step

    queue, visited, qhead, qtail, ecur, overflow, key, steps = (
        jax.lax.while_loop(cond, body,
                           (queue, visited, qhead, qtail, ecur, overflow, key,
                            jnp.int32(0))))
    return queue[:, :qcap], qtail, overflow, steps


@functools.partial(jax.jit,
                   static_argnames=("batch", "qcap", "ec", "n", "m",
                                    "dedup"))
def _queue_round(key, offsets, indices, weights, root_table, *, batch, qcap,
                 ec, n, m, dedup="sort"):
    """Root draw + queue BFS as ONE jit: every operand is a device array, so
    a round triggers no host↔device traffic (runs under
    ``jax.transfer_guard("disallow")``).  The key-split structure matches the
    historical host wrapper exactly, keeping sample streams bit-identical
    (``root_table=None`` -> the identical uniform randint)."""
    key, sub = jax.random.split(key)
    roots = draw_roots(sub, batch, n, root_table)
    nodes, lengths, overflowed, steps = _sample_queue(
        key, offsets, indices, weights, roots,
        batch=batch, qcap=qcap, ec=ec, n=n, m=m, dedup=dedup)
    return nodes, lengths, roots, overflowed, steps


def sample_rrsets_queue(key, g_rev: CSRGraph, batch: int, qcap: int,
                        ec: int = EC_DEFAULT,
                        dedup: str | None = None,
                        root_table=None) -> QueueSample:
    """Sample ``batch`` RR sets (one round) on the reverse CSR.

    ``dedup=None`` runs :func:`detect_dedup_mode` on the host once per call
    (engines cache the detection at construction).  ``root_table`` (an
    :class:`~repro.core.roots.AliasTable`) switches the root draw to
    weight-proportional sampling (weighted IM)."""
    n, m = g_rev.n_nodes, g_rev.n_edges
    if dedup is None:
        dedup = detect_dedup_mode(g_rev)
    nodes, lengths, roots, overflowed, steps = _queue_round(
        key, g_rev.offsets, g_rev.indices, g_rev.weights, root_table,
        batch=batch, qcap=qcap, ec=ec, n=n, m=m, dedup=dedup)
    return QueueSample(nodes=nodes, lengths=lengths, roots=roots,
                       overflowed=overflowed, steps=steps)


def to_lists(sample: QueueSample) -> list[list[int]]:
    nodes = np.asarray(sample.nodes)
    lens = np.asarray(sample.lengths)
    return [nodes[i, :lens[i]].tolist() for i in range(nodes.shape[0])]


# ---------------------------------------------------------------------------
# Persistent-lane ("refill") engine — the paper's Alg. 6 worker structure.
#
# The round-based sampler above retires a whole batch before starting new
# roots, so every lane waits for the round's largest RR set (measured lane
# utilization ~21% on WC/BA workloads — see EXPERIMENTS.md §Perf/IM).  Here
# a lane starts a new RR set the moment it finishes one, exactly like a gIM
# block looping "repeat ... until N_RR >= theta"; RR sets append into a flat
# per-lane output row (the paper's RR array + Offsets_RR).
# ---------------------------------------------------------------------------

class RefillSample(NamedTuple):
    flat: jnp.ndarray      # (B, OutCap) int32 — concatenated RR sets
    lengths: jnp.ndarray   # (B, sets_per_lane) int32 — per-set lengths
    n_done: jnp.ndarray    # (B,) int32 — completed sets per lane
    overflowed: jnp.ndarray  # (B,) bool — lane ran out of OutCap
    steps: jnp.ndarray     # () int32


@functools.partial(jax.jit,
                   static_argnames=("batch", "out_cap", "quota",
                                    "max_sets_per_lane", "ec", "n", "m",
                                    "dedup"))
def _sample_refill(key, offsets, indices, weights, roots0, root_table, *,
                   batch, out_cap, quota, max_sets_per_lane, ec, n, m,
                   dedup="sort"):
    n_words = (n + 31) // 32
    lane = jnp.arange(batch, dtype=jnp.int32)
    arange_ec = jnp.arange(ec, dtype=jnp.int32)
    sets_per_lane = max_sets_per_lane

    # EC-wide pad tail absorbs the contiguous-append slice windows
    out = jnp.zeros((batch, out_cap + ec), jnp.int32)
    out = out.at[:, 0].set(roots0)
    lengths = jnp.zeros((batch, sets_per_lane), jnp.int32)
    visited = jnp.zeros((batch, n_words), jnp.uint32)
    visited = visited.at[lane, roots0 >> 5].set(
        jnp.left_shift(jnp.uint32(1), (roots0 & 31).astype(jnp.uint32)))
    set_start = jnp.zeros_like(roots0)         # current set's base offset
    qhead = jnp.zeros_like(roots0)             # read head (relative)
    tail = jnp.ones_like(roots0)               # absolute write offset
    ecur = jnp.zeros_like(roots0)
    n_done = jnp.zeros_like(roots0)
    overflow = roots0 < 0
    in_set = roots0 >= 0            # lane currently building a set

    def cond(st):
        (_, _, _, _, _, _, _, _, overflow, in_set, _, _) = st
        return (in_set & ~overflow).any()

    def body(st):
        (out, lengths, visited, set_start, qhead, tail, ecur, n_done,
         overflow, in_set, key, step) = st
        working = (n_done < sets_per_lane) & ~overflow & in_set
        active = working & (set_start + qhead < tail)
        u = out[lane, jnp.clip(set_start + qhead, 0, out_cap - 1)]
        s = offsets[u]
        deg = offsets[u + 1] - s
        pos = ecur[:, None] + arange_ec[None, :]
        valid = (pos < deg[:, None]) & active[:, None]
        eidx = jnp.clip(s[:, None] + pos, 0, m - 1)
        nbr = indices[eidx]
        pw = weights[eidx]
        # ONE uniform draw per micro-step: EC edge trials + 1 refill-root
        # column per lane (a second split+randint per step costs a whole
        # extra threefry dispatch)
        key, sub = jax.random.split(key)
        urand = jax.random.uniform(sub, (batch, ec + 1))
        keep = (urand[:, :ec] < pw) & valid
        unseen = ~_bit_test(visited, nbr)
        cand = keep & unseen
        accept = _first_occurrence(nbr, cand, arange_ec, mode=dedup)
        out, visited, cnt, take = _enqueue_chunk(
            out, visited, accept, nbr, tail, out_cap, ec, n, n_words,
            arange_ec)
        overflow = overflow | (cnt > take)
        tail = tail + take
        ecur2 = ecur + ec
        row_done = ecur2 >= deg
        qhead = jnp.where(active & row_done, qhead + 1, qhead)
        ecur = jnp.where(active & ~row_done, ecur2, 0)
        # --- lane refill: set finished when the read head catches the tail
        finished = working & (set_start + qhead >= tail)
        in_set = in_set & ~finished
        set_len = tail - set_start
        lengths = lengths.at[
            lane, jnp.where(finished, jnp.clip(n_done, 0, sets_per_lane - 1),
                            sets_per_lane)].set(set_len, mode="drop")
        n_done = n_done + finished.astype(jnp.int32)
        # global quota race (gIM Alg. 6: blocks loop until N_RR >= theta);
        # in-flight sets always complete (no size-biased discarding),
        # lanes just stop *starting* once the global count is met
        quota_open = n_done.sum() < quota
        more = finished & (n_done < sets_per_lane) & quota_open
        # room check for the new root
        has_room = tail < out_cap
        overflow = overflow | (more & ~has_room)
        start_new = more & has_room
        # refill roots from the step's spare uniform column: uniform when
        # root_table is None (bit-identical to the historical floor(u*n)),
        # weight-proportional through the alias table otherwise
        new_roots = roots_from_uniform(urand[:, ec], n, root_table)
        # clear this lane's visited set and seed the new root
        visited = jnp.where(start_new[:, None], jnp.uint32(0), visited)
        visited = visited.at[
            lane, jnp.where(start_new, new_roots >> 5, n_words)].add(
            jnp.where(start_new,
                      jnp.left_shift(jnp.uint32(1),
                                     (new_roots & 31).astype(jnp.uint32)),
                      jnp.uint32(0)), mode="drop")
        out = out.at[lane, jnp.where(start_new, tail, out_cap + ec)].set(
            new_roots, mode="drop")
        set_start = jnp.where(start_new, tail, set_start)
        qhead = jnp.where(start_new, 0, qhead)
        ecur = jnp.where(start_new, 0, ecur)
        tail = tail + start_new.astype(jnp.int32)
        in_set = in_set | start_new
        return (out, lengths, visited, set_start, qhead, tail, ecur,
                n_done, overflow, in_set, key, step + 1)

    st = (out, lengths, visited, set_start, qhead, tail, ecur, n_done,
          overflow, in_set, key, jnp.int32(0))
    (out, lengths, visited, set_start, qhead, tail, ecur, n_done, overflow,
     in_set, key, steps) = jax.lax.while_loop(cond, body, st)
    return out[:, :out_cap], lengths, n_done, overflow, steps


@functools.partial(jax.jit,
                   static_argnames=("batch", "out_cap", "quota",
                                    "max_sets_per_lane", "ec", "n", "m",
                                    "dedup"))
def _refill_round(key, offsets, indices, weights, root_table, *, batch,
                  out_cap, quota, max_sets_per_lane, ec, n, m, dedup="sort"):
    """Root draw + persistent-lane worker as ONE jit (see ``_queue_round``)."""
    key, sub = jax.random.split(key)
    roots = draw_roots(sub, batch, n, root_table)
    return _sample_refill(
        key, offsets, indices, weights, roots, root_table,
        batch=batch, out_cap=out_cap, quota=quota,
        max_sets_per_lane=max_sets_per_lane, ec=ec, n=n, m=m, dedup=dedup)


def sample_rrsets_refill(key, g_rev: CSRGraph, batch: int,
                         quota: int, out_cap: int,
                         max_sets_per_lane: int | None = None,
                         ec: int = EC_DEFAULT,
                         dedup: str | None = None,
                         root_table=None) -> RefillSample:
    """Persistent-lane sampling with a global quota: lanes refill with new
    roots until >= ``quota`` RR sets are complete across all lanes (the
    paper's Alg. 6 worker loop); in-flight sets always finish (unbiased)."""
    n, m = g_rev.n_nodes, g_rev.n_edges
    if max_sets_per_lane is None:
        max_sets_per_lane = max(4 * quota // batch + 4, 4)
    if dedup is None:
        dedup = detect_dedup_mode(g_rev)
    flat, lengths, n_done, overflow, steps = _refill_round(
        key, g_rev.offsets, g_rev.indices, g_rev.weights, root_table,
        batch=batch, out_cap=out_cap, quota=quota,
        max_sets_per_lane=max_sets_per_lane, ec=ec, n=n, m=m, dedup=dedup)
    return RefillSample(flat=flat, lengths=lengths, n_done=n_done,
                        overflowed=overflow, steps=steps)


def refill_to_lists(sample: RefillSample) -> list[list[int]]:
    flat = np.asarray(sample.flat)
    lengths = np.asarray(sample.lengths)
    n_done = np.asarray(sample.n_done)
    out = []
    for b in range(flat.shape[0]):
        off = 0
        for i in range(int(n_done[b])):
            ln = int(lengths[b, i])
            out.append(flat[b, off:off + ln].tolist())
            off += ln
    return out


@jax.jit
def refill_to_padded_device(flat, lengths, n_done):
    """Device-resident unpack of a RefillSample into fixed-shape padded rows.

    (B, OutCap), (B, S), (B,) -> rows (B*S, OutCap) + lengths (B*S,).  Unlike
    :func:`refill_to_padded` the row count is *static* (every lane slot
    becomes a row); slots beyond a lane's ``n_done`` come back with length 0
    — padding rows carrying no RR set, dropped by the device store's
    compaction.  This keeps the solver's per-round shapes stable and the
    whole unpack on device (no host round-trip, no recompiles).
    """
    b, s = lengths.shape
    out_cap = flat.shape[1]
    set_valid = jnp.arange(s, dtype=n_done.dtype)[None, :] < n_done[:, None]
    lens = jnp.where(set_valid, lengths, 0)
    starts = jnp.concatenate(
        [jnp.zeros((b, 1), lengths.dtype),
         jnp.cumsum(lengths, axis=1)[:, :-1]], axis=1)
    idx = starts[:, :, None] + jnp.arange(out_cap, dtype=starts.dtype)[
        None, None, :]
    rows = jnp.take_along_axis(flat[:, None, :],
                               jnp.clip(idx, 0, out_cap - 1), axis=2)
    col_valid = jnp.arange(out_cap)[None, None, :] < lens[:, :, None]
    rows = jnp.where(col_valid, rows, 0)
    return rows.reshape(b * s, out_cap), lens.reshape(b * s)


def refill_to_padded(sample: RefillSample):
    """Vectorized unpack of a RefillSample into (nodes (R, W), lengths (R,)).

    R = total completed sets across lanes, W = max set size.  Sets are laid
    out contiguously per lane (root first), so per-set start offsets are an
    exclusive prefix sum of the recorded lengths; one broadcast gather plus a
    validity mask replaces the per-set python slicing loop.
    """
    flat = np.asarray(sample.flat)
    lengths = np.asarray(sample.lengths, np.int64)    # (B, S)
    n_done = np.asarray(sample.n_done, np.int64)      # (B,)
    b, s = lengths.shape
    set_valid = np.arange(s)[None, :] < n_done[:, None]
    if not set_valid.any():
        return np.zeros((0, 1), np.int64), np.zeros(0, np.int64)
    starts = np.concatenate(
        [np.zeros((b, 1), np.int64), lengths.cumsum(axis=1)[:, :-1]], axis=1)
    width = max(int(lengths[set_valid].max()), 1)
    idx = starts[:, :, None] + np.arange(width, dtype=np.int64)[None, None, :]
    rows = np.take_along_axis(flat[:, None, :],
                              np.clip(idx, 0, flat.shape[1] - 1), axis=2)
    col_valid = np.arange(width)[None, None, :] < lengths[:, :, None]
    rows = np.where(col_valid, rows, 0).reshape(b * s, width)
    keep = set_valid.reshape(b * s)
    return rows[keep].astype(np.int64), lengths[set_valid]
