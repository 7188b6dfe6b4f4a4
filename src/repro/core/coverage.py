"""Greedy max-coverage seed selection (paper Alg. 1 L6-10 / Alg. 7), TPU-adapted.

RR sets are stored exactly like the paper's memory-optimized layout (Alg. 6):
one flat concatenated array ``rr_flat`` plus ``rr_offsets`` (CSR-of-RR).  For
vectorized processing we carry ``rr_ids`` = the row id of every flat element
(the inverse of Offsets_RR), so the Alg. 7 kernel becomes:

  argmax(Occur)                 -> jnp.argmax of the psum-reduced histogram
  per-RR membership scan of u   -> equality scan + segment_max by rr_ids
  Covered flag + decrement      -> mask + segment scatter-sub on Occur

The pool itself is *mesh-resident* (:class:`ShardedDeviceRRStore`): the flat
buffers carry a leading shard dimension equal to the device-mesh size and
stay sharded over the ``samples`` axis — each device keeps the rows it was
dealt, rr_ids are **local**, and appends are per-shard jit'd windowed
writes, driven by the batch's row offsets, into donated doubling buffers.
The selection scan — one exact greedy for plain and variant problems
alike — runs as a ``shard_map`` over the same sharded views:
Occur is psum-reduced, argmax is replicated math, coverage updates stay
local — per seed the only collective is one ``psum(n)`` (plus one scalar
psum for the gain).  A single device is simply the mesh=1 special case of
the same code path; there is no separate single-device implementation.

The per-node coverage sketch is maintained **as packed uint32 words**
(``core/sketch.py``), replicated across the mesh: every device folds the
identical full batch into its replica (cheaper than any cross-device OR of
sketch deltas — see DESIGN.md §5), and the sketch sweep (approximate
mode, the θ early exit, degraded results) scores a disjoint stripe of
candidates per device, combined by one psum.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import sketch as sketch_mod
from repro.core import spans
from repro.core.packing import PACK_WINDOW, row_window
from repro.kernels import ops as kops
from repro.kernels.bitset import _popcount

# replication checking off: the pool programs run while_loops and Pallas
# calls whose outputs carry no varying-axis annotation
_shard_map = functools.partial(jax.shard_map, check_vma=False)


def _ceil_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


class RRStore(NamedTuple):
    """CSR-of-RR.  ``rr_flat[rr_offsets[i]:rr_offsets[i+1]]`` is RR set i."""
    rr_flat: jnp.ndarray     # (T,) int32 node ids (padded tail = n, masked out)
    rr_ids: jnp.ndarray      # (T,) int32 row id per element
    valid: jnp.ndarray       # (T,) bool
    n_rr: int                # number of RR sets
    n_nodes: int


def _compact_padded(nodes, lens, base: int = 0):
    """(B, W) padded rows + lengths -> (flat elements, row ids + base), the
    CSR-of-RR compaction shared by ``build_store`` and the incremental
    store (paper Alg. 6 lines 4-11, vectorized).

    Lengths are clamped to ``[0, W]`` exactly like the device append path
    (:func:`_append_rows_local`): an overflowed lane may report its true
    pre-truncation length while ``nodes`` only materializes ``W`` columns —
    without the clamp the element count (masked by width) and the row-id
    count (repeated by raw length) drift apart and the host mirror
    diverges from the device store.
    """
    nodes = np.asarray(nodes)
    lens = np.clip(np.asarray(lens, dtype=np.int64), 0, nodes.shape[1])
    mask = np.arange(nodes.shape[1])[None, :] < lens[:, None]
    flat = nodes[mask].astype(np.int64)
    ids = np.repeat(np.arange(len(lens), dtype=np.int64) + base, lens)
    return flat, ids, lens


def build_store(rr_lists_or_arrays, n: int, pad_to: int | None = None) -> RRStore:
    """Host-side compaction (paper Alg. 6 lines 4-11)."""
    if isinstance(rr_lists_or_arrays, list):
        lens = np.asarray([len(r) for r in rr_lists_or_arrays], dtype=np.int64)
        flat = (np.concatenate([np.asarray(r, dtype=np.int64)
                                for r in rr_lists_or_arrays])
                if lens.sum() else np.zeros(0, np.int64))
        ids = np.repeat(np.arange(len(lens), dtype=np.int64), lens)
    else:  # (nodes (B, Q), lengths (B,)) padded arrays from the samplers
        flat, ids, lens = _compact_padded(*rr_lists_or_arrays)
    t = flat.shape[0]
    t_pad = pad_to if pad_to is not None else t
    if t_pad < t:
        raise ValueError("pad_to smaller than payload")
    valid = np.zeros(t_pad, bool); valid[:t] = True
    flat = np.concatenate([flat, np.full(t_pad - t, n, np.int64)])
    ids = np.concatenate([ids, np.full(t_pad - t, len(lens), np.int64)])
    return RRStore(rr_flat=jnp.asarray(flat, jnp.int32),
                   rr_ids=jnp.asarray(ids, jnp.int32),
                   valid=jnp.asarray(valid),
                   n_rr=int(len(lens)), n_nodes=n)


class IncrementalRRStore:
    """Growing CSR-of-RR with amortized-O(1)-per-element ``append_batch``.

    The Alg. 2 LB loop selects seeds after every θ_i escalation; rebuilding
    the store from the per-round pool each time is O(rounds · T) host work
    per selection (O(rounds²) over the loop).  Here each round's batch is
    compacted exactly once into doubling flat/ids buffers, and ``snapshot``
    returns a cached device-resident :class:`RRStore` view (invalidated only
    by the next append).
    """

    def __init__(self, n_nodes: int, capacity: int = 1024):
        self.n_nodes = n_nodes
        self._flat = np.empty(max(capacity, 1), np.int64)
        self._ids = np.empty(max(capacity, 1), np.int64)
        self._t = 0
        self._n_rr = 0
        self._cache: RRStore | None = None

    @property
    def n_rr(self) -> int:
        return self._n_rr

    def _reserve(self, extra: int):
        need = self._t + extra
        if need <= self._flat.shape[0]:
            return
        cap = self._flat.shape[0]
        while cap < need:
            cap *= 2
        for name in ("_flat", "_ids"):
            buf = np.empty(cap, np.int64)
            buf[:self._t] = getattr(self, name)[:self._t]
            setattr(self, name, buf)

    def append_batch(self, batch) -> None:
        """Append one engine batch: an ``RRBatch`` or a ``(nodes, lengths)``
        pair of padded arrays (the ``build_store`` array form).  Rows with
        length 0 are *padding rows* (no RR set — fixed-shape device engine
        paths emit them) and are dropped: they get no row id and do not count
        toward ``n_rr``."""
        nodes, lens = (batch.nodes, batch.lengths) if hasattr(batch, "nodes") \
            else batch
        flat, ids, lens = _compact_padded(nodes, lens)
        row_rank = np.cumsum(lens > 0) - 1           # compact out empty rows
        self._reserve(flat.shape[0])
        self._flat[self._t:self._t + flat.shape[0]] = flat
        self._ids[self._t:self._t + flat.shape[0]] = \
            self._n_rr + row_rank[ids]
        self._t += flat.shape[0]
        self._n_rr += int((lens > 0).sum())
        self._cache = None

    def snapshot(self) -> RRStore:
        if self._cache is None:
            self._cache = RRStore(
                rr_flat=jnp.asarray(self._flat[:self._t], jnp.int32),
                rr_ids=jnp.asarray(self._ids[:self._t], jnp.int32),
                valid=jnp.ones(self._t, bool),
                n_rr=self._n_rr, n_nodes=self.n_nodes)
        return self._cache


# ---------------------------------------------------------------------------
# Mesh-sharded device-resident RR pool (paper §3.5 layout × DiFuseR sharding).
# ---------------------------------------------------------------------------

def _default_mesh() -> Mesh:
    """The mesh=1 special case: a single-device mesh over the default
    device.  Single-device execution is *not* a separate code path — it is
    this mesh driving the same shard_map programs with psum over one shard."""
    return Mesh(np.asarray(jax.devices()[:1]), ("samples",))


@functools.partial(jax.jit, static_argnames=("pad", "n"))
def _pad_batch_rows(nodes, lens, *, pad, n):
    """Append ``pad`` zero-length sentinel rows so the batch divides the
    shard count (jitted: ``jnp.full`` outside jit commits the fill scalar
    host->device and trips the transfer guard)."""
    w = nodes.shape[1]
    return (jnp.concatenate([nodes, jnp.full((pad, w), n, nodes.dtype)]),
            jnp.concatenate([lens, jnp.zeros((pad,), lens.dtype)]))


@functools.partial(jax.jit, static_argnames=("pad",))
def _pad_row_weights(roww, *, pad):
    """Zero-weight sentinel rows matching :func:`_pad_batch_rows`."""
    return jnp.concatenate([roww, jnp.zeros((pad,), roww.dtype)])


@functools.partial(jax.jit, static_argnames=("d", "width"))
def _shard_counts(lens, *, d, width):
    """Per-shard (elements, valid rows) of one padded batch: (D, 2) int32."""
    l = jnp.minimum(jnp.maximum(lens.astype(jnp.int32), 0), width)
    l = l.reshape(d, -1)
    return jnp.stack([l.sum(axis=1, dtype=jnp.int32),
                      (l > 0).sum(axis=1, dtype=jnp.int32)], axis=1)


def _append_rows_local(flat, ids, valid, t, n_rr, nodes, lens, *, pack, n,
                       ew=None, wsum=None, roww=None):
    """Append one padded batch into one shard's live buffers, driven by its
    R row lengths: the device engines' rows are mostly padding, so work and
    memory follow the elements drawn, never R·W.

    The batch's elements are packed in row-major order (rows stay
    contiguous, as in the host compaction) through the row offsets
    (:func:`~repro.core.packing.row_window`: a search over the R row ends,
    then one gather) into ``pack``-wide windows, each written with a
    contiguous ``dynamic_update_slice``; a batch with more elements takes
    consecutive windows in a loop.  Rows with length 0 are padding and
    receive no row id; row ids are shard-*local*.  Slots past the batch's
    element count get the virgin-buffer values (sentinel/0/False), which
    the next append overwrites.  A window that would pass the buffer's end
    starts earlier instead and keeps the slots before ``t`` as they are, so
    any capacity that holds ``t`` plus the elements will do.

    Row-weighted stores pass ``ew``/``wsum``/``roww`` (all three or none):
    the row weight lands on every element of its row (weighted Occur is
    then one scatter-add of ``ew``) and the shard's total valid-row weight
    accumulates into ``wsum`` (the weighted F_R denominator).
    """
    cap = flat.shape[0]
    win = min(pack, cap)
    with jax.named_scope("store.pack"):
        lens = jnp.minimum(jnp.maximum(lens.astype(jnp.int32), 0),
                           nodes.shape[1])
        ends = jnp.cumsum(lens, dtype=jnp.int32)
        total = ends[-1]
        row_valid = lens > 0
        rid = n_rr + jnp.cumsum(row_valid, dtype=jnp.int32) - 1
        slot = jnp.arange(win, dtype=jnp.int32)
        bufs = (flat, ids, valid) + (() if ew is None else (ew,))
        virgin = (n, 0, False, 0.0)

        def window(i, bufs):
            at = jnp.minimum(t + i * win, cap - win)
            j = at - t + slot                        # element rank per slot
            node, row, is_elem = row_window(nodes, lens, ends, j)
            new = (node.astype(jnp.int32), rid[row], is_elem) + (
                () if ew is None else (roww[row].astype(jnp.float32),))
            return tuple(
                jax.lax.dynamic_update_slice(b, jnp.where(
                    j < 0, jax.lax.dynamic_slice(b, (at,), (win,)),
                    jnp.where(is_elem, x, jnp.asarray(v, b.dtype))), (at,))
                for b, x, v in zip(bufs, new, virgin))

        bufs = jax.lax.fori_loop(0, (total + win - 1) // win, window, bufs)
    t_out = t + total
    nrr_out = n_rr + row_valid.sum(dtype=jnp.int32)
    if ew is None:
        return bufs + (t_out, nrr_out)
    flat, ids, valid, ew = bufs
    wsum = wsum + jnp.where(row_valid, roww.astype(jnp.float32),
                            0.0).sum(dtype=jnp.float32)
    return flat, ids, valid, ew, t_out, nrr_out, wsum


@functools.lru_cache(maxsize=None)
def _mesh_store_fns(mesh: Mesh):
    """Per-mesh jitted shard_map programs for the pool (append/grow/sketch).

    Cached on the mesh so every store on the same mesh shares one jit cache
    (shapes recompile only at capacity doublings, as before).
    """
    ax = mesh.axis_names[0]
    buf, vec, b3 = P(ax, None), P(ax), P(ax, None, None)

    def _by_shard(nodes, lens, *rest):
        # (R, W) -> (D, R/D, W) inside the program: on one device a bitcast,
        # where a reshape outside a jit would copy the whole batch
        d = mesh.devices.size
        return (nodes.reshape(d, -1, nodes.shape[1]), lens.reshape(d, -1),
                *(x.reshape(d, -1) for x in rest))

    @functools.partial(jax.jit, static_argnames=("pack", "n"),
                       donate_argnums=(0, 1, 2, 3, 4))
    def append_rows(flat, ids, valid, t, nrr, nodes, lens, *, pack, n):
        def local(flat, ids, valid, t, nrr, nodes, lens):
            out = _append_rows_local(flat[0], ids[0], valid[0], t[0], nrr[0],
                                     nodes[0], lens[0], pack=pack, n=n)
            return tuple(x[None] for x in out)
        return _shard_map(
            local, mesh=mesh,
            in_specs=(buf, buf, buf, vec, vec, b3, buf),
            out_specs=(buf, buf, buf, vec, vec))(
            flat, ids, valid, t, nrr, *_by_shard(nodes, lens))

    @functools.partial(jax.jit, static_argnames=("pack", "n"),
                       donate_argnums=(0, 1, 2, 3, 4, 5, 6))
    def append_rows_w(flat, ids, valid, ew, t, nrr, wsum, nodes, lens,
                      roww, *, pack, n):
        def local(flat, ids, valid, ew, t, nrr, wsum, nodes, lens, roww):
            out = _append_rows_local(flat[0], ids[0], valid[0], t[0], nrr[0],
                                     nodes[0], lens[0], pack=pack, n=n,
                                     ew=ew[0], wsum=wsum[0], roww=roww[0])
            return tuple(x[None] for x in out)
        return _shard_map(
            local, mesh=mesh,
            in_specs=(buf, buf, buf, buf, vec, vec, vec, b3, buf, buf),
            out_specs=(buf, buf, buf, buf, vec, vec, vec))(
            flat, ids, valid, ew, t, nrr, wsum,
            *_by_shard(nodes, lens, roww))

    @functools.partial(jax.jit, static_argnames=("newcap",))
    def grow_ew(ew, *, newcap):
        def local(e):
            pad = newcap - e.shape[1]
            return jnp.concatenate(
                [e, jnp.zeros((1, pad), jnp.float32)], 1)
        return _shard_map(
            local, mesh=mesh, in_specs=(buf,), out_specs=buf)(ew)

    @functools.partial(jax.jit, static_argnames=("newcap", "n"))
    def grow(flat, ids, valid, *, newcap, n):
        # no donation: the outputs are larger than the inputs, so aliasing
        # is impossible — growth is the one amortized O(cap) device copy
        def local(f, i, v):
            pad = newcap - f.shape[1]
            return (jnp.concatenate([f, jnp.full((1, pad), n, jnp.int32)], 1),
                    jnp.concatenate([i, jnp.zeros((1, pad), jnp.int32)], 1),
                    jnp.concatenate([v, jnp.zeros((1, pad), bool)], 1))
        return _shard_map(
            local, mesh=mesh, in_specs=(buf, buf, buf),
            out_specs=(buf, buf, buf))(flat, ids, valid)

    @functools.partial(jax.jit, static_argnames=("k", "mode"),
                       donate_argnums=(0,))
    def sketch_fold(sk, nodes, lens, base, *, k, mode):
        # replication beats sharding for the fold: every device folds the
        # identical full batch into its replica — zero collectives, and the
        # packed fold is O(batch · log batch) regardless of sketch size
        def local(sk, nodes, lens, base):
            return sketch_mod.fold_batch_packed(
                sk[0], nodes, lens, base, k=k, mode=mode)[None]
        return _shard_map(
            local, mesh=mesh, in_specs=(b3, P(), P(), P()),
            out_specs=b3)(sk, nodes, lens, base)

    @functools.partial(jax.jit, static_argnames=("n_rows", "k", "mode"))
    def sketch_from_pool(flat, ids, valid, *, n_rows, k, mode):
        # on-demand sketch for stores built without an incremental one:
        # per-shard partial fold by *local* row ids (collisions across
        # shards only cost precision, never soundness — Δocc stays a lower
        # bound), combined into identical replicas by one psum-OR
        # (all_gather + OR-reduce over the shard axis)
        def local(flat, ids, valid):
            v, b = sketch_mod.flat_to_packed_bits(
                flat[0], ids[0], valid[0], n_rows=n_rows, k=k, mode=mode)
            part = sketch_mod.scatter_or_bits(
                jnp.zeros((n_rows, k // 32), jnp.uint32), v, b)
            g = jax.lax.all_gather(part, ax)
            return jax.lax.reduce(g, jnp.uint32(0),
                                  jax.lax.bitwise_or, (0,))[None]
        return _shard_map(
            local, mesh=mesh, in_specs=(buf, buf, buf),
            out_specs=b3)(flat, ids, valid)

    class Fns:
        pass

    fns = Fns()
    fns.append_rows = append_rows
    fns.append_rows_w = append_rows_w
    fns.grow = grow
    fns.grow_ew = grow_ew
    fns.sketch_fold = sketch_fold
    fns.sketch_from_pool = sketch_from_pool
    return fns


class ShardedDeviceRRStore:
    """Growing CSR-of-RR pool sharded over a device mesh (DESIGN.md §5).

    The flat pool (``flat``/``ids``/``valid``) carries a leading shard
    dimension equal to the mesh size and is sharded over the ``samples``
    axis: each device keeps the rows it was dealt, row ids are *local*, and
    ``append_batch`` is one jit'd ``shard_map`` write per shard (windows
    packed through the batch's row offsets) into donated doubling buffers
    (amortized O(1) growth, like the paper's Alg. 6 pool but per device).
    Batches are dealt to shards in contiguous row blocks; a batch that is
    already sharded on the same mesh (a sharded engine's
    ``sample_sharded``) is re-laid-out by one explicit ``device_put`` with
    no host round-trip.

    The per-node coverage sketch is maintained **directly as packed uint32
    words** — (D, n_pad, k/32), a replica per shard, folded by every device
    from the identical replicated batch with canonical *global* (batch
    order) row numbering.  No (n+1, k) bool occupancy buffer exists on the
    append path (the ~8× sketch-memory cut of the ROADMAP).

    Host knowledge: exact per-shard element/row counts are mirrored on the
    host via one *explicit* (D, 2) scalar fetch per append — the same
    per-relaunch ``N_RR`` readback gIM's Alg. 6 host loop performs, and the
    only host↔device traffic an append causes.  Explicit transfers are
    permitted under ``jax.transfer_guard("disallow")``, which the IMM
    driver holds over the whole sampling+selection loop — on a mesh of any
    size.

    ``DeviceRRStore`` (the historical single-device pool) is this class on
    a 1-device mesh: shard_map over one shard, psum over one device.
    """

    DEFAULT_SKETCH_K = 1024

    def __init__(self, n_nodes: int, capacity: int = 4096,
                 sketch_k: int | None = None, sketch_mode: str = "mod",
                 mesh: Mesh | None = None, row_weighted: bool = False):
        if n_nodes >= np.iinfo(np.int32).max:
            raise ValueError("item space must fit int32")
        self.n_nodes = n_nodes
        self.row_weighted = row_weighted
        self.mesh = mesh if mesh is not None else _default_mesh()
        self.axis = self.mesh.axis_names[0]
        self.n_shards = d = int(self.mesh.devices.size)
        self._sh_buf = NamedSharding(self.mesh, P(self.axis, None))
        self._sh_vec = NamedSharding(self.mesh, P(self.axis))
        self._sh_b3 = NamedSharding(self.mesh, P(self.axis, None, None))
        self._sh_rep = NamedSharding(self.mesh, P())
        cap = _ceil_pow2(max(-(-capacity // d), 1))
        self._flat = jax.device_put(
            np.full((d, cap), n_nodes, np.int32), self._sh_buf)
        self._ids = jax.device_put(np.zeros((d, cap), np.int32), self._sh_buf)
        self._valid = jax.device_put(np.zeros((d, cap), bool), self._sh_buf)
        self._t_dev = jax.device_put(np.zeros(d, np.int32), self._sh_vec)
        self._nrr_dev = jax.device_put(np.zeros(d, np.int32), self._sh_vec)
        self._t_loc = np.zeros(d, np.int64)      # host mirrors (exact)
        self._nrr_loc = np.zeros(d, np.int64)
        # weighted rows (weighted IM, importance-weighted estimator): ew is
        # the per-*element* row weight (weighted Occur = one scatter-add of
        # ew), _w_dev the per-shard total valid-row weight (the weighted
        # F_R denominator, psum'd at selection)
        self._ew = (jax.device_put(np.zeros((d, cap), np.float32),
                                   self._sh_buf) if row_weighted else None)
        self._w_dev = (jax.device_put(np.zeros(d, np.float32), self._sh_vec)
                       if row_weighted else None)
        self._cache: RRStore | None = None
        self.sketch_mode = sketch_mode
        self.sketch_k = (sketch_mod.resolve_sketch_k(sketch_k)
                         if sketch_k is not None else None)
        # sketch rows padded to a multiple of the shard count so the
        # sketch sweep can stripe candidates evenly across devices
        self.sketch_rows = -(-(n_nodes + 1) // d) * d
        self._sk_words = (jax.device_put(
            np.zeros((d, self.sketch_rows, self.sketch_k // 32), np.uint32),
            self._sh_b3) if self.sketch_k is not None else None)
        self._sk_cache = None            # on-demand (no incremental sketch)
        # optional pre-allocation gate, called (store, newcap) before any
        # growth allocation; may raise PoolAllocError (fault policy / real
        # memory-budget enforcement).  append_batch stays un-mutated until
        # every allocation has passed this gate, so a refused growth is
        # retryable (DESIGN.md §8).
        self.alloc_check = None
        # per-append ("sampling round") row/element watermarks, one (D,)
        # int64 vector each — the granularity windowed eviction drops at
        # (oldest round first; DESIGN.md §9)
        self._round_rows: list[np.ndarray] = []
        self._round_elems: list[np.ndarray] = []
        self._fns = _mesh_store_fns(self.mesh)

    # -- sizes -------------------------------------------------------------
    @property
    def n_rr(self) -> int:
        return int(self._nrr_loc.sum())

    @property
    def n_elems(self) -> int:
        return int(self._t_loc.sum())

    @property
    def capacity(self) -> int:
        """Per-shard element capacity."""
        return int(self._flat.shape[1])

    @property
    def n_rr_dev(self):
        """Per-shard row counts as a sharded (D,) device vector (selection
        psums it for the F_R denominator under the guard)."""
        return self._nrr_dev

    @property
    def n_rounds(self) -> int:
        """Sampling rounds (appends) still represented in the pool — the
        windowed-eviction granularity."""
        return len(self._round_rows)

    def per_device_pool_bytes(self) -> int:
        """Live pool bytes on each device: flat + ids + valid buffers
        (+ the element-weight buffer on row-weighted stores)."""
        return self.capacity * (4 + 4 + 1 + (4 if self.row_weighted else 0))

    def sketch_bytes(self) -> int:
        """Per-replica packed sketch bytes (0 without an incremental
        sketch).  The deleted bool occupancy would be 8× this."""
        if self._sk_words is None:
            return 0
        return self.sketch_rows * (self.sketch_k // 32) * 4

    # -- append ------------------------------------------------------------
    def append_batch(self, batch, row_w=None) -> None:
        """Compact one batch (``RRBatch`` or ``(nodes, lengths)``) into the
        sharded pool.  Zero-length rows are padding (fixed-shape device
        engine paths emit them) and are dropped.  Rows are dealt to shards
        in contiguous blocks; the tail shard absorbs the divisibility
        padding.

        ``row_w`` — (R,) per-row weights, required on ``row_weighted``
        stores (ignored entries on padding rows): the weight lands on every
        element of the row (``ew``), making weighted Occur one scatter-add.
        """
        nodes, lens = (batch.nodes, batch.lengths) if hasattr(batch, "nodes") \
            else batch
        nodes = jnp.asarray(nodes)
        lens = jnp.asarray(lens)
        if nodes.ndim != 2 or lens.shape != (nodes.shape[0],):
            raise ValueError("append_batch wants padded (R, W) nodes + (R,) "
                             "lengths")
        if self.row_weighted:
            if row_w is None:
                raise ValueError("row_weighted store needs row_w= per append")
            roww = jnp.asarray(row_w, jnp.float32)
            if roww.shape != (nodes.shape[0],):
                raise ValueError("row_w must be (R,) aligned with the batch")
        elif row_w is not None:
            raise ValueError("row_w given but the store was built without "
                             "row_weighted=True")
        r, w = nodes.shape
        d = self.n_shards
        rloc = -(-r // d)
        pad = rloc * d - r
        if pad:
            nodes, lens = _pad_batch_rows(nodes, lens, pad=pad,
                                          n=self.n_nodes)
            if self.row_weighted:
                roww = _pad_row_weights(roww, pad=pad)
        counts = np.asarray(spans.fetch("shard_counts",
            _shard_counts(lens, d=d, width=w)), np.int64)
        elems_l, rows_l = counts[:, 0], counts[:, 1]
        # a row append fits any capacity that holds ``t`` plus its elements.
        # Growth runs *before* the sketch fold so an allocation failure
        # leaves the store completely un-mutated — the whole append is then
        # safe to retry after the caller frees memory (DESIGN.md §8)
        need = int((self._t_loc + elems_l).max())
        if need > self.capacity:
            self._grow_to(need)
        if self._sk_words is not None:
            # fold the batch into the packed coverage sketch *before* the
            # append advances the row counters: bucketing uses canonical
            # global (batch-order) row ids, identical on any mesh size
            nodes_rep = jax.device_put(nodes, self._sh_rep)
            lens_rep = jax.device_put(lens, self._sh_rep)
            base = jax.device_put(np.int32(self.n_rr), self._sh_rep)
            self._sk_words = self._fns.sketch_fold(
                self._sk_words, nodes_rep, lens_rep, base,
                k=self.sketch_k, mode=self.sketch_mode)
        # rows dealt to shards in contiguous blocks; on one device the
        # batch as it is (a device_put to its own sharding does not copy)
        nodes_sh = jax.device_put(nodes, self._sh_buf)
        lens_sh = jax.device_put(lens, self._sh_vec)
        if self.row_weighted:
            (self._flat, self._ids, self._valid, self._ew, self._t_dev,
             self._nrr_dev, self._w_dev) = self._fns.append_rows_w(
                self._flat, self._ids, self._valid, self._ew, self._t_dev,
                self._nrr_dev, self._w_dev, nodes_sh, lens_sh,
                jax.device_put(roww, self._sh_vec), pack=PACK_WINDOW,
                n=self.n_nodes)
        else:
            (self._flat, self._ids, self._valid, self._t_dev,
             self._nrr_dev) = self._fns.append_rows(
                self._flat, self._ids, self._valid, self._t_dev,
                self._nrr_dev, nodes_sh, lens_sh, pack=PACK_WINDOW,
                n=self.n_nodes)
        self._t_loc += elems_l
        self._nrr_loc += rows_l
        if rows_l.sum():
            self._round_rows.append(rows_l.copy())
            self._round_elems.append(elems_l.copy())
        self._cache = None
        self._sk_cache = None

    def _grow_to(self, need: int) -> None:
        """Double the per-shard capacity until ``need`` fits, gated by
        ``alloc_check`` (which may raise :class:`PoolAllocError` *before*
        the donated buffers are re-allocated)."""
        newcap = self.capacity
        while newcap < need:
            newcap *= 2
        if self.alloc_check is not None:
            self.alloc_check(self, newcap)
        self._flat, self._ids, self._valid = self._fns.grow(
            self._flat, self._ids, self._valid,
            newcap=newcap, n=self.n_nodes)
        if self.row_weighted:
            self._ew = self._fns.grow_ew(self._ew, newcap=newcap)

    # -- checkpoint state --------------------------------------------------
    def state(self) -> dict:
        """Every append-relevant buffer as host numpy arrays (one explicit
        ``device_get``, legal under ``transfer_guard("disallow")``) — the
        array half of a durable pool checkpoint.  Restoring this dict via
        :meth:`from_state` reproduces the store bit-identically: flat pool,
        packed sketch words, device counters and the exact host mirrors."""
        arrs = {"flat": self._flat, "ids": self._ids, "valid": self._valid,
                "t_dev": self._t_dev, "nrr_dev": self._nrr_dev}
        if self.row_weighted:
            arrs["ew"] = self._ew
            arrs["w_dev"] = self._w_dev
        if self._sk_words is not None:
            arrs["sk_words"] = self._sk_words
        host = {k: np.asarray(v) for k, v in jax.device_get(arrs).items()}
        host["t_loc"] = self._t_loc.copy()
        host["nrr_loc"] = self._nrr_loc.copy()
        if self._round_rows:
            # (rounds, D) watermark history — windowed eviction keeps its
            # per-round granularity across a checkpoint round-trip
            host["round_rows"] = np.stack(self._round_rows)
            host["round_elems"] = np.stack(self._round_elems)
        return host

    def config(self) -> dict:
        """json-serializable construction parameters matching :meth:`state`
        (stored in the checkpoint manifest's ``meta``)."""
        return {"n_nodes": int(self.n_nodes),
                "per_shard_capacity": int(self.capacity),
                "n_shards": int(self.n_shards),
                "sketch_k": self.sketch_k,
                "sketch_mode": self.sketch_mode,
                "row_weighted": bool(self.row_weighted)}

    @classmethod
    def from_state(cls, state: dict, config: dict, mesh: Mesh | None = None):
        """Rebuild a store from :meth:`state` + :meth:`config` onto ``mesh``.

        The mesh must have the same shard count the state was saved with:
        rows carry *local* ids plus a shard dimension, so re-dealing them
        across a different D would renumber rows and break bit-identity.
        (Elastic re-meshing belongs to a compaction pass, not restore.)
        """
        store = cls(config["n_nodes"],
                    capacity=config["per_shard_capacity"] * config["n_shards"],
                    sketch_k=config["sketch_k"],
                    sketch_mode=config["sketch_mode"],
                    mesh=mesh, row_weighted=config["row_weighted"])
        if store.n_shards != int(config["n_shards"]):
            raise ValueError(
                f"pool checkpoint was saved on {config['n_shards']} shard(s) "
                f"but the restore mesh has {store.n_shards}; restore onto a "
                "same-size mesh")
        if store.capacity != int(config["per_shard_capacity"]):
            raise ValueError("per-shard capacity drifted across restore")
        store._flat = jax.device_put(state["flat"], store._sh_buf)
        store._ids = jax.device_put(state["ids"], store._sh_buf)
        store._valid = jax.device_put(state["valid"], store._sh_buf)
        store._t_dev = jax.device_put(state["t_dev"], store._sh_vec)
        store._nrr_dev = jax.device_put(state["nrr_dev"], store._sh_vec)
        if store.row_weighted:
            store._ew = jax.device_put(state["ew"], store._sh_buf)
            store._w_dev = jax.device_put(state["w_dev"], store._sh_vec)
        if store._sk_words is not None:
            store._sk_words = jax.device_put(state["sk_words"], store._sh_b3)
        store._t_loc = np.asarray(state["t_loc"], np.int64).copy()
        store._nrr_loc = np.asarray(state["nrr_loc"], np.int64).copy()
        rr = state.get("round_rows")
        if rr is not None:
            store._round_rows = [np.asarray(r, np.int64).copy() for r in rr]
            store._round_elems = [np.asarray(r, np.int64).copy()
                                  for r in state["round_elems"]]
        elif store._nrr_loc.any():
            # pre-watermark checkpoint: degrade to whole-pool granularity
            store._round_rows = [store._nrr_loc.copy()]
            store._round_elems = [store._t_loc.copy()]
        return store

    # -- windowed eviction (streaming graphs, DESIGN.md §9) -----------------
    def _rewrite(self, keep) -> dict:
        """Rebuild the pool keeping only the rows ``keep`` selects.

        ``keep(shard, flat, ids, ew) -> (flat', ids', ew', n_rows')`` maps
        one shard's compacted valid elements (host int64 arrays, ids local)
        to the surviving elements with dense renumbered local ids in
        ``[0, n_rows')``.  A maintenance operation in the spirit of
        :meth:`snapshot` — *not* on the guarded hot loop: shards gather to
        the host (explicit transfers, legal under the guard), buffers
        re-pack at the smallest power-of-two capacity, and the packed
        sketch rebuilds from the surviving flat pool via
        :func:`~repro.core.sketch.sketch_packed_from_flat` with shard-major
        global row numbering — the numbering future ``append_batch`` folds
        continue from (``base = n_rr``); any injective renumbering of
        survivors composes correctly because bucketing only ever reads row
        ids, never pool positions.
        """
        d = self.n_shards
        old_rows, old_elems = self.n_rr, self.n_elems
        arrs = (self._flat, self._ids, self._valid) + \
            ((self._ew,) if self.row_weighted else ())
        host = [np.asarray(a) for a in jax.device_get(arrs)]
        flat, ids, valid = host[0], host[1], host[2]
        ew = host[3] if self.row_weighted else None
        fs, iss, ews = [], [], []
        t_new = np.zeros(d, np.int64)
        r_new = np.zeros(d, np.int64)
        for s in range(d):
            m = valid[s]
            f2, i2, e2, rows_s = keep(
                s, flat[s][m].astype(np.int64), ids[s][m].astype(np.int64),
                ew[s][m] if ew is not None else None)
            fs.append(np.asarray(f2, np.int64))
            iss.append(np.asarray(i2, np.int64))
            ews.append(None if e2 is None else np.asarray(e2, np.float32))
            t_new[s] = int(fs[s].shape[0])
            r_new[s] = int(rows_s)
        cap = _ceil_pow2(max(int(t_new.max()), 1))
        nf = np.full((d, cap), self.n_nodes, np.int32)
        ni = np.zeros((d, cap), np.int32)
        nv = np.zeros((d, cap), bool)
        ne = np.zeros((d, cap), np.float32) if self.row_weighted else None
        w_new = np.zeros(d, np.float32) if self.row_weighted else None
        for s in range(d):
            t = int(t_new[s])
            nf[s, :t] = fs[s]
            ni[s, :t] = iss[s]
            nv[s, :t] = True
            if self.row_weighted and t:
                ne[s, :t] = ews[s]
                # the per-row weight sits on every element of the row; sum
                # one representative element per surviving row
                _, first = np.unique(iss[s], return_index=True)
                w_new[s] = np.float32(ews[s][first].sum())
        self._flat = jax.device_put(nf, self._sh_buf)
        self._ids = jax.device_put(ni, self._sh_buf)
        self._valid = jax.device_put(nv, self._sh_buf)
        self._t_dev = jax.device_put(t_new.astype(np.int32), self._sh_vec)
        self._nrr_dev = jax.device_put(r_new.astype(np.int32), self._sh_vec)
        if self.row_weighted:
            self._ew = jax.device_put(ne, self._sh_buf)
            self._w_dev = jax.device_put(w_new, self._sh_vec)
        self._t_loc = t_new.copy()
        self._nrr_loc = r_new.copy()
        if self._sk_words is not None:
            prefix = np.concatenate([[0], np.cumsum(r_new)[:-1]])
            gids = np.concatenate(
                [iss[s] + prefix[s] for s in range(d)]).astype(np.int32)
            fall = np.concatenate(fs).astype(np.int32)
            words = np.asarray(jax.device_get(
                sketch_mod.sketch_packed_from_flat(
                    jax.device_put(fall), jax.device_put(gids),
                    jax.device_put(np.ones(fall.shape[0], bool)),
                    n_rows=self.sketch_rows, k=self.sketch_k,
                    mode=self.sketch_mode)))
            self._sk_words = jax.device_put(
                np.broadcast_to(words[None], (d,) + words.shape).copy(),
                self._sh_b3)
        self._cache = None
        self._sk_cache = None
        return {"rows_dropped": old_rows - self.n_rr,
                "rows_kept": self.n_rr,
                "elems_dropped": old_elems - self.n_elems,
                "per_shard_capacity": self.capacity}

    def evict_earliest_rounds(self, n_rounds: int) -> dict:
        """Drop the ``n_rounds`` earliest sampling rounds (windowed pool).

        Per-shard local row ids are append-ordered, so the earliest rounds
        occupy exactly the id prefix ``[0, thr)`` on every shard: surviving
        rows keep their relative order and renumber by one subtraction.
        The packed sketch rebuilds from the surviving flat pool (the
        rebuild the bit-identity conformance test pins).  Returns the
        :meth:`_rewrite` stats dict.
        """
        n_rounds = max(0, min(int(n_rounds), self.n_rounds))
        if n_rounds == 0:
            return {"rows_dropped": 0, "rows_kept": self.n_rr,
                    "elems_dropped": 0,
                    "per_shard_capacity": self.capacity}
        thr = np.sum(self._round_rows[:n_rounds], axis=0).astype(np.int64)
        old_nrr = self._nrr_loc.copy()

        def keep(s, f, i, e):
            m = i >= thr[s]
            return (f[m], i[m] - thr[s],
                    e[m] if e is not None else None,
                    int(old_nrr[s] - thr[s]))

        stats = self._rewrite(keep)
        self._round_rows = self._round_rows[n_rounds:]
        self._round_elems = self._round_elems[n_rounds:]
        stats["rounds_dropped"] = n_rounds
        return stats

    def evict_to_bytes(self, max_bytes_per_device: int) -> dict:
        """Drop earliest rounds until :meth:`per_device_pool_bytes` fits
        ``max_bytes_per_device``.  Best effort: the latest round is always
        kept (a bound smaller than one round cannot be met — the returned
        ``met`` flag says whether the bound holds).  When no round needs
        dropping but allocated capacity alone exceeds the bound (append
        growth over-allocates), the pool compacts in place to the smallest
        power-of-two capacity without touching any row.
        """
        bpe = 4 + 4 + 1 + (4 if self.row_weighted else 0)
        elems = (np.stack(self._round_elems) if self._round_elems
                 else np.zeros((0, self.n_shards), np.int64))

        def bytes_after(j):
            surv = (elems[j:].sum(axis=0) if j < elems.shape[0]
                    else np.zeros(self.n_shards, np.int64))
            return _ceil_pow2(max(int(surv.max()), 1)) * bpe

        drop = 0
        while drop < max(elems.shape[0] - 1, 0) and \
                bytes_after(drop) > max_bytes_per_device:
            drop += 1
        if drop == 0 and \
                self.per_device_pool_bytes() > max_bytes_per_device:
            nloc = self._nrr_loc.copy()
            stats = self._rewrite(
                lambda s, f, i, e: (f, i, e, int(nloc[s])))
            stats["rounds_dropped"] = 0
        else:
            stats = self.evict_earliest_rounds(drop)
        stats["met"] = self.per_device_pool_bytes() <= max_bytes_per_device
        return stats

    def evict_rows_containing(self, nodes) -> dict:
        """Drop every RR row containing any of ``nodes`` — the delta
        invalidation primitive of ``IMMSolver.resolve_incremental``
        (``nodes`` = the reverse-adjacency rows an edge-delta batch
        touches, :func:`repro.core.stream.affected_nodes`).  Surviving rows
        renumber densely per shard; the round watermark history collapses
        to one synthetic round (membership eviction cuts across rounds).
        """
        aff = np.unique(np.asarray(nodes, np.int64).reshape(-1))

        def keep(s, f, i, e):
            bad = np.unique(i[np.isin(f, aff)])
            m = ~np.isin(i, bad)
            f2, i_old = f[m], i[m]
            u = np.unique(i_old)
            return (f2, np.searchsorted(u, i_old),
                    e[m] if e is not None else None, int(u.shape[0]))

        stats = self._rewrite(keep)
        self._round_rows = [self._nrr_loc.copy()] if self.n_rr else []
        self._round_elems = [self._t_loc.copy()] if self.n_rr else []
        stats["affected_nodes"] = int(aff.shape[0])
        return stats

    # -- views -------------------------------------------------------------
    def snapshot(self) -> RRStore:
        """Back-compat :class:`RRStore` view (valid until the next append).

        On a 1-device mesh this is a device-side slice of the live extent
        with the exact single-device layout.  On a multi-device mesh the
        shards are gathered to the host and renumbered shard-major (local
        ids + per-shard offsets) — a debugging/compat view; the hot paths
        never call it.
        """
        if self._cache is not None:
            return self._cache
        if self.n_shards == 1:
            t = int(self._t_loc[0])
            self._cache = RRStore(
                rr_flat=_slice_extent(self._flat, t=t),
                rr_ids=_slice_extent(self._ids, t=t),
                valid=_slice_extent(self._valid, t=t),
                n_rr=self.n_rr, n_nodes=self.n_nodes)
            return self._cache
        flat, ids, valid = (np.asarray(x) for x in jax.device_get(
            (self._flat, self._ids, self._valid)))
        parts_f, parts_i, base = [], [], 0
        for s in range(self.n_shards):
            m = valid[s]
            parts_f.append(flat[s][m])
            parts_i.append(ids[s][m] + base)
            base += int(self._nrr_loc[s])
        ff = np.concatenate(parts_f) if parts_f else np.zeros(0, np.int64)
        ii = np.concatenate(parts_i) if parts_i else np.zeros(0, np.int64)
        self._cache = RRStore(
            rr_flat=jax.device_put(ff.astype(np.int32)),
            rr_ids=jax.device_put(ii.astype(np.int32)),
            valid=jax.device_put(np.ones(ff.shape[0], bool)),
            n_rr=self.n_rr, n_nodes=self.n_nodes)
        return self._cache

    def row_capacity(self) -> int:
        """Static per-shard row bound for selection: next power of two ≥
        the largest shard's row count (and ≥ 32 so the Covered bitset packs
        whole words).  Selection recompiles only when this doubles."""
        return max(32, _ceil_pow2(max(int(self._nrr_loc.max()), 1)))

    def sketch_words_mesh(self, k: int | None = None):
        """(D, sketch_rows, k/32) packed per-node coverage sketch — one
        replica per shard.

        Stores constructed with ``sketch_k`` return the incrementally
        maintained fold (bit-identical on any mesh size); otherwise the
        sketch is built on demand from the sharded flat pool (per-shard
        partial folds by local row ids, combined by one psum-OR).
        """
        if self._sk_words is not None:
            if k is not None and \
                    sketch_mod.resolve_sketch_k(k) != self.sketch_k:
                raise ValueError(
                    f"store maintains an incremental sketch of k="
                    f"{self.sketch_k}; requested k={k} cannot be honored")
            return self._sk_words
        kk = sketch_mod.resolve_sketch_k(k if k is not None
                                         else self.DEFAULT_SKETCH_K)
        if self._sk_cache is None or self._sk_cache.shape[2] != kk // 32:
            self._sk_cache = self._fns.sketch_from_pool(
                self._flat, self._ids, self._valid,
                n_rows=self.sketch_rows, k=kk, mode=self.sketch_mode)
        return self._sk_cache

    def sketch_words(self, k: int | None = None):
        """Single-replica (n+1, k/32) view of the packed sketch (the mesh
        replicas pad rows to a multiple of the shard count for the striped
        sweep; the canonical view slices that padding off, so the view is
        identical on any mesh size)."""
        return _slice_extent(self.sketch_words_mesh(k), t=self.n_nodes + 1)

    def select(self, k: int, spec: "SelectionSpec | None" = None
               ) -> "CoverageResult":
        if spec is not None:
            return select_variant(self, spec)
        return select_seeds_device(self, k)


# the historical single-device pool IS the mesh=1 case — same class, same
# code path, a 1-device mesh by default
DeviceRRStore = ShardedDeviceRRStore


@functools.partial(jax.jit, static_argnames=("t",))
def _slice_extent(x, *, t):
    return x[0, :t]


@functools.lru_cache(maxsize=None)
def _mesh_sketch_fns(mesh: Mesh):
    """Per-mesh jitted shard_map program for the *pool-free* frontier fold
    (``mode="approximate"``, DESIGN.md §10).

    Unlike the exact store's replicated ``sketch_fold`` (every device folds
    the identical full batch — cheap next to pool appends it rides along
    with), here the fold IS the hot loop, so the batch is split: each shard
    scatter-ORs only its contiguous ``rloc``-row block (D× less work per
    device) into a zero delta, and the deltas merge by one psum-OR
    (all_gather + OR-reduce).  Row ids are computed over the *full*
    replicated batch before slicing, so bucketing is canonical batch-order
    numbering — identical on any mesh size; OR is associative and
    commutative, so the merged words are bit-identical at any shard count.
    """
    ax = mesh.axis_names[0]
    b3 = P(ax, None, None)

    @functools.partial(jax.jit, static_argnames=("k", "mode", "rloc"),
                       donate_argnums=(0,))
    def frontier_fold(sk, nodes, lens, base, *, k, mode, rloc):
        def local(sk, nodes, lens, base):
            w = nodes.shape[1]
            row_valid = lens.astype(jnp.int32) > 0
            rid = base + jnp.cumsum(row_valid, dtype=jnp.int32) - 1
            i = jax.lax.axis_index(ax)
            nb = jax.lax.dynamic_slice(nodes, (i * rloc, 0), (rloc, w))
            lb = jax.lax.dynamic_slice(lens, (i * rloc,), (rloc,))
            rb = jax.lax.dynamic_slice(rid, (i * rloc,), (rloc,))
            part = sketch_mod.fold_frontier_rows(
                jnp.zeros_like(sk[0]), nb, lb, rb, k=k, mode=mode)
            g = jax.lax.all_gather(part, ax)
            delta = jax.lax.reduce(g, jnp.uint32(0),
                                   jax.lax.bitwise_or, (0,))
            return (sk[0] | delta)[None]
        return _shard_map(
            local, mesh=mesh, in_specs=(b3, P(), P(), P()),
            out_specs=b3)(sk, nodes, lens, base)

    class Fns:
        pass

    fns = Fns()
    fns.frontier_fold = frontier_fold
    return fns


class SketchRRStore:
    """Pool-free sketch-only RR "store" — the ``mode="approximate"`` engine
    state (DiFuseR mode, DESIGN.md §10).

    Every sampling micro-step's frontier folds straight into the packed
    (D, sketch_rows, k/32) per-node occupancy words via the sort-dedup
    scatter-OR fold; the flat pool / ids / valid buffers of
    :class:`ShardedDeviceRRStore` are **never allocated** — O(n·k/8) bytes
    per device independent of θ, vs the exact pool's O(θ·E[|RR|]).  The
    only sampling state besides the words is the per-shard row counter
    (host mirror of the same explicit (D, 2) scalar fetch the exact store
    performs per append), which drives the IMM θ accounting.

    What is *lost* relative to the exact store is the exact-acceptance
    contract: no pool exists to verify marginals against, so selection
    (:func:`select_seeds_sketch`) runs on linear-counting estimates and
    results carry a certified error bound instead of exactness.  Row
    weights, budgets and MRIM tags all need the pool and are rejected at
    the :class:`~repro.core.problem.IMProblem` layer.
    """

    pool_free = True
    row_weighted = False

    def __init__(self, n_nodes: int, sketch_k: int | None = None,
                 sketch_mode: str = "mod", mesh: Mesh | None = None):
        if n_nodes >= np.iinfo(np.int32).max:
            raise ValueError("item space must fit int32")
        self.n_nodes = n_nodes
        self.mesh = mesh if mesh is not None else _default_mesh()
        self.axis = self.mesh.axis_names[0]
        self.n_shards = d = int(self.mesh.devices.size)
        self._sh_buf = NamedSharding(self.mesh, P(self.axis, None))
        self._sh_vec = NamedSharding(self.mesh, P(self.axis))
        self._sh_b3 = NamedSharding(self.mesh, P(self.axis, None, None))
        self._sh_rep = NamedSharding(self.mesh, P())
        self.sketch_mode = sketch_mode
        self.sketch_k = sketch_mod.resolve_sketch_k(
            sketch_k if sketch_k is not None
            else ShardedDeviceRRStore.DEFAULT_SKETCH_K)
        self.sketch_rows = -(-(n_nodes + 1) // d) * d
        self._sk_words = jax.device_put(
            np.zeros((d, self.sketch_rows, self.sketch_k // 32), np.uint32),
            self._sh_b3)
        self._nrr_loc = np.zeros(d, np.int64)    # the θ row counter
        self._t_loc = np.zeros(d, np.int64)      # element count (stats only)
        self.alloc_check = None                  # API compat; never grows
        self._fns = _mesh_sketch_fns(self.mesh)

    # -- sizes -------------------------------------------------------------
    @property
    def n_rr(self) -> int:
        return int(self._nrr_loc.sum())

    @property
    def n_elems(self) -> int:
        return int(self._t_loc.sum())

    def per_device_pool_bytes(self) -> int:
        """No pool buffers exist — the point of the mode."""
        return 0

    def sketch_bytes(self) -> int:
        return self.sketch_rows * (self.sketch_k // 32) * 4

    # -- append (the fused sample→sketch hot path) -------------------------
    def append_batch(self, batch, row_w=None) -> None:
        """Fold one padded frontier batch into the packed words — the
        entire "append".  Same calling convention as the exact store (the
        engines and the solver's fault-policy retry wrapper are shared);
        the one explicit host fetch is the (D, 2) shard-count scalar."""
        if row_w is not None:
            raise ValueError("pool-free sketch store is unweighted")
        nodes, lens = (batch.nodes, batch.lengths) \
            if hasattr(batch, "nodes") else batch
        nodes = jnp.asarray(nodes)
        lens = jnp.asarray(lens)
        if nodes.ndim != 2 or lens.shape != (nodes.shape[0],):
            raise ValueError("append_batch wants padded (R, W) nodes + (R,) "
                             "lengths")
        r, w = nodes.shape
        d = self.n_shards
        rloc = -(-r // d)
        pad = rloc * d - r
        if pad:
            nodes, lens = _pad_batch_rows(nodes, lens, pad=pad,
                                          n=self.n_nodes)
        counts = np.asarray(spans.fetch("shard_counts",
            _shard_counts(lens, d=d, width=w)), np.int64)
        base = jax.device_put(np.int32(self.n_rr), self._sh_rep)
        nodes_rep = jax.device_put(nodes, self._sh_rep)
        lens_rep = jax.device_put(lens, self._sh_rep)
        self._sk_words = self._fns.frontier_fold(
            self._sk_words, nodes_rep, lens_rep, base,
            k=self.sketch_k, mode=self.sketch_mode, rloc=rloc)
        self._t_loc += counts[:, 0]
        self._nrr_loc += counts[:, 1]

    # -- checkpoint state (im-pool v2 sub-kind) ----------------------------
    def state(self) -> dict:
        return {"sk_words": np.asarray(jax.device_get(self._sk_words)),
                "t_loc": self._t_loc.copy(),
                "nrr_loc": self._nrr_loc.copy()}

    def config(self) -> dict:
        return {"kind": "sketch",
                "n_nodes": int(self.n_nodes),
                "n_shards": int(self.n_shards),
                "sketch_k": self.sketch_k,
                "sketch_mode": self.sketch_mode,
                "row_weighted": False}

    @classmethod
    def from_state(cls, state: dict, config: dict, mesh: Mesh | None = None):
        store = cls(config["n_nodes"], sketch_k=config["sketch_k"],
                    sketch_mode=config["sketch_mode"], mesh=mesh)
        if store.n_shards != int(config["n_shards"]):
            raise ValueError(
                f"sketch checkpoint was saved on {config['n_shards']} "
                f"shard(s) but the restore mesh has {store.n_shards}; "
                "restore onto a same-size mesh")
        store._sk_words = jax.device_put(state["sk_words"], store._sh_b3)
        store._t_loc = np.asarray(state["t_loc"], np.int64).copy()
        store._nrr_loc = np.asarray(state["nrr_loc"], np.int64).copy()
        return store

    # -- views + selection -------------------------------------------------
    def sketch_words_mesh(self, k: int | None = None):
        if k is not None and \
                sketch_mod.resolve_sketch_k(k) != self.sketch_k:
            raise ValueError(
                f"store maintains an incremental sketch of k="
                f"{self.sketch_k}; requested k={k} cannot be honored")
        return self._sk_words

    def sketch_words(self, k: int | None = None):
        return _slice_extent(self.sketch_words_mesh(k), t=self.n_nodes + 1)

    def select(self, k: int, spec: "SelectionSpec | None" = None
               ) -> "CoverageResult":
        if spec is not None:
            raise ValueError("pool-free sketch store supports plain (or "
                             "candidate-masked) selection only; weighted/"
                             "budgeted/MRIM specs need the exact store")
        return select_seeds_sketch(self, k)


def merge_stores(stores: list[RRStore]) -> RRStore:
    n = stores[0].n_nodes
    flats, ids, valids, base = [], [], [], 0
    for s in stores:
        flats.append(np.asarray(s.rr_flat)[np.asarray(s.valid)])
        ids.append(np.asarray(s.rr_ids)[np.asarray(s.valid)] + base)
        base += s.n_rr
    flat = np.concatenate(flats) if flats else np.zeros(0, np.int64)
    rid = np.concatenate(ids) if ids else np.zeros(0, np.int64)
    return RRStore(rr_flat=jnp.asarray(flat, jnp.int32),
                   rr_ids=jnp.asarray(rid, jnp.int32),
                   valid=jnp.ones(flat.shape[0], bool),
                   n_rr=base, n_nodes=n)


def occur_histogram(store: RRStore) -> jnp.ndarray:
    """Occur[n]: #RR sets containing each node (elements are row-unique)."""
    ones = store.valid.astype(jnp.int32)
    return jnp.zeros(store.n_nodes + 1, jnp.int32).at[store.rr_flat].add(
        ones, mode="drop")[:store.n_nodes]


@functools.partial(jax.jit, static_argnames=("n_rr", "n", "k"))
def _greedy(rr_flat, rr_ids, valid, occur0, *, n_rr, n, k):
    def step(carry, _):
        occur, covered = carry
        u = jnp.argmax(occur).astype(jnp.int32)
        match = (rr_flat == u) & valid                       # membership scan
        row_has = jax.ops.segment_max(match.astype(jnp.int32), rr_ids,
                                      num_segments=n_rr + 1,
                                      indices_are_sorted=True)[:n_rr] > 0
        newly = row_has & ~covered
        elem_newly = jnp.concatenate([newly, jnp.zeros(1, bool)])[
            jnp.clip(rr_ids, 0, n_rr)] & valid
        dec = jnp.zeros(n + 1, jnp.int32).at[rr_flat].add(
            elem_newly.astype(jnp.int32), mode="drop")[:n]
        occur = occur - dec
        covered = covered | row_has
        gain = newly.sum(dtype=jnp.int32)
        return (occur, covered), (u, gain)

    covered = jnp.zeros(n_rr, bool)
    (occur, covered), (seeds, gains) = jax.lax.scan(
        step, (occur0, covered), None, length=k)
    return seeds, gains, covered


class CoverageResult(NamedTuple):
    seeds: jnp.ndarray    # (k,) int32
    gains: jnp.ndarray    # (k,) int32 — newly covered RR sets per seed
    frac: jnp.ndarray     # () float32 — F_R(S): covered fraction


def select_seeds(store: RRStore, k: int) -> CoverageResult:
    occur0 = occur_histogram(store)
    seeds, gains, covered = _greedy(store.rr_flat, store.rr_ids, store.valid,
                                    occur0, n_rr=store.n_rr,
                                    n=store.n_nodes, k=k)
    frac = gains.sum() / jnp.maximum(store.n_rr, 1)
    return CoverageResult(seeds=seeds, gains=gains, frac=frac.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Mesh-sharded selection: the fused Alg. 7 scan and its variant twin.
# ---------------------------------------------------------------------------

def _unpack_covered(cov_words):
    """(nw,) packed uint32 Covered bitset -> (nw*32,) bool rows."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (((cov_words[:, None] >> shifts[None, :])
             & jnp.uint32(1)) != 0).reshape(cov_words.shape[0] * 32)


def _pack_covered(rows):
    """(nw*32,) bool rows -> (nw,) packed uint32 words."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (rows.reshape(-1, 32).astype(jnp.uint32)
            << shifts[None, :]).sum(axis=1)


def _newly_rows(flat, ids, valid, covered, u):
    """Rows containing ``u`` that are not yet covered — THE membership pass.

    Single shared body for the fused scan step, its variant twin and the
    stacked scan; every caller runs it per shard on local rows, so the
    stacked==solo parity contract hangs on every path computing
    newly-covered rows identically.
    """
    match = (flat == u) & valid
    row_has = jax.ops.segment_max(match.astype(jnp.int32), ids,
                                  num_segments=covered.shape[0]) > 0
    return row_has & ~covered


@functools.lru_cache(maxsize=None)
def _mesh_select_fns(mesh: Mesh):
    """Per-mesh jitted shard_map selection programs.

    Every program reads the same sharded pool views: Occur partials are
    psum-reduced, argmax is replicated math, Covered stays shard-local, and
    per seed the only collectives are one ``psum(n)`` (decrement) and one
    scalar psum (gain) — exactly the protocol of DESIGN.md §5.  Replicated
    outputs come back through ``out_specs=P()``, so no host-side slicing
    (which would commit an index scalar under the transfer guard) is
    needed.
    """
    ax = mesh.axis_names[0]
    buf, vec, b3 = P(ax, None), P(ax), P(ax, None, None)

    @functools.partial(jax.jit, static_argnames=("num_rows", "n", "k"))
    def fused(flat, ids, valid, nrr, *, num_rows, n, k):
        """Alg. 7 as ONE scan over the capacity-padded sharded buffers.

        Operands are the pool's *capacity* buffers (shapes change only at
        doublings, so the LB loop re-selects without recompiling), the row
        counts arrive as per-shard device scalars (only the F_R denominator
        needs their psum), and Covered lives as a packed per-shard
        ``(num_rows/32,)`` uint32 bitset — per-seed gains are popcount
        arithmetic on the newly-covered words.  The Occur decrement stays a
        masked scatter over the local flat elements: O(elements/D) per
        device, whatever the pool's density.
        """
        def local(flat, ids, valid, nrr):
            flat, ids, valid = flat[0], ids[0], valid[0]
            occur0 = jnp.zeros(n + 1, jnp.int32).at[flat].add(
                valid.astype(jnp.int32), mode="drop")[:n]
            occur0 = jax.lax.psum(occur0, ax)
            nrr_tot = jax.lax.psum(nrr[0], ax)

            def step(carry, _):
                occur, cov_words = carry
                u = jnp.argmax(occur).astype(jnp.int32)
                newly = _newly_rows(flat, ids, valid,
                                    _unpack_covered(cov_words), u)
                new_words = _pack_covered(newly)
                gain = jax.lax.psum(
                    _popcount(new_words).sum(dtype=jnp.int32), ax)
                elem_newly = newly[jnp.clip(ids, 0, num_rows - 1)] & valid
                dec = jnp.zeros(n + 1, jnp.int32).at[flat].add(
                    elem_newly.astype(jnp.int32), mode="drop")[:n]
                occur = occur - jax.lax.psum(dec, ax)
                return (occur, cov_words | new_words), (u, gain)

            cov0 = jnp.zeros(num_rows // 32, jnp.uint32)
            _, (seeds, gains) = jax.lax.scan(
                step, (occur0, cov0), None, length=k)
            frac = gains.sum(dtype=jnp.int32) / jnp.maximum(nrr_tot, 1)
            return seeds, gains, frac.astype(jnp.float32)

        return _shard_map(
            local, mesh=mesh, in_specs=(buf, buf, buf, vec),
            out_specs=(P(), P(), P()))(flat, ids, valid, nrr)

    @functools.partial(jax.jit, static_argnames=("n",))
    def occur(flat, valid, *, n):
        """Exact psum-reduced Occur histogram (the degraded result's
        union upper bound)."""
        def local(flat, valid):
            h = jnp.zeros(n + 1, jnp.int32).at[flat[0]].add(
                valid[0].astype(jnp.int32), mode="drop")[:n]
            return jax.lax.psum(h, ax)
        return _shard_map(
            local, mesh=mesh, in_specs=(buf, buf),
            out_specs=P())(flat, valid)

    # -- variant programs (weighted Occur / candidate mask / cost-ratio /
    # group budgets) — the generalized Alg. 7 all four IM variants share.
    # Plain problems never route here (they keep the bit-identical fused
    # scan above); every variant knob composes inside one scan.

    def _variant_locals(weighted):
        """Scan body pieces of the variant program, by Occur dtype."""

        def occur_init(flat, ids, valid, ew, *, n, num_rows):
            if weighted:
                ew_l = jnp.where(valid, ew, 0.0)
                occ = jnp.zeros(n + 1, jnp.float32).at[flat].add(
                    ew_l, mode="drop")[:n]
                # per-row weight for gains: every element of a row carries
                # the row weight, so a segment max recovers it (>= 0 floors
                # the -inf of element-less padding rows)
                roww = jnp.maximum(jax.ops.segment_max(
                    ew_l, jnp.clip(ids, 0, num_rows - 1),
                    num_segments=num_rows), 0.0)
                return occ, ew_l, roww
            occ = jnp.zeros(n + 1, jnp.int32).at[flat].add(
                valid.astype(jnp.int32), mode="drop")[:n]
            return occ, None, None

        def pick(occur, feas, costs, budget, spent, *, n, use_costs):
            """Argmax of the variant score; returns (u, ok) with u == n (the
            sentinel, matching nothing) when no feasible pick exists.  Ties
            resolve to the lowest id (jnp.argmax), exactly like the plain
            scan."""
            if use_costs:
                feas = feas & (costs <= budget - spent) & (occur > 0)
                score = jnp.where(feas, occur.astype(jnp.float32) / costs,
                                  -jnp.inf)
                best = jnp.argmax(score).astype(jnp.int32)
                ok = score[best] > -jnp.inf
            else:
                zero = jnp.float32(-1.0) if weighted else jnp.int32(-1)
                masked = jnp.where(feas, occur, zero)
                best = jnp.argmax(masked).astype(jnp.int32)
                ok = masked[best] >= 0
            return jnp.where(ok, best, n).astype(jnp.int32), ok

        def gain_of(newly, new_words, roww):
            if weighted:
                return jnp.where(newly, roww, 0.0).sum(dtype=jnp.float32)
            return _popcount(new_words).sum(dtype=jnp.int32)

        def dec_of(flat, ids, valid, ew_l, newly, *, n, num_rows):
            elem_newly = newly[jnp.clip(ids, 0, num_rows - 1)] & valid
            if weighted:
                return jnp.zeros(n + 1, jnp.float32).at[flat].add(
                    jnp.where(elem_newly, ew_l, 0.0), mode="drop")[:n]
            return jnp.zeros(n + 1, jnp.int32).at[flat].add(
                elem_newly.astype(jnp.int32), mode="drop")[:n]

        return occur_init, pick, gain_of, dec_of

    def _make_variant(weighted):
        occur_init, pick, gain_of, dec_of = _variant_locals(weighted)
        statics = ("num_rows", "n", "k_steps", "n_group", "n_groups",
                   "group_quota", "use_costs")

        def program(flat, ids, valid, nrr, wvec, ew, cand, costs, budget,
                    *, num_rows, n, k_steps, n_group, n_groups, group_quota,
                    use_costs):
            def local(flat, ids, valid, nrr, wvec, ew, cand, costs, budget):
                flat, ids, valid = flat[0], ids[0], valid[0]
                ew_sh = ew[0] if weighted else None
                occur0, ew_l, roww = occur_init(flat, ids, valid, ew_sh,
                                                n=n, num_rows=num_rows)
                occur0 = jax.lax.psum(occur0, ax)
                nrr_tot = jax.lax.psum(nrr[0], ax)
                denom = (jax.lax.psum(wvec[0], ax) if weighted
                         else nrr_tot.astype(jnp.float32))
                group_of = jnp.arange(n, dtype=jnp.int32) // n_group

                def step(carry, _):
                    occur, cov_words, spent, gbud, picked = carry
                    # ~picked: a seed is never re-selected — once chosen its
                    # marginal is 0 forever (submodularity), so re-picking
                    # could only pad the result with duplicates (the plain
                    # scan tolerates that; the variant result must not)
                    feas = (gbud[group_of] > 0) & cand & ~picked
                    u, ok = pick(occur, feas, costs, budget, spent,
                                 n=n, use_costs=use_costs)
                    newly = _newly_rows(flat, ids, valid,
                                        _unpack_covered(cov_words), u)
                    new_words = _pack_covered(newly)
                    gain = jax.lax.psum(gain_of(newly, new_words, roww), ax)
                    dec = jax.lax.psum(
                        dec_of(flat, ids, valid, ew_l, newly,
                               n=n, num_rows=num_rows), ax)
                    if use_costs:
                        spent = spent + jnp.where(
                            ok, costs[jnp.minimum(u, n - 1)], 0.0)
                    gbud = gbud.at[jnp.where(ok, u // n_group, n_groups)].add(
                        -1, mode="drop")
                    picked = picked.at[u].set(True, mode="drop")
                    occur = occur - dec
                    if weighted:
                        # f32 decrement chains can drift a saturated node's
                        # marginal to ~-1ulp; clamping keeps the feasibility
                        # test (occur >= 0 / > 0) aligned with the exact
                        # marginals, which are never negative
                        occur = jnp.maximum(occur, 0.0)
                    return ((occur, cov_words | new_words, spent, gbud,
                             picked), (u, gain))

                cov0 = jnp.zeros(num_rows // 32, jnp.uint32)
                carry0 = (occur0, cov0, jnp.float32(0.0),
                          jnp.full((n_groups,), group_quota, jnp.int32),
                          jnp.zeros(n, bool))
                (_, _, spent, _, _), (seeds, gains) = jax.lax.scan(
                    step, carry0, None, length=k_steps)
                frac = (gains.sum(dtype=gains.dtype)
                        / jnp.maximum(denom, jnp.float32(1e-30))
                        ).astype(jnp.float32)
                return seeds, gains, frac, spent

            dummy = P()
            return _shard_map(
                local, mesh=mesh,
                in_specs=(buf, buf, buf, vec,
                          vec if weighted else dummy,
                          buf if weighted else dummy,
                          dummy, dummy, dummy),
                out_specs=(P(), P(), P(), P()))(
                flat, ids, valid, nrr, wvec, ew, cand, costs, budget)

        return jax.jit(program, static_argnames=statics)

    fused_variant = _make_variant(weighted=False)
    fused_variant_w = _make_variant(weighted=True)

    @functools.partial(jax.jit, static_argnames=(
        "num_rows", "n", "k_max", "n_group", "n_groups"))
    def stacked(flat, ids, valid, nrr, cand, costs, budget, ks, quota,
                plain, use_costs, *, num_rows, n, k_max, n_group, n_groups):
        """R selections in ONE padded scan over the shared pool (serving's
        batched-selection path).

        Per scan step a vmapped body picks one node per request — the plain
        unmasked argmax for ``plain`` rows, the variant score (candidate
        mask, group budgets, optional cost ratio) otherwise — then the
        per-request newly-covered rows, gain and Occur decrement are
        computed shard-locally and the stacked ``(R, n)`` decrement /
        ``(R,)`` gain arrays are psum-reduced in one collective each, so
        the per-step collective count does not grow with R.  Every
        per-request expression (pick, tie-break, gain popcount, decrement
        scatter, spent/group-budget updates, the final frac division)
        mirrors :func:`fused` / the variant scan verbatim; inactive steps
        (``t >= ks[r]``) emit the sentinel ``u == n`` which matches no pool
        element and mutates nothing — so each row of the output is
        bit-identical to the solo program at any mesh width.
        """
        def local(flat, ids, valid, nrr, cand, costs, budget, ks, quota,
                  plain, use_costs):
            flat, ids, valid = flat[0], ids[0], valid[0]
            occur0 = jnp.zeros(n + 1, jnp.int32).at[flat].add(
                valid.astype(jnp.int32), mode="drop")[:n]
            occur0 = jax.lax.psum(occur0, ax)
            nrr_tot = jax.lax.psum(nrr[0], ax)
            r_count = ks.shape[0]
            group_of = jnp.arange(n, dtype=jnp.int32) // n_group

            def step(carry, t):
                occur, cov, spent, gbud, picked = carry

                def pick_one(occ_r, spent_r, gbud_r, picked_r, cand_r,
                             costs_r, budget_r, plain_r, usec_r, k_r):
                    active = t < k_r
                    u_plain = jnp.argmax(occ_r).astype(jnp.int32)
                    feas = (gbud_r[group_of] > 0) & cand_r & ~picked_r
                    feas_c = feas & (costs_r <= budget_r - spent_r) \
                        & (occ_r > 0)
                    score = jnp.where(
                        feas_c, occ_r.astype(jnp.float32) / costs_r,
                        -jnp.inf)
                    best_c = jnp.argmax(score).astype(jnp.int32)
                    ok_c = score[best_c] > -jnp.inf
                    masked = jnp.where(feas, occ_r, jnp.int32(-1))
                    best_m = jnp.argmax(masked).astype(jnp.int32)
                    ok_m = masked[best_m] >= 0
                    ok_v = jnp.where(usec_r, ok_c, ok_m)
                    u_var = jnp.where(
                        ok_v, jnp.where(usec_r, best_c, best_m),
                        jnp.int32(n))
                    u = jnp.where(plain_r, u_plain, u_var)
                    ok = jnp.where(plain_r, True, ok_v) & active
                    return jnp.where(active, u, jnp.int32(n)), ok

                u, ok = jax.vmap(pick_one)(
                    occur, spent, gbud, picked, cand, costs, budget,
                    plain, use_costs, ks)

                def cover_one(cov_r, u_r):
                    newly = _newly_rows(flat, ids, valid,
                                        _unpack_covered(cov_r), u_r)
                    new_words = _pack_covered(newly)
                    g_loc = _popcount(new_words).sum(dtype=jnp.int32)
                    elem_newly = newly[jnp.clip(ids, 0, num_rows - 1)] \
                        & valid
                    dec_loc = jnp.zeros(n + 1, jnp.int32).at[flat].add(
                        elem_newly.astype(jnp.int32), mode="drop")[:n]
                    return cov_r | new_words, g_loc, dec_loc

                cov, g_loc, dec_loc = jax.vmap(cover_one)(cov, u)
                gain = jax.lax.psum(g_loc, ax)
                dec = jax.lax.psum(dec_loc, ax)
                rows = jnp.arange(r_count)
                spent = spent + jnp.where(
                    ok & use_costs, costs[rows, jnp.minimum(u, n - 1)], 0.0)
                gbud = gbud.at[rows, jnp.where(ok, u // n_group,
                                               n_groups)].add(
                    -1, mode="drop")
                picked = picked.at[rows, u].set(True, mode="drop")
                occur = occur - dec
                return (occur, cov, spent, gbud, picked), (u, gain)

            cov0 = jnp.zeros((r_count, num_rows // 32), jnp.uint32)
            carry0 = (jnp.broadcast_to(occur0, (r_count, n)), cov0,
                      jnp.zeros(r_count, jnp.float32),
                      jnp.broadcast_to(quota[:, None],
                                       (r_count, n_groups)).astype(
                                           jnp.int32),
                      jnp.zeros((r_count, n), bool))
            (_, _, spent, _, _), (seeds, gains) = jax.lax.scan(
                step, carry0, jnp.arange(k_max, dtype=jnp.int32))
            seeds, gains = seeds.T, gains.T          # (R, k_max)
            gsum = gains.sum(axis=1, dtype=jnp.int32)
            # plain rows use the solo fused division (int/int); variant
            # rows the solo variant one (int over f32 denom) — IEEE-equal
            # for any sampled pool, but kept distinct for exact bit-parity
            frac = jnp.where(
                plain, gsum / jnp.maximum(nrr_tot, 1),
                gsum / jnp.maximum(nrr_tot.astype(jnp.float32),
                                   jnp.float32(1e-30))).astype(jnp.float32)
            return seeds, gains, frac, spent

        return _shard_map(
            local, mesh=mesh,
            in_specs=(buf, buf, buf, vec,
                      P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P(), P()))(
            flat, ids, valid, nrr, cand, costs, budget, ks, quota,
            plain, use_costs)

    @functools.partial(jax.jit, static_argnames=("stripe", "interpret"))
    def sweep(sk, cov_sk, *, stripe, interpret=None):
        """Δocc lower bounds for every node in one mesh-parallel sweep:
        each device scores its contiguous stripe of candidates against its
        sketch replica; one psum of the disjoint stripes yields the full
        replicated vector (the sketch sweep is embarrassingly parallel).
        ``interpret`` must be resolved by the caller outside the trace —
        it picks the popcount algorithm (kernel vs SWAR fallback)."""
        def local(sk, cov):
            i = jax.lax.axis_index(ax)
            g = sketch_mod.union_gains_stripe(
                sk[0], cov[0], i * stripe, stripe, interpret=interpret)
            full = jax.lax.dynamic_update_slice(
                jnp.zeros(sk.shape[1], jnp.int32), g, (i * stripe,))
            return jax.lax.psum(full, ax)
        return _shard_map(
            local, mesh=mesh, in_specs=(b3, buf),
            out_specs=P())(sk, cov_sk)

    @jax.jit
    def union(cov_sk, sk, u):
        """Fold one accepted seed into every replica of the union sketch —
        the per-seed psum-OR of k/32 words (zero-cost here: replicas are
        identical, so each shard ORs its own copy)."""
        def local(cov, sk, u):
            return (cov[0] | sk[0, u])[None]
        return _shard_map(
            local, mesh=mesh, in_specs=(buf, b3, P()),
            out_specs=buf)(cov_sk, sk, u)

    class Fns:
        pass

    fns = Fns()
    fns.fused = fused
    fns.occur = occur
    fns.sweep = sweep
    fns.union = union
    fns.fused_variant = fused_variant
    fns.fused_variant_w = fused_variant_w
    fns.stacked = stacked
    return fns


def select_seeds_device(store: "ShardedDeviceRRStore", k: int
                        ) -> CoverageResult:
    """Fused greedy selection directly on a :class:`ShardedDeviceRRStore`:
    the flat scan (:func:`_mesh_select_fns` ``fused``) over the pool's
    capacity buffers.

    Everything stays on the mesh; the returned ``frac`` uses the psum of
    the per-shard device row counts, so the call is legal under
    ``jax.transfer_guard("disallow")`` on a mesh of any size.
    """
    fns = _mesh_select_fns(store.mesh)
    seeds, gains, frac = fns.fused(
        store._flat, store._ids, store._valid, store.n_rr_dev,
        num_rows=store.row_capacity(), n=store.n_nodes, k=k)
    return CoverageResult(seeds=seeds, gains=gains, frac=frac)


class SelectionSpec(NamedTuple):
    """Variant knobs for the generalized Alg. 7 (host-side; numpy arrays).

    ``n_group``/``n_groups``/``group_quota`` express partition-budget
    constraints over the item space (MRIM: groups are rounds, quota is the
    per-round k; plain variants: one group of quota ``k_steps``).  ``cand``
    masks the argmax to a candidate set; ``costs``+``budget`` switch the
    greedy to cost-ratio (argmax marginal-gain/cost among affordable
    nodes); ``weighted`` reads the store's per-row weights into Occur and
    the gains (the importance-weighted estimator).  Plain top-k problems
    never build a spec — they keep the plain fused scan.
    """
    k_steps: int                       # scan length / max seeds
    n_group: int                       # group width over the item space
    n_groups: int = 1
    group_quota: int = 1
    cand: object = None                # (n_items,) bool or None
    costs: object = None               # (n_items,) float32 or None
    budget: object = None              # float or None
    weighted: bool = False


class VariantResult(NamedTuple):
    """CoverageResult + the budget actually spent.  ``seeds`` may contain
    the sentinel ``n_items`` on steps where no feasible pick existed
    (budget exhausted) — callers trim them (gain 0, no state change)."""
    seeds: jnp.ndarray
    gains: jnp.ndarray    # int32 rows covered, or float32 covered weight
    frac: jnp.ndarray     # () float32 — covered rows (or weight) fraction
    spent: jnp.ndarray    # () float32 — total cost of the picked seeds


def _spec_operands(store: "ShardedDeviceRRStore", spec: SelectionSpec):
    """Normalize a spec's host arrays into replicated device operands (+
    defaults for the unused slots — explicit device_puts, guard-legal)."""
    n = store.n_nodes
    rep = store._sh_rep
    cand = jax.device_put(
        np.ones(n, bool) if spec.cand is None else
        np.asarray(spec.cand, bool), rep)
    costs = jax.device_put(
        np.ones(n, np.float32) if spec.costs is None else
        np.asarray(spec.costs, np.float32), rep)
    budget = jax.device_put(
        np.float32(np.inf if spec.budget is None else spec.budget), rep)
    return cand, costs, budget


def select_variant(store: "ShardedDeviceRRStore", spec: SelectionSpec
                   ) -> VariantResult:
    """Generalized greedy (weighted / candidate-masked / cost-ratio /
    group-budgeted) over the sharded pool — the scan twin of
    :func:`select_seeds_device` for non-plain :class:`SelectionSpec`.

    Runs as the same shard_map protocol as the plain scan (Occur psum,
    replicated argmax, shard-local Covered), so results are bit-identical
    across mesh sizes whenever the weight sums are exact in float32 (always
    for unweighted specs; for weighted ones use integer-valued weights if
    bit-parity across meshes matters — float psum association differs).
    """
    if spec.weighted and store._ew is None:
        raise ValueError("weighted selection needs a row_weighted store")
    fns = _mesh_select_fns(store.mesh)
    num_rows = store.row_capacity()
    n = store.n_nodes
    cand, costs, budget = _spec_operands(store, spec)
    dummy = jax.device_put(np.zeros(1, np.float32), store._sh_rep)
    wvec = store._w_dev if spec.weighted else dummy
    ew = store._ew if spec.weighted else dummy
    program = fns.fused_variant_w if spec.weighted else fns.fused_variant
    seeds, gains, frac, spent = program(
        store._flat, store._ids, store._valid, store.n_rr_dev, wvec,
        ew, cand, costs, budget,
        num_rows=num_rows, n=n, k_steps=spec.k_steps,
        n_group=spec.n_group, n_groups=spec.n_groups,
        group_quota=spec.group_quota,
        use_costs=spec.budget is not None)
    return VariantResult(seeds=seeds, gains=gains, frac=frac, spent=spent)


class StackedRequest(NamedTuple):
    """One request's selection knobs inside a stacked batch (host-side).

    ``plain`` rows replay the unmasked plain scan (duplicates tolerated,
    like :func:`select_seeds_device`); variant rows carry the
    candidate-mask / costs / budget / group-quota knobs of a
    :class:`SelectionSpec`.  The group geometry (``n_group``/``n_groups``)
    is batch-level — it derives from ``t_rounds``, which is part of the
    pool signature, so one stacked batch can only ever see one geometry.
    """
    k_steps: int
    plain: bool = True
    cand: object = None                # (n_items,) bool or None
    costs: object = None               # (n_items,) float32 or None
    budget: object = None              # float or None
    quota: int = 0                     # group quota; 0 -> k_steps


class StackedResult(NamedTuple):
    """Device outputs of :func:`select_seeds_stacked` — row r of each array
    is bit-identical to the solo program's output for request r.  Rows are
    padded to ``n_requests <= seeds.shape[0]`` and columns to a pow2
    ``k_max``; callers slice ``[r, :k_steps_r]`` and trim the ``n_items``
    sentinel exactly as for :class:`VariantResult`."""
    seeds: jnp.ndarray    # (R_pad, k_max) int32
    gains: jnp.ndarray    # (R_pad, k_max) int32
    frac: jnp.ndarray     # (R_pad,) float32
    spent: jnp.ndarray    # (R_pad,) float32
    n_requests: int


def select_seeds_stacked(store: "ShardedDeviceRRStore",
                         reqs: "list[StackedRequest]", *,
                         n_group: int | None = None,
                         n_groups: int = 1) -> StackedResult:
    """Batched selection: R mixed (k, candidates, variant) requests in ONE
    padded scan over the shared pool instead of R sequential scans.

    The request count and scan length are padded to powers of two (dummy
    rows run zero active steps), so serving traffic compiles O(log) stacked
    program variants per pool shape rather than one per batch composition.
    Guard-legal: operands go up as explicit replicated device_puts, outputs
    stay on device.  Row-weighted stores are not stackable — the weighted
    estimator changes the Occur dtype per request; callers route those to
    the solo path.
    """
    if store.row_weighted:
        raise ValueError("stacked selection does not support row-weighted "
                         "stores — route weighted requests to the solo path")
    if not reqs:
        raise ValueError("select_seeds_stacked needs at least one request")
    n = store.n_nodes
    if n_group is None:
        n_group = n
    fns = _mesh_select_fns(store.mesh)
    r_pad = _ceil_pow2(len(reqs))
    k_max = _ceil_pow2(max(max(r.k_steps for r in reqs), 1))
    cand = np.ones((r_pad, n), bool)
    costs = np.ones((r_pad, n), np.float32)
    budget = np.full(r_pad, np.inf, np.float32)
    ks = np.zeros(r_pad, np.int32)
    quota = np.zeros(r_pad, np.int32)
    plain = np.ones(r_pad, bool)
    use_costs = np.zeros(r_pad, bool)
    for i, r in enumerate(reqs):
        ks[i] = r.k_steps
        quota[i] = r.quota if r.quota else r.k_steps
        plain[i] = r.plain
        use_costs[i] = r.budget is not None
        if r.cand is not None:
            cand[i] = np.asarray(r.cand, bool)
        if r.costs is not None:
            costs[i] = np.asarray(r.costs, np.float32)
        if r.budget is not None:
            budget[i] = np.float32(r.budget)
    rep = store._sh_rep
    ops = [jax.device_put(x, rep)
           for x in (cand, costs, budget, ks, quota, plain, use_costs)]
    seeds, gains, frac, spent = fns.stacked(
        store._flat, store._ids, store._valid, store.n_rr_dev, *ops,
        num_rows=store.row_capacity(), n=n, k_max=k_max,
        n_group=n_group, n_groups=n_groups)
    return StackedResult(seeds=seeds, gains=gains, frac=frac, spent=spent,
                         n_requests=len(reqs))


def select_seeds_sketch(store, k: int, *, cand=None,
                        info_out: dict | None = None) -> CoverageResult:
    """Greedy selection on sketch estimates alone — no exact verification.

    The approximate-mode (pool-free) selection path: per seed one
    mesh-parallel Δocc sweep over all candidates (striped across devices,
    psum of disjoint int32 stripes — bit-identical at any shard count), a
    host argmax (first max == lowest id, matching ``jnp.argmax``), and one
    psum-OR union fold.  No pool exists to verify against, so this is the
    documented departure from the exact greedy of
    :func:`select_seeds_device`; what survives is a *certified* error
    estimate from linear counting, surfaced via ``info_out`` and
    ``IMResult.spread_bounds``:

    * ``lo_rows`` — Δocc never exceeds the exact marginal (new buckets need
      new rows), so the summed gains are a deterministic lower bound on the
      rows the seed set covers.
    * ``hi_rows`` — the linear-counting estimate widened by the z-sigma
      relative StdErr at the realized load
      (:func:`~repro.core.sketch.linear_count_rel_error`); on a
      *saturated* union row the estimate carries no information beyond its
      k·ln(k) ceiling, so the upper bound widens to all of ``n_rr`` rather
      than reporting a silently-finite estimate.

    **Exact regime:** with ``"mod"`` bucketing and ``n_rr <= sketch_k`` the
    bucketing is injective, Δocc *is* the exact marginal gain, and the
    seeds are bit-identical to the fused scan (ties to lowest id in both;
    a zero-gain argmax is still picked, matching the scan's fixed-length
    behavior).  The estimate is then ``occ_union`` itself, error 0.

    Works on any store exposing the sketch surface (``SketchRRStore`` or a
    sketch-maintaining ``ShardedDeviceRRStore``).  ``cand`` optionally
    masks selection to a candidate set.
    """
    n = store.n_nodes
    d = store.n_shards
    fns = _mesh_select_fns(store.mesh)
    sk_words = store.sketch_words_mesh()
    sk_k = int(sk_words.shape[2]) * 32
    stripe = store.sketch_rows // d
    itp = kops.resolve_interpret(None)
    cov_sk = jax.device_put(
        np.zeros((d, sk_words.shape[2]), np.uint32), store._sh_buf)
    mask = (np.ones(n, bool) if cand is None
            else np.asarray(cand, bool)[:n].copy())
    n_rr = store.n_rr
    seeds, gains = [], []
    for _ in range(k):
        deltas = np.asarray(spans.fetch("select",
            fns.sweep(sk_words, cov_sk, stripe=stripe, interpret=itp)))[:n].astype(np.int64)
        score = np.where(mask, deltas, -1)
        u = int(np.argmax(score))        # first max == lowest id on ties
        if score[u] < 0:                 # no feasible candidate left
            break
        seeds.append(u)
        gains.append(int(deltas[u]))
        mask[u] = False
        cov_sk = fns.union(cov_sk, sk_words,
                           jax.device_put(np.int32(u), store._sh_rep))
    occ_union = int(sum(gains))
    exact_regime = (store.sketch_mode == "mod" and n_rr <= sk_k)
    if exact_regime:
        est_rows, lo_rows, hi_rows = float(occ_union), occ_union, occ_union
        saturated, rel_err = False, 0.0
    else:
        est_arr, sat_arr = sketch_mod.linear_count_saturated(
            [occ_union], sk_k)
        saturated = bool(sat_arr[0])
        est_rows = min(float(est_arr[0]), float(n_rr))
        rel_err = float(np.asarray(
            sketch_mod.linear_count_rel_error(est_arr, sk_k))[0])
        lo_rows = min(occ_union, n_rr)   # certified: Δocc <= exact marginal
        hi_rows = (n_rr if saturated
                   else min(float(n_rr), est_rows * (1.0 + rel_err)))
    if info_out is not None:
        info_out.update(occ_union=occ_union, est_rows=est_rows,
                        lo_rows=lo_rows, hi_rows=hi_rows,
                        saturated=saturated, rel_error=rel_err,
                        exact_regime=exact_regime, sketch_k=sk_k, n_rr=n_rr)
    # pad to k with the sentinel item (trimmed by the solver's live mask),
    # matching the fixed-length contract of the fused scan
    pad = [n] * (k - len(seeds))
    frac = est_rows / max(n_rr, 1)
    return CoverageResult(
        seeds=jax.device_put(np.asarray(seeds + pad, np.int32)),
        gains=jax.device_put(np.asarray(gains + [0] * len(pad), np.int32)),
        frac=jax.device_put(np.float32(frac)))


class PaddedStore(NamedTuple):
    """2D tile layout for the Pallas membership kernel (DESIGN.md §2):
    TPU prefers rectangular VMEM tiles over the GPU's ragged flat array."""
    rows: jnp.ndarray     # (R, L) int32, padded with n
    lengths: jnp.ndarray  # (R,) int32
    n_nodes: int


def build_padded_store(rr_lists, n: int, row_len: int | None = None,
                       pad_rows_to: int = 8) -> PaddedStore:
    lens = np.asarray([len(r) for r in rr_lists], dtype=np.int64)
    l = row_len if row_len is not None else int(max(lens.max(), 1))
    l = ((l + 127) // 128) * 128                       # lane-align
    r = ((len(rr_lists) + pad_rows_to - 1) // pad_rows_to) * pad_rows_to
    rows = np.full((r, l), n, dtype=np.int32)
    for i, rr in enumerate(rr_lists):
        if len(rr) > l:
            raise ValueError("row_len too small")
        rows[i, :len(rr)] = rr
    lengths = np.zeros(r, np.int32)
    lengths[:len(lens)] = lens
    return PaddedStore(rows=jnp.asarray(rows), lengths=jnp.asarray(lengths),
                       n_nodes=n)


def select_seeds_padded(store: PaddedStore, k: int) -> CoverageResult:
    """Greedy selection with the Pallas membership kernel as the Alg. 7 scan.

    One fused ``lax.scan`` over the k seeds (the former per-seed python loop
    unrolled k kernel launches and re-traced per call): the membership scan
    (R×L element compares per seed) runs in the kernel; Covered flags and
    the Occur decrement (scatter-add) stay in XLA, which lowers scatter
    natively on TPU.
    """
    from repro.kernels import ops as kops
    rows, lengths, n = store.rows, store.lengths, store.n_nodes
    r, l = rows.shape
    lane = jnp.arange(l, dtype=jnp.int32)[None, :]
    valid = lane < lengths[:, None]
    occur0 = jnp.zeros(n + 1, jnp.int32).at[rows].add(
        valid.astype(jnp.int32), mode="drop")[:n]

    def step(carry, _):
        occur, covered = carry
        u = jnp.argmax(occur).astype(jnp.int32)
        hit = kops.membership_rows(rows, lengths, u)
        newly = hit & ~covered
        dec = jnp.zeros(n + 1, jnp.int32).at[rows].add(
            (valid & newly[:, None]).astype(jnp.int32), mode="drop")[:n]
        return (occur - dec, covered | hit), (u, newly.sum(dtype=jnp.int32))

    _, (seeds, gains) = jax.lax.scan(step, (occur0, jnp.zeros(r, bool)),
                                     None, length=k)
    n_rr = int((lengths > 0).sum())
    return CoverageResult(seeds=seeds, gains=gains,
                          frac=(gains.sum() / jnp.maximum(n_rr, 1)
                                ).astype(jnp.float32))


def shard_stores(per_shard_rr: list[list[list[int]]], n: int) -> RRStore:
    """Stack per-device RR pools into a leading-shard-dim RRStore.

    Pads every shard to the max flat length and max row count so the arrays
    stack; ``n_rr`` becomes rows-per-shard (uniform after padding with empty
    rows, which are never covered and never matched).
    """
    n_shards = len(per_shard_rr)
    rows = max(len(p) for p in per_shard_rr)
    per_shard_rr = [p + [[]] * (rows - len(p)) for p in per_shard_rr]
    stores = [build_store(p, n) for p in per_shard_rr]
    t_max = max(int(s.rr_flat.shape[0]) for s in stores)
    stores = [build_store(p, n, pad_to=t_max) for p in per_shard_rr]
    return RRStore(
        rr_flat=jnp.stack([s.rr_flat for s in stores]),
        rr_ids=jnp.stack([s.rr_ids for s in stores]),
        valid=jnp.stack([s.valid for s in stores]),
        n_rr=rows, n_nodes=n)


# ---------------------------------------------------------------------------
# Legacy distributed variant on host-built shard stacks (pre-dates the
# mesh-native ShardedDeviceRRStore; kept for the host shard_stores API).
# ---------------------------------------------------------------------------

def select_seeds_sharded(mesh, store_shards, k: int, n: int, axis_names):
    """store_shards: RRStore pytree whose arrays carry a leading shard dim
    equal to the mesh size (one row per device); rr_ids are *local* row ids.
    Per-seed collective cost: one psum over (n,) int32 — see DESIGN.md §5.
    """
    local_n_rr = store_shards.n_rr  # rows per shard (uniform)

    def local_fn(rr_flat, rr_ids, valid):
        rr_flat, rr_ids, valid = rr_flat[0], rr_ids[0], valid[0]
        occur = jnp.zeros(n + 1, jnp.int32).at[rr_flat].add(
            valid.astype(jnp.int32), mode="drop")[:n]
        occur = jax.lax.psum(occur, axis_names)

        def step(carry, _):
            occur, covered = carry
            u = jnp.argmax(occur).astype(jnp.int32)
            match = (rr_flat == u) & valid
            row_has = jax.ops.segment_max(
                match.astype(jnp.int32), rr_ids,
                num_segments=local_n_rr + 1,
                indices_are_sorted=True)[:local_n_rr] > 0
            newly = row_has & ~covered
            elem_newly = jnp.concatenate([newly, jnp.zeros(1, bool)])[
                jnp.clip(rr_ids, 0, local_n_rr)] & valid
            dec = jnp.zeros(n + 1, jnp.int32).at[rr_flat].add(
                elem_newly.astype(jnp.int32), mode="drop")[:n]
            occur = occur - jax.lax.psum(dec, axis_names)
            gain = jax.lax.psum(newly.sum(dtype=jnp.int32), axis_names)
            return (occur, covered | row_has), (u, gain)

        covered = jnp.zeros(local_n_rr, bool)
        (_, covered), (seeds, gains) = jax.lax.scan(
            step, (occur, covered), None, length=k)
        return seeds[None], gains[None]

    specs = P(axis_names if isinstance(axis_names, str) else tuple(axis_names))
    fn = _shard_map(local_fn, mesh=mesh,
                    in_specs=(specs, specs, specs),
                    out_specs=(specs, specs))
    seeds, gains = fn(store_shards.rr_flat, store_shards.rr_ids,
                      store_shards.valid)
    return seeds[0], gains[0]
