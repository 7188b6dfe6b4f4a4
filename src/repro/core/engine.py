"""SamplerEngine protocol + registry: one API for every RR-sampling engine.

The paper's claim that "other variations of the IM problem need only minor
modifications" (§3.7 LT, §4.8 MRIM) becomes a first-class contract here:
every sampling engine — the gIM queue decomposition, the dense-frontier
reference, the persistent-lane refill worker, the LT walk sampler, and
MRIM's round-tagged variant — is an adapter class that

* is configured by a per-engine ``Config`` dataclass,
* is registered under a short name (``register_engine`` / ``get_engine``),
* returns one canonical :class:`RRBatch` from ``sample(key)``.

Downstream (``IMMSolver``, ``solve_mrim``, the sharded launch pipeline,
benchmarks) consumes only the protocol, so adding a diffusion model means
writing one adapter — no solver changes.  See DESIGN.md §3.

Layering: this module imports the low-level samplers (``rrset``, ``dense``,
``lt``); it is imported by the solvers (``imm``, ``mrim``) and launchers.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph, coalesce_ic
from repro.core import rrset as rr_queue
from repro.core import dense as rr_dense
from repro.core import lt as rr_lt
from repro.core.packing import pack_rows_device
from repro.core.roots import (AliasTable, ONE_UNIFORM_MAX_N,  # noqa: F401
                              build_alias_table, draw_roots,
                              roots_from_uniform)


@jax.jit
def split_key(key):
    """Guard-safe (carry, sub) key split: the pair indexing happens inside
    the jit, so no host index scalar is committed under
    ``jax.transfer_guard("disallow")``.  Shared by the device-resident
    solvers (imm, mrim)."""
    ks = jax.random.split(key)
    return ks[0], ks[1]


# Weighted root sampling (weighted IM: roots drawn ∝ node_weights).  The
# implementation lives one layer down in ``core/roots.py`` (the samplers
# import it without a cycle); this module is the engine-facing surface.


class RRBatch(NamedTuple):
    """Canonical, device-resident result of one ``SamplerEngine.sample`` call.

    One row per completed RR set; rows are padded to the batch's max length.
    ``nodes`` entries beyond ``lengths[i]`` are undefined (consumers mask by
    length — ``coverage.build_store`` / ``IncrementalRRStore.append_batch``
    do).  Node ids live in the engine's ``item_space`` (plain engines:
    ``[0, n)``; MRIM: ``round * n + node`` in ``[0, n * t_rounds)``).

    ``overflowed`` is per *lane* (engines whose lanes each emit one set have
    lanes == rows; the refill engine reports its persistent lanes).
    ``steps`` is the scalar count of lockstep micro-steps this batch cost —
    the hardware-transferable parallel-time metric of §Perf/IM.

    ``sample()`` returns only real sets (every ``lengths[i] >= 1``).  The
    fixed-shape device paths (``sample_device``, preferred by the solvers
    under ``jax.transfer_guard("disallow")``) may additionally emit *padding
    rows* with ``lengths[i] == 0`` — no RR set at all — which the stores
    drop without assigning a row id.

    ``roots`` (optional) is the *base-space* root node of each row —
    undefined for padding rows.  Engines that know their roots report them
    so the solver can weight rows by ``node_weights[root]`` (the
    importance-weighted fallback for weighted problems on engines without
    weight-proportional root sampling); ``None`` is a valid value for
    third-party adapters.
    """
    nodes: jnp.ndarray       # (R, W) int32/int64, padded per-set node ids
    lengths: jnp.ndarray     # (R,) int — RR-set sizes (>= 1)
    overflowed: jnp.ndarray  # (L,) bool — per-lane truncation flags
    steps: jnp.ndarray       # () int — lockstep micro-steps executed
    roots: Optional[jnp.ndarray] = None  # (R,) int32 base-node root per row

    @property
    def n_sets(self) -> int:
        return int(self.lengths.shape[0])

    @classmethod
    def make(cls, nodes, lengths, overflowed, steps, roots=None) -> "RRBatch":
        return cls(nodes=jnp.asarray(nodes), lengths=jnp.asarray(lengths),
                   overflowed=jnp.asarray(overflowed),
                   steps=jnp.asarray(steps),
                   roots=None if roots is None else jnp.asarray(roots))


@runtime_checkable
class SamplerEngine(Protocol):
    """What the solvers require of an engine (structural — no inheritance).

    Optional extensions the solvers exploit when present:

    * ``device_resident = True`` — every op in ``sample`` runs on device
      (all operands are committed device arrays; host graph preprocessing
      happened at construction).  The IMM driver then holds
      ``jax.transfer_guard("disallow")`` over its whole hot loop.  Engines
      that do host work per sample simply omit the attribute and the driver
      falls back to unguarded execution — third-party adapters keep working.
    * ``sample_device(key)`` — fixed-shape variant of ``sample`` that may
      return zero-length padding rows (see :class:`RRBatch`); preferred by
      the solvers because stable shapes mean stable jit caches.
    * ``mesh`` + ``sample_sharded(key)`` — mesh-fanned engines expose the
      jax ``Mesh`` they sample over and a variant whose batch arrays stay
      *sharded* across it (per-device rows resident on the device that
      sampled them, no gather).  When the solver's pool shares the same
      mesh (``IMMSolver(mesh=...)``), it prefers this path and the rows
      never leave their sampling device.
    """
    name: str

    @property
    def item_space(self) -> int:
        """Size of the id space ``nodes`` draws from (coverage histogram n)."""
        ...

    def sample(self, key) -> RRBatch:
        """Sample one batch of RR sets; ``key`` is a jax PRNG key."""
        ...


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ENGINES: dict[str, type] = {}

# engines living outside core (to avoid core -> launch import cycles) are
# resolved by importing their home module on first lookup
_LAZY_ENGINES: dict[str, str] = {"queue_sharded": "repro.launch.im_solve"}


def register_engine(name: str):
    """Class decorator: register ``cls`` under ``name`` (sets ``cls.name``)."""
    def deco(cls):
        cls.name = name
        _ENGINES[name] = cls
        return cls
    return deco


def get_engine(name: str) -> type:
    if name not in _ENGINES and name in _LAZY_ENGINES:
        import importlib
        importlib.import_module(_LAZY_ENGINES[name])
    try:
        return _ENGINES[name]
    except KeyError:
        raise KeyError(f"unknown engine {name!r}; registered: "
                       f"{sorted(set(_ENGINES) | set(_LAZY_ENGINES))}"
                       ) from None


def list_engines() -> list[str]:
    return sorted(set(_ENGINES) | set(_LAZY_ENGINES))


def make_engine(name: str, g_rev: CSRGraph, root_weights=None,
                **opts) -> "SamplerEngine":
    """Instantiate a registered engine on the reverse graph.

    ``opts`` may be a superset of the engine's ``Config`` fields — unknown
    keys and ``None`` values are dropped, so callers (``IMMSolver``) can pass
    one uniform option set (batch/qcap/ec/...) to any engine.

    ``root_weights`` (weighted IM) is forwarded to every registered engine:
    roots come out ∝ the weights through the shared alias table
    (:func:`draw_roots`); ``None`` keeps the historical uniform draw,
    bit-identical streams included.
    """
    cls = get_engine(name)
    fields = {f.name for f in dataclasses.fields(cls.Config)}
    cfg = cls.Config(**{k: v for k, v in opts.items()
                        if k in fields and v is not None})
    if root_weights is None:
        return cls(g_rev, cfg)
    return cls(g_rev, cfg, root_weights=root_weights)


def resolve_engine_name(engine: str, model: str = "ic") -> str:
    """Back-compat mapping from the old (engine, model) pair to an engine
    name: ``model="lt"`` overrides the IC engine choice (the LT walk sampler
    is the only LT engine)."""
    return "lt" if model == "lt" else engine


def resolve_qcap(qcap: Optional[int], g_rev: CSRGraph) -> int:
    """Default queue capacity: the whole node set (an RR set can never be
    larger, so the default never overflows)."""
    return qcap if qcap is not None else g_rev.n_nodes


class FusedSketchEngine:
    """Adapter marking an engine as the pool-free fused sample→sketch path
    (``IMProblem(mode="approximate")``, DESIGN.md §10).

    Sampling itself is untouched — every batch the inner engine emits is
    byte-for-byte what the exact path would have appended (so a fixed-θ
    approximate solve consumes the *identical* RNG stream as the exact
    one).  What changes is the destination: the solver pairs this adapter
    with a :class:`~repro.core.coverage.SketchRRStore`, whose
    ``append_batch`` folds the frontier straight into the packed per-node
    sketches and never allocates the flat pool.  The adapter exists so the
    solver signature / stats / checkpoints name the mode explicitly and
    so engine-specific extensions (``sample_device``, ``sample_sharded``,
    ``mesh``, ``device_resident``) pass through untouched.
    """

    def __init__(self, inner: "SamplerEngine"):
        self._inner = inner
        self.name = f"fused-sketch[{inner.name}]"

    def __getattr__(self, attr):
        # only consulted for attributes not set on the adapter itself —
        # delegates sample/sample_device/sample_sharded/mesh/
        # device_resident/... verbatim
        return getattr(self._inner, attr)

    @property
    def item_space(self) -> int:
        return self._inner.item_space


# ---------------------------------------------------------------------------
# Engine adapters
# ---------------------------------------------------------------------------

@jax.jit
def _row_roots(nodes):
    """First column of a root-first padded batch = per-row roots.  Jitted so
    the slice indices never cross host->device (legal under
    ``jax.transfer_guard("disallow")``)."""
    return nodes[:, 0].astype(jnp.int32)


def _resolve_root_table(root_weights):
    """(weights or None) -> (weights np array or None, AliasTable or None)."""
    if root_weights is None:
        return None, None
    w = np.asarray(root_weights, np.float32)
    return w, build_alias_table(w)


@register_engine("queue")
class QueueEngine:
    """gIM-faithful work-efficient sampler (paper Alg. 3/6; core/rrset.py).
    ``sample`` is one jit (root draw included) over device operands."""

    device_resident = True

    @dataclass(frozen=True)
    class Config:
        batch: int = 256
        qcap: Optional[int] = None   # default: n_nodes
        ec: int = rr_queue.EC_DEFAULT

    def __init__(self, g_rev: CSRGraph, config: Optional[Config] = None,
                 root_weights=None):
        # IC equivalence: parallel edges merge to p' = 1-∏(1-p), making the
        # rows simple and the chunk dedup a no-op (detect returns "none")
        self.g_rev = coalesce_ic(g_rev)
        self.config = config if config is not None else self.Config()
        self.qcap = resolve_qcap(self.config.qcap, self.g_rev)
        self._dedup = rr_queue.detect_dedup_mode(self.g_rev)
        self._edge_rows = rr_queue.edge_row_table(
            self.g_rev.indices, self.g_rev.weights, ec=self.config.ec)
        self.root_weights, self._root_table = _resolve_root_table(root_weights)

    @property
    def item_space(self) -> int:
        return self.g_rev.n_nodes

    def sample(self, key) -> RRBatch:
        s = rr_queue.sample_rrsets_queue(key, self.g_rev, self.config.batch,
                                         self.qcap, dedup=self._dedup,
                                         root_table=self._root_table,
                                         edge_rows=self._edge_rows)
        return RRBatch.make(s.nodes, s.lengths, s.overflowed, s.steps,
                            roots=s.roots)


@register_engine("dense")
class DenseEngine:
    """Dense-frontier masked-SpMV sampler (core/dense.py); membership is
    converted to padded rows by one device rank-scatter inside the same jit
    as the BFS (``edge_src`` is precomputed once here, not per round)."""

    device_resident = True

    @dataclass(frozen=True)
    class Config:
        batch: int = 256

    def __init__(self, g_rev: CSRGraph, config: Optional[Config] = None,
                 root_weights=None):
        self.g_rev = coalesce_ic(g_rev)      # exact for IC, fewer edges
        self.config = config if config is not None else self.Config()
        self._edge_src = rr_dense._edge_src(self.g_rev)
        self.root_weights, self._root_table = _resolve_root_table(root_weights)

    @property
    def item_space(self) -> int:
        return self.g_rev.n_nodes

    def sample(self, key) -> RRBatch:
        g = self.g_rev
        nodes, lens, roots, overflow, levels = rr_dense._dense_round(
            key, self._edge_src, g.indices, g.weights, self._root_table,
            batch=self.config.batch, n=g.n_nodes, m=g.n_edges)
        return RRBatch.make(nodes, lens, overflow, levels, roots=roots)


@register_engine("refill")
class RefillEngine:
    """Persistent-lane worker (paper Alg. 6): lanes refill with fresh roots
    until ``batch`` RR sets are complete; a sample may return slightly more
    than ``batch`` rows (in-flight sets always finish, unbiased)."""

    device_resident = True

    @dataclass(frozen=True)
    class Config:
        batch: int = 256             # quota: target RR sets per sample()
        lanes: Optional[int] = None  # default: batch//2 clamped to [8, 512]
        out_cap: Optional[int] = None
        ec: int = rr_queue.EC_DEFAULT

    def __init__(self, g_rev: CSRGraph, config: Optional[Config] = None,
                 root_weights=None):
        self.g_rev = coalesce_ic(g_rev)
        cfg = config if config is not None else self.Config()
        self.config = cfg
        # wide lane count: lockstep micro-steps (the dominant cost, fixed
        # overhead per step) scale ~1/lanes; the paper's Alg. 6 likewise
        # sizes persistent blocks to fill the machine
        self.lanes = (cfg.lanes if cfg.lanes is not None
                      else max(min(cfg.batch // 2, 512), 8))
        self.out_cap = (cfg.out_cap if cfg.out_cap is not None
                        else min(8 * cfg.batch // self.lanes, 64) * 64)
        self._dedup = rr_queue.detect_dedup_mode(self.g_rev)
        self._edge_rows = rr_queue.edge_row_table(
            self.g_rev.indices, self.g_rev.weights, ec=cfg.ec)
        self.root_weights, self._root_table = _resolve_root_table(root_weights)
        if (self._root_table is not None
                and self.g_rev.n_nodes > ONE_UNIFORM_MAX_N):
            raise ValueError(
                "weighted refill roots use the one-uniform alias draw, "
                f"which is only exact for n <= {ONE_UNIFORM_MAX_N}; use the "
                "queue or dense engine for weighted IM on larger graphs")

    @property
    def item_space(self) -> int:
        return self.g_rev.n_nodes

    def _sample_raw(self, key):
        return rr_queue.sample_rrsets_refill(key, self.g_rev, self.lanes,
                                             quota=self.config.batch,
                                             out_cap=self.out_cap,
                                             dedup=self._dedup,
                                             root_table=self._root_table,
                                             edge_rows=self._edge_rows)

    def sample(self, key) -> RRBatch:
        s = self._sample_raw(key)
        nodes, lens = rr_queue.refill_to_padded(s)
        # refill rows are root-first (each set's segment starts with the
        # root that seeded the lane), so the row root is column 0
        return RRBatch.make(nodes, lens, s.overflowed, s.steps,
                            roots=_row_roots(jnp.asarray(nodes)))

    def sample_device(self, key) -> RRBatch:
        """Fixed-shape device unpack: every (lane, slot) becomes a row,
        unfinished slots as zero-length padding rows.  Same sample stream as
        ``sample`` (identical key splits), but no host round-trip and a
        shape that never depends on the data."""
        s = self._sample_raw(key)
        nodes, lens = rr_queue.refill_to_padded_device(s.flat, s.lengths,
                                                       s.n_done)
        return RRBatch.make(nodes, lens, s.overflowed, s.steps,
                            roots=_row_roots(nodes))


@register_engine("lt")
class LTEngine:
    """Linear-threshold walk sampler (paper §3.7; core/lt.py).  The
    segmented weight cumsum is built once here (the historical path redid
    that host pass — and its upload — every round)."""

    device_resident = True

    @dataclass(frozen=True)
    class Config:
        batch: int = 256
        qcap: Optional[int] = None

    def __init__(self, g_rev: CSRGraph, config: Optional[Config] = None,
                 root_weights=None):
        self.g_rev = g_rev
        self.config = config if config is not None else self.Config()
        self.qcap = resolve_qcap(self.config.qcap, g_rev)
        self._rowcum = rr_lt.row_cumweights(g_rev)
        self.root_weights, self._root_table = _resolve_root_table(root_weights)

    @property
    def item_space(self) -> int:
        return self.g_rev.n_nodes

    def sample(self, key) -> RRBatch:
        g = self.g_rev
        nodes, lengths, roots, overflowed, steps = rr_lt._lt_round(
            key, g.offsets, g.indices, self._rowcum, self._root_table,
            batch=self.config.batch, qcap=self.qcap,
            n=g.n_nodes, m=g.n_edges)
        return RRBatch.make(nodes, lengths, overflowed, steps, roots=roots)


@functools.partial(jax.jit,
                   static_argnames=("batch", "t", "qcap", "n", "m", "dedup"))
def _mrim_round(key, offsets, edge_rows, root_table, *, batch, t, qcap, n, m,
                dedup="sort"):
    """Root draw + T tagged BFS + segment merge as ONE jit (device path).
    Key-split structure matches the historical host implementation, keeping
    sample streams bit-identical."""
    key, kroot, ksample = jax.random.split(key, 3)
    roots = draw_roots(kroot, batch, n, root_table)
    tiled_roots = jnp.repeat(roots, t)                # lane b*T+r -> root b
    nodes, lengths, overflowed, steps = rr_queue._sample_queue(
        ksample, offsets, edge_rows, tiled_roots,
        batch=batch * t, qcap=qcap, n=n, m=m, dedup=dedup)
    rounds = jnp.tile(jnp.arange(t, dtype=jnp.int32), batch)
    q = nodes.shape[1]                       # qcap plus the queue's pad tail
    enc = (nodes + (rounds * n)[:, None]).reshape(batch, t * q)
    lane_len = lengths.reshape(batch, t)
    # valid positions: within each lane's segment, first lane_len entries
    seg = jnp.arange(t * q, dtype=jnp.int32) // q
    pos = jnp.arange(t * q, dtype=jnp.int32) % q
    mask = pos[None, :] < lane_len[:, seg]
    out_nodes, out_lens = pack_rows_device(enc, mask)
    overflow = overflowed.reshape(batch, t).any(axis=1)
    return out_nodes, out_lens, roots, overflow, steps


@register_engine("mrim")
class MRIMEngine:
    """Multi-round IM sampler (paper §4.8): each RR sample is T tagged BFS
    from a shared root, run as T adjacent queue-engine lanes; elements are
    encoded ``round * n + node`` so coverage machinery is reused verbatim on
    an item space of n·T.  Lane segments are merged into one padded row per
    sample by a device rank-scatter inside the sampling jit."""

    device_resident = True

    @dataclass(frozen=True)
    class Config:
        batch: int = 64
        t_rounds: int = 2
        qcap: Optional[int] = None
        ec: int = rr_queue.EC_DEFAULT

    def __init__(self, g_rev: CSRGraph, config: Optional[Config] = None,
                 root_weights=None):
        self.g_rev = coalesce_ic(g_rev)
        self.config = config if config is not None else self.Config()
        self.qcap = resolve_qcap(self.config.qcap, self.g_rev)
        self._dedup = rr_queue.detect_dedup_mode(self.g_rev)
        self._edge_rows = rr_queue.edge_row_table(
            self.g_rev.indices, self.g_rev.weights, ec=self.config.ec)
        self.root_weights, self._root_table = _resolve_root_table(root_weights)
        if self.item_space >= np.iinfo(np.int32).max:
            raise ValueError("n_nodes * t_rounds must fit int32")

    @property
    def item_space(self) -> int:
        return self.g_rev.n_nodes * self.config.t_rounds

    def sample(self, key) -> RRBatch:
        g, cfg = self.g_rev, self.config
        out_nodes, out_lens, roots, overflow, steps = _mrim_round(
            key, g.offsets, self._edge_rows, self._root_table,
            batch=cfg.batch, t=cfg.t_rounds, qcap=self.qcap,
            n=g.n_nodes, m=g.n_edges, dedup=self._dedup)
        return RRBatch.make(out_nodes, out_lens, overflow, steps, roots=roots)
