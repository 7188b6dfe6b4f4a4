"""IMM driver (paper Alg. 2 + θ sampling + seed selection), engine-agnostic.

The host orchestrates rounds of RR batches (exactly like gIM's persistent
N_b-block kernel relaunches, Alg. 6) against any registered
:class:`~repro.core.engine.SamplerEngine` — ``queue`` (gIM-faithful),
``dense`` (frontier-SpMV), ``refill`` (persistent lanes), ``lt`` (LT walks),
or a caller-supplied engine instance (e.g. the sharded launcher's).  Every
round is ``batch = engine.sample(key)`` → ``store.append_batch(batch)``; the
solver never inspects engine internals.

**One entry point, five problems** (DESIGN.md §6): the solver is driven by a
declarative :class:`~repro.core.problem.IMProblem` —

    IMMSolver(g).solve(IMProblem(k=10, eps=0.3))                  # plain
    IMMSolver(g).solve(IMProblem(k=10, eps=0.3, node_weights=w))  # weighted
    IMMSolver(g).solve(IMProblem(eps=0.3, costs=c, budget=B))     # budgeted
    IMMSolver(g).solve(IMProblem(k=10, eps=0.3, candidates=ids))  # targeted
    IMMSolver(g).solve(IMProblem(k=3, t_rounds=4, theta=4096))    # MRIM

returning a typed :class:`~repro.core.problem.IMResult` (seeds, spread on
the problem's scale, per-seed marginal gains, stats).  Plain problems take
exactly the historical code paths — same RNG streams, same selection
programs — so their seeds/gains/F_R are bit-identical to the historical
``solve(k, eps)`` form (removed after its deprecation window; DESIGN.md §6
has the migration notes).

Variants thread through every layer: weighted problems draw roots ∝
``node_weights`` through the engines' shared alias table
(:func:`~repro.core.engine.draw_roots`; engines without weighted-root
support fall back to the importance-weighted row estimator on a
``row_weighted`` store), and non-plain selection runs the generalized
shard_map scan (:func:`~repro.core.coverage.select_variant`) with
candidate masks, cost-ratio greedy and per-round (group) budgets — on a
mesh of any size, under the same transfer guard.

The hot loop is *mesh-resident*: the RR pool is a
:class:`~repro.core.coverage.ShardedDeviceRRStore` sharded over the device
mesh chosen once at solver construction (``mesh=`` — ``None`` is the
1-device mesh, the same code path), selection is the capacity-stable
psum-reduced greedy, and for engines that declare ``device_resident``
the whole sampling+selection loop runs under
``jax.transfer_guard("disallow")`` on a mesh of any size.  The only
host↔device traffic per round is the store's explicit per-shard count
fetch — the same per-relaunch ``N_RR`` readback gIM's Alg. 6 host loop
performs.

All martingale math (λ', λ*, the Alg. 2 LB loop) follows IMM [Tang et al.'15]
and is shared with the numpy oracle (core/oracle.py) so both sides compute
identical θ schedules.  For non-plain variants the schedule is reused with
the spread scale swapped in (``Σw`` for weighted problems) — a heuristic
extension; the selection itself stays exact greedy on the sampled pool.
"""
from __future__ import annotations

import math
import time
from dataclasses import asdict, dataclass, field
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph, reverse
from repro.ckpt import checkpoint as ckpt_mod
from repro.core import coverage as cov
from repro.core import sketch as sketch_mod
from repro.core import spans
from repro.core.oracle import imm_theta_params
from repro.core.problem import (IMProblem, IMResult, ResolvedProblem,
                                problem_from_state, problem_state)
from repro.core.engine import (FusedSketchEngine, SamplerEngine, make_engine,
                               resolve_engine_name, split_key as _split_key)
from repro.ft.failures import DeadlineExceeded, FaultPolicy


@jax.jit
def _accum_round_stats(steps_acc, ovf_acc, steps, overflowed):
    """Device-scalar stat accumulation — replaces the per-round blocking
    ``int(batch.steps)`` / ``np.asarray(batch.overflowed)`` syncs."""
    return (steps_acc + steps.astype(jnp.int32),
            ovf_acc + overflowed.sum(dtype=jnp.int32))


@jax.jit
def _gather_row_weights(w_dev, roots):
    """Row weight of each batch row: its root's node weight (the
    importance-weighted fallback estimator)."""
    return w_dev[jnp.clip(roots.astype(jnp.int32), 0, w_dev.shape[0] - 1)]


@dataclass
class IMMStats:
    theta: int = 0
    n_rr_sampled: int = 0
    lb: float = 1.0
    lb_iters: int = 0
    rounds: int = 0
    overflow_fraction: float = 0.0
    frac_covered: float = 0.0
    sampling_steps: int = 0
    # the selection that ran: "fused" (the exact scan over the pool) or
    # "sketch" (approximate mode's pool-free greedy)
    selection: str = "fused"
    variant: str = "plain"
    early_exit_skips: int = 0
    budget_spent: float = 0.0
    mesh_shape: tuple = (1,)
    pool_sharding: str = "samples:1"
    per_device_pool_bytes: int = 0
    # explicit device-to-host fetches made inside solves (one per
    # ``imm.fetch`` span, core/spans.py)
    host_fetches: int = 0
    # RR-set elements appended to the pool (the ``imm.append`` spans'
    # ``elements``, summed): the bytes an append must move follow it
    pool_elements: int = 0
    # resume watermark for the Alg. 2 LB loop: index of the last LB
    # iteration that finished *without* breaking.  A restored solve skips
    # iterations <= lb_completed instead of re-running them over the (now
    # larger) pool, which would shift est/break points (DESIGN.md §8).
    lb_completed: int = 0
    history: list = field(default_factory=list)


@dataclass
class PoolLease:
    """Explicit ownership of a prepared solver's sampled state.

    ``IMMSolver.export_pool()`` detaches the RR pool — plus everything that
    makes it *resumable*: the signature-defining problem, the RNG cursor,
    and the stat accumulators — and hands it to the caller;
    ``adopt_pool(lease)`` installs it into a (same-graph, same-options)
    solver, which then continues bit-identically to the exporter.  The
    serving registry (``repro.serve``) uses this to own pool memory
    outside any solver: an evicted lease is *the* reference to the device
    buffers, so dropping it frees them accountably.
    """
    problem: IMProblem                 # pool-signature template
    store: "cov.ShardedDeviceRRStore"
    key: jax.Array                     # RNG cursor (sampling resumes here)
    stats: IMMStats
    steps_acc: jax.Array
    ovf_acc: jax.Array
    ovf_lanes: int
    # signature_digest of an eps-driven solve that was interrupted
    # mid-flight (None when no solve is in progress): the adopting solver
    # resumes that solve's LB loop from stats.lb_completed instead of
    # restarting it
    active_solve: Optional[str] = None

    def pool_bytes(self) -> int:
        s = self.store
        return s.n_shards * (s.per_device_pool_bytes() + s.sketch_bytes())


class IMMSolver:
    """Stateful solver: owns the RR pool so Alg. 2 reuses earlier samples.

    ``engine`` is a registered engine name or a ready ``SamplerEngine``
    instance; ``batch``/``qcap``/``ec`` are forwarded to the engine's config
    (each engine takes the subset it understands).  ``model="lt"`` keeps its
    historical meaning by resolving to the ``lt`` engine (a problem's
    ``model=`` field overrides it per solve).

    Selection is one exact greedy, the selection scan over the pool
    (:func:`~repro.core.coverage.select_seeds_device`, or
    :func:`~repro.core.coverage.select_variant` for non-plain problems);
    ``mode="approximate"`` problems take the pool-free sketch greedy.
    ``sketch_k`` sizes the exact pool's optional coverage sketch, which
    only the θ early exit and deadline-degraded results read (an
    ``early_exit`` problem builds one at the default size when unset).

    The engine and the pool are rebuilt whenever a solve's problem changes
    their *signature* (diffusion model, ``t_rounds``, ``node_weights``) —
    repeated solves of same-signature problems keep reusing the pool, like
    the historical solver did.
    """

    def __init__(self, g: CSRGraph, *,
                 engine: Union[str, SamplerEngine] = "queue",
                 batch: Optional[int] = None, qcap: Optional[int] = None,
                 ec: Optional[int] = None, model: Optional[str] = None,
                 sketch_k: Optional[int] = None,
                 mesh=None, seed: int = 0,
                 fault_policy: Optional[FaultPolicy] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, checkpoint_keep: int = 3):
        self.g = g
        self.n = g.n_nodes
        self._engine_arg = engine
        self._engine_opts = dict(batch=batch, qcap=qcap, ec=ec)
        self._model_arg = model
        # fault tolerance (DESIGN.md §8): the policy wraps every hot-loop
        # boundary (sample/append/grow/select) in retry-with-backoff;
        # checkpoint_dir + checkpoint_every>0 turn on periodic durable pool
        # saves every N sampling rounds (auto-resume is the caller's
        # restore_pool call — see launch/im_solve.py)
        self.fault_policy = fault_policy
        self._ckpt_dir = checkpoint_dir
        self._ckpt_every = int(checkpoint_every)
        self._ckpt_keep = int(checkpoint_keep)
        self._last_ckpt_round = 0
        self._active_solve: Optional[str] = None
        self._solves = 0                   # the ``imm.solve`` span's id
        if isinstance(engine, str):
            self.g_rev = reverse(g)
        else:
            # engine instance passed in: it owns its graph + configuration,
            # so sampling options on the solver would be silently ignored
            if any(v is not None for v in (batch, qcap, ec, model)):
                raise ValueError(
                    "batch/qcap/ec/model have no effect when an engine "
                    "instance is passed; configure the engine instead")
            self.g_rev = getattr(engine, "g_rev", None)
        self._sketch_k_arg = sketch_k
        self._mesh = mesh
        self.key = jax.random.key(seed)
        self._engine_obj = None
        self._store_obj = None
        self._sig = None
        self._sig_problem = None
        self._row_weight_mode = False
        self._node_w_dev = None
        # selection-side certificate of the last approximate-mode (pool-
        # free) solve: lo/hi covered-row bounds, saturation, rel. error
        self._sketch_info = None
        if isinstance(engine, str):
            if engine == "mrim":
                # fail fast like the historical API: the tagged engine's
                # item space is n*t_rounds, not the graph's n nodes — MRIM
                # goes through IMProblem(t_rounds=...), which picks the
                # engine itself
                raise ValueError(
                    "engine 'mrim' samples a tagged item space, not the "
                    "graph's nodes; set t_rounds= on the IMProblem instead "
                    "(the solver resolves the mrim engine per problem)")
            # eager default build: construction happens *outside* any
            # caller transfer guard, so the graph uploads land here — a
            # first solve with a different signature (weights/t_rounds)
            # rebuilds once via prepare(), which callers holding an outer
            # guard invoke explicitly before entering it
            self._prepare(IMProblem(k=1, eps=0.5,
                                    model=self._default_model()))
        elif (engine.item_space == self.n
              and getattr(engine, "root_weights", None) is None):
            # engine instance on the plain node space: build eagerly —
            # cheap (the instance is reused) and keeps `solver.engine is
            # eng` true right after construction
            self._prepare(IMProblem(k=1, eps=0.5,
                                    model=self._default_model()))
        # a tagged (item_space != n) or weighted-root engine INSTANCE
        # defers instead — its first solve must carry the matching
        # t_rounds / node_weights (callers holding an outer transfer guard
        # call prepare(problem) explicitly first)

    def _default_model(self) -> str:
        return "lt" if self._model_arg == "lt" else "ic"

    def _resolve_sketch_k(self, problem: IMProblem) -> Optional[int]:
        """The exact store's sketch size: ``sketch_k=`` as given, else the
        default for an ``early_exit`` problem, whose θ gate reads the
        incremental sketch (its global row numbering keeps the
        occupancy==count identity on any mesh — the on-demand per-shard
        fold does not), else none."""
        if self._sketch_k_arg is None and problem.early_exit:
            return cov.ShardedDeviceRRStore.DEFAULT_SKETCH_K
        return self._sketch_k_arg

    def _ensure_prepared(self):
        if self._sig is None:
            self._prepare(IMProblem(k=1, eps=0.5,
                                    model=self._default_model()))

    @property
    def engine(self):
        self._ensure_prepared()
        return self._engine_obj

    @property
    def store(self) -> "cov.ShardedDeviceRRStore":
        self._ensure_prepared()
        return self._store_obj

    # -- problem-driven engine/store lifecycle ------------------------------
    def prepare(self, problem: IMProblem) -> ResolvedProblem:
        """Pre-build the engine + pool for ``problem`` (idempotent per
        signature).  ``solve(problem)`` calls this itself; call it
        explicitly to do the host-side construction (reverse graph, alias
        table, device placement) *before* entering an outer
        ``jax.transfer_guard("disallow")`` region."""
        return self._prepare(problem)

    def _prepare(self, problem: IMProblem,
                 _store: "cov.ShardedDeviceRRStore | None" = None
                 ) -> ResolvedProblem:
        with spans.span("imm.prepare"):
            r = problem.resolve(self.n)
            # the constructor's model= survives as the default for problems
            # that don't set one (IMProblem.model=None); an explicit model on
            # the problem — including "ic" — always wins
            model = problem.model or self._default_model()
            if problem.t_rounds is not None and model == "lt":
                raise ValueError("MRIM sampling is IC-only (paper §4.8); the "
                                 "solver's default model is 'lt'")
            w = r.node_weights
            sketch_k = self._resolve_sketch_k(problem)
            # approximate (pool-free) mode: the sketch IS the pool, so one
            # always exists, auto-sized from (ε, n) so the certified
            # estimator error stays within ε/2 at design load
            # (core/sketch.auto_sketch_k)
            approx = problem.mode == "approximate"
            if approx and sketch_k is None:
                sketch_k = sketch_mod.auto_sketch_k(problem.eps, self.n)
            # engine/pool lifecycle is keyed on the problem's canonical pool
            # signature (content hash of model/t_rounds/node_weights — see
            # IMProblem.pool_digest): problems differing only in weight
            # *values* can never alias one pool, unlike the old
            # hash(tobytes) tuple key
            if isinstance(self._engine_arg, str):
                name = ("mrim" if problem.t_rounds is not None
                        else resolve_engine_name(self._engine_arg, model))
                sig = ("name", name, problem.pool_digest(model=model),
                       sketch_k)
            else:
                sig = ("inst", id(self._engine_arg), problem.pool_digest(),
                       sketch_k)
            if sig == self._sig:
                return r
            # (re)build engine + pool for this problem signature
            row_weight_mode = False
            if isinstance(self._engine_arg, str):
                opts = dict(self._engine_opts)
                if problem.t_rounds is not None:
                    opts["t_rounds"] = problem.t_rounds
                engine = make_engine(name, self.g_rev, root_weights=w, **opts)
            else:
                engine = self._engine_arg
                eng_w = getattr(engine, "root_weights", None)
                if w is None and eng_w is not None:
                    # converse mismatch: the engine samples roots ∝ its own
                    # weights, so a plain solve on it would silently return
                    # the weighted objective on the uniform scale — a wrong
                    # number
                    raise ValueError(
                        "engine instance draws weighted roots (root_weights "
                        "set) but the problem has no node_weights; set "
                        "node_weights on the IMProblem (or use an unweighted "
                        "engine)")
                if w is not None and not (
                        eng_w is not None
                        and np.array_equal(np.asarray(eng_w, np.float32), w)):
                    # instance without matching weighted-root sampling: fall
                    # back to the importance-weighted row estimator (uniform
                    # roots, rows weighted by node_weights[root])
                    row_weight_mode = True
            if engine.item_space != r.n_items:
                raise ValueError(
                    f"engine {getattr(engine, 'name', '?')!r} samples an "
                    f"item space of {engine.item_space}, not the problem's "
                    f"{r.n_items} items; tagged engines need a matching "
                    f"t_rounds= on the IMProblem")
            if approx:
                # same sampler, same RNG stream — only the batch
                # *destination* changes: appends fold into the pool-free
                # sketch store below
                engine = FusedSketchEngine(engine)
            self._engine_obj = engine
            self.engine_name = getattr(engine, "name", type(engine).__name__)
            self._row_weight_mode = row_weight_mode
            self._node_w_dev = (jax.device_put(w) if row_weight_mode else None)
            # mesh placement is decided exactly once, here: the pool, the
            # sketch, and the selection programs live on this mesh for the
            # solver's lifetime (mesh=None -> the 1-device mesh special case)
            if _store is not None:                   # adopt_pool() hand-off
                want_k = (sketch_mod.resolve_sketch_k(sketch_k)
                          if sketch_k is not None else None)
                if getattr(_store, "pool_free", False) != approx:
                    raise ValueError(
                        "adopted pool kind does not match the problem mode: "
                        "a pool-free sketch store can only back "
                        "mode='approximate' solves, and an exact pool only "
                        "exact ones")
                if (_store.n_nodes != engine.item_space
                        or _store.row_weighted != row_weight_mode
                        or _store.sketch_k != want_k):
                    raise ValueError(
                        "adopted pool does not match the problem signature: "
                        f"store (n={_store.n_nodes}, row_weighted="
                        f"{_store.row_weighted}, sketch_k={_store.sketch_k}) "
                        f"vs engine (n={engine.item_space}, row_weighted="
                        f"{row_weight_mode}, sketch_k={want_k})")
                if self._mesh is not None and _store.mesh != self._mesh:
                    raise ValueError("adopted pool lives on a different mesh "
                                     "than the solver's mesh= argument")
                self._store_obj = _store
            elif approx:
                # pool-free: the flat pool / ids / valid buffers are never
                # allocated — frontier batches fold straight into the packed
                # sketch words (the DiFuseR-mode memory model, DESIGN.md §10)
                self._store_obj = cov.SketchRRStore(
                    engine.item_space, sketch_k=sketch_k, mesh=self._mesh)
            else:
                self._store_obj = cov.ShardedDeviceRRStore(
                    engine.item_space, sketch_k=sketch_k, mesh=self._mesh,
                    row_weighted=row_weight_mode)
            if self.fault_policy is not None:
                # gate pool growth through the policy's "grow" site, so an
                # injected (or real) allocation failure surfaces *before* any
                # buffer is re-allocated and the append stays retryable
                pol = self.fault_policy
                self._store_obj.alloc_check = (
                    lambda store, newcap: pol.check(
                        "grow", {"newcap": newcap,
                                 "bytes": newcap * store.n_shards * 9}))
            self._sig = sig
            self._sig_problem = problem
            store = self._store_obj
            self._stats = IMMStats(
                variant=problem.variant,
                mesh_shape=tuple(int(s) for s in store.mesh.devices.shape),
                pool_sharding=f"{store.axis}:{store.n_shards}")
            self._stats_dirty = False
            # stats accumulate as device scalars; materialized once per
            # sample_until / on `stats` access, not per round
            self._steps_acc = jnp.zeros((), jnp.int32)
            self._ovf_acc = jnp.zeros((), jnp.int32)
            self._ovf_lanes = 0
            # engines advertising full device residency let the solver hold a
            # transfer guard over the whole hot loop; host-path engines (e.g.
            # third-party adapters) fall back to unguarded execution
            self._guard = ("disallow"
                           if getattr(engine, "device_resident", False)
                           else "allow")
            self._sample = getattr(engine, "sample_device", engine.sample)
            # a sharded engine on the *same* mesh hands the store rows that are
            # already resident on their sampling device — no dev0 gather
            if (store.n_shards > 1
                    and getattr(engine, "mesh", None) == store.mesh
                    and hasattr(engine, "sample_sharded")):
                self._sample = engine.sample_sharded
            return r

    # -- pool ownership (serving registry lifecycle) -----------------------
    def pool_bytes(self) -> int:
        """Total live device bytes of the solver's pool + sketch across all
        shards (0 when unprepared) — the serving registry's memory-budget
        accounting unit."""
        if self._store_obj is None:
            return 0
        s = self._store_obj
        return s.n_shards * (s.per_device_pool_bytes() + s.sketch_bytes())

    def export_pool(self) -> PoolLease:
        """Transfer ownership of the prepared pool *out* of the solver.

        Returns a :class:`PoolLease` holding the store, the RNG cursor and
        the stat accumulators; the solver reverts to the unprepared state
        (its next solve builds a fresh pool).  The lease is the only
        remaining reference to the device buffers — dropping it frees
        them; handing it to :meth:`adopt_pool` on a same-graph solver
        resumes sampling/selection bit-identically to this solver.
        """
        if self._sig is None:
            raise RuntimeError("export_pool() needs a prepared solver — "
                               "nothing to export")
        self._materialize_stats()
        lease = PoolLease(
            problem=self._sig_problem, store=self._store_obj, key=self.key,
            stats=self._stats, steps_acc=self._steps_acc,
            ovf_acc=self._ovf_acc, ovf_lanes=self._ovf_lanes,
            active_solve=self._active_solve)
        self._store_obj = None
        self._engine_obj = None
        self._sig = None
        self._sig_problem = None
        self._active_solve = None
        return lease

    def drop_pool(self) -> int:
        """Discard the prepared pool *without* exporting it; returns the
        bytes dropped.  This is the quarantine path (DESIGN.md §8): after a
        solve died mid-flight the device buffers may be ahead of the host
        mirrors (partially-appended pool), so the state must neither serve
        nor be checkpointed — it is simply dereferenced.  No-op on an
        unprepared solver."""
        freed = self.pool_bytes()
        self._store_obj = None
        self._engine_obj = None
        self._sig = None
        self._sig_problem = None
        self._active_solve = None
        return freed

    def adopt_pool(self, lease: PoolLease) -> None:
        """Install an exported pool (same graph, matching signature/options)
        and resume from the lease's RNG cursor and stats."""
        self._sig = None                       # force the rebuild path
        self._prepare(lease.problem, _store=lease.store)
        self.key = lease.key
        self._stats = lease.stats
        self._steps_acc = lease.steps_acc
        self._ovf_acc = lease.ovf_acc
        self._ovf_lanes = lease.ovf_lanes
        self._active_solve = lease.active_solve
        self._stats_dirty = True

    # -- durable pool checkpoints (DESIGN.md §8) ---------------------------
    POOL_CKPT_FORMAT = "im-pool"
    POOL_CKPT_VERSION = 1
    # v2 sub-kind: pool-free (mode="approximate") checkpoints carry only
    # the sketch words + row counters + RNG cursor; the store config's
    # "kind" field dispatches the restore class
    POOL_CKPT_VERSION_SKETCH = 2

    def save_pool(self, ckpt_dir: str, *, keep: Optional[int] = None) -> str:
        """Write the prepared pool as a durable checkpoint: sharded store
        buffers + exact host mirrors, RNG cursor, stat accumulators, and
        the signature problem — everything a fresh process needs to resume
        sampling bit-identically via :meth:`restore_pool`.  Atomic (tmpdir
        + rename, via ``repro.ckpt.checkpoint``), rotated to ``keep``
        checkpoints; the step number is the sampling round count."""
        self._ensure_prepared()
        self._materialize_stats()
        state = dict(self.store.state())
        state["rng_key"] = np.asarray(
            jax.device_get(jax.random.key_data(self.key)))
        state["steps_acc"] = np.asarray(jax.device_get(self._steps_acc))
        state["ovf_acc"] = np.asarray(jax.device_get(self._ovf_acc))
        st = asdict(self._stats)
        st["mesh_shape"] = list(st["mesh_shape"])
        st["history"] = [list(h) for h in st["history"]]
        meta = {
            "format": self.POOL_CKPT_FORMAT,
            "version": (self.POOL_CKPT_VERSION_SKETCH
                        if getattr(self.store, "pool_free", False)
                        else self.POOL_CKPT_VERSION),
            "store": self.store.config(),
            "problem": problem_state(self._sig_problem),
            "stats": st,
            "ovf_lanes": int(self._ovf_lanes),
            "active_solve": self._active_solve,
        }
        return ckpt_mod.save(ckpt_dir, self._stats.rounds, state,
                             keep=self._ckpt_keep if keep is None else keep,
                             meta=meta)

    def restore_pool(self, ckpt_dir: str, *, step: Optional[int] = None
                     ) -> int:
        """Rebuild the pool from a :meth:`save_pool` checkpoint (latest step
        unless ``step=``) and adopt it: subsequent ``sample_until`` rounds
        continue from the saved RNG cursor against the saved buffers,
        bit-identically to the process that wrote the checkpoint.  The
        solver must be configured with the same options and a same-size
        mesh; returns the restored step."""
        if step is None:
            step = ckpt_mod.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(
                    f"no pool checkpoint under {ckpt_dir!r}")
        meta = ckpt_mod.load_manifest(ckpt_dir, step)["meta"]
        if meta.get("format") != self.POOL_CKPT_FORMAT:
            raise ValueError(f"{ckpt_dir!r} step {step} is not an im-pool "
                             f"checkpoint (format={meta.get('format')!r})")
        if meta.get("version") not in (self.POOL_CKPT_VERSION,
                                       self.POOL_CKPT_VERSION_SKETCH):
            raise ValueError(
                f"pool checkpoint version {meta.get('version')} not "
                f"supported (want {self.POOL_CKPT_VERSION} or "
                f"{self.POOL_CKPT_VERSION_SKETCH})")
        items = {k.strip("[]'\""): v
                 for k, v in ckpt_mod.restore_items(ckpt_dir, step).items()}
        kind = meta["store"].get("kind", "sharded")
        store_cls = (cov.SketchRRStore if kind == "sketch"
                     else cov.ShardedDeviceRRStore)
        store = store_cls.from_state(items, meta["store"], mesh=self._mesh)
        st = dict(meta["stats"])
        st["mesh_shape"] = tuple(st["mesh_shape"])
        st["history"] = [tuple(h) for h in st["history"]]
        # explicit device_puts: the whole restore is legal under an outer
        # jax.transfer_guard("disallow")
        lease = PoolLease(
            problem=problem_from_state(meta["problem"]), store=store,
            key=jax.random.wrap_key_data(
                jax.device_put(np.asarray(items["rng_key"]))),
            stats=IMMStats(**st),
            steps_acc=jax.device_put(np.asarray(items["steps_acc"])),
            ovf_acc=jax.device_put(np.asarray(items["ovf_acc"])),
            ovf_lanes=int(meta["ovf_lanes"]),
            active_solve=meta.get("active_solve"))
        self.adopt_pool(lease)
        self._last_ckpt_round = self._stats.rounds
        return int(step)

    # -- stats -------------------------------------------------------------
    @property
    def stats(self) -> IMMStats:
        self._ensure_prepared()
        self._materialize_stats()
        return self._stats

    def _materialize_stats(self):
        if self._stats_dirty:
            steps, ovf = (int(x) for x in spans.fetch("stats",
                (self._steps_acc, self._ovf_acc)))
            st = self._stats
            st.sampling_steps = steps
            st.n_rr_sampled = self.store.n_rr
            st.overflow_fraction = (ovf / self._ovf_lanes
                                    if self._ovf_lanes else 0.0)
            st.per_device_pool_bytes = self.store.per_device_pool_bytes()
            self._stats_dirty = False

    # -- sampling ----------------------------------------------------------
    def _round(self):
        """One sampling round, *transactional* w.r.t. the RNG cursor: the
        split key is committed only after the batch has landed in the
        store, so a failed (and policy-retried) round replays the exact
        same subkey against unchanged buffers — the fault-free and
        retried streams stay bit-identical (DESIGN.md §8)."""
        self._ensure_prepared()
        pol = self.fault_policy
        timer = pol.round_timer if pol is not None else None
        if timer is not None:
            timer.start()
        with spans.span("imm.round", round=self._stats.rounds + 1):
            with spans.span("imm.sample"):
                new_key, sub = _split_key(self.key)
                batch = (pol.run(lambda: self._sample(sub), "sample")
                         if pol is not None else self._sample(sub))

            def _append():
                if self._row_weight_mode:
                    if batch.roots is None:
                        raise ValueError(
                            "weighted problem on an engine that neither "
                            "supports root_weights nor reports batch roots "
                            "— cannot form the importance-weighted "
                            "estimator")
                    self.store.append_batch(
                        batch, row_w=_gather_row_weights(self._node_w_dev,
                                                         batch.roots))
                else:
                    self.store.append_batch(batch)

            with spans.span("imm.append") as sp:
                before = self.store.n_elems
                if pol is not None:
                    pol.run(_append, "append")
                else:
                    _append()
                elems = self.store.n_elems - before
                sp.set_metadata(elements=elems)
            self._stats.pool_elements += elems
            self.key = new_key   # commit the cursor: the round is durable
            self._steps_acc, self._ovf_acc = _accum_round_stats(
                self._steps_acc, self._ovf_acc, batch.steps,
                batch.overflowed)
            self._ovf_lanes += int(np.prod(batch.overflowed.shape))
            self._stats.rounds += 1
            self._stats_dirty = True
        if timer is not None:
            dt = timer.stop()
            if timer.is_straggler(dt):
                pol.straggler_rounds += 1

    def sample_until(self, theta: int):
        # the loop condition reads the store's exact host-mirrored row count
        # (explicit scalar fetch per append — gIM's Alg. 6 N_RR readback);
        # no pool data crosses to the host.  A restored solver re-enters
        # here with n_rr already at the saved watermark and simply tops up.
        with spans.span("imm.sample_until", theta=int(theta)):
            while self.store.n_rr < theta:
                self._round()
                if (self._ckpt_dir and self._ckpt_every > 0
                        and self._stats.rounds - self._last_ckpt_round
                        >= self._ckpt_every):
                    self.save_pool(self._ckpt_dir)
                    self._last_ckpt_round = self._stats.rounds
        self._materialize_stats()

    def _store(self) -> cov.RRStore:
        return self.store.snapshot()

    # -- variant plumbing --------------------------------------------------
    def _selection_spec(self, r: ResolvedProblem):
        """None for plain problems (the bit-identical fast paths); a
        :class:`~repro.core.coverage.SelectionSpec` otherwise.  A weighted
        problem whose engine samples roots ∝ w needs *no* selection change
        (rows are equi-weighted by construction), so weights alone only
        force a spec in row-weight fallback mode."""
        p = r.problem
        if p.is_plain and not self._row_weight_mode:
            return None
        if (p.node_weights is not None and not self._row_weight_mode
                and p.budget is None and p.candidates is None
                and p.t_rounds is None):
            return None
        if p.t_rounds is not None:
            n_group, n_groups, quota = r.n_nodes, r.t_rounds, p.k
        else:
            n_group, n_groups, quota = r.n_items, 1, r.k_steps
        costs = None
        if r.costs is not None:
            costs = np.tile(r.costs, r.t_rounds)
        return cov.SelectionSpec(
            k_steps=r.k_steps, n_group=n_group, n_groups=n_groups,
            group_quota=quota, cand=r.cand_mask_items, costs=costs,
            budget=p.budget, weighted=self._row_weight_mode)

    def _early_exit_skip(self, r: ResolvedProblem, threshold: float) -> bool:
        """Sketch-driven θ early exit (Alg. 2 LB gate): skip the exact
        selection of one LB iteration when even an *upper bound* on the
        achievable coverage cannot pass the ``est >= threshold`` check.

        The bound is linear counting over the per-item sketch occupancy
        (one mesh-parallel popcount sweep).  It is only applied in the
        exact-safe regime ``n_rr <= sketch_k`` with ``"mod"`` bucketing,
        where occupancy == exact per-item row count and linear counting can
        only round *up* — so ``Σ top-k LC(occ) >= coverage of any k seeds``
        and skipping provably never changes the loop's outcome (the exact
        est would have failed the check too).  Weighted/budgeted problems
        skip the gate (their objective is not a row count).
        """
        p = r.problem
        st = self.store
        if (not p.early_exit or st.sketch_k is None
                or st.sketch_mode != "mod" or self._row_weight_mode
                or r.node_weights is not None or p.budget is not None):
            return False
        n_rr = st.n_rr
        if n_rr == 0 or n_rr > st.sketch_k:
            return False
        fns = cov._mesh_select_fns(st.mesh)
        empty = jax.device_put(
            np.zeros((st.n_shards, st.sketch_k // 32), np.uint32),
            st._sh_buf)
        occ = np.asarray(spans.fetch("lb_bound", fns.sweep(
            st.sketch_words_mesh(), empty,
            stripe=st.sketch_rows // st.n_shards)))[:r.n_items]
        counts = sketch_mod.linear_count(occ, st.sketch_k)
        mask = r.cand_mask_items
        if mask is not None:
            counts = counts[mask]
        top = float(np.sort(counts)[::-1][:r.k_steps].sum())
        est_ub = r.scale * min(float(n_rr), top) / max(n_rr, 1)
        return est_ub < threshold

    def _approx_bounds(self, r: ResolvedProblem, info: dict):
        """Certified spread bounds from a sketch-selection certificate
        (:func:`~repro.core.coverage.select_seeds_sketch` ``info_out``):
        lower from the deterministic Δocc sum, upper from the z-sigma
        linear-counting error — widened to the whole pool on a saturated
        union row, never a silently-finite estimate."""
        n_rr = max(int(info.get("n_rr", 0)), 1)
        return (r.scale * float(info["lo_rows"]) / n_rr,
                r.scale * float(info["hi_rows"]) / n_rr)

    def _degraded_result(self, r: ResolvedProblem) -> IMResult:
        """Deadline-clipped answer from the pool sampled so far (DESIGN.md
        §8): greedy over the packed coverage sketch (certified Δ-occupancy
        lower bounds per pick) with an exact-Occur union upper bound, never
        a silently wrong exact answer.  Only counting objectives qualify —
        weighted/budgeted/MRIM objectives have no certified sketch
        estimate, so they raise :class:`DeadlineExceeded` instead."""
        p = r.problem
        st = self.store
        if (p.budget is not None or r.node_weights is not None
                or self._row_weight_mode or p.t_rounds is not None):
            raise DeadlineExceeded(
                f"deadline expired mid-solve and the {p.variant!r} "
                "objective has no certified sketch estimate")
        n_rr = st.n_rr
        if n_rr == 0:
            raise DeadlineExceeded("deadline expired before any sampling "
                                   "round completed")
        if getattr(st, "pool_free", False):
            # approximate mode clipped mid-solve: its selection path is
            # already the certified sketch greedy — run it over whatever
            # was folded so far and mark the answer degraded
            info = {}
            res = cov.select_seeds_sketch(st, r.k_steps,
                                          cand=r.cand_mask_items,
                                          info_out=info)
            seeds, gains, frac = spans.fetch("result",
                (res.seeds, res.gains, res.frac))
            seeds, gains = np.asarray(seeds), np.asarray(gains)
            live = seeds < r.n_items
            seeds, gains = seeds[live], gains[live]
            frac = float(frac)
            self._materialize_stats()
            self._stats.frac_covered = frac
            self._stats.variant = p.variant
            return IMResult(
                seeds=seeds.astype(np.int64), spread=r.scale * frac,
                gains=gains.astype(np.int64), frac=frac,
                stats=self.stats, problem=p, n_nodes=self.n,
                degraded=True, spread_bounds=self._approx_bounds(r, info))
        fns = cov._mesh_select_fns(st.mesh)
        # exact per-item row counts: the union upper bound + the
        # sketch-free fallback ranking (one mesh reduction, explicit fetch)
        occ_exact = np.asarray(spans.fetch("occur", fns.occur(
            st._flat, st._valid, n=st.n_nodes)), np.int64)[:r.n_items]
        mask = (np.ones(r.n_items, bool) if r.cand_mask_items is None
                else r.cand_mask_items.copy())
        seeds, lb_gains = [], []
        if st.sketch_k is not None:
            # sketch greedy: k sweeps, each pick scored by its certified
            # Δocc (distinct sketch buckets newly covered ≤ distinct rows
            # newly covered), the pick folded into the union sketch
            stripe = st.sketch_rows // st.n_shards
            sk = st.sketch_words_mesh()
            cov_sk = jax.device_put(
                np.zeros((st.n_shards, st.sketch_k // 32), np.uint32),
                st._sh_buf)
            for _ in range(r.k_steps):
                docc = np.asarray(spans.fetch("sketch_gain",
                    fns.sweep(sk, cov_sk, stripe=stripe)))[:r.n_items]
                docc = np.where(mask, docc, -1)
                u = int(docc.argmax())
                if docc[u] < 0:
                    break
                seeds.append(u)
                lb_gains.append(int(docc[u]))
                mask[u] = False
                cov_sk = fns.union(
                    cov_sk, sk, jax.device_put(np.int32(u), st._sh_rep))
            covered_lb = float(sum(lb_gains))
        else:
            # no sketch on this pool: rank by exact per-item counts
            # (overlap-blind).  Any single seed covers occ_exact[seed]
            # rows, so the best pick alone is a certified lower bound.
            order = np.argsort(np.where(mask, occ_exact, -1))[::-1]
            seeds = [int(u) for u in order[:r.k_steps] if mask[u]]
            lb_gains = [int(occ_exact[u]) for u in seeds]
            covered_lb = float(max(lb_gains, default=0))
        covered_ub = float(min(n_rr, sum(int(occ_exact[u]) for u in seeds)))
        # point estimate: linear counting on the union occupancy, clamped
        # into the certified bracket
        if st.sketch_k is not None and seeds:
            est = float(sketch_mod.linear_count(
                np.asarray([int(sum(lb_gains))]), st.sketch_k)[0])
        else:
            est = covered_lb
        est = min(max(est, covered_lb), covered_ub)
        frac = est / n_rr
        self._materialize_stats()
        self._stats.frac_covered = frac
        self._stats.variant = p.variant
        lo, hi = (r.scale * covered_lb / n_rr, r.scale * covered_ub / n_rr)
        return IMResult(
            seeds=np.asarray(seeds, np.int64), spread=r.scale * frac,
            gains=np.asarray(lb_gains, np.int64), frac=frac,
            stats=self.stats, problem=p, n_nodes=self.n,
            degraded=True, spread_bounds=(lo, hi))

    # -- full IMM ----------------------------------------------------------
    def solve(self, problem: Optional[IMProblem] = None,
              *_args, **_kw) -> IMResult:
        """Solve an :class:`~repro.core.problem.IMProblem` -> ``IMResult``.

        The pre-problem positional form ``solve(k, eps)`` was removed after
        its one-release deprecation window (DESIGN.md §6): construct an
        ``IMProblem`` and read ``res.seeds / res.spread / res.stats``.
        """
        if not isinstance(problem, IMProblem) or _args or _kw:
            raise TypeError(
                "IMMSolver.solve() takes exactly one IMProblem; the "
                "deprecated solve(k, eps) form was removed — write "
                "solve(IMProblem(k=..., eps=..., max_theta=...)) and set "
                "ell/max_theta on the problem (DESIGN.md §6)")
        return self.solve_problem(problem)

    def solve_problem(self, problem: IMProblem, *,
                      deadline_s: Optional[float] = None) -> IMResult:
        """``deadline_s`` (seconds of remaining budget, serving-side) turns
        on the in-solve deadline check between LB iterations: once it
        expires the solve returns a ``degraded=True`` sketch-bound answer
        over the pool sampled so far instead of blowing the deadline —
        or raises :class:`~repro.ft.failures.DeadlineExceeded` when the
        objective has no certified sketch estimate (DESIGN.md §8)."""
        self._solves += 1
        with spans.solve(self._solves, self._count_fetch,
                         variant=problem.variant):
            r = self._prepare(problem)
            spec = self._selection_spec(r)
            scale = r.scale
            p = problem
            k_theta = p.k if p.k is not None else r.k_steps
            deadline = (time.monotonic() + deadline_s
                        if deadline_s is not None else None)
            # resume: a restored pool carrying this very solve's digest
            # picks the LB loop back up at stats.lb_completed + 1 —
            # re-running completed iterations over the larger restored pool
            # would shift their est/break points and fork from the
            # uninterrupted stream
            sig = p.signature_digest()
            resume = (self._active_solve == sig)
            self._active_solve = sig

            def _expired() -> bool:
                return (deadline is not None
                        and time.monotonic() >= deadline)

            self._sketch_info = None
            pool_free = getattr(self.store, "pool_free", False)
            method = "sketch" if pool_free else "fused"
            self._stats.selection = method

            def _select():
                if pool_free:
                    # approximate mode: no pool to verify against —
                    # selection runs purely on sketch estimates and leaves
                    # its error certificate in _sketch_info for the final
                    # spread_bounds
                    info = {}
                    self._sketch_info = info
                    fn = (lambda: cov.select_seeds_sketch(
                        self.store, r.k_steps, cand=r.cand_mask_items,
                        info_out=info))
                else:
                    fn = (lambda: self.store.select(r.k_steps, spec=spec))
                if self.fault_policy is not None:
                    # ctx identifies the request so a match-gated injector
                    # can poison one problem in a batch (serving isolation
                    # tests)
                    return self.fault_policy.run(
                        fn, "select", {"problem": p, "k": r.k_steps})
                return fn()

            with jax.transfer_guard(self._guard):
                if p.theta is not None:
                    # fixed-θ mode (benchmarks, MRIM's Table-3 experiment):
                    # sample to θ, one selection, no LB loop.  Re-entry
                    # after a restore needs no resume bookkeeping:
                    # sample_until tops up from the watermark and selection
                    # is pool-deterministic.
                    self._stats.theta = p.theta
                    self._stats.lb = 1.0
                    self.sample_until(p.theta)
                    if _expired():
                        return self._degraded_result(r)
                    with spans.span("imm.select", method=method):
                        res = _select()
                elif resume and self._stats.theta:
                    # the LB loop had already concluded when the checkpoint
                    # was written; only the final θ top-up remains
                    self.sample_until(self._stats.theta)
                    with spans.span("imm.select", method=method):
                        res = _select()
                else:
                    lam_p, lam_star, eps_p, _ = imm_theta_params(
                        self.n, k_theta, p.eps, p.ell)
                    lb = self._stats.lb if resume else 1.0
                    start_i = (self._stats.lb_completed + 1) if resume else 1
                    res = None
                    for i in range(start_i,
                                   max(int(math.log2(self.n)), 2)):  # Alg. 2
                        if _expired():
                            return self._degraded_result(r)
                        x = scale / (2.0 ** i)
                        theta_i = int(math.ceil(lam_p / x))
                        if p.max_theta:
                            theta_i = min(theta_i, p.max_theta)
                        with spans.span("imm.lb_iter", i=i, theta=theta_i):
                            self.sample_until(theta_i)
                            threshold = (1.0 + eps_p) * x
                            if self._early_exit_skip(r, threshold):
                                self._stats.early_exit_skips += 1
                                self._stats.history.append(
                                    ("lb_skip", i, theta_i))
                                self._stats.lb_completed = i
                                continue
                            with spans.span("imm.select", method=method):
                                res = _select()
                                # explicit scalar fetch: Alg. 2 L7 break is
                                # host control
                                est = scale * float(
                                    spans.fetch("lb_frac", res.frac))
                        self._stats.lb_iters = i
                        self._stats.history.append(
                            ("lb_iter", i, theta_i, est))
                        if est >= threshold:                     # Alg. 2 L7
                            lb = est / (1.0 + eps_p)             # Alg. 2 L8
                            break
                        self._stats.lb_completed = i
                        self._stats.lb = lb
                    theta = int(math.ceil(lam_star / lb))
                    if p.max_theta:
                        theta = min(theta, p.max_theta)
                    self._stats.theta = theta
                    self._stats.lb = lb
                    if _expired():
                        return self._degraded_result(r)
                    self.sample_until(theta)
                    with spans.span("imm.select", method=method):
                        res = _select()
            self._active_solve = None
            # final result materialization — the loop's only bulk transfer
            spent_dev = getattr(res, "spent", None)
            fetched = spans.fetch(
                "result", (res.seeds, res.gains, res.frac)
                + ((spent_dev,) if spent_dev is not None else ()))
            seeds, gains, frac = fetched[0], fetched[1], float(fetched[2])
            spent = float(fetched[3]) if spent_dev is not None else 0.0
            seeds = np.asarray(seeds)
            gains = np.asarray(gains)
            live = seeds < r.n_items      # budgeted scans pad with sentinels
            seeds, gains = seeds[live], gains[live]
            self._stats.frac_covered = frac
            self._stats.variant = p.variant
            self._stats.budget_spent = spent
            spread = scale * frac                                # Eq. (3)
            bounds = (self._approx_bounds(r, self._sketch_info)
                      if self._sketch_info else None)
            return IMResult(seeds=seeds, spread=spread, gains=gains,
                            frac=frac, stats=self.stats, problem=p,
                            n_nodes=self.n, cost=spent, spread_bounds=bounds)

    def _count_fetch(self):
        self._stats.host_fetches += 1

    def solve_stacked(self, problems: "list[IMProblem]") -> "list[IMResult]":
        """Fixed-θ micro-batch solve: one padded
        :func:`~repro.core.coverage.select_seeds_stacked` scan over the
        shared pool instead of one selection per request — the serving
        front's batched-selection path (DESIGN.md §11).

        Every problem must pin the same ``theta`` and share this solver's
        pool signature (the front batches by registry key, which guarantees
        both — ``_prepare`` would rebuild the pool otherwise), and each
        returned :class:`IMResult` is bit-identical to ``solve_problem`` on
        the same solver at any mesh width.  ``mode="approximate"`` and the
        row-weighted fallback estimator are not stackable; callers route
        those per request.
        """
        if not problems:
            return []
        theta = problems[0].theta
        for p in problems:
            if p.theta is None or p.theta != theta:
                raise ValueError(
                    "solve_stacked needs one common fixed theta= on every "
                    "problem (LB-loop solves cannot share a scan)")
            if p.mode == "approximate":
                raise ValueError("solve_stacked needs the exact pool; "
                                 "approximate-mode problems go solo")
        rs, sig0 = [], None
        for p in problems:
            rs.append(self._prepare(p))
            if sig0 is None:
                sig0 = self._sig
            elif self._sig != sig0:
                raise ValueError("all stacked problems must share one pool "
                                 "signature (solver_key batches do)")
        if self._row_weight_mode:
            raise ValueError("solve_stacked does not support the "
                             "row-weighted fallback estimator")
        specs = [self._selection_spec(r) for r in rs]
        n_group = n_groups = None
        reqs = []
        for r, spec in zip(rs, specs):
            if spec is None:
                reqs.append(cov.StackedRequest(k_steps=r.k_steps))
                continue
            reqs.append(cov.StackedRequest(
                k_steps=spec.k_steps, plain=False, cand=spec.cand,
                costs=spec.costs, budget=spec.budget,
                quota=spec.group_quota))
            if n_group is None:
                n_group, n_groups = spec.n_group, spec.n_groups
            elif (n_group, n_groups) != (spec.n_group, spec.n_groups):
                # unreachable when batched by registry key: the geometry
                # derives from t_rounds, which is part of the pool signature
                raise ValueError("mixed group geometry in a stacked batch")
        with jax.transfer_guard(self._guard):
            self._stats.theta = theta
            self._stats.lb = 1.0
            self.sample_until(theta)
            sel = (lambda: cov.select_seeds_stacked(
                self.store, reqs,
                n_group=n_group if n_group is not None else self.n,
                n_groups=n_groups if n_groups is not None else 1))
            if self.fault_policy is not None:
                # the scan is one fused call, but the "select" fault
                # boundary still fires once per request with the solo
                # ctx — a match-gated injector can poison one problem,
                # and the serving front quarantines the batch and
                # re-runs each request alone (front._run_group)
                for p, r in zip(problems, rs):
                    self.fault_policy.run(
                        lambda: None, "select",
                        {"problem": p, "k": r.k_steps, "stacked": True})
                out = self.fault_policy.run(
                    sel, "select", {"stacked_batch": len(problems)})
            else:
                out = sel()
        seeds_all, gains_all, frac_all, spent_all = jax.device_get(
            (out.seeds, out.gains, out.frac, out.spent))
        results = []
        for i, (p, r) in enumerate(zip(problems, rs)):
            seeds = np.asarray(seeds_all[i, :r.k_steps])
            gains = np.asarray(gains_all[i, :r.k_steps])
            live = seeds < r.n_items      # sentinel trim, as in solve_problem
            seeds, gains = seeds[live], gains[live]
            frac = float(frac_all[i])
            spent = float(spent_all[i])
            self._stats.frac_covered = frac
            self._stats.variant = p.variant
            self._stats.budget_spent = spent
            results.append(IMResult(
                seeds=seeds, spread=r.scale * frac, gains=gains, frac=frac,
                stats=self.stats, problem=p, n_nodes=self.n, cost=spent))
        return results

    # -- streaming graphs (DESIGN.md §9) -----------------------------------
    def resolve_incremental(self, problem: IMProblem, deltas, *,
                            min_surviving_fraction: float = 0.0,
                            deadline_s: Optional[float] = None) -> IMResult:
        """Apply edge ``deltas`` (``repro.core.stream`` spec) to the
        solver's graph and re-solve ``problem``, reusing every RR set the
        deltas provably leave untouched.

        A forward edge u→v lives in reverse-adjacency row v, and an RR-BFS
        only examines the rows of nodes it visits — so a pre-delta RR set
        containing no destination of any changed edge ran an identical-law
        trajectory on both graphs and survives as an exact post-delta
        sample *conditioned on avoiding the changed rows*
        (:func:`repro.core.stream.affected_nodes`; DESIGN.md §9 states the
        guarantee and the residual conditioning term, which the KS/5σ
        conformance suite polices).  Touched rows are evicted
        (``evict_rows_containing``), the engine rebuilds on the mutated
        reverse graph, and θ tops back up through the normal
        FaultPolicy-wrapped ``sample_until`` loop — checkpoints, resume and
        the transfer guard all keep working.

        The pool is reused only when its signature matches ``problem``
        (same pool digest / engine / sketch) — otherwise, and when fewer
        than ``min_surviving_fraction`` of the rows survive, the solve
        falls back to a cold pool on the post-delta graph.  MRIM problems
        (``t_rounds``) are rejected: their tagged item space has no
        per-node invalidation frontier.  Reuse bookkeeping lands in
        ``self.last_incremental`` and the stats history (``"delta"``
        entry).
        """
        from repro.core import stream as stream_mod
        if not isinstance(self._engine_arg, str):
            raise ValueError(
                "resolve_incremental needs a string engine= (the solver "
                "rebuilds its engine on the mutated graph); an engine "
                "instance owns its own graph and cannot be re-pointed")
        if problem.t_rounds is not None:
            raise ValueError(
                "resolve_incremental does not support MRIM (t_rounds=): "
                "the round-tagged item space has no per-node invalidation "
                "frontier")
        if problem.mode == "approximate":
            raise ValueError(
                "resolve_incremental needs the exact pool (mode="
                "'approximate' keeps no RR rows to invalidate); re-solve "
                "from a cold sketch instead")
        d = stream_mod.as_deltas(deltas)
        new_g = stream_mod.apply_edge_deltas(self.g, d)
        aff = stream_mod.affected_nodes(d)
        # reuse is sound only for a same-signature pool: the expected sig
        # mirrors _prepare's keying exactly
        model = problem.model or self._default_model()
        sketch_k = self._resolve_sketch_k(problem)
        name = resolve_engine_name(self._engine_arg, model)
        want_sig = ("name", name, problem.pool_digest(model=model), sketch_k)
        store = self._store_obj if self._sig == want_sig else None
        info = {"affected_nodes": int(aff.shape[0]),
                "n_rr_before": store.n_rr if store is not None else 0,
                "rows_dropped": 0, "rows_kept": 0,
                "surviving_fraction": 0.0, "reused": False}
        if store is not None:
            ev = store.evict_rows_containing(aff)
            info["rows_dropped"] = int(ev["rows_dropped"])
            info["rows_kept"] = int(ev["rows_kept"])
            if info["n_rr_before"]:
                info["surviving_fraction"] = (info["rows_kept"]
                                              / info["n_rr_before"])
            if info["surviving_fraction"] < min_surviving_fraction:
                store = None                     # cold restart: too few left
        # swap in the post-delta graph and force the engine rebuild; the
        # RNG cursor carries over (sampling continues the stream)
        self.g = new_g
        self.n = new_g.n_nodes
        self.g_rev = reverse(new_g)
        self._sig = None
        self._engine_obj = None
        self._active_solve = None
        self._last_ckpt_round = 0
        if store is not None:
            # adoption path: fresh stats/accumulators, surviving pool kept
            self._prepare(problem, _store=store)
            info["reused"] = True
            self._stats.history.append(
                ("delta", info["rows_dropped"], info["rows_kept"]))
        else:
            self._store_obj = None
        self.last_incremental = info
        return self.solve_problem(problem, deadline_s=deadline_s)


_SOLVER_KEYS = frozenset(("engine", "batch", "qcap", "ec", "model", "seed",
                          "sketch_k", "mesh", "fault_policy",
                          "checkpoint_dir", "checkpoint_every",
                          "checkpoint_keep"))
_PROBLEM_KEYS = frozenset(("model", "ell", "max_theta", "node_weights",
                           "costs", "budget", "candidates", "t_rounds",
                           "theta", "early_exit", "mode"))


def imm(g: CSRGraph, k: Optional[int] = None, eps: Optional[float] = None,
        **kw):
    """One-shot convenience wrapper; returns (seeds, spread_estimate, stats).

    Keyword arguments split between the solver (engine/batch/sketch_k/...)
    and the problem (node_weights/costs/budget/candidates/t_rounds/...);
    anything else raises ``TypeError`` — the historical whitelist filter
    silently swallowed typos like ``sketchk=64``.
    """
    unknown = set(kw) - _SOLVER_KEYS - _PROBLEM_KEYS
    if unknown:
        raise TypeError("imm() got unexpected keyword argument(s): "
                        + ", ".join(sorted(unknown)))
    solver_kw = {k_: v for k_, v in kw.items() if k_ in _SOLVER_KEYS}
    pkw = {k_: v for k_, v in kw.items()
           if k_ in _PROBLEM_KEYS and k_ != "model" and v is not None}
    if kw.get("model") is not None:
        pkw["model"] = kw["model"]
    if k is not None:
        pkw["k"] = k
    if eps is not None:
        pkw["eps"] = eps
    res = IMMSolver(g, **solver_kw).solve_problem(IMProblem(**pkw))
    return res.seeds, res.spread, res.stats


def imm_result(g: CSRGraph, problem: IMProblem, **solver_kw) -> IMResult:
    """Typed one-shot: ``IMMSolver(g, **solver_kw).solve(problem)``."""
    unknown = set(solver_kw) - _SOLVER_KEYS
    if unknown:
        raise TypeError("imm_result() got unexpected keyword argument(s): "
                        + ", ".join(sorted(unknown)))
    return IMMSolver(g, **solver_kw).solve_problem(problem)
