"""Declarative IM problem spec: one ``solve(problem)`` surface for every
variant the paper claims the RIS pipeline covers (§variants).

The gIM paper closes on the observation that the same sampling+coverage
pipeline "can solve other variations of the IM problem, only by applying
minor modifications".  :class:`IMProblem` turns each of those modifications
into a declarative knob, and the solver stack (``core/imm.py``,
``core/coverage.py``, ``core/engine.py``) threads them through every layer:

* **plain IM** — ``IMProblem(k=10, eps=0.3)``: uniform roots, top-k greedy.
* **weighted IM** (Cohen et al., sketch-based IM with per-node utilities) —
  ``node_weights=w``: engines draw roots ∝ ``w`` through the shared alias
  table (:func:`repro.core.engine.draw_roots`), so Eq. 3 estimates
  ``Σ_v w_v · P[v influenced]`` and the spread scale becomes ``Σ w``.
* **budgeted IM** — ``costs=c, budget=B`` *replacing* ``k``: cost-ratio lazy
  greedy (argmax of marginal-gain / cost among affordable nodes) until the
  budget is exhausted.
* **candidate-restricted / targeted IM** — ``candidates=mask_or_ids``: the
  greedy argmax only ever picks inside the candidate set.
* **MRIM** (paper §4.8) — ``t_rounds=T``: T round-tagged BFS per sample on
  the ``round * n + node`` item space, per-round seed quota ``k`` (the
  cross-round greedy of CR-NAIMM as a *group-budget* constraint).

``theta=`` pins a fixed RR-pool size (skipping the Alg. 2 LB loop — the
fixed-ε benchmark mode of ``solve_mrim``); ``early_exit=`` gates the LB
escalation on the sketch's linear-counting coverage bound (see
``IMMSolver._early_exit_skip``), provably without changing the final
seeds/θ.

Everything here is host-side spec + validation; no jax imports.  The solver
resolves a problem once per solve into a :class:`ResolvedProblem` carrying
normalized numpy arrays.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, fields
from typing import Any, Optional

import numpy as np


def _as_node_array(x, n: int, name: str, dtype) -> np.ndarray:
    a = np.asarray(x, dtype=dtype)
    if a.shape != (n,):
        raise ValueError(f"{name} must have shape ({n},), got {a.shape}")
    return a


def candidates_mask(candidates, n: int) -> np.ndarray:
    """Normalize a candidate spec (bool mask or iterable of node ids) into
    an (n,) bool mask."""
    a = np.asarray(candidates)
    if a.dtype == bool:
        if a.shape != (n,):
            raise ValueError(f"candidates mask must have shape ({n},), "
                             f"got {a.shape}")
        mask = a.copy()
    else:
        ids = a.astype(np.int64).reshape(-1)
        if ids.size == 0:
            raise ValueError("candidates must be non-empty")
        if (ids < 0).any() or (ids >= n).any():
            raise ValueError(f"candidate ids must lie in [0, {n})")
        mask = np.zeros(n, bool)
        mask[ids] = True
    if not mask.any():
        raise ValueError("candidates must select at least one node")
    return mask


def _digest_value(h: "hashlib._Hash", name: str, value) -> None:
    """Fold one field into a content hash, collision-safely.

    Arrays contribute dtype + shape + raw bytes (two weight vectors with
    equal python ``hash`` of their id, or equal repr, still hash apart);
    scalars contribute their repr; every field is framed by its name and a
    terminator so adjacent fields can never alias.
    """
    h.update(name.encode())
    h.update(b"=")
    if value is None:
        h.update(b"None")
    elif isinstance(value, np.ndarray) or hasattr(value, "__array__") or \
            isinstance(value, (list, tuple)):
        a = np.asarray(value)
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    else:
        h.update(repr(value).encode())
    h.update(b";")


# fields whose value changes what the *sampler* produces (and therefore
# which engine + RR pool a solve needs): the diffusion model picks the
# engine, t_rounds the tagged item space, node_weights the root
# distribution, mode the store species itself (a pool-free sketch store
# can never back an exact solve or vice versa — keying it here separates
# warm-solver registry entries and serving micro-batches in one place).
# Everything else (k, eps, candidates, costs, budget, theta, ...) only
# changes selection / the θ schedule and can share a pool.
_POOL_FIELDS = ("model", "t_rounds", "node_weights", "mode")


@dataclass(frozen=True)
class IMProblem:
    """Declarative influence-maximization problem (see module docstring).

    Exactly one of ``k`` / ``budget`` must be given; ``budget`` implies the
    budgeted variant (``costs`` default to unit costs).  ``t_rounds``
    requires ``k`` (the per-round quota) and is incompatible with
    ``budget``.  ``candidates``/``node_weights``/``costs`` are specified
    over the *base* node space ``[0, n)`` — for MRIM they broadcast across
    rounds.
    """
    k: Optional[int] = None
    eps: float = 0.5
    model: Optional[str] = None        # None = inherit the solver's default
    node_weights: Optional[Any] = None
    costs: Optional[Any] = None
    budget: Optional[float] = None
    candidates: Optional[Any] = None
    t_rounds: Optional[int] = None
    ell: float = 1.0
    max_theta: Optional[int] = None
    theta: Optional[int] = None
    early_exit: bool = False
    mode: str = "exact"

    def __post_init__(self):
        if self.mode not in ("exact", "approximate"):
            raise ValueError(f"unknown mode {self.mode!r}; expected 'exact' "
                             "or 'approximate'")
        if self.mode == "approximate":
            # the pool-free engine scores seeds on row-count sketches only;
            # anything that weights rows or re-reads the pool after
            # sampling (budget ratios, MRIM round tags) needs the exact
            # store.  Candidate restriction is fine — it only masks the
            # sweep.
            if self.node_weights is not None:
                raise ValueError("mode='approximate' does not support "
                                 "node_weights (row-weighted pools need the "
                                 "exact store)")
            if self.budget is not None:
                raise ValueError("mode='approximate' does not support "
                                 "budget= (cost-ratio greedy needs exact "
                                 "marginals)")
            if self.t_rounds is not None:
                raise ValueError("mode='approximate' does not support "
                                 "t_rounds= (MRIM needs the tagged pool)")
        if (self.k is None) == (self.budget is None):
            raise ValueError("exactly one of k= (cardinality) or budget= "
                             "(budgeted IM) must be set")
        if self.k is not None and (not isinstance(self.k, (int, np.integer))
                                   or self.k < 1):
            raise ValueError(f"k must be a positive int, got {self.k!r}")
        if self.budget is not None:
            if self.budget <= 0:
                raise ValueError("budget must be positive")
            if self.t_rounds is not None:
                raise ValueError("budgeted MRIM (budget= with t_rounds=) is "
                                 "not supported; give a per-round k instead")
        if self.costs is not None and self.budget is None:
            raise ValueError("costs= requires budget= (budgeted IM)")
        if self.t_rounds is not None and self.t_rounds < 1:
            raise ValueError("t_rounds must be >= 1")
        if self.model not in (None, "ic", "lt"):
            raise ValueError(f"unknown diffusion model {self.model!r}")
        if self.model == "lt" and self.t_rounds is not None:
            raise ValueError("MRIM sampling is IC-only (paper §4.8)")
        if not (0.0 < self.eps < 1.0):
            raise ValueError("eps must lie in (0, 1)")
        if self.theta is not None and self.theta < 1:
            raise ValueError("theta must be >= 1")

    # -- derived -----------------------------------------------------------
    @property
    def is_plain(self) -> bool:
        """True iff the problem is exactly the historical top-k solve
        (selection and sampling take the untouched fast paths)."""
        return (self.node_weights is None and self.budget is None
                and self.candidates is None and self.t_rounds is None)

    @property
    def variant(self) -> str:
        knobs = []
        if self.node_weights is not None:
            knobs.append("weighted")
        if self.budget is not None:
            knobs.append("budgeted")
        if self.candidates is not None:
            knobs.append("candidates")
        if self.t_rounds is not None:
            knobs.append("mrim")
        return "+".join(knobs) if knobs else "plain"

    # -- canonical signatures ----------------------------------------------
    def signature_digest(self) -> str:
        """Frozen content hash of the *whole* problem — every field, arrays
        by dtype+shape+bytes.  Two problems share a digest iff they are the
        same problem, so this is the result-cache key (``repro.serve``) and
        the base of :meth:`pool_digest`.  Stable across processes (sha256,
        no python ``hash``)."""
        h = hashlib.sha256(b"IMProblem:")
        for f in fields(self):
            _digest_value(h, f.name, getattr(self, f.name))
        return h.hexdigest()

    def pool_digest(self, model: Optional[str] = None, *,
                    graph_digest: Optional[str] = None) -> str:
        """Content hash of the fields that determine the engine + RR pool
        a solve needs (``_POOL_FIELDS``: diffusion model, ``t_rounds``,
        ``node_weights``).  Problems with equal pool digests can share a
        warm solver's sampled pool; ``IMMSolver._prepare`` keys its
        engine/pool lifecycle on this (replacing the ad-hoc tuple key).

        ``model=`` supplies the solver-resolved model when the problem
        leaves ``model=None`` (inherit), so an explicit ``model="ic"`` and
        an inherited ic default share a pool.

        ``graph_digest=`` mixes in the graph's content identity
        (:func:`repro.graph.csr.graph_digest`): an RR pool is a sample of
        one concrete graph, so serving layers that key pools by name must
        also key them by content — a re-registered or delta-mutated graph
        then hashes to a different pool key and can never serve a stale
        pool (``repro.serve``, ``repro.core.stream``).
        """
        h = hashlib.sha256(b"IMPool:")
        vals = {f: getattr(self, f) for f in _POOL_FIELDS}
        if vals["model"] is None:
            vals["model"] = model
        for f in _POOL_FIELDS:
            _digest_value(h, f, vals[f])
        if graph_digest is not None:
            _digest_value(h, "graph", graph_digest)
        return h.hexdigest()

    def resolve(self, n: int) -> "ResolvedProblem":
        """Validate against a concrete graph size and normalize every array
        knob to numpy (weights float32 non-negative, costs float32 positive,
        candidates (n,) bool)."""
        w = None
        if self.node_weights is not None:
            w = _as_node_array(self.node_weights, n, "node_weights",
                               np.float32)
            if (w < 0).any() or not np.isfinite(w).all() or w.sum() <= 0:
                raise ValueError("node_weights must be non-negative, finite, "
                                 "and not all zero")
        costs = None
        if self.budget is not None:
            costs = (_as_node_array(self.costs, n, "costs", np.float32)
                     if self.costs is not None
                     else np.ones(n, np.float32))
            if (costs <= 0).any() or not np.isfinite(costs).all():
                raise ValueError("costs must be positive and finite")
        cand = (candidates_mask(self.candidates, n)
                if self.candidates is not None else None)
        t = self.t_rounds if self.t_rounds is not None else 1
        n_items = n * t
        if self.budget is not None:
            feas_costs = costs[cand] if cand is not None else costs
            affordable = feas_costs[feas_costs <= self.budget]
            if affordable.size == 0:
                raise ValueError("no candidate node is affordable under "
                                 "the given budget")
            # scan-length bound: can never pick more seeds than the budget
            # buys at the cheapest affordable cost (capped at the node set)
            k_steps = int(min(len(affordable),
                              self.budget // float(affordable.min())))
            k_steps = max(k_steps, 1)
        else:
            k_steps = self.k * t
        scale = float(w.sum()) if w is not None else float(n)
        return ResolvedProblem(
            problem=self, n_nodes=n, n_items=n_items, t_rounds=t,
            k_steps=k_steps, node_weights=w, costs=costs, cand_mask=cand,
            scale=scale)


@dataclass(frozen=True)
class ResolvedProblem:
    """An :class:`IMProblem` validated against a graph: normalized arrays
    plus the derived sizes the solver and the selection scan consume."""
    problem: IMProblem
    n_nodes: int
    n_items: int                       # n * t_rounds (the coverage id space)
    t_rounds: int
    k_steps: int                       # selection scan length / max seeds
    node_weights: Optional[np.ndarray]
    costs: Optional[np.ndarray]
    cand_mask: Optional[np.ndarray]    # (n_nodes,) bool over base nodes
    scale: float                       # Eq. 3 spread scale: Σw (or n)

    @property
    def cand_mask_items(self) -> Optional[np.ndarray]:
        """Candidate mask over the (possibly round-tagged) item space."""
        if self.cand_mask is None:
            return None
        return np.tile(self.cand_mask, self.t_rounds)


@dataclass
class IMResult:
    """Typed result of ``IMMSolver.solve(problem)``.

    ``seeds`` are item ids (round-tagged for MRIM — use
    :meth:`seeds_per_round`); ``gains`` are the per-seed marginal coverage
    gains (int32 rows covered, float32 covered weight for weighted
    problems); ``spread`` is the Eq. 3 estimate on the problem's scale
    (``Σ node_weights`` when weighted, else ``n``).  Budgeted solves stop
    early: ``len(seeds)`` is the number of seeds actually afforded and
    ``cost`` their total price.
    """
    seeds: np.ndarray
    spread: float
    gains: np.ndarray
    frac: float
    stats: Any
    problem: IMProblem
    n_nodes: int
    cost: float = 0.0
    # deadline-clipped sketch answer (DESIGN.md §8): seeds picked by
    # certified sketch lower bounds, spread_bounds = (lo, hi) bracketing the
    # true Eq. 3 spread (lo certified from sketch occupancy gains, hi a
    # union bound from the exact Occur histogram).  Exact results keep
    # degraded=False / spread_bounds=None — a degraded answer is labelled,
    # never silently substituted.
    degraded: bool = False
    spread_bounds: Optional[tuple] = None

    def seeds_per_round(self) -> list:
        """MRIM decode: T sorted per-round seed lists (plain problems: one
        list holding all seeds)."""
        t = self.problem.t_rounds or 1
        n = self.n_nodes
        s = np.asarray(self.seeds)
        return [sorted((s[s // n == r] % n).tolist()) for r in range(t)]


# -- checkpoint (de)serialization -------------------------------------------
def problem_state(p: IMProblem) -> dict:
    """json-serializable encoding of an :class:`IMProblem` for pool
    checkpoints.  Arrays round-trip through dtype-tagged nested lists;
    :func:`problem_from_state` rebuilds a problem with an identical
    ``signature_digest``."""
    out = {}
    for f in fields(p):
        v = getattr(p, f.name)
        if v is None or isinstance(v, (bool, int, float, str)):
            out[f.name] = v
        else:
            a = np.asarray(v)
            out[f.name] = {"__array__": True, "dtype": str(a.dtype),
                           "data": a.tolist()}
    return out


def problem_from_state(state: dict) -> IMProblem:
    kw = {}
    for name, v in state.items():
        if isinstance(v, dict) and v.get("__array__"):
            kw[name] = np.asarray(v["data"], dtype=np.dtype(v["dtype"]))
        else:
            kw[name] = v
    return IMProblem(**kw)
