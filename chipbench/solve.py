"""Closed loop of whole ε-driven IMM solves.

Set-up builds the graph, the sampling engine and one whole solve at the
cell's cap.  The window then runs solves back to back, each a fresh
``IMMSolver`` (empty pool, its own RNG key) on the engine built in set-up;
none starts after ``--seconds``, and the window ends with the last whole
solve.  Every run cycles through the same fixed keys (``solve_keys`` of
them, from the mix's ``key_seed``) in the same order: a solve's work is the
lockstep maximum over its lanes and swings by a third from key to key, so
keys, or an order of them, drawn from ``--seed`` would make the seed, not
the program, set the rate.  A solve is ``IMMSolver`` on one chip with
the queue engine at the mix's ``batch`` lanes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from chipbench import graphs, reference, roofline

SPANS = ("warmup", "solve", "check")


@dataclass
class State:
    cell: object
    seed: int
    host: graphs.HostGraph
    new_solver: object
    problem: object
    keys: list
    phases: dict
    solves: list = field(default_factory=list)


WARMUP, WINDOW, RIS = 1, 2, 3          # seed streams


def rng_for(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, *stream])


def solver_seed(seed: int, stream: int, i: int = 0) -> int:
    """A 31-bit RNG seed drawn from the run's seed."""
    return int(rng_for(seed, stream, i).integers(0, 2 ** 31 - 1))


def window_keys(mix: dict) -> list:
    """The solver seeds of the window, in cycle order."""
    return [solver_seed(mix["key_seed"], WINDOW, j)
            for j in range(mix["solve_keys"])]


def setup(cell, seed: int, devices) -> State:
    import jax
    from repro.core.engine import make_engine
    from repro.core.imm import IMMSolver
    from repro.core.problem import IMProblem
    from repro.graph import csr
    mix = cell.traffic
    t = time.perf_counter()
    host = graphs.make(cell.config)
    phases = {"graph_s": time.perf_counter() - t}
    t = time.perf_counter()
    g = graphs.to_program(host)
    engine = make_engine("queue", csr.reverse(g), batch=mix["batch"])
    phases["engine_s"] = time.perf_counter() - t

    def new_solver(s):
        return IMMSolver(g, engine=engine, seed=s)
    problem = IMProblem(k=mix["k"], eps=mix["eps"], model="ic",
                        max_theta=mix["max_theta"])
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("warmup"):
        new_solver(solver_seed(mix["key_seed"], WARMUP)).solve(problem)
    phases["warmup_s"] = time.perf_counter() - t
    return State(cell=cell, seed=seed, host=host, new_solver=new_solver,
                 problem=problem, keys=window_keys(mix), phases=phases)


def _counters(done: list) -> dict:
    return {
        "solves": len(done),
        "rounds": sum(r.stats.rounds for r in done),
        "sampling_steps": sum(r.stats.sampling_steps for r in done),
        "selections": sum(1 + sum(h[0] == "lb_iter" for h in r.stats.history)
                          for r in done)}


def window(state: State, seconds: float, tracer) -> dict:
    """``tracer`` traces the first cycle alone — building its solver and
    solving — since every cycle repeats the same host and device work, and
    a trace of more is too large to read in a run's time.  The cycles
    follow each other with nothing between them: ``outside_cycles_s``
    reports the window's time that no cycle covers."""
    import jax
    t0 = time.perf_counter()
    t_end, failed, i, cycles_s = t0, 0, 0, 0.0
    while time.perf_counter() - t0 < seconds:
        t_cycle = time.perf_counter()
        with tracer(i == 0), jax.profiler.TraceAnnotation("solve"):
            solver = state.new_solver(state.keys[i % len(state.keys)])
            try:
                res = solver.solve(state.problem)
            except Exception as e:          # a solve that never answers
                failed += 1
                res = e
        t_end = time.perf_counter()
        cycles_s += t_end - t_cycle
        state.solves.append((solver, res))
        i += 1
    done = [r for _, r in state.solves if not isinstance(r, Exception)]
    elapsed = t_end - t0
    theta = sum(r.stats.theta for r in done)
    counters = _counters(done)
    return {"metrics": {"solve_rr_per_s": theta / elapsed},
            "attempted": len(state.solves), "failed": failed,
            "counters": _counters(done[:1]),
            "report": {"elapsed_s": elapsed, "theta": theta,
                       "outside_cycles_s": elapsed - cycles_s, **counters}}


def fetch(solver, res) -> dict:
    """What one solve produced, on the host: its pool and its answer."""
    import jax
    snap = solver.store.snapshot()
    flat, ids, valid = (np.asarray(x) for x in jax.device_get(
        (snap.rr_flat, snap.rr_ids, snap.valid)))
    return {"flat": flat[valid], "ids": ids[valid], "n_rr": snap.n_rr,
            "n_rr_sampled": res.stats.n_rr_sampled,
            "seeds": [int(s) for s in res.seeds],
            "gains": [int(x) for x in res.gains],
            "frac": float(res.frac), "spread": float(res.spread)}


def compare(host, out: dict, k: int, alter=None) -> dict:
    """The reference's verdict on one solve: bad pool rows, and whether the
    answer is the exact greedy of the pool.  ``alter(out, pool)`` (controls
    and fault drills only) replaces the program's answer before it is
    judged."""
    n = host.n
    pool = reference.Pool(out["flat"], out["ids"], out["n_rr"], n)
    if alter is not None:
        out = alter(out, pool)
    seeds, gains = pool.greedy(k)
    frac = float(np.float32(sum(gains)) / np.float32(max(pool.n_rows, 1)))
    same = (out["seeds"] == seeds and out["gains"] == gains
            and out["frac"] == frac and out["spread"] == n * frac
            and out["n_rr"] == out["n_rr_sampled"])
    return {"bad_rows": pool.bad_rows(host), "greedy_mismatch": int(not same),
            "pool": pool}


def share_gap(host, pools, lim: dict, seed: int) -> float:
    """How far the pools' share of rows that hold one of the graph's hubs
    lies from the same share of fresh reference RR sets, relative to the
    latter: the sampler's distribution against the plain reverse BFS."""
    probe = reference.hubs(host, lim["probe_nodes"])
    rows = sum(p.n_rows for p in pools)
    if rows == 0:
        return 1e9
    share = sum(p.rows_hit(probe) for p in pools) / rows
    want = reference.ris_share(graphs.reverse(host), probe, lim["ris_sets"],
                               seed)
    return abs(share - want) / max(want, 1e-12)


def check(state: State, window: dict, alter=None) -> dict:
    import jax
    lim = state.cell.limits
    with jax.profiler.TraceAnnotation("check"):
        outs = [fetch(s, r) for s, r in state.solves
                if not isinstance(r, Exception)]
        state.solves.clear()            # free the program's pools first
        in_deg = roofline.distinct_in_degree(state.host)
        window["counters"]["sample_bytes"] = (        # the traced solve
            roofline.bfs_bytes(outs[0]["flat"], in_deg) if outs else 0)
        verdicts = [compare(state.host, o, state.problem.k, alter)
                    for o in outs]
        gap = share_gap(state.host, [v["pool"] for v in verdicts], lim,
                        solver_seed(state.seed, RIS))
    return {
        "failed_solves": {"value": window["failed"], "limit": 0},
        "bad_rows": {"value": sum(v["bad_rows"] for v in verdicts),
                     "limit": 0},
        "greedy_mismatch": {"value": sum(v["greedy_mismatch"]
                                         for v in verdicts), "limit": 0},
        "sample_share_gap": {"value": gap, "limit": lim["sample_share_gap"]},
    }
