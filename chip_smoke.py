#!/usr/bin/env python3
"""Chip smoke test: the IMM solve and the serving path on a TPU.

    python chip_smoke.py              # one chip, phases a-d
    python chip_smoke.py --chips 4    # four chips: mesh-sharded solve only

Runs the system through the entry points a user calls, on the paper's
epinions-like stand-in (``configs/im_paper.py``: a Barabasi-Albert graph
with n=75,879, r=4, weighted-cascade probabilities, made from a seed) and
checks every answer.  The graph is uncut; the number of RR sets is cut.
On one v5e the queue sampler draws about 207 RR sets per second on this
graph (2.5 s per 512-set round of ~244 lockstep steps at ~10 ms each), and
approximate mode, which folds every batch into a sketch, about 113.  The
paper default's θ (about 2.2e6 here) would take about 3 h, so the ε-driven
solve is capped at ``MAX_THETA`` and the pinned solves use ``THETA``, which
keeps a run with a cold compile cache inside the 1200 s it may take:

a. ``IMMSolver(g, engine="queue", batch=512)`` solves the paper default
   ``IMProblem(k=50, eps=0.05)`` with ``max_theta=MAX_THETA``, through the
   whole LB loop; its RIS estimate must agree with forward Monte-Carlo
   within 10%.
b. A θ-pinned solve must equal ``core/oracle.greedy_max_coverage`` on the
   pool fetched to the host: seeds, per-seed gains, covered rows.
c. A solver that adopts phase b's pool must select the same answer
   from it, bit for bit, without sampling.  ``mode="approximate"`` with
   one sketch bucket per RR set (the exact regime) must return the same
   seeds and gains, and certify their true coverage on that pool.
d. ``IMNetServer`` over ``build_service`` answers mixed θ-pinned requests
   over HTTP in this process's event loop, with no error status and every
   answer bit-identical to a direct solve.

``--chips 4`` runs only the four-chip path: ``launch.im_solve.solve`` on a
4-chip ``samples`` mesh (phase a's problem and check), and a θ-pinned
``IMMSolver`` on mesh=4 against mesh=1, which must agree bit for bit.  It
checks the sharded pool and its collectives, not the sampler at scale, so
it runs at the smaller ``MESH_MAX_THETA`` / ``MESH_THETA``: four chips
cost four times the chip time.

Each phase prints one JSON line.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure, a non-TPU backend, or a kernel left in interpret mode exits
non-zero without it.  Everything is generated from seeds; the process is
the only one that touches the chip.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

N_EPINIONS, R_EPINIONS = 75_879, 4          # configs/im_paper.py
K, EPS = 50, 0.05                           # im_paper DEFAULTS
BATCH = 512
MAX_THETA = 65_536                          # cuts: sampling time on the
THETA = 16_384                              # chip (see the docstring)
MESH_MAX_THETA, MESH_THETA = 16_384, 4_096  # --chips 4
MC_SIMS = 64
MC_TOL = 0.10


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


class CompileClock:
    """Seconds spent tracing, lowering and compiling (or fetching from the
    persistent cache), and persistent-cache hits/misses, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, secs, **_):
            if event.startswith("/jax/core/compile/"):
                self.secs += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.secs, self.hits, self.misses, time.perf_counter()

    def since(self, mark) -> dict:
        secs, hits, misses, t0 = mark
        wall = time.perf_counter() - t0
        compile_s = self.secs - secs
        return {"wall_s": wall, "compile_s": compile_s,
                "run_s": wall - compile_s, "cache_hits": self.hits - hits,
                "cache_misses": self.misses - misses}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def epinions_graph():
    from repro.graph import csr, generators, weights
    src, dst = generators.barabasi_albert(N_EPINIONS, R_EPINIONS, seed=0)
    return weights.wc_weights(csr.from_edges(src, dst, N_EPINIONS))


def solver(g, **kw):
    from repro.core.imm import IMMSolver
    return IMMSolver(g, engine="queue", batch=BATCH, seed=0, **kw)


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def mc_check(g, seeds, estimate) -> dict:
    import jax
    from repro.core import forward
    mc = forward.ic_spread(jax.random.key(11), g, list(map(int, seeds)),
                           n_sims=MC_SIMS)
    rel = abs(estimate - mc) / mc
    check(rel <= MC_TOL, f"RIS estimate {estimate:.1f} vs Monte-Carlo "
          f"{mc:.1f}: rel err {rel:.4f} > {MC_TOL}")
    return {"estimate": estimate, "mc_spread": mc, "mc_sims": MC_SIMS,
            "rel_err": rel}


def same_answer(a, b) -> bool:
    import numpy as np
    return (np.array_equal(np.asarray(a.seeds), np.asarray(b.seeds))
            and np.array_equal(np.asarray(a.gains), np.asarray(b.gains))
            and a.frac == b.frac and a.spread == b.spread)


# ------------------------------------------------------------------ phases

def phase_a(ctx, clock):
    """Main path at paper scale: ε-driven IMM with the LB loop."""
    from repro.core.oracle import imm_theta_params
    from repro.core.problem import IMProblem
    g = ctx["g"]
    mark = clock.mark()
    s = solver(g)
    res = s.solve(IMProblem(k=K, eps=EPS, model="ic",
                            max_theta=ctx["max_theta"]))
    times = clock.since(mark)
    st = res.stats
    lam_star = imm_theta_params(g.n_nodes, K, EPS)[1]
    out = {"n": g.n_nodes, "m": g.n_edges, "k": K, "eps": EPS,
           "lambda_star": lam_star,
           "theta_uncapped": math.ceil(lam_star / st.lb),
           "theta": st.theta, "n_rr": st.n_rr_sampled, "rounds": st.rounds,
           "sampling_steps": st.sampling_steps,
           "overflow_fraction": st.overflow_fraction,
           "lb_iters": st.lb_iters, "lb": st.lb,
           "pool_bytes_per_device": st.per_device_pool_bytes, **times}
    mark = clock.mark()
    out.update(mc_check(g, res.seeds, res.spread))
    out["mc"] = clock.since(mark)
    return out


def phase_b(ctx, clock):
    """θ-pinned solve vs the numpy greedy on the pool fetched to the host."""
    import jax
    import numpy as np
    from repro.core import oracle
    from repro.core.problem import IMProblem
    g = ctx["g"]
    mark = clock.mark()
    s = solver(g)
    res = s.solve(IMProblem(k=K, theta=ctx["theta"]))
    times = clock.since(mark)
    snap = s.store.snapshot()
    flat, ids = (np.asarray(x) for x in jax.device_get(
        (snap.rr_flat, snap.rr_ids)))
    n_rr = snap.n_rr
    check(bool(np.all(np.diff(ids) >= 0)), "pool rows are not contiguous")
    bounds = np.cumsum(np.bincount(ids, minlength=n_rr))[:-1]
    rr_sets = [r.tolist() for r in np.split(flat, bounds)]
    mark = clock.mark()
    ref_seeds, ref_frac = oracle.greedy_max_coverage(rr_sets, g.n_nodes, K)
    ref_s = time.perf_counter() - mark[3]
    covered = np.zeros(n_rr, bool)
    ref_gains = []
    for u in ref_seeds:
        rows = np.unique(ids[flat == u])
        ref_gains.append(int((~covered[rows]).sum()))
        covered[rows] = True
    n_cov = int(covered.sum())
    check(res.seeds.tolist() == ref_seeds,
          f"seeds differ from the oracle: {res.seeds.tolist()} vs "
          f"{ref_seeds}")
    check(res.gains.tolist() == ref_gains, "per-seed gains differ")
    check(n_cov == round(ref_frac * n_rr) == int(res.gains.sum()),
          "covered rows differ")
    check(abs(res.frac - n_cov / n_rr) <= 2.0 ** -23 * res.frac,
          f"F_R {res.frac!r} is not the f32 quotient {n_cov}/{n_rr}")
    ctx["b_solver"], ctx["pool"] = s, (flat, ids, n_rr)
    ctx["b_result"] = res
    return {"theta": ctx["theta"], "n_rr": n_rr, "elements": int(flat.size),
            "pool_bytes_per_device": res.stats.per_device_pool_bytes,
            "covered_rows": n_cov, "frac": res.frac, "oracle_s": ref_s,
            **times}


def phase_c(ctx, clock):
    """The fused scan on an adopted pool, and the pool-free approximate
    mode."""
    import numpy as np
    from repro.core.problem import IMProblem
    g, theta = ctx["g"], ctx["theta"]
    base = ctx["b_result"]
    s = solver(g)
    s.adopt_pool(ctx.pop("b_solver").export_pool())
    mark = clock.mark()
    res = s.solve(IMProblem(k=K, theta=theta))
    out = {"fused": clock.since(mark)}
    check(res.stats.n_rr_sampled == theta, "the adopted pool was resampled")
    check(same_answer(res, base),
          f"the adopted pool's answer differs: seeds {res.seeds.tolist()} "
          f"gains {res.gains.tolist()} frac {res.frac!r} vs "
          f"{base.seeds.tolist()} {base.gains.tolist()} {base.frac!r}")
    ctx["lease"] = s.export_pool()

    # the pool-free fold with one sketch bucket per RR set (sketch_k ≥ θ):
    # the exact regime, where the approximate solve must pick the exact
    # answer (same RNG stream, injective buckets) and certify it tightly
    flat, ids, n_rr = ctx["pool"]
    mark = clock.mark()
    s = solver(g, sketch_k=theta)
    res = s.solve(IMProblem(k=K, theta=theta, mode="approximate"))
    out["approximate"] = clock.since(mark)
    info = s._sketch_info
    check(s.store.per_device_pool_bytes() == 0, "approximate mode kept a "
          "pool")
    check(s.store.n_rr == n_rr, "approximate mode sampled another θ")
    check(info["exact_regime"] and not info["saturated"],
          f"approximate mode left the exact regime: {info}")
    check(np.array_equal(res.seeds, base.seeds)
          and np.array_equal(res.gains, base.gains),
          f"approximate seeds {res.seeds.tolist()} gains "
          f"{res.gains.tolist()} differ from exact {base.seeds.tolist()} "
          f"{base.gains.tolist()}")
    true_rows = np.unique(ids[np.isin(flat, res.seeds)]).size
    true_spread = g.n_nodes * true_rows / n_rr
    lo, hi = res.spread_bounds
    tol = 1e-6 * true_spread                  # f32 F_R vs f64 quotient
    check(abs(res.spread - true_spread) <= tol,
          f"estimate {res.spread!r} is not the true coverage "
          f"{true_spread!r}")
    check(lo - tol <= true_spread <= hi + tol, f"true coverage "
          f"{true_spread:.1f} outside spread_bounds [{lo:.1f}, {hi:.1f}]")
    out["approximate"].update(
        estimate=res.spread, bounds=[lo, hi], true_spread=true_spread,
        exact_regime=bool(info["exact_regime"]),
        saturated=bool(info["saturated"]), sketch_k=info["sketch_k"],
        sketch_bytes_per_device=s.store.sketch_bytes())
    return out


def serve_problems(g, theta):
    import numpy as np
    from repro.core.problem import IMProblem
    n = g.n_nodes
    top = np.argsort(-np.diff(np.asarray(g.offsets)), kind="stable")
    distinct = [IMProblem(k=k, theta=theta) for k in (1, 5, 10, 25, K)]
    distinct += [IMProblem(k=5, theta=theta, candidates=top[:n // 4]),
                 IMProblem(k=10, theta=theta, candidates=top[:1000]),
                 IMProblem(k=K, theta=theta, candidates=top[:n // 2])]
    first = distinct + distinct[:4]            # repeats inside one wave
    second = distinct                          # repeats after: cache hits
    return distinct, first, second


def phase_d(ctx, clock):
    """The network serving surface, in this process and event loop."""
    from repro.serve import IMClient, IMNetServer, ServeConfig, \
        build_service
    g = ctx["g"]
    distinct, first, second = serve_problems(g, ctx["theta"])
    ref = solver(g)
    ref.adopt_pool(ctx.pop("lease"))
    direct = {p.signature_digest(): ref.solve(p) for p in distinct}

    async def run():
        svc = build_service({"graph": g}, ServeConfig(
            max_batch=8, batch_window_s=0.002,
            solver_opts={"engine": "queue", "batch": BATCH, "seed": 0}))
        server = IMNetServer(svc, host="127.0.0.1", port=0)
        await server.start()
        try:
            client = IMClient("127.0.0.1", server.port, timeout_s=600.0)
            answers = []
            for wave in (first, second):
                got = await asyncio.gather(
                    *(client.solve_raw("graph", p) for p in wave),
                    return_exceptions=True)
                answers += list(zip(wave, got))
            stats = await client.stats()
        finally:
            await server.shutdown()
        return answers, stats

    mark = clock.mark()
    answers, stats = asyncio.run(run())
    times = clock.since(mark)
    failures, mismatches = [], 0
    for p, got in answers:
        if isinstance(got, BaseException):
            failures.append(f"{type(got).__name__}: {got}")
            continue
        status, doc = got
        if status != 200:
            failures.append(f"{status}: {doc}")
            continue
        want, res = direct[p.signature_digest()], doc["result"]
        if (res["seeds"] != want.seeds.tolist()
                or res["gains"] != want.gains.tolist()
                or res["frac"] != float(want.frac)
                or res["spread"] != float(want.spread)):
            mismatches += 1
    sv = stats["serve"]
    check(not failures, f"{len(failures)} failed requests: {failures[:3]}")
    check(mismatches == 0, f"{mismatches} wire answers differ from direct "
          "solves")
    check(sv["served"] >= len(answers), "served count below requests")
    return {"requests": len(answers), "distinct": len(distinct),
            "errors": 0, "bit_identical": len(answers),
            "statsz": {key: sv[key] for key in (
                "served", "cache_hits", "batches", "batch_occupancy_mean",
                "stacked_batches", "stacked_requests", "shed", "expired")},
            **times}


def phase_mesh4(ctx, clock):
    """The mesh-sharded pool users reach through ``im_solve --mesh``."""
    from repro.core.problem import IMProblem
    from repro.launch.im_solve import solve
    from repro.launch.mesh import make_sample_mesh
    g = ctx["g"]
    mark = clock.mark()
    # batch_per_dev: four samplers of BATCH/4 lanes draw one BATCH round
    seeds, est, stats = solve(g, problem=IMProblem(
                                  k=K, eps=EPS, max_theta=ctx["max_theta"]),
                              batch_per_dev=BATCH // 4,
                              mesh=make_sample_mesh(4))
    out = {"solve": {"devices": stats["devices"],
                     "pool_sharding": stats["pool_sharding"],
                     "theta": stats["theta"], "n_rr": stats["sampled"],
                     "pool_bytes_per_device":
                         stats["per_device_pool_bytes"],
                     **clock.since(mark)}}
    check(stats["devices"] == 4, "the solve did not run on 4 chips")
    out["solve"].update(mc_check(g, seeds, est))
    results = {}
    for chips in (4, 1):
        mark = clock.mark()
        s = solver(g, mesh=make_sample_mesh(chips))
        results[chips] = s.solve(IMProblem(k=K, theta=ctx["theta"]))
        out[f"theta_pinned_mesh{chips}"] = {
            "pool_sharding": results[chips].stats.pool_sharding,
            "pool_bytes_per_device":
                results[chips].stats.per_device_pool_bytes,
            **clock.since(mark)}
    check(same_answer(results[4], results[1]),
          "θ-pinned answers differ between mesh=4 and mesh=1")
    out["bit_identical"] = True
    out["frac"] = results[1].frac
    return out


# -------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        from repro.kernels import ops
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing: {e}",
              file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {device}",
              file=sys.stderr)
        return 2
    if ops.resolve_interpret():
        print("chip_smoke: Pallas kernels resolve to interpret mode "
              f"(REPRO_KERNELS_INTERPRET / ops.INTERPRET) on {device}",
              file=sys.stderr)
        return 2
    clock = CompileClock()
    emit({"phase": "setup", "device": device, "chips": args.chips,
          "compile_cache": cache_dir, "interpret": False,
          "jax": jax.__version__})

    ctx = ({"max_theta": MESH_MAX_THETA, "theta": MESH_THETA}
           if args.chips == 4 else {"max_theta": MAX_THETA, "theta": THETA})
    mark = clock.mark()
    ctx["g"] = epinions_graph()
    emit({"phase": "graph", "n": ctx["g"].n_nodes, "m": ctx["g"].n_edges,
          **clock.since(mark)})
    phases = ([("mesh4", phase_mesh4)] if args.chips == 4 else
              [("a_main_path", phase_a), ("b_exact_vs_oracle", phase_b),
               ("c_selection", phase_c), ("d_serving", phase_d)])
    failed = []
    for name, fn in phases:
        try:
            out = fn(ctx, clock)
            ok = True
        except Exception as e:               # report, then fail the run
            out = {"error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            ok = False
            failed.append(name)
        emit({"phase": name, "ok": ok, **out, "peak_bytes": peak_bytes(),
              "interpret": ops.resolve_interpret()})
    if failed or ops.resolve_interpret():
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
