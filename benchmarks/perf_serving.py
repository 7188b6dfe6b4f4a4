"""§Perf/Serving: load test for the IM-as-a-service front (DESIGN.md §7).

An asyncio open-loop load generator drives the micro-batched request front
with a mixed θ-pinned workload — varying ``k``, candidate restrictions, and
repeated requests (the cache's food) — at ≥2 offered QPS levels, and
records per-level:

* latency percentiles (p50/p95/p99) and mean, measured submit→response;
* achieved throughput (served requests / wall time);
* batch occupancy (mean/max requests per executed micro-batch);
* cache-hit rate and shed/expired counts.

Before the load levels run, a **parity gate** solves a probe subset of the
workload on *fresh single-request solvers* (same solver_opts) and asserts
the served seeds/gains/spread are bit-identical — the θ-in-key contract
the registry guarantees (ISSUE 6 acceptance criterion).

Writes ``experiments/bench/BENCH_serving.json``.

``--smoke`` (CI's serve-smoke job): small graph, ~50 requests, asserts
nonzero cache hits and zero shed requests, then exits 0.

``--chaos`` (CI's chaos-smoke job, DESIGN.md §8): reruns the workload with
a ~10% seeded fault rate injected across every pipeline boundary
(sample/append/grow/select/executor) and asserts the service's
fault-tolerance contract: **every** request resolves to a typed outcome
(served, degraded, or a ``ServeError`` subclass — zero hangs, zero
untyped exceptions), and every non-degraded answer is bit-identical to a
fault-free fresh solve (which also proves no quarantined pool ever
served).  Writes ``experiments/bench/BENCH_chaos.json``.

CPU-container scaling note (benchmarks/common.py): offered QPS here
exercises the *front* (admission, batching, cache) — per-request solve cost
on this single scalar core is milliseconds, so the interesting numbers are
occupancy and hit-rate, not absolute latency.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import time

import numpy as np

from benchmarks.common import OUT_DIR, ba_graph
from repro.graph import generators
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem
from repro.serve import ServeConfig, ServeError, build_service

SOLVER_OPTS = {"batch": 64, "seed": 0}


def make_workload(deg, requests: int, theta: int, seed: int = 0):
    """Mixed θ-pinned request stream: varying k, two candidate pools, and a
    zipf-ish repeat pattern so the cache sees realistic re-asks.  ``deg``
    is the (n,) out-degree vector (host numpy: building the workload
    touches no device)."""
    n = len(deg)
    top = np.argsort(-np.asarray(deg), kind="stable")
    distinct = [IMProblem(k=k, theta=theta) for k in (1, 2, 5, 10)]
    distinct += [IMProblem(k=1, theta=theta, candidates=top[:m])
                 for m in (n // 4, n // 2)]
    distinct += [IMProblem(k=3, theta=theta, candidates=top[:n // 4])]
    rng = np.random.default_rng(seed)
    # zipf-like popularity: low indices re-asked often
    idx = np.minimum(rng.zipf(1.5, size=requests) - 1, len(distinct) - 1)
    return [distinct[i] for i in idx], distinct


def parity_gate(g, probe, served_by_digest):
    """Assert serving answers == fresh single-request cold solves."""
    for p in probe:
        fresh = IMMSolver(g, **SOLVER_OPTS).solve(p)
        got = served_by_digest[p.signature_digest()]
        np.testing.assert_array_equal(fresh.seeds, got.seeds)
        np.testing.assert_array_equal(fresh.gains, got.gains)
        assert fresh.frac == got.frac
        assert fresh.spread == got.spread
    return len(probe)


async def run_level(g, workload, qps: float, *, max_batch: int,
                    deadline_s=None, queue_cap: int = 256):
    """Open-loop load: submit at the offered rate regardless of completion
    (closed-loop load generators hide queueing collapse)."""
    svc = build_service({"g": g}, ServeConfig(
        max_batch=max_batch, queue_cap=queue_cap, batch_window_s=0.002,
        default_deadline_s=deadline_s, solver_opts=SOLVER_OPTS))
    lat, shed, results = [], 0, {}

    async def one(p):
        nonlocal shed
        t0 = time.perf_counter()
        try:
            resp = await svc.submit("g", p)
        except Exception:
            shed += 1
            return
        lat.append(time.perf_counter() - t0)
        results[p.signature_digest()] = resp.result

    interval = 1.0 / qps
    t_start = time.perf_counter()
    async with svc:
        tasks = []
        for i, p in enumerate(workload):
            # open loop: sleep to the scheduled submit time, don't await
            lag = t_start + i * interval - time.perf_counter()
            if lag > 0:
                await asyncio.sleep(lag)
            tasks.append(asyncio.ensure_future(one(p)))
        await asyncio.gather(*tasks)
        wall = time.perf_counter() - t_start
        st = svc.stats()
    lat_ms = np.asarray(sorted(lat)) * 1e3
    pct = (lambda q: float(np.percentile(lat_ms, q)) if lat_ms.size else 0.0)
    return {
        "offered_qps": qps,
        "requests": len(workload),
        "served": st.served,
        "shed": st.shed,
        "expired": st.expired,
        "achieved_qps": st.served / wall if wall > 0 else 0.0,
        "latency_ms": {"p50": pct(50), "p95": pct(95), "p99": pct(99),
                       "mean": float(lat_ms.mean()) if lat_ms.size else 0.0},
        "batches": st.batches,
        "batch_occupancy_mean": st.batch_occupancy_mean,
        "batch_occupancy_max": st.batch_occupancy_max,
        "occur_fastpath": st.occur_fastpath,
        "cache_hit_rate": st.cache.hit_rate,
        "cache_hits": st.cache_hits,
        "registry_solvers": st.registry.solvers,
        "registry_bytes": st.registry.bytes_in_use,
    }, results


async def run_chaos(g, workload, *, max_batch: int, rate: float,
                    deadline_probes: int, probe_theta: int,
                    timeout_s: float = 120.0):
    """Chaos run: seeded Bernoulli faults at every pipeline boundary, every
    request wrapped in ``wait_for`` so a hang is an *observed outcome*, not
    a stuck bench.  ``deadline_probes`` extra requests carry a deadline too
    tight for their big-θ cold solve, exercising the degraded path."""
    from repro.ft.failures import SITES, FaultInjector, FaultPolicy

    # the executor boundary is crossed once per *batch* (tens of crossings
    # vs thousands of solver-loop ones), so it gets a higher rate to make
    # the quarantine + isolation path actually fire in a short smoke run
    rates = {s: rate for s in SITES}
    rates["executor"] = min(1.0, 3.0 * rate)
    injector = FaultInjector(rate=rates, seed=1234)
    policy = FaultPolicy(injector=injector, backoff_base_s=0.001,
                         backoff_cap_s=0.01)
    svc = build_service({"g": g}, ServeConfig(
        max_batch=max_batch, queue_cap=512, batch_window_s=0.002,
        solver_opts={**SOLVER_OPTS, "fault_policy": policy, "sketch_k": 64},
        breaker_threshold=5, breaker_cooldown_s=0.05))
    outcomes: dict = {"served": 0, "degraded": 0, "hang": 0}
    results, degraded_bounds_ok = {}, []

    async def one(p, dl=None):
        try:
            resp = await asyncio.wait_for(
                svc.submit("g", p, deadline_s=dl), timeout_s)
        except asyncio.TimeoutError:
            outcomes["hang"] += 1
        except ServeError as e:
            outcomes[e.code] = outcomes.get(e.code, 0) + 1
        except Exception as e:        # untyped leak: the gate will fail
            tag = f"untyped:{type(e).__name__}"
            outcomes[tag] = outcomes.get(tag, 0) + 1
        else:
            if resp.degraded:
                outcomes["degraded"] += 1
                lo, hi = resp.result.spread_bounds
                degraded_bounds_ok.append(lo <= resp.result.spread <= hi)
            else:
                outcomes["served"] += 1
                results[p.signature_digest()] = resp.result
    t0 = time.perf_counter()
    async with svc:
        # tight-deadline probes on a big-θ cold key go in first (before the
        # queue builds up): sampling outlasts the budget, so these degrade
        # to certified sketch answers (or expire in-queue — both typed)
        tasks = [asyncio.ensure_future(
            one(IMProblem(k=3 + i, theta=probe_theta), dl=0.3))
            for i in range(deadline_probes)]
        tasks += [asyncio.ensure_future(one(p)) for p in workload]
        await asyncio.gather(*tasks)
        wall = time.perf_counter() - t0
        st = svc.stats()
    return outcomes, results, degraded_bounds_ok, st, policy, wall


def chaos_main(args):
    n = args.n or 300
    requests = args.requests or 120
    theta = args.theta or 1024
    g = ba_graph(n, 4)
    workload, distinct = make_workload(np.diff(np.asarray(g.offsets)),
                                       requests, theta)
    outcomes, results, dbounds, st, policy, wall = asyncio.run(run_chaos(
        g, workload, max_batch=args.max_batch, rate=args.fault_rate,
        deadline_probes=4, probe_theta=16 * theta))
    inj = policy.injector
    total = requests + 4
    typed = sum(v for k, v in outcomes.items()
                if not k.startswith("untyped:") and k != "hang")
    fires_by_site = {}
    for site, _ in inj.fired_log:
        fires_by_site[site] = fires_by_site.get(site, 0) + 1
    print(f"chaos outcomes: {outcomes}")
    print(f"chaos faults: fires={inj.fires} by_site={fires_by_site} "
          f"retries={policy.retries} oom_recoveries={policy.oom_recoveries} "
          f"gave_up={policy.gave_up}")
    print(f"chaos service: quarantines={st.quarantines} "
          f"isolated_retries={st.isolated_retries} "
          f"breaker_trips={st.breaker_trips} wall={wall:.1f}s")

    # gate 1: the run actually injected faults (a quiet run proves nothing)
    assert inj.fires > 0, "chaos: no faults fired — raise --fault-rate"
    # gate 2: zero hangs, 100% typed outcomes
    assert outcomes["hang"] == 0, f"chaos: {outcomes['hang']} hung requests"
    assert typed == total, f"chaos: {total - typed}/{total} untyped outcomes"
    # gate 3: degraded answers honour their certified bounds
    assert all(dbounds), "chaos: degraded estimate escaped spread_bounds"
    # gate 4: every non-degraded answer bit-identical to a fault-free fresh
    # solve — this is also the quarantine proof: a partially-appended pool
    # that served would fork the stream and fail here
    probe = [p for p in distinct if p.signature_digest() in results]
    probe += [p for p in (IMProblem(k=3 + i, theta=16 * theta)
                          for i in range(4))
              if p.signature_digest() in results]
    n_checked = parity_gate(g, probe, results)
    print(f"chaos parity: {n_checked} non-degraded answers bit-identical "
          "to fault-free solves")

    out = {
        "config": {"n": n, "r": 4, "theta": theta, "requests": total,
                   "max_batch": args.max_batch, "fault_rate": args.fault_rate,
                   "solver_opts": SOLVER_OPTS},
        "outcomes": outcomes,
        "faults": {"fires": inj.fires, "fires_by_site": fires_by_site,
                   "checks_by_site": dict(inj.counts),
                   "retries": policy.retries,
                   "oom_recoveries": policy.oom_recoveries,
                   "gave_up": policy.gave_up},
        "service": {"quarantines": st.quarantines,
                    "isolated_retries": st.isolated_retries,
                    "breaker_trips": st.breaker_trips,
                    "degraded": st.degraded,
                    "solver_retries": st.solver_retries},
        "parity": {"checked": n_checked, "bit_identical": True},
        "wall_s": wall,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "BENCH_chaos.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.relpath(path)}")
    print(f"chaos OK: typed={typed}/{total} hangs=0 fires={inj.fires} "
          f"parity={n_checked}")


async def run_net_level(client, workload, qps: float):
    """Open-loop load against the HTTP server: latencies here include the
    network hop (socket connect + JSON both ways), statuses are counted
    raw so the zero-5xx gate sees everything."""
    lat, statuses, results = [], {}, {}

    async def one(p):
        t0 = time.perf_counter()
        try:
            status, doc = await client.solve_raw("graph", p)
        except Exception:
            statuses["transport"] = statuses.get("transport", 0) + 1
            return
        lat.append(time.perf_counter() - t0)
        statuses[status] = statuses.get(status, 0) + 1
        if status == 200:
            results[p.signature_digest()] = doc["result"]

    interval = 1.0 / qps
    t_start = time.perf_counter()
    tasks = []
    for i, p in enumerate(workload):
        lag = t_start + i * interval - time.perf_counter()
        if lag > 0:
            await asyncio.sleep(lag)
        tasks.append(asyncio.ensure_future(one(p)))
    await asyncio.gather(*tasks)
    wall = time.perf_counter() - t_start
    lat_ms = np.asarray(sorted(lat)) * 1e3
    pct = (lambda q: float(np.percentile(lat_ms, q)) if lat_ms.size else 0.0)
    n5xx = sum(v for k, v in statuses.items()
               if isinstance(k, int) and k >= 500)
    return {
        "offered_qps": qps,
        "requests": len(workload),
        "statuses": {str(k): v for k, v in statuses.items()},
        "n_5xx": n5xx,
        "achieved_qps": len(lat) / wall if wall > 0 else 0.0,
        "latency_ms": {"p50": pct(50), "p95": pct(95), "p99": pct(99),
                       "mean": float(lat_ms.mean()) if lat_ms.size else 0.0},
    }, results


def stacked_throughput(g, theta: int, reps: int = 5):
    """Stacked-vs-solo selection throughput at equal batch occupancy: the
    same 8 θ-pinned requests (no k=1, so the occur fastpath peels nothing)
    run through one padded scan vs 8 sequential solo selections on an
    equally warm pool.  Compile + sampling are excluded by warmup."""
    from repro.serve.batching import execute_batch
    probs = [IMProblem(k=k, theta=theta) for k in (2, 3, 4, 5)]
    deg = np.diff(np.asarray(g.offsets))
    top = np.argsort(-deg, kind="stable")
    probs += [IMProblem(k=k, theta=theta, candidates=top[:g.n_nodes // 2])
              for k in (2, 3, 4, 5)]

    def timed(stacked):
        solver = IMMSolver(g, **SOLVER_OPTS)
        execute_batch(solver, probs, stacked=stacked)     # warm pool+compile
        t0 = time.perf_counter()
        for _ in range(reps):
            res = execute_batch(solver, probs, stacked=stacked)
        dt = time.perf_counter() - t0
        return reps * len(probs) / dt, res

    solo_rps, res_solo = timed(False)
    stacked_rps, res_stacked = timed(True)
    for a, b in zip(res_solo, res_stacked):               # parity, again
        np.testing.assert_array_equal(a.seeds, b.seeds)
        assert a.spread == b.spread
    return {"batch": len(probs), "reps": reps,
            "solo_rps": solo_rps, "stacked_rps": stacked_rps,
            "speedup": stacked_rps / solo_rps}


def net_main(args):
    """--net: spawn the HTTP server as a subprocess, drive the mixed
    workload (plus the approximate tier) through repro.serve.client at two
    offered QPS levels, gate on zero 5xx + cache hits + θ-pinned parity
    against a fresh in-process solve, measure stacked-vs-solo selection
    throughput, then SIGTERM the server and assert a clean drain."""
    import signal
    import socket
    import subprocess
    import sys
    import tempfile

    from repro.serve.client import IMClient

    n = args.n or (300 if args.smoke else 600)
    requests = args.requests or (40 if args.smoke else 120)
    theta = args.theta or 1024
    qps_levels = args.qps or ([100.0, 400.0] if args.smoke
                              else [100.0, 500.0])

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src") + (
        os.pathsep + env["PYTHONPATH"] if "PYTHONPATH" in env else "")
    cmd = [sys.executable, "-m", "repro.serve.net",
           "--host", "127.0.0.1", "--port", str(port),
           "--n", str(n), "--r", "4", "--graph-seed", "0",
           "--max-batch", str(args.max_batch),
           "--batch", str(SOLVER_OPTS["batch"]),
           "--seed", str(SOLVER_OPTS["seed"])]
    logf = tempfile.NamedTemporaryFile("w+", suffix=".log", delete=False)
    proc = subprocess.Popen(cmd, env=env, stdout=logf, stderr=logf)
    client = IMClient("127.0.0.1", port, timeout_s=120.0)

    def server_log():
        logf.flush()
        with open(logf.name) as f:
            return f.read()[-3000:]

    async def wait_ready():
        deadline = time.perf_counter() + 120.0
        while time.perf_counter() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server died rc={proc.returncode}\n{server_log()}")
            try:
                status, _ = await asyncio.wait_for(client.readyz(), 2.0)
                if status == 200:
                    return
            except Exception:
                pass
            await asyncio.sleep(0.25)
        raise RuntimeError(f"server never ready\n{server_log()}")

    # one process per chip: while the server child runs, this process
    # touches no device, so the workload is built from host edge lists and
    # the graph object (device arrays) only after the child has exited
    src, _dst = generators.barabasi_albert(n, 4, seed=0)
    workload, distinct = make_workload(np.bincount(src, minlength=n),
                                       requests, theta)
    # the approximate tier rides the same wire (satellite): sketch-mode
    # answers plus their pool-footprint ratio in /statsz
    approx = [IMProblem(k=3, theta=theta, mode="approximate"),
              IMProblem(k=5, theta=theta, mode="approximate")]
    workload = workload + approx
    distinct = distinct + approx

    try:
        asyncio.run(wait_ready())
        levels, results = [], {}
        for qps in qps_levels:
            level, res = asyncio.run(run_net_level(client, workload, qps))
            results.update(res)
            levels.append(level)
            print(f"net qps={qps:g}: "
                  f"p50={level['latency_ms']['p50']:.1f}ms "
                  f"p99={level['latency_ms']['p99']:.1f}ms "
                  f"achieved={level['achieved_qps']:.0f}/s "
                  f"5xx={level['n_5xx']}")
        st = asyncio.run(client.stats())

        # drain: SIGTERM -> admission stops, in-flight flushes, exit 0
        proc.send_signal(signal.SIGTERM)
        drain_rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    g = ba_graph(n, 4)

    # gates ------------------------------------------------------------
    total_5xx = sum(l["n_5xx"] for l in levels)
    assert total_5xx == 0, f"net: {total_5xx} 5xx responses\n{server_log()}"
    assert all("transport" not in l["statuses"] for l in levels), levels
    assert st["serve"]["cache_hits"] > 0, "net: expected cache hits"
    assert drain_rc == 0, f"net: drain exit {drain_rc}\n{server_log()}"

    # θ-pinned parity: every served JSON doc vs a fresh in-process solve
    n_checked = 0
    for p in distinct:
        doc = results.get(p.signature_digest())
        if doc is None:
            continue
        fresh = IMMSolver(g, **SOLVER_OPTS).solve(p)
        assert doc["seeds"] == np.asarray(fresh.seeds).tolist(), p
        assert doc["gains"] == np.asarray(fresh.gains).tolist(), p
        assert doc["spread"] == float(fresh.spread), p
        assert doc["frac"] == float(fresh.frac), p
        n_checked += 1
    print(f"net parity: {n_checked} served answers bit-identical to fresh "
          "in-process solves (JSON float round-trip is exact)")

    thr = stacked_throughput(g, theta)
    print(f"stacked selection: {thr['stacked_rps']:.1f} req/s vs solo "
          f"{thr['solo_rps']:.1f} req/s "
          f"(x{thr['speedup']:.2f} at occupancy {thr['batch']})")
    if args.smoke:
        # soft floor in CI (shared runners jitter); the committed artifact
        # shows the real improvement
        assert thr["speedup"] >= 0.8, thr

    fp = st.get("approx_footprint", {})
    out = {
        "config": {"n": n, "r": 4, "theta": theta,
                   "requests": len(workload), "qps_levels": qps_levels,
                   "max_batch": args.max_batch, "solver_opts": SOLVER_OPTS},
        "levels": levels,
        "serve": {k: st["serve"][k] for k in
                  ("served", "batches", "batch_occupancy_mean",
                   "batch_occupancy_max", "cache_hits", "occur_fastpath",
                   "stacked_batches", "stacked_requests", "shed",
                   "expired")},
        "approx_footprint": fp,
        "stacked_selection": thr,
        "parity": {"checked": n_checked, "bit_identical": True},
        "drain": {"signal": "SIGTERM", "exit_code": drain_rc},
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "BENCH_serving_net.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.relpath(path)}")
    print(f"net OK: 5xx=0 cache_hits={st['serve']['cache_hits']} "
          f"parity={n_checked} drain_rc={drain_rc} "
          f"stacked_x{thr['speedup']:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: small graph, ~50 requests, assert "
                         "cache hits > 0 and shed == 0")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--theta", type=int, default=None)
    ap.add_argument("--qps", type=float, nargs="+", default=None,
                    help="offered load levels (default: two levels)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--chaos", action="store_true",
                    help="CI gate: rerun the workload under ~10%% injected "
                         "faults; assert typed outcomes, zero hangs, and "
                         "fault-free parity (DESIGN.md §8)")
    ap.add_argument("--fault-rate", type=float, default=0.1,
                    help="per-boundary Bernoulli fault rate for --chaos")
    ap.add_argument("--net", action="store_true",
                    help="CI gate (serve-net-smoke): drive the HTTP server "
                         "subprocess through repro.serve.client; assert "
                         "zero 5xx, cache hits, θ-pinned parity, clean "
                         "SIGTERM drain (DESIGN.md §11)")
    args = ap.parse_args()

    if args.chaos:
        chaos_main(args)
        return
    if args.net:
        net_main(args)
        return

    n = args.n or (300 if args.smoke else 2000)
    requests = args.requests or (50 if args.smoke else 200)
    theta = args.theta or (1024 if args.smoke else 4096)
    qps_levels = args.qps or ([200.0, 1000.0] if args.smoke
                              else [100.0, 500.0])

    g = ba_graph(n, 4)
    workload, distinct = make_workload(np.diff(np.asarray(g.offsets)),
                                       requests, theta)

    levels = []
    results = {}
    for qps in qps_levels:
        level, res = asyncio.run(run_level(
            g, workload, qps, max_batch=args.max_batch))
        results.update(res)
        levels.append(level)
        print(f"serving qps={qps:g}: "
              f"p50={level['latency_ms']['p50']:.1f}ms "
              f"p99={level['latency_ms']['p99']:.1f}ms "
              f"achieved={level['achieved_qps']:.0f}/s "
              f"occ={level['batch_occupancy_mean']:.2f} "
              f"hit={level['cache_hit_rate']:.2f} shed={level['shed']}")

    # bit-identity parity gate: every distinct problem that was actually
    # served vs a fresh cold solver
    probe = [p for p in distinct if p.signature_digest() in results]
    n_checked = parity_gate(g, probe, results)
    print(f"serving parity: {n_checked}/{len(distinct)} distinct requests "
          "bit-identical to fresh solvers")

    out = {
        "config": {"n": n, "r": 4, "theta": theta, "requests": requests,
                   "max_batch": args.max_batch, "solver_opts": SOLVER_OPTS,
                   "distinct_problems": len(distinct)},
        "levels": levels,
        "parity": {"checked": n_checked, "bit_identical": True},
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.relpath(path)}")

    if args.smoke:
        total_hits = sum(l["cache_hits"] for l in levels)
        total_shed = sum(l["shed"] for l in levels)
        assert total_hits > 0, "smoke: expected nonzero cache hits"
        assert total_shed == 0, f"smoke: {total_shed} requests shed"
        print(f"smoke OK: cache_hits={total_hits} shed=0 "
              f"parity={n_checked}")


if __name__ == "__main__":
    main()
