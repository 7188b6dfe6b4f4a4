"""§Perf/IM: engine comparison in *parallel time* (lockstep micro-steps).

On this single scalar core the vectorized engines run their B×EC lanes
sequentially, so CPU wall-clock says nothing about TPU/GPU throughput
(table2 reports it anyway, honestly).  The hardware-transferable metric is
the number of lockstep micro-steps: one micro-step = one EC-wide chunk on
every lane = one parallel time unit on width-B vector hardware.

  modelled parallel speedup = serial edge-operations / engine micro-steps

which is exactly the quantity the paper's GPU measures (they report 33-220x
on a 2560-warp V100; we report the same ratio for the 512-lane config).
Also measures the round->refill utilization win (paper Alg. 6 structure).

Both engines are driven through the SamplerEngine protocol: the benchmark
sees only ``engine.sample(key) -> RRBatch`` and the canonical ``steps``
counter, so any registered engine can be dropped into the comparison.

Second half (``BENCH_pipeline.json``): *wall-clock* end-to-end ``imm()`` per
engine on the default benchmark graph — the device-resident pipeline's
figure of merit.  Wall time on this CPU container is meaningful here because
it measures exactly what the device pipeline changed: host↔device bounces,
per-round recompiles, and the O(EC²) dedup — not vector throughput.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import jax

from benchmarks.common import OUT_DIR, ba_graph, write_csv, report
from repro.graph import csr as csr_mod
from repro.core import coverage as cov
from repro.core.engine import make_engine
from repro.core.imm import imm

N, R, QUOTA, B = 20000, 8, 2048, 512
PIPELINE_ENGINES = ("queue", "refill", "dense", "lt")
SELECTION_PATHS = ("fused", "bitset", "celf-sketch")


def bench_selection(n=2000, r=4, k=10, pool_rows=2048, batch=256,
                    sketch_k=512, reps=3, seed=0,
                    eval_batches=(8, 32, 128)):
    """Time the three selection backends on one shared RR pool.

    The pool is sampled once (queue engine) into a ``DeviceRRStore`` with an
    incremental coverage sketch; each path then selects the same k seeds.
    First call per path is reported separately as compile+run; steady-state
    is the min over ``reps`` repeats.  The celf-sketch path additionally
    sweeps the exact-verification batch width (``IMMSolver(eval_batch=)`` /
    ``--eval-batch``): wider batches amortize sweep launches against wasted
    speculative exact evals, and the sweep records where that trade lands
    on this pool.  Writes BENCH_selection.json.
    """
    g = ba_graph(n, r)
    g_rev = csr_mod.reverse(g)
    eng = make_engine("queue", g_rev, batch=batch)
    store = cov.DeviceRRStore(n, sketch_k=sketch_k)
    i = 0
    while store.n_rr < pool_rows:
        store.append_batch(eng.sample(jax.random.key(seed * 100003 + i)))
        i += 1
    out = {"graph": {"kind": "barabasi_albert", "n": n, "r": r,
                     "weights": "wc"},
           "pool": {"rows": store.n_rr, "elements": store.n_elems,
                    "sketch_k": store.sketch_k, "batch": batch},
           "params": {"k": k, "reps": reps, "seed": seed},
           "paths": {}}
    seeds_by_path = {}
    for path in SELECTION_PATHS:
        method = {"fused": "flat", "bitset": "bitset",
                  "celf-sketch": "celf"}[path]
        t0 = time.perf_counter()
        if method == "celf":
            stats = {}
            res = cov.select_seeds_celf(store, k, stats_out=stats)
        else:
            res = store.select(k, method=method)
        jax.block_until_ready(res.seeds)
        first = time.perf_counter() - t0
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            if method == "celf":
                res = cov.select_seeds_celf(store, k)
            else:
                res = store.select(k, method=method)
            jax.block_until_ready(res.seeds)
            best = min(best, time.perf_counter() - t0)
        seeds = np.asarray(res.seeds).tolist()
        seeds_by_path[path] = seeds
        out["paths"][path] = {
            "first_call_s": round(first, 4),
            "steady_s": round(best, 4),
            "seeds": seeds,
            "frac": round(float(res.frac), 6),
        }
        if method == "celf":
            out["paths"][path]["exact_evals"] = stats["n_exact_evals"]
            out["paths"][path]["eval_calls"] = stats["n_eval_calls"]
            sweep = {}
            for eb in eval_batches:
                st = {}
                res_eb = cov.select_seeds_celf(store, k, eval_batch=eb,
                                               stats_out=st)
                jax.block_until_ready(res_eb.seeds)   # compile pass
                best_eb = float("inf")
                for _ in range(reps):
                    t0 = time.perf_counter()
                    res_eb = cov.select_seeds_celf(store, k, eval_batch=eb)
                    jax.block_until_ready(res_eb.seeds)
                    best_eb = min(best_eb, time.perf_counter() - t0)
                assert (np.asarray(res_eb.seeds).tolist()
                        == seeds), "eval_batch must not change seeds"
                sweep[str(eb)] = {
                    "steady_s": round(best_eb, 4),
                    "exact_evals": st["n_exact_evals"],
                    "eval_calls": st["n_eval_calls"],
                }
                report(f"perf_im/selection/celf-eb{eb}", best_eb * 1e6,
                       f"steady={best_eb * 1e3:.1f}ms;"
                       f"evals={st['n_exact_evals']}")
            out["paths"][path]["eval_batch_sweep"] = sweep
        report(f"perf_im/selection/{path}", best * 1e6,
               f"steady={best * 1e3:.1f}ms;first={first:.2f}s")
    out["seeds_identical"] = all(
        s == seeds_by_path[SELECTION_PATHS[0]] for s in seeds_by_path.values())
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "BENCH_selection.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return out


def bench_sharded(n=100_000, rows=1 << 20, k=10, sketch_k=1024,
                  batch_rows=8192, mean_len=8, mesh_spec=None, seed=0):
    """Selection at the post-bitset-matrix scale on the mesh-sharded pool.

    Builds a synthetic RR pool past the point where the packed bitset
    matrix no longer fits (default n=1e5, θ=2^20 ≈ 1e6: the matrix would be
    ``row_capacity · ceil(n/32) · 4`` ≈ 13 GB), then times the fused scan
    and CELF-sketch selection on the sharded store.  Synthetic sets (random
    base + stride, row-unique by construction) keep pool-building O(rows)
    — selection cost does not depend on how the sets were sampled.

    Also the acceptance check for the packed-word sketch: asserts that *no*
    (n+1, k) bool occupancy buffer exists anywhere (store attribute and a
    live-array scan) and records the bool-vs-packed memory comparison.
    Writes ``experiments/bench/BENCH_sharded.json``.
    """
    from repro.launch.mesh import make_sample_mesh
    mesh = make_sample_mesh(mesh_spec)
    store = cov.ShardedDeviceRRStore(n, capacity=batch_rows * mean_len,
                                     sketch_k=sketch_k, mesh=mesh)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    stride = max(n // (2 * mean_len + 2), 1)
    while store.n_rr < rows:
        cnt = min(batch_rows, rows - store.n_rr)
        lens = rng.integers(1, 2 * mean_len, cnt)
        base = rng.integers(0, n, cnt)
        nodes = (base[:, None]
                 + np.arange(lens.max(), dtype=np.int64)[None, :] * stride) % n
        store.append_batch((nodes, lens))
    build_s = time.perf_counter() - t0
    # ---- acceptance: packed-word occupancy end to end, no bool buffer
    assert not hasattr(store, "_occ"), "bool occupancy resurrected"
    packed_bytes = store.sketch_bytes()
    bool_bytes = store.sketch_rows * store.sketch_k          # 1 byte/bucket
    assert packed_bytes * 8 == bool_bytes
    assert not any(
        a.dtype == bool and a.ndim >= 2 and store.sketch_k in a.shape[1:]
        for a in jax.live_arrays()), "live (..., k) bool occupancy found"
    n_words = (n + 31) // 32
    bitset_bytes = store.row_capacity() * n_words * 4 * store.n_shards
    out = {"graph": {"kind": "synthetic", "n": n, "mean_len": mean_len},
           "mesh": {"devices": store.n_shards,
                    "pool_sharding": f"{store.axis}:{store.n_shards}",
                    "per_device_pool_bytes": store.per_device_pool_bytes()},
           "pool": {"rows": store.n_rr, "elements": store.n_elems,
                    "build_s": round(build_s, 2)},
           "sketch_memory": {
               "packed_bytes": packed_bytes, "bool_bytes": bool_bytes,
               "ratio": bool_bytes / max(packed_bytes, 1),
               "sketch_k": store.sketch_k},
           "bitset_matrix_bytes": bitset_bytes,
           "bitset_skipped": bitset_bytes > (1 << 31),
           "params": {"k": k, "seed": seed}, "paths": {}}
    seeds_by = {}
    for path, fn in (("fused", lambda: store.select(k, method="flat")),
                     ("celf-sketch",
                      lambda: cov.select_seeds_celf(store, k))):
        t0 = time.perf_counter()
        res = fn()
        jax.block_until_ready(res.seeds)
        dt = time.perf_counter() - t0
        seeds_by[path] = np.asarray(res.seeds).tolist()
        out["paths"][path] = {"wall_s": round(dt, 3),
                              "seeds": seeds_by[path],
                              "frac": round(float(res.frac), 6)}
        report(f"perf_im/sharded/{path}", dt * 1e6, f"wall={dt:.2f}s")
    out["seeds_identical"] = seeds_by["fused"] == seeds_by["celf-sketch"]
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "BENCH_sharded.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def bench_fused(n=100_000, rows=1 << 20, k=10, sketch_k=1024,
                batch_rows=None, mean_len=8, mesh_spec=None, seed=0,
                quality_n=5000, quality_r=4, quality_k=8,
                quality_theta=16384, quality_sketch_k=8192,
                quality_batch=512, min_mem_ratio=10.0):
    """Pool-free fused sample→sketch pipeline vs the exact sharded pipeline
    (the ``mode="approximate"`` acceptance benchmark).  Three legs, one
    JSON (``experiments/bench/BENCH_fused.json``):

    * **scale** — identical synthetic frontier batches drive a pool-free
      :class:`SketchRRStore` and an exact :class:`ShardedDeviceRRStore`
      side by side at the post-bitset-matrix scale (default n=1e5,
      θ=2^20).  The sketch leg runs *first* and a live-array scan then
      proves the flat pool was never allocated: no int32/bool device
      array at pool scale exists anywhere.  The memory ratio (exact
      per-device pool bytes / per-replica sketch bytes) is **asserted**
      ≥ ``min_mem_ratio``; cold (compile included) and steady build+select
      wall-clock ratios are both recorded.
    * **quality** — end-to-end ``mode="approximate"`` solve on a real WC
      graph in the genuine estimate regime (θ ≫ sketch_k).  The fused
      sketch engine preserves the sampling RNG stream, so the exact twin
      solve materialises *the same* RR pool the approximate solve folded
      away; re-scoring the approximate seeds on that pool must land inside
      the certified ``[lo_rows, hi_rows]`` interval (hard assert), and the
      MC spread must lie within the certified spread bounds (30 % slack
      for MC noise, matching the conformance test).
    * **exact-regime** — θ ≤ sketch_k ⇒ the approximate solve is asserted
      bit-identical to the fused-exact solve (injective mod bucketing).
    """
    from repro.core import forward
    from repro.core.imm import IMMSolver
    from repro.core.problem import IMProblem
    from repro.launch.mesh import make_sample_mesh
    if batch_rows is None:
        batch_rows = max(256, min(8192, rows // 128))
    mesh = make_sample_mesh(mesh_spec)

    def feed(store):
        """Identical synthetic batch stream for both stores (same rng
        seed): selection cost does not depend on how rows were sampled."""
        rng = np.random.default_rng(seed)
        stride = max(n // (2 * mean_len + 2), 1)
        t0 = time.perf_counter()
        while store.n_rr < rows:
            cnt = min(batch_rows, rows - store.n_rr)
            lens = rng.integers(1, 2 * mean_len, cnt)
            base = rng.integers(0, n, cnt)
            nodes = (base[:, None]
                     + np.arange(lens.max(), dtype=np.int64)[None, :]
                     * stride) % n
            store.append_batch((nodes, lens))
        return time.perf_counter() - t0

    # ---- sketch pipeline first: the pool must never exist ---------------
    sk_store = cov.SketchRRStore(n, sketch_k=sketch_k, mesh=mesh)
    sk_build = feed(sk_store)
    assert sk_store.pool_free and sk_store.per_device_pool_bytes() == 0
    t0 = time.perf_counter()
    res_sk = cov.select_seeds_sketch(sk_store, k)
    jax.block_until_ready(res_sk.seeds)
    sk_sel_cold = time.perf_counter() - t0
    info = {}
    t0 = time.perf_counter()
    res_sk = cov.select_seeds_sketch(sk_store, k, info_out=info)
    jax.block_until_ready(res_sk.seeds)
    sk_sel = time.perf_counter() - t0
    # acceptance: nothing pool-shaped is live anywhere on device.  The
    # batch feed stays below pool scale (batch_rows·2·mean_len < rows), so
    # any int32/bool array with ≥ rows elements could only be the pool.
    assert batch_rows * 2 * mean_len < rows, "feed batches reach pool scale"
    leaked = [a.shape for a in jax.live_arrays()
              if a.dtype in (np.dtype(np.int32), np.dtype(bool))
              and a.size >= rows]
    assert not leaked, f"pool-scale arrays live in pool-free mode: {leaked}"

    # ---- exact pipeline on the same batch stream -------------------------
    ex_store = cov.ShardedDeviceRRStore(n, capacity=batch_rows * mean_len,
                                        sketch_k=sketch_k, mesh=mesh)
    ex_build = feed(ex_store)
    assert ex_store.n_rr == sk_store.n_rr == rows
    t0 = time.perf_counter()
    res_ex = ex_store.select(k, method="flat")
    jax.block_until_ready(res_ex.seeds)
    ex_sel_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res_ex = ex_store.select(k, method="flat")
    jax.block_until_ready(res_ex.seeds)
    ex_sel = time.perf_counter() - t0

    mem_ratio = ex_store.per_device_pool_bytes() / max(
        sk_store.sketch_bytes(), 1)
    assert mem_ratio >= min_mem_ratio, (
        f"memory ratio {mem_ratio:.1f}x < {min_mem_ratio}x")
    wall_cold = (ex_build + ex_sel_cold) / max(sk_build + sk_sel_cold, 1e-9)
    wall_steady = (ex_build + ex_sel) / max(sk_build + sk_sel, 1e-9)
    seeds_sk = np.asarray(res_sk.seeds).tolist()
    seeds_ex = np.asarray(res_ex.seeds).tolist()
    report("perf_im/fused/scale", (sk_build + sk_sel) * 1e6,
           f"mem={mem_ratio:.1f}x;wall={wall_steady:.2f}x")

    out = {
        "scale": {
            "graph": {"kind": "synthetic", "n": n, "mean_len": mean_len},
            "mesh": {"devices": sk_store.n_shards},
            "rows": rows, "sketch_k": sk_store.sketch_k,
            "pool_free_live_scan": "passed",
            "memory": {
                "exact_per_device_pool_bytes":
                    ex_store.per_device_pool_bytes(),
                "sketch_bytes_per_replica": sk_store.sketch_bytes(),
                "ratio": round(mem_ratio, 2),
                "min_ratio_asserted": min_mem_ratio},
            "wall_s": {
                "sketch": {"build": round(sk_build, 2),
                           "select_cold": round(sk_sel_cold, 3),
                           "select": round(sk_sel, 3)},
                "exact": {"build": round(ex_build, 2),
                          "select_cold": round(ex_sel_cold, 3),
                          "select": round(ex_sel, 3)},
                "ratio_cold": round(wall_cold, 2),
                "ratio_steady": round(wall_steady, 2)},
            "seeds": {"sketch": seeds_sk, "exact": seeds_ex,
                      "overlap": len(set(seeds_sk) & set(seeds_ex))},
            "estimate": {kk: (float(info[kk]) if kk != "saturated"
                              else bool(info[kk]))
                         for kk in ("occ_union", "est_rows", "lo_rows",
                                    "hi_rows", "rel_error", "saturated")},
        },
        "params": {"k": k, "seed": seed, "batch_rows": batch_rows},
    }

    # ---- quality: certified interval vs the real (re-materialised) pool --
    gq = ba_graph(quality_n, quality_r)
    se = IMMSolver(gq, engine="queue", batch=quality_batch, seed=seed + 1,
                   selection="fused")
    t0 = time.perf_counter()
    r_ex = se.solve(IMProblem(k=quality_k, theta=quality_theta))
    q_ex_wall = time.perf_counter() - t0
    sa = IMMSolver(gq, engine="queue", batch=quality_batch, seed=seed + 1,
                   sketch_k=quality_sketch_k)
    t0 = time.perf_counter()
    r_ap = sa.solve(IMProblem(k=quality_k, theta=quality_theta,
                              mode="approximate"))
    q_ap_wall = time.perf_counter() - t0
    info_q = dict(sa._sketch_info)
    assert sa.store.per_device_pool_bytes() == 0
    assert se.store.n_rr == sa.store.n_rr   # same RNG stream, same θ walk
    # the exact twin's pool IS the approximate solve's never-materialised
    # pool: the approximate seeds' true coverage on it must respect the
    # certificate
    snap = se.store.snapshot()
    flat, ids, valid = (np.asarray(x) for x in
                        (snap.rr_flat, snap.rr_ids, snap.valid))
    hit = np.isin(flat, np.asarray(r_ap.seeds)) & valid
    rows_cov = int(np.unique(ids[hit]).size)
    assert info_q["lo_rows"] <= rows_cov <= info_q["hi_rows"], (
        rows_cov, info_q)
    lo, hi = r_ap.spread_bounds
    mc_ap = forward.ic_spread(jax.random.key(123), gq,
                              np.asarray(r_ap.seeds).tolist(), n_sims=256)
    mc_ex = forward.ic_spread(jax.random.key(123), gq,
                              np.asarray(r_ex.seeds).tolist(), n_sims=256)
    assert lo * 0.7 <= mc_ap <= hi * 1.3, (lo, mc_ap, hi)
    report("perf_im/fused/quality", q_ap_wall * 1e6,
           f"mc={mc_ap:.0f}∈[{lo:.0f},{hi:.0f}];exact_mc={mc_ex:.0f}")
    out["quality"] = {
        "graph": {"kind": "barabasi_albert", "n": quality_n,
                  "r": quality_r, "weights": "wc"},
        "theta": quality_theta, "sketch_k": quality_sketch_k,
        "k": quality_k, "n_rr": int(sa.store.n_rr),
        "wall_s": {"approximate": round(q_ap_wall, 2),
                   "exact": round(q_ex_wall, 2)},
        "rows_covered_on_exact_pool": rows_cov,
        "certified_rows": {"lo": float(info_q["lo_rows"]),
                           "est": float(info_q["est_rows"]),
                           "hi": float(info_q["hi_rows"]),
                           "saturated": bool(info_q["saturated"])},
        "spread_bounds": [round(lo, 1), round(hi, 1)],
        "mc_spread": {"approximate": round(float(mc_ap), 1),
                      "exact": round(float(mc_ex), 1)},
        "seeds": {"approximate": np.asarray(r_ap.seeds).tolist(),
                  "exact": np.asarray(r_ex.seeds).tolist()},
        "mc_within_bounds": bool(lo * 0.7 <= mc_ap <= hi * 1.3),
    }

    # ---- exact-regime identity: θ ≤ sketch_k ⇒ bit-identical seeds -------
    th0 = min(quality_sketch_k // 2, 1024)
    e1 = IMMSolver(gq, engine="queue", batch=quality_batch, seed=seed + 2,
                   selection="fused")
    r1 = e1.solve(IMProblem(k=quality_k, theta=th0))
    e2 = IMMSolver(gq, engine="queue", batch=quality_batch, seed=seed + 2,
                   sketch_k=quality_sketch_k)
    r2 = e2.solve(IMProblem(k=quality_k, theta=th0, mode="approximate"))
    identical = bool(np.array_equal(np.asarray(r1.seeds),
                                    np.asarray(r2.seeds)))
    assert identical, (np.asarray(r1.seeds), np.asarray(r2.seeds))
    out["exact_regime"] = {"theta": th0, "seeds_identical": identical}

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "BENCH_fused.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


def bench_variants(n=2000, r=4, k=8, eps=0.4, max_theta=2048, batch=256,
                   seed=0):
    """End-to-end ``IMMSolver.solve(IMProblem)`` across the problem variants
    (plain / weighted / budgeted / candidate-restricted / MRIM) on one
    graph: wall time, θ, seed count, spread on each variant's scale, and
    budget spent.  Writes ``experiments/bench/BENCH_variants.json``.

    Weights are integer-valued so weighted solves stay bit-reproducible
    across mesh sizes (float32 sums exact — DESIGN.md §6).
    """
    from repro.core.imm import IMMSolver
    from repro.core.problem import IMProblem
    g = ba_graph(n, r)
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 9, n).astype(np.float32)
    costs = rng.integers(1, 5, n).astype(np.float32)
    deg = np.diff(np.asarray(g.offsets))
    cand = np.argsort(-deg, kind="stable")[:max(n // 10, k)]
    problems = {
        "plain": IMProblem(k=k, eps=eps, max_theta=max_theta),
        "weighted": IMProblem(k=k, eps=eps, max_theta=max_theta,
                              node_weights=w),
        "budgeted": IMProblem(eps=eps, max_theta=max_theta, costs=costs,
                              budget=float(2 * k)),
        "weighted+budgeted": IMProblem(eps=eps, max_theta=max_theta,
                                       node_weights=w, costs=costs,
                                       budget=float(2 * k)),
        "candidates": IMProblem(k=k, eps=eps, max_theta=max_theta,
                                candidates=cand),
        "mrim": IMProblem(k=max(k // 2, 1), t_rounds=2, theta=max_theta),
    }
    out = {"graph": {"kind": "barabasi_albert", "n": n, "r": r,
                     "weights": "wc"},
           "params": {"k": k, "eps": eps, "max_theta": max_theta,
                      "batch": batch, "seed": seed,
                      "budget": float(2 * k)},
           "variants": {}}
    for name, problem in problems.items():
        t0 = time.perf_counter()
        res = IMMSolver(g, batch=batch, seed=seed).solve(problem)
        dt = time.perf_counter() - t0
        out["variants"][name] = {
            "wall_s": round(dt, 3),
            "theta": res.stats.theta,
            "rr_sets": res.stats.n_rr_sampled,
            "n_seeds": int(len(res.seeds)),
            "seeds": np.asarray(res.seeds).tolist(),
            "spread_estimate": round(float(res.spread), 1),
            "scale": ("sum_w" if problem.node_weights is not None else "n"),
            "cost": round(float(res.cost), 3),
        }
        report(f"perf_im/variants/{name}", dt * 1e6,
               f"wall={dt:.2f}s;seeds={len(res.seeds)};"
               f"spread={res.spread:.0f}")
    assert out["variants"]["budgeted"]["cost"] <= 2 * k + 1e-6
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "BENCH_variants.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


STREAM8_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from jax.sharding import Mesh
from benchmarks.common import ba_graph
from repro.core import stream
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem

assert len(jax.devices()) == 8
mesh8 = Mesh(np.asarray(jax.devices()), ("samples",))
g = ba_graph(600, 4)
p = IMProblem(k=5, theta=1024)
rng = np.random.default_rng(3)
n = g.n_nodes
deltas = stream.make_deltas(adds=(
    rng.integers(0, n, 8), rng.integers(0, n, 8),
    (0.05 + 0.2 * rng.random(8)).astype(np.float32)))
res = {}
for mesh in (None, mesh8):
    solver = IMMSolver(g, engine="queue", batch=64, seed=9, mesh=mesh)
    solver.solve(p)
    r = solver.resolve_incremental(p, deltas)
    res[r.stats.pool_sharding] = (r.seeds.tolist(),
                                  round(float(r.spread), 6),
                                  solver.last_incremental["rows_kept"])
assert res["samples:1"] == res["samples:8"], res
print("STREAM-8DEV-OK", res["samples:8"])
"""


def bench_streaming(n=2000, r=4, k=8, theta=4096, batch=256, rounds=3,
                    edges=8, seed=0, mesh8=True):
    """Streaming graphs (DESIGN.md §9): incremental re-solve vs cold.

    One cold ``IMMSolver.solve`` at fixed θ, then ``rounds`` random
    edge-delta batches; each round times ``resolve_incremental`` (reusing
    every untouched RR row) against a cold solve of the post-delta graph
    and records the pool-reuse fraction, the wall-clock speedup, and the
    parity flags: graph-digest agreement plus seed *quality* — incremental
    seeds re-scored on the unbiased cold pool must sit within the
    documented residual-bias allowance β·P(touch) (DESIGN.md §9.5) plus 5σ
    sampling noise of the cold seeds' own score.  Raw pool-spread gaps are
    recorded but not asserted: the merged pool is a conditional-law
    mixture, so its own spread estimate is legitimately biased by up to
    β·P(touch).  A windowed-eviction section exercises ``evict_to_bytes``
    on the final cold pool (the incremental solver's round history is
    collapsed by eviction, so its own pool is the wrong demo subject), and
    a subprocess leg re-runs the
    incremental path on a forced 8-fake-device mesh asserting it is
    bit-identical to the 1-device mesh.  Writes
    ``experiments/bench/BENCH_streaming.json``.
    """
    from repro.core import stream
    from repro.core.imm import IMMSolver
    from repro.core.problem import IMProblem

    def pool_rows(slv):
        snap = slv.store.snapshot()
        flat = np.asarray(jax.device_get(snap.rr_flat))
        ids = np.asarray(jax.device_get(snap.rr_ids))
        valid = np.asarray(jax.device_get(snap.valid))
        return flat[valid], ids[valid], int(snap.n_rr)

    def hit_frac(flat, ids, n_rr, seed_set):
        hit = np.unique(ids[np.isin(flat, np.asarray(seed_set))]).size
        return hit / max(n_rr, 1)

    g = ba_graph(n, r)
    rng = np.random.default_rng(seed)
    p = IMProblem(k=k, theta=theta)
    solver = IMMSolver(g, engine="queue", batch=batch, seed=seed)
    t0 = time.perf_counter()
    res_cold0 = solver.solve(p)
    cold0_s = time.perf_counter() - t0
    out = {"graph": {"kind": "barabasi_albert", "n": n, "r": r,
                     "weights": "wc"},
           "params": {"k": k, "theta": theta, "batch": batch,
                      "rounds": rounds, "edges_per_delta": edges,
                      "seed": seed},
           "cold": {"wall_s": round(cold0_s, 3),
                    "seeds": np.asarray(res_cold0.seeds).tolist(),
                    "spread_estimate": round(float(res_cold0.spread), 1)},
           "rounds": []}
    cur_g = g
    for i in range(rounds):
        deltas = stream.make_deltas(adds=(
            rng.integers(0, n, edges), rng.integers(0, n, edges),
            (0.05 + 0.25 * rng.random(edges)).astype(np.float32)))
        t0 = time.perf_counter()
        res_inc = solver.resolve_incremental(p, deltas)
        inc_s = time.perf_counter() - t0
        info = solver.last_incremental
        cur_g = stream.apply_edge_deltas(cur_g, deltas)
        t0 = time.perf_counter()
        cold_solver = IMMSolver(cur_g, engine="queue", batch=batch,
                                seed=seed + 7 * (i + 1))
        res_cold = cold_solver.solve(p)
        cold_s = time.perf_counter() - t0
        # parity: same post-delta graph content; incremental seeds re-scored
        # on the *cold* pool (unbiased under the post-delta law) must be
        # within the residual-bias allowance β·P(touch) plus 5σ noise of the
        # cold seeds' score.  The merged pool's own spread estimate is
        # biased by up to that same allowance, so it is recorded, not
        # asserted.
        flat_c, ids_c, n_c = pool_rows(cold_solver)
        q_inc = hit_frac(flat_c, ids_c, n_c,
                         np.asarray(res_inc.seeds))
        q_cold = hit_frac(flat_c, ids_c, n_c,
                          np.asarray(res_cold.seeds))
        p_touch = hit_frac(flat_c, ids_c, n_c,
                           np.asarray(sorted(
                               stream.affected_nodes(deltas))))
        beta = float(info["surviving_fraction"])
        se = np.sqrt(max(q_cold * (1 - q_cold), 1e-12) * (2.0 / n_c))
        quality_ok = q_cold - q_inc <= beta * p_touch + 5.0 * se
        digest_ok = (csr_mod.graph_digest(solver.g)
                     == csr_mod.graph_digest(cur_g))
        out["rounds"].append({
            "edges_added": edges,
            "affected_nodes": info["affected_nodes"],
            "surviving_fraction": round(info["surviving_fraction"], 4),
            "rows_kept": info["rows_kept"],
            "rows_dropped": info["rows_dropped"],
            "pool_reused": info["reused"],
            "incremental_wall_s": round(inc_s, 3),
            "cold_wall_s": round(cold_s, 3),
            "speedup_vs_cold": round(cold_s / max(inc_s, 1e-9), 2),
            "incremental_spread": round(float(res_inc.spread), 1),
            "cold_spread": round(float(res_cold.spread), 1),
            "cold_pool_quality_inc_seeds": round(q_inc, 4),
            "cold_pool_quality_cold_seeds": round(q_cold, 4),
            "residual_bias_allowance": round(beta * p_touch, 4),
            "graph_digest_parity": bool(digest_ok),
            "seed_quality_within_bound": bool(quality_ok),
        })
        report(f"perf_im/streaming/round{i}", inc_s * 1e6,
               f"inc={inc_s:.2f}s;cold={cold_s:.2f}s;"
               f"reuse={info['surviving_fraction']:.0%}")
    out["parity_ok"] = all(rr["graph_digest_parity"]
                           and rr["seed_quality_within_bound"]
                           and rr["pool_reused"] for rr in out["rounds"])
    out["mean_speedup_vs_cold"] = round(
        float(np.mean([rr["speedup_vs_cold"] for rr in out["rounds"]])), 2)
    # windowed eviction: bound the final cold pool to half its footprint.
    # The cold store still has its genuine per-round append history; the
    # incremental store's history was collapsed to one synthetic round by
    # evict_rows_containing, so it has nothing windowed left to drop.
    store = cold_solver.store
    before = store.per_device_pool_bytes()
    ev = store.evict_to_bytes(before // 2)
    out["window"] = {"bytes_before": before,
                     "bytes_after": store.per_device_pool_bytes(),
                     "bound": before // 2, "met": bool(ev["met"]),
                     "rows_dropped": int(ev["rows_dropped"])}
    if mesh8:
        import subprocess
        import sys
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        # the 8 forced host devices are a CPU leg; pinned so the child
        # never reaches for a chip this process may hold
        env["JAX_PLATFORMS"] = "cpu"
        rp = subprocess.run([sys.executable, "-c", STREAM8_SCRIPT], env=env,
                            capture_output=True, text=True,
                            cwd=os.path.dirname(os.path.dirname(
                                os.path.abspath(__file__))), timeout=900)
        ok = rp.returncode == 0 and "STREAM-8DEV-OK" in rp.stdout
        out["mesh8"] = {"ok": bool(ok)}
        if not ok:
            out["mesh8"]["stdout"] = rp.stdout[-1000:]
            out["mesh8"]["stderr"] = rp.stderr[-2000:]
        report("perf_im/streaming/mesh8", 0.0,
               "ok" if ok else "FAILED")
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "BENCH_streaming.json"), "w") as f:
        json.dump(out, f, indent=2)
    assert out["parity_ok"], "streaming parity flags failed"
    if mesh8:
        assert out["mesh8"]["ok"], "8-device streaming parity failed"
    return out


def bench_pipeline(n=N, r=R, k=10, eps=0.4, max_theta=4096, batch=512,
                   engines=PIPELINE_ENGINES, seed=0):
    """Time end-to-end ``imm()`` per engine; returns the result dict."""
    g = ba_graph(n, r)
    out = {"graph": {"kind": "barabasi_albert", "n": n, "r": r,
                     "weights": "wc"},
           "params": {"k": k, "eps": eps, "max_theta": max_theta,
                      "batch": batch, "seed": seed},
           # same imm() call measured on the parent commit (host-pipeline
           # IncrementalRRStore + per-escalation recompiles + O(EC²) dedup),
           # same machine/config; recorded for the device-pipeline A/B
           "baseline_main": ({"queue": {"wall_s": 98.57},
                              "refill": {"wall_s": 34.54},
                              "commit": "5812556"}
                             if (n, r, k, eps, max_theta, batch) ==
                                (20000, 8, 10, 0.4, 4096, 512) else None),
           "engines": {}}
    for name in engines:
        t0 = time.perf_counter()
        seeds, est, stats = imm(g, k, eps, engine=name, batch=batch,
                                seed=seed, max_theta=max_theta)
        dt = time.perf_counter() - t0
        out["engines"][name] = {
            "wall_s": round(dt, 3),
            "theta": stats.theta,
            "rr_sets": stats.n_rr_sampled,
            "rounds": stats.rounds,
            "micro_steps": stats.sampling_steps,
            "lb_iters": stats.lb_iters,
            "spread_estimate": round(float(est), 1),
        }
        report(f"perf_im/pipeline/{name}", dt * 1e6,
               f"wall={dt:.2f}s;rr={stats.n_rr_sampled};"
               f"rounds={stats.rounds}")
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "BENCH_pipeline.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    return out


def main(n=N, r=R, quota=QUOTA, b=B, pipeline_kw=None, selection_kw=None):
    g = ba_graph(n, r)
    g_rev = csr_mod.reverse(g)
    deg = np.diff(np.asarray(g_rev.offsets))
    rows = []
    # serial work model: ops = nodes visited + edges examined (the oracle
    # walks each adjacency once per visited node)
    # --- round engine
    round_eng = make_engine("queue", g_rev, batch=b, qcap=n)
    steps_round = 0
    serial_ops = 0
    done = 0
    i = 0
    while done < quota:
        b_ = round_eng.sample(jax.random.key(i))
        steps_round += int(b_.steps)
        nodes = np.asarray(b_.nodes); lens = np.asarray(b_.lengths)
        for row in range(b_.n_sets):
            vis = nodes[row, :lens[row]]
            serial_ops += lens[row] + deg[vis].sum()
        done += b_.n_sets
        i += 1
    # --- refill engine (same quota, B persistent lanes)
    refill_eng = make_engine("refill", g_rev, batch=quota, lanes=b,
                             out_cap=8 * quota // b * 64)
    bf = refill_eng.sample(jax.random.key(99))
    steps_refill = int(bf.steps)
    n_sets = bf.n_sets
    speedup_round = serial_ops / max(steps_round, 1)
    speedup_refill = serial_ops / max(steps_refill, 1) * done / max(n_sets, 1)
    rows.append(["round", done, steps_round, int(serial_ops),
                 round(speedup_round, 1)])
    rows.append(["refill", n_sets, steps_refill, int(serial_ops),
                 round(speedup_refill, 1)])
    write_csv("perf_im_engines",
              ["engine", "rr_sets", "micro_steps", "serial_ops",
               "modelled_parallel_speedup"], rows)
    report("perf_im/round", steps_round, f"par_speedup={speedup_round:.0f}x")
    report("perf_im/refill", steps_refill,
           f"par_speedup={speedup_refill:.0f}x;"
           f"step_win={steps_round / max(steps_refill, 1):.2f}x")
    bench_pipeline(n=n, r=r, **(pipeline_kw or {}))
    bench_selection(**(selection_kw or {}))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=N)
    ap.add_argument("--r", type=int, default=R)
    ap.add_argument("--quota", type=int, default=QUOTA)
    ap.add_argument("--b", type=int, default=B)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--eps", type=float, default=0.4)
    ap.add_argument("--max-theta", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--engines", default=",".join(PIPELINE_ENGINES))
    ap.add_argument("--pipeline-only", action="store_true",
                    help="skip the micro-step section (CI smoke)")
    ap.add_argument("--selection-only", action="store_true",
                    help="run only the selection-backend comparison")
    ap.add_argument("--sharded", action="store_true",
                    help="mesh-sharded selection sweep past the bitset-"
                         "matrix limit (writes BENCH_sharded.json)")
    ap.add_argument("--fused-sketch", action="store_true",
                    help="pool-free fused sample→sketch vs exact pipeline: "
                         "memory ratio (asserted ≥10×), wall-clock, and "
                         "certified-quality legs (writes BENCH_fused.json)")
    ap.add_argument("--variants", action="store_true",
                    help="IMProblem variant sweep: plain/weighted/budgeted/"
                         "candidates/mrim (writes BENCH_variants.json)")
    ap.add_argument("--streaming", action="store_true",
                    help="streaming-graph sweep: incremental re-solve vs "
                         "cold after edge deltas, windowed eviction, and "
                         "the 8-fake-device parity leg (writes "
                         "BENCH_streaming.json)")
    ap.add_argument("--stream-rounds", type=int, default=3,
                    help="delta batches for --streaming (default 3)")
    ap.add_argument("--theta", type=int, default=4096,
                    help="fixed θ for --streaming solves (default 4096)")
    ap.add_argument("--pool-rows", type=int, default=2048,
                    help="RR pool size for --selection-only")
    ap.add_argument("--rows", type=int, default=None,
                    help="target pool rows for --sharded (default 2^20)")
    ap.add_argument("--sketch-k", type=int, default=512)
    ap.add_argument("--mesh", default=None,
                    help="--sharded mesh spec (device count or 'axis:N')")
    args = ap.parse_args()
    pkw = dict(k=args.k, eps=args.eps, max_theta=args.max_theta,
               batch=args.batch, engines=tuple(args.engines.split(",")))
    skw = dict(n=args.n, r=args.r, k=args.k, pool_rows=args.pool_rows,
               batch=args.batch, sketch_k=args.sketch_k)
    if args.streaming:
        bench_streaming(n=args.n, r=args.r, k=args.k, theta=args.theta,
                        batch=args.batch, rounds=args.stream_rounds)
    elif args.variants:
        bench_variants(n=args.n, r=args.r, k=args.k, eps=args.eps,
                       max_theta=args.max_theta, batch=args.batch)
    elif args.fused_sketch:
        rows = args.rows if args.rows is not None else 1 << 20
        bench_fused(n=args.n, rows=rows, k=args.k,
                    sketch_k=args.sketch_k, mesh_spec=args.mesh)
    elif args.sharded:
        rows = args.rows if args.rows is not None else 1 << 20
        bench_sharded(n=args.n, rows=rows, k=args.k,
                      sketch_k=args.sketch_k, mesh_spec=args.mesh)
    elif args.selection_only:
        bench_selection(**skw)
    elif args.pipeline_only:
        bench_pipeline(n=args.n, r=args.r, **pkw)
    else:
        main(n=args.n, r=args.r, quota=args.quota, b=args.b, pipeline_kw=pkw,
             selection_kw=skw)
