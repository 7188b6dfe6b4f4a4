"""Distributed IM solve: the paper's pipeline on an N-device mesh.

Every device runs the batched queue sampler on its own threefry counter
range (gIM's grid dimension -> mesh dimension, DESIGN.md §4); the per-device
rows are stacked into one canonical :class:`~repro.core.engine.RRBatch`, so
the whole pipeline is just ``IMMSolver`` driving a ``SamplerEngine`` whose
``sample()`` happens to fan out over the mesh.  Works on any device count
(elastic); on this CPU container use XLA_FLAGS to fake devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.im_solve --n 2000 --k 10
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.graph import csr, generators, weights
from repro.core import rrset
from repro.core.engine import (RRBatch, build_alias_table, draw_roots,
                               register_engine, resolve_qcap)
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_sample_mesh


@register_engine("queue_sharded")
class ShardedQueueEngine:
    """Queue engine fanned out over a device mesh (one lane block per device).

    ``batch`` is per-device; a ``sample()`` returns ``n_dev * batch`` rows.
    Per-device keys are derived by folding the device index into the caller's
    key, mirroring gIM's per-block curand streams.
    """

    device_resident = True           # sample() is one jitted shard_map call

    @dataclass(frozen=True)
    class Config:
        batch: int = 128             # RR sets per device per round
        qcap: Optional[int] = None
        ec: int = rrset.EC_DEFAULT

    def __init__(self, g_rev, config: Optional[Config] = None,
                 mesh: Optional[Mesh] = None, root_weights=None):
        self.g_rev = csr.coalesce_ic(g_rev)
        self.config = config if config is not None else self.Config()
        self.qcap = resolve_qcap(self.config.qcap, self.g_rev)
        self._dedup = rrset.detect_dedup_mode(self.g_rev)
        self.mesh = mesh if mesh is not None else Mesh(
            np.asarray(jax.devices()), ("dev",))
        self.root_weights = (None if root_weights is None
                             else np.asarray(root_weights, np.float32))
        self._table = (None if root_weights is None
                       else build_alias_table(self.root_weights))
        self._fn = None

    @property
    def item_space(self) -> int:
        return self.g_rev.n_nodes

    def _build(self):
        g_rev, mesh = self.g_rev, self.mesh
        n, m = g_rev.n_nodes, g_rev.n_edges
        axis = mesh.axis_names[0]
        bpd, qcap, ec = self.config.batch, self.qcap, self.config.ec
        dedup = self._dedup

        # the alias table joins the pre-placed replicated operands (graph
        # arrays below get the same treatment): closing over explicitly
        # replicated arrays keeps the per-round call free of implicit
        # cross-device transfers under the solver's transfer guard
        rep0 = NamedSharding(self.mesh, P())
        table = (None if self._table is None else type(self._table)(
            *(jax.device_put(x, rep0) for x in self._table)))

        def local(offsets, edge_rows, keydata):
            # full 128-bit key state travels as raw uint32 data (typed keys
            # don't cross shard_map on older jax); fold_in(dev) gives each
            # device its own collision-free stream, like gIM's per-block
            # curand sequences
            dev = jax.lax.axis_index(axis).astype(jnp.uint32)
            key = jax.random.fold_in(jax.random.wrap_key_data(keydata), dev)
            key, sub = jax.random.split(key)
            # uniform (table=None) is the historical randint, bit-identical;
            # weighted IM draws ∝ node_weights through the alias table
            roots = draw_roots(sub, bpd, n, table)
            nodes, lengths, overflow, steps = rrset._sample_queue(
                key, offsets, edge_rows, roots,
                batch=bpd, qcap=qcap, n=n, m=m, dedup=dedup)
            return nodes[None], lengths[None], overflow[None], steps[None]

        # jit the shard_map so rounds hit a compiled executable (no
        # per-round retrace); graph operands are pre-placed replicated so
        # the per-round call does no *implicit* cross-device transfer (the
        # IMM driver holds transfer_guard("disallow") over the hot loop)
        rep = NamedSharding(mesh, P())
        edge_rows = rrset.edge_row_table(g_rev.indices, g_rev.weights, ec=ec)
        self._replicated = tuple(
            jax.device_put(x, rep) for x in (g_rev.offsets, edge_rows))
        self._rep_sharding = rep
        # on-device all-gather to a replicated copy: a device_put of a
        # sharded array onto one device would gather through the host,
        # which the solver's transfer guard refuses on a TPU
        self._replicate = jax.jit(lambda *xs: xs, out_shardings=rep)
        return jax.jit(jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(P(), P(), P()),
            out_specs=(P(axis), P(axis), P(axis), P(axis))))

    def _sample_raw(self, key):
        if self._fn is None:
            self._fn = self._build()
        # the key broadcast is the fan-out's inherent data movement — an
        # *explicit* device_put (permitted under the transfer guard)
        keydata = jax.device_put(jax.random.key_data(key),
                                 self._rep_sharding)
        return self._fn(*self._replicated, keydata)

    def _to_dev0(self, *xs):
        """Mesh arrays onto device 0 with no host round trip: replicate on
        the mesh, then take device 0's copy (explicit, guard-legal)."""
        dev0 = self.mesh.devices.reshape(-1)[0]
        return tuple(jax.device_put(x, dev0) for x in self._replicate(*xs))

    def sample(self, key) -> RRBatch:
        nodes, lengths, overflow, steps = self._sample_raw(key)
        n_dev = self.mesh.devices.size
        # gather the per-device rows onto one device for a single-device
        # consumer
        nodes, lengths, overflow, steps = self._to_dev0(
            nodes, lengths, overflow, steps)
        # devices run concurrently: the batch's parallel-time cost is the
        # slowest device's lockstep count, not the sum
        return RRBatch.make(nodes.reshape(n_dev * self.config.batch, -1),
                            lengths.reshape(-1), overflow.reshape(-1),
                            steps.max())

    def sample_sharded(self, key) -> RRBatch:
        """Mesh-native sample: the batch's *pool* arrays (nodes/lengths)
        stay sharded over the mesh — each device's rows resident where they
        were sampled, no dev0 gather.  A
        :class:`~repro.core.coverage.ShardedDeviceRRStore` on the same mesh
        re-lays them out with one explicit device_put.  Only the per-round
        *stats* (the steps scalar and the per-lane overflow flags) are
        explicitly gathered to one device for the solver's accumulators —
        O(lanes) bools instead of the O(rows·width) node gather ``sample``
        performs."""
        nodes, lengths, overflow, steps = self._sample_raw(key)
        n_dev = self.mesh.devices.size
        overflow, steps = self._to_dev0(overflow, steps)
        return RRBatch.make(nodes.reshape(n_dev * self.config.batch, -1),
                            lengths.reshape(-1), overflow.reshape(-1),
                            steps.max())


def solve(g, k: int | None = None, eps: float | None = None, *,
          batch_per_dev: int = 128, seed: int = 0, selection: str = "auto",
          eval_batch: int | None = None, mesh=None,
          problem: IMProblem | None = None, fault_policy=None,
          checkpoint_dir: str | None = None, checkpoint_every: int = 0):
    """Distributed IM solve: sampler fan-out AND pool/selection sharing one
    mesh.  ``mesh=None`` builds a mesh over every local device; the engine
    samples on it, the solver's pool is sharded over it (``samples`` axis),
    and the per-device rows never leave the device that sampled them
    (``sample_sharded``).

    ``problem`` routes any :class:`~repro.core.problem.IMProblem` variant
    through the same mesh (weighted problems hand the engine their alias
    table; MRIM needs the tagged engine and is served by ``imm()`` /
    ``IMMSolver`` directly, not the sharded queue fan-out).

    ``checkpoint_dir`` makes the solve durable (DESIGN.md §8): the pool is
    checkpointed every ``checkpoint_every`` sampling rounds, and a
    pre-existing checkpoint in the directory is restored before solving —
    the solve resumes from the saved round watermark and stays bit-identical
    to an uninterrupted run.  ``fault_policy`` wraps the hot loop in
    retry-with-backoff (and powers ``--inject-fault`` drills).
    """
    mesh = mesh if mesh is not None else make_sample_mesh(None)
    if problem is None:
        if k is None or eps is None:
            raise TypeError("solve() needs either problem= or the (k, eps) "
                            "pair")
        problem = IMProblem(k=k, eps=eps)
    if problem.t_rounds is not None:
        raise ValueError("the sharded queue engine samples the plain node "
                         "space; solve MRIM via IMMSolver(g).solve(problem)")
    g_rev = csr.reverse(g)
    engine = ShardedQueueEngine(
        g_rev, ShardedQueueEngine.Config(batch=batch_per_dev), mesh=mesh,
        root_weights=problem.node_weights)
    solver = IMMSolver(g, engine=engine, seed=seed, selection=selection,
                       eval_batch=eval_batch, mesh=mesh,
                       fault_policy=fault_policy,
                       checkpoint_dir=checkpoint_dir,
                       checkpoint_every=checkpoint_every)
    resumed_step = None
    if checkpoint_dir is not None:
        from repro.ckpt import checkpoint as ckpt_mod
        if ckpt_mod.latest_step(checkpoint_dir) is not None:
            resumed_step = solver.restore_pool(checkpoint_dir)
    res = solver.solve_problem(problem)
    stats = res.stats
    return res.seeds, res.spread, dict(
        theta=stats.theta, sampled=stats.n_rr_sampled,
        selection=stats.selection, variant=stats.variant,
        n_seeds=len(res.seeds), cost=res.cost,
        devices=engine.mesh.devices.size,
        mesh_shape=stats.mesh_shape,
        pool_sharding=stats.pool_sharding,
        per_device_pool_bytes=stats.per_device_pool_bytes,
        resumed_step=resumed_step)


def _node_vector(spec: str, g, *, seed: int, name: str):
    """CLI node-vector spec -> (n,) float array: 'degree' (out-degree + 1),
    'random' (uniform [1, 2)), or a comma-separated list of n floats.
    Validated here, at parse time, so a bad spec is a one-line error
    instead of a traceback from deep inside the solver."""
    n = g.n_nodes
    if spec == "degree":
        return (np.diff(np.asarray(g.offsets)) + 1.0).astype(np.float32)
    if spec == "random":
        rng = np.random.default_rng(seed)
        return (1.0 + rng.random(n)).astype(np.float32)
    try:
        vals = np.asarray([float(x) for x in spec.split(",")], np.float32)
    except ValueError:
        raise SystemExit(
            f"--{name}: expected 'degree', 'random', or a comma-separated "
            f"list of floats, got {spec!r}") from None
    if vals.shape != (n,):
        raise SystemExit(
            f"--{name}: list has {vals.shape[0]} entries but the graph has "
            f"n={n} nodes — the vector must give one value per node")
    return vals


def _candidate_ids(spec: str, g):
    """CLI candidate spec -> id array: 'top:N' (highest out-degree) or a
    comma-separated id list.  Ids are range-checked against the graph at
    parse time (out-of-range ids used to surface as an opaque traceback
    from the selection kernels)."""
    n = g.n_nodes
    if spec.startswith("top:"):
        try:
            top = int(spec[4:])
        except ValueError:
            raise SystemExit(
                f"--candidates: 'top:N' needs an integer N, got "
                f"{spec!r}") from None
        if not 1 <= top <= n:
            raise SystemExit(
                f"--candidates: top:{top} out of range for a graph with "
                f"n={n} nodes (need 1 <= N <= n)")
        deg = np.diff(np.asarray(g.offsets))
        return np.argsort(-deg, kind="stable")[:top]
    try:
        ids = np.asarray([int(x) for x in spec.split(",")])
    except ValueError:
        raise SystemExit(
            f"--candidates: expected 'top:N' or a comma-separated list of "
            f"node ids, got {spec!r}") from None
    if ids.size == 0:
        raise SystemExit("--candidates: candidate set must be non-empty")
    bad = ids[(ids < 0) | (ids >= n)]
    if bad.size:
        raise SystemExit(
            f"--candidates: ids {sorted(set(bad.tolist()))} out of range "
            f"for a graph with n={n} nodes (valid ids are 0..{n - 1})")
    return ids


def _fault_policy(spec: str):
    """CLI fault-drill spec ``SITE[:N]`` -> FaultPolicy injecting one
    failure at the N-th crossing (default 1) of the named boundary.
    Site names are validated at parse time against ``ft.failures.SITES``
    so a typo is a one-line error, not a deep-solver traceback."""
    from repro.ft.failures import SITES, FaultInjector, FaultPolicy
    site, _, occ = spec.partition(":")
    if site not in SITES:
        raise SystemExit(
            f"--inject-fault: unknown site {site!r}; valid sites: "
            + ", ".join(SITES))
    if occ:
        try:
            n = int(occ)
        except ValueError:
            raise SystemExit(
                f"--inject-fault: occurrence must be an integer, got "
                f"{occ!r} (format: SITE or SITE:N)") from None
        if n < 1:
            raise SystemExit(
                f"--inject-fault: occurrence must be >= 1, got {n}")
    else:
        n = 1
    return FaultPolicy(injector=FaultInjector(fail_at={site: {n}}))


def _serve(args, g):
    """``--serve``: start the network serving surface
    (:class:`repro.serve.IMNetServer`) on an ephemeral local port, drive a
    generated mixed workload (varying k/candidates, repeats for cache
    hits) over real HTTP through :class:`repro.serve.IMClient`, and print
    the ServeStats counters read back from ``/statsz`` (DESIGN.md §7/§11).
    Ctrl-C drains cleanly — admission stops, in-flight batches flush,
    the loop shuts down — instead of a traceback."""
    import asyncio
    import signal

    from repro.serve import IMClient, IMNetServer, ServeConfig, \
        build_service

    theta = args.serve_theta
    deg = np.diff(np.asarray(g.offsets))
    top = np.argsort(-deg, kind="stable")
    base = [IMProblem(k=k, theta=theta) for k in (1, 2, args.k)]
    base += [IMProblem(k=1, theta=theta, candidates=top[:m])
             for m in (g.n_nodes // 4, g.n_nodes // 2)]
    workload = [base[i % len(base)] for i in range(args.serve)]

    async def run():
        svc = build_service({"graph": g}, ServeConfig(
            max_batch=8, batch_window_s=0.002,
            solver_opts={"batch": 64, "seed": 0,
                         "selection": args.selection}))
        server = IMNetServer(svc, host="127.0.0.1", port=0)
        await server.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for s in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(s, stop.set)
        client = IMClient("127.0.0.1", server.port)
        print(f"serving on http://127.0.0.1:{server.port} "
              f"({len(workload)} requests over HTTP)")
        t0 = time.time()
        work = asyncio.ensure_future(asyncio.gather(
            *(client.solve("graph", p) for p in workload),
            return_exceptions=True))
        stopper = asyncio.ensure_future(stop.wait())
        await asyncio.wait({work, stopper},
                           return_when=asyncio.FIRST_COMPLETED)
        if stop.is_set():
            work.cancel()
            try:
                await work
            except asyncio.CancelledError:
                pass
            await server.shutdown()
            print("\ninterrupted: admission stopped, in-flight batches "
                  "flushed, server drained cleanly")
            return
        stopper.cancel()
        sv = (await client.stats())["serve"]
        await server.shutdown()
        print(f"served={sv['served']} cache_hits={sv['cache_hits']} "
              f"batches={sv['batches']} "
              f"occupancy_mean={sv['batch_occupancy_mean']:.2f} "
              f"occur_fastpath={sv['occur_fastpath']} "
              f"stacked={sv['stacked_requests']} shed={sv['shed']} "
              f"expired={sv['expired']} time={time.time() - t0:.2f}s")
        print(f"registry: solvers={sv['registry']['solvers']} "
              f"bytes_in_use={sv['registry']['bytes_in_use']}")
    asyncio.run(run())


def _stream(args, g):
    """``--stream-deltas``: streaming-graph demo (DESIGN.md §9) — one cold
    solve, then ROUNDS random edge-delta batches through
    ``resolve_incremental``, printing the pool-reuse bookkeeping per
    round (kept rows never resample; θ tops back up on the mutated
    graph)."""
    from repro.core import stream

    rng = np.random.default_rng(11)
    problem = IMProblem(k=args.k, theta=args.stream_theta)
    solver = IMMSolver(g, engine="queue", batch=128, seed=0,
                       selection=args.selection, eval_batch=args.eval_batch)
    t0 = time.time()
    res = solver.solve(problem)
    print(f"cold: theta={res.stats.theta} "
          f"seeds={sorted(res.seeds.tolist())} estimate={res.spread:.1f} "
          f"time={time.time() - t0:.2f}s")
    n = g.n_nodes
    for r in range(args.stream_deltas):
        e = args.stream_edges
        deltas = stream.make_deltas(adds=(
            rng.integers(0, n, e), rng.integers(0, n, e),
            (0.05 + 0.25 * rng.random(e)).astype(np.float32)))
        t0 = time.time()
        res = solver.resolve_incremental(problem, deltas)
        info = solver.last_incremental
        print(f"delta[{r}]: +{deltas.n_adds} edges "
              f"affected={info['affected_nodes']} "
              f"kept={info['rows_kept']}/{info['n_rr_before']} "
              f"({info['surviving_fraction']:.1%}) "
              f"reused={info['reused']} "
              f"seeds={sorted(res.seeds.tolist())} "
              f"estimate={res.spread:.1f} time={time.time() - t0:.2f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--r", type=int, default=4)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--eps", type=float, default=0.4)
    ap.add_argument("--serve", type=int, default=None, metavar="REQUESTS",
                    help="serve a generated mixed workload of REQUESTS "
                         "requests through the micro-batched front instead "
                         "of one solve (DESIGN.md §7)")
    ap.add_argument("--serve-theta", type=int, default=4096,
                    help="fixed θ for --serve requests (θ-pinned requests "
                         "are bit-identical to cold solves)")
    ap.add_argument("--stream-deltas", type=int, default=None,
                    metavar="ROUNDS",
                    help="streaming-graph demo: apply ROUNDS random "
                         "edge-delta batches through the incremental "
                         "re-solve path, reusing untouched RR sets "
                         "(DESIGN.md §9)")
    ap.add_argument("--stream-edges", type=int, default=8,
                    help="edges added per --stream-deltas batch (default 8)")
    ap.add_argument("--stream-theta", type=int, default=4096,
                    help="fixed θ for --stream-deltas solves (default 4096)")
    ap.add_argument("--selection", default="auto",
                    choices=("auto", "fused", "bitset", "celf-sketch"),
                    help="seed-selection backend (DESIGN.md §3)")
    ap.add_argument("--eval-batch", type=int, default=None,
                    help="CELF exact-verification batch width (celf-sketch "
                         "selection); default: backend default (32).  Swept "
                         "by benchmarks/perf_im_engines --selection-only")
    ap.add_argument("--mesh", default=None,
                    help="device count or axis spec for the sampling mesh "
                         "(e.g. '4' or 'samples:8'; default: all devices)")
    ap.add_argument("--weights", default=None, metavar="SPEC",
                    help="weighted IM node weights: 'degree', 'random', or "
                         "a comma-separated list (DESIGN.md §6)")
    ap.add_argument("--costs", default=None, metavar="SPEC",
                    help="budgeted IM per-node costs (same specs as "
                         "--weights); requires --budget")
    ap.add_argument("--budget", type=float, default=None,
                    help="budgeted IM total budget (replaces --k)")
    ap.add_argument("--candidates", default=None, metavar="SPEC",
                    help="candidate restriction: 'top:N' (by out-degree) "
                         "or comma-separated node ids")
    ap.add_argument("--t-rounds", type=int, default=None,
                    help="MRIM round count (solved on the tagged mrim "
                         "engine, single-device pool)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="durable solve: checkpoint the pool into DIR every "
                         "--checkpoint-every rounds and auto-resume from an "
                         "existing checkpoint (DESIGN.md §8)")
    ap.add_argument("--checkpoint-every", type=int, default=8,
                    metavar="ROUNDS",
                    help="sampling rounds between pool checkpoints "
                         "(with --checkpoint-dir; default 8)")
    ap.add_argument("--inject-fault", default=None, metavar="SITE[:N]",
                    help="fault drill: inject one transient failure at the "
                         "N-th crossing of SITE (sample/append/grow/select/"
                         "executor; default N=1) and recover via the retry "
                         "policy")
    args = ap.parse_args()
    configure_compile_cache()
    if args.checkpoint_every < 1:
        raise SystemExit("--checkpoint-every: must be >= 1, got "
                         f"{args.checkpoint_every}")
    fault_policy = (None if args.inject_fault is None
                    else _fault_policy(args.inject_fault))
    src, dst = generators.barabasi_albert(args.n, args.r, seed=0)
    g = weights.wc_weights(csr.from_edges(src, dst, args.n))
    if args.serve is not None:
        _serve(args, g)
        return
    if args.stream_deltas is not None:
        if args.stream_deltas < 1 or args.stream_edges < 1:
            raise SystemExit("--stream-deltas/--stream-edges: must be >= 1")
        _stream(args, g)
        return
    problem = IMProblem(
        k=None if args.budget is not None else args.k,
        eps=args.eps,
        node_weights=(None if args.weights is None
                      else _node_vector(args.weights, g, seed=1,
                                        name="weights")),
        costs=(None if args.costs is None
               else _node_vector(args.costs, g, seed=2, name="costs")),
        budget=args.budget,
        candidates=(None if args.candidates is None
                    else _candidate_ids(args.candidates, g)),
        t_rounds=args.t_rounds)
    t0 = time.time()
    if args.t_rounds is not None:
        from repro.core.imm import imm_result
        res = imm_result(g, problem, selection=args.selection)
        print(f"variant={res.stats.variant} theta={res.stats.theta} "
              f"sampled={res.stats.n_rr_sampled} "
              f"selection={res.stats.selection} time={time.time() - t0:.2f}s")
        print(f"seeds_per_round={res.seeds_per_round()} "
              f"estimate={res.spread:.1f}")
        return
    seeds, est, stats = solve(g, selection=args.selection,
                              eval_batch=args.eval_batch,
                              mesh=make_sample_mesh(args.mesh),
                              problem=problem, fault_policy=fault_policy,
                              checkpoint_dir=args.checkpoint_dir,
                              checkpoint_every=args.checkpoint_every)
    print(f"devices={stats['devices']} mesh={stats['pool_sharding']} "
          f"pool_bytes/dev={stats['per_device_pool_bytes']} "
          f"theta={stats['theta']} sampled={stats['sampled']} "
          f"selection={stats['selection']} variant={stats['variant']} "
          f"cost={stats['cost']:.1f} time={time.time() - t0:.2f}s")
    if stats["resumed_step"] is not None:
        print(f"resumed from checkpoint step={stats['resumed_step']} "
              f"({args.checkpoint_dir})")
    if fault_policy is not None:
        inj = fault_policy.injector
        print(f"fault drill: injected={inj.fires} at={inj.fired_log} "
              f"retries={fault_policy.retries} "
              f"oom_recoveries={fault_policy.oom_recoveries} "
              f"gave_up={fault_policy.gave_up}")
    print(f"seeds={sorted(seeds.tolist())} estimate={est:.1f}")


if __name__ == "__main__":
    main()
