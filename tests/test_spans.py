"""The solve loop's host spans and fetch counter (core/spans.py), and the
queue sampler's named scopes (core/rrset.py), on the CPU."""
import contextlib
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import rrset, spans
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem
from repro.graph import csr as csr_mod
from repro.graph import generators, weights

SCOPES = ("rr.edges", "rr.draw", "rr.visited_test", "rr.dedup", "rr.append",
          "rr.visited_write", "rr.advance")


def _graph(n=300, seed=0):
    src, dst = generators.barabasi_albert(n, 3, seed=seed)
    return weights.wc_weights(csr_mod.from_edges(src, dst, n))


def _imm_spans(logdir):
    """(name, start, end, args) of every ``imm.*`` event in the trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("imm."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans_, i):
    """Index of the innermost span that encloses span ``i``."""
    _, a, b, _ = spans_[i]
    best = None
    for j, (_, a2, b2, _) in enumerate(spans_):
        if j != i and a2 <= a and b <= b2 and (
                best is None or a2 >= spans_[best][1]):
            best = j
    return best


@pytest.fixture(scope="module")
def traced_solve(tmp_path_factory):
    g = _graph()
    solver = IMMSolver(g, engine="queue", batch=64, seed=3)
    problem = IMProblem(k=3, eps=0.5, max_theta=512)
    solver.solve(problem)                    # compile outside the trace
    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(logdir)
    try:
        solver = IMMSolver(g, engine="queue", batch=64, seed=4)
        res = solver.solve(problem)
    finally:
        jax.profiler.stop_trace()
    return res, _imm_spans(logdir)


def test_solve_spans_nest_with_their_arguments(traced_solve):
    res, got = traced_solve
    solves = [s for s in got if s[0] == "imm.solve"]
    assert len(solves) == 1
    assert int(solves[0][3]["solve"]) == 1
    assert solves[0][3]["variant"] == "plain"
    # building the solver comes first, outside any solve
    assert got[0][0] == "imm.prepare" and "solve" not in got[0][3]
    got = got[1:]
    names = [s[0] for s in got]
    # every span of the solve carries its id
    assert all(int(s[3]["solve"]) == 1 for s in got)
    parent = {i: (got[_parent(got, i)][0] if _parent(got, i) is not None
                  else None) for i in range(len(got))}
    want = {"imm.solve": {None}, "imm.prepare": {"imm.solve"},
            "imm.lb_iter": {"imm.solve"},
            "imm.sample_until": {"imm.lb_iter", "imm.solve"},
            "imm.round": {"imm.sample_until"}, "imm.sample": {"imm.round"},
            "imm.append": {"imm.round"},
            "imm.select": {"imm.lb_iter", "imm.solve"},
            "imm.fetch": {"imm.append", "imm.select", "imm.lb_iter",
                          "imm.solve"}}
    for i, name in enumerate(names):
        assert parent[i] in want[name], (name, parent[i])
    # the chain imm.solve > lb_iter > sample_until > round > append > fetch
    shard = [i for i, s in enumerate(got) if s[0] == "imm.fetch"
             and s[3]["what"] == "shard_counts"]
    assert shard and all(parent[i] == "imm.append" for i in shard)
    chain, i = [], shard[0]
    while i is not None:
        chain.append(got[i][0])
        i = _parent(got, i)
    assert chain == ["imm.fetch", "imm.append", "imm.round",
                     "imm.sample_until", "imm.lb_iter", "imm.solve"]
    rounds = [s for s in got if s[0] == "imm.round"]
    assert [int(s[3]["round"]) for s in rounds] == list(
        range(1, res.stats.rounds + 1))
    lb = [s for s in got if s[0] == "imm.lb_iter"]
    assert lb and all(int(s[3]["theta"]) > 0 for s in lb)
    assert [int(s[3]["i"]) for s in lb] == list(range(1, len(lb) + 1))
    lb_frac = [i for i, s in enumerate(got) if s[0] == "imm.fetch"
               and s[3]["what"] == "lb_frac"]
    assert lb_frac and all(parent[i] == "imm.select" for i in lb_frac)
    assert sum(s[3].get("what") == "result" for s in got) == 1


def test_host_fetches_counts_every_fetch_span(traced_solve):
    res, got = traced_solve
    fetches = [s for s in got if s[0] == "imm.fetch"]
    assert res.stats.host_fetches == len(fetches)
    assert res.stats.host_fetches >= res.stats.rounds + 1


def test_append_spans_carry_the_round_elements(traced_solve):
    res, got = traced_solve
    appends = [s for s in got if s[0] == "imm.append"]
    assert len(appends) == res.stats.rounds
    elems = [int(s[3]["elements"]) for s in appends]
    assert all(e > 0 for e in elems)
    assert sum(elems) == res.stats.pool_elements


def test_host_fetches_without_a_trace():
    g = _graph(seed=1)
    problem = IMProblem(k=2, theta=256)
    solver = IMMSolver(g, engine="queue", batch=64, seed=0)
    res = solver.solve(problem)
    # one shard count per round, the stats, the result
    assert res.stats.host_fetches == res.stats.rounds + 2
    before = res.stats.host_fetches
    solver.solve(problem)          # a second solve on the same pool
    assert solver.stats.host_fetches > before


def test_fetch_outside_a_solve_returns_the_value():
    x = jax.numpy.arange(3)
    assert spans.fetch("x", x).tolist() == [0, 1, 2]


def _sample_queue_hlo(g_rev, dedup):
    return rrset._sample_queue.lower(
        jax.random.key(0), g_rev.offsets,
        rrset.edge_row_table(g_rev.indices, g_rev.weights, ec=32),
        jax.numpy.arange(16, dtype=jax.numpy.int32),
        batch=16, qcap=g_rev.n_nodes + 1, n=g_rev.n_nodes,
        m=g_rev.n_edges, dedup=dedup).compile().as_text()


def _strip(hlo: str) -> str:
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    return "\n".join(line for line in hlo.splitlines() if not re.match(
        r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", line))


def test_sample_queue_hlo_names_every_scope():
    g_rev = csr_mod.reverse(_graph(n=200))
    hlo = _sample_queue_hlo(g_rev, rrset.detect_dedup_mode(g_rev))
    found = set(re.findall(r"rr\.[a-z_]+", hlo))
    assert found == set(SCOPES)


def test_scopes_change_only_metadata(monkeypatch):
    g_rev = csr_mod.reverse(_graph(n=200))
    dedup = rrset.detect_dedup_mode(g_rev)
    jax.clear_caches()
    scoped = _sample_queue_hlo(g_rev, dedup)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain = _sample_queue_hlo(g_rev, dedup)
    jax.clear_caches()
    assert "rr.edges" in scoped and "rr.edges" not in plain
    assert _strip(scoped) == _strip(plain)


def test_scopes_keep_the_draws(monkeypatch):
    g_rev = csr_mod.reverse(_graph(n=200))

    def draw():
        jax.clear_caches()
        out = rrset.sample_rrsets_queue(jax.random.key(5), g_rev, 32,
                                        g_rev.n_nodes + 1)
        return [np.asarray(x) for x in (out.nodes, out.lengths, out.steps)]

    scoped = draw()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = draw()
    jax.clear_caches()
    for a, b in zip(scoped, plain):
        np.testing.assert_array_equal(a, b)
