"""Mesh-sharded RR pool: packed-word sketch fold properties and the
single-device == multi-device parity contract.

The packed fold (sort+dedup+scatter-add in ``core/sketch.py``, and the
Pallas scatter-or kernel in ``kernels/sketch.py``) must be bit-identical to
the PR-3 bool-matrix fold it replaced; the sharded selection backends
(fused scan, Pallas bitset, CELF) must return seeds/gains/F_R bit-identical
to the 1-device mesh on a forced 8-way host-device mesh, with the whole
solve legal under ``jax.transfer_guard("disallow")``.  Device count is
locked at first jax init, so the multi-device checks run in a subprocess
with XLA_FLAGS set (the suite itself must keep seeing 1 device).
"""
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.core import coverage as cov, sketch as sk


def _random_batches(rng, n, batches=4, count=50, max_len=8,
                    with_empty=True, with_overflow=False):
    out = []
    for i in range(batches):
        lo = 0 if (with_empty and i % 2 == 0) else 1
        lens = rng.integers(lo, max_len, count)
        w = max(int(lens.max()), 1)
        nodes = np.zeros((count, w), np.int64)
        for j, ln in enumerate(lens):
            if ln:
                nodes[j, :ln] = rng.choice(n, size=min(ln, w), replace=False)
        if with_overflow and i == batches - 1:
            lens = lens + w          # overflowed lanes: raw length > width
        out.append((nodes, lens))
    return out


# ------------------------------------------- packed fold == bool fold

@pytest.mark.parametrize("seed,mode", [(0, "mod"), (1, "mod"), (2, "mix"),
                                       (3, "mod")])
def test_packed_fold_bit_identical_to_bool_matrix_fold(seed, mode):
    """Property: the incremental packed-word fold equals
    ``pack_sketch(bool fold)`` bit for bit, across appends with empty rows,
    overflowed lengths, and both hash modes (the PR-3 bool fold is the
    reference oracle; no production path materializes it anymore)."""
    rng = np.random.default_rng(seed)
    n, k = 41, 64
    store = cov.ShardedDeviceRRStore(n, capacity=8, sketch_k=k,
                                     sketch_mode=mode)
    for b in _random_batches(rng, n, with_overflow=(seed == 3)):
        store.append_batch(b)
    occ = sk.sketch_from_flat(store._flat[0], store._ids[0], store._valid[0],
                              n=n, k=store.sketch_k, mode=mode)
    ref = np.asarray(sk.pack_sketch(occ, words=store.sketch_k // 32))
    got = np.asarray(store.sketch_words())
    np.testing.assert_array_equal(got, ref)


def test_scatter_or_kernel_matches_sort_based_fold():
    """The lexsort fold (the one scatter-OR on every backend) commits the
    same words as a serial atomicOr-style loop, including duplicate
    (row, bucket) pairs, bits already present, and dropped sentinels."""
    rng = np.random.default_rng(11)
    rows, k, e = 37, 64, 500
    v = rng.integers(0, rows + 2, e).astype(np.int32)    # some OOB sentinels
    b = rng.integers(0, k, e).astype(np.int32)
    base = rng.integers(0, 2**32, (rows, k // 32),
                        dtype=np.uint64).astype(np.uint32)
    got_s = np.asarray(sk.scatter_or_bits(
        jax.numpy.asarray(base), jax.numpy.asarray(v), jax.numpy.asarray(b)))
    ref = base.copy()
    for vv, bb in zip(v, b):
        if 0 <= vv < rows:
            ref[vv, bb >> 5] |= np.uint32(1) << (bb & 31)
    np.testing.assert_array_equal(got_s, ref)


def test_packed_from_flat_matches_bool_reference():
    rng = np.random.default_rng(5)
    n, k = 30, 32
    store = cov.ShardedDeviceRRStore(n, capacity=8)
    for b in _random_batches(rng, n, batches=2):
        store.append_batch(b)
    flat, ids, valid = store._flat[0], store._ids[0], store._valid[0]
    got = np.asarray(sk.sketch_packed_from_flat(
        flat, ids, valid, n_rows=n + 1, k=k, mode="mod"))
    ref = np.asarray(sk.pack_sketch(
        sk.sketch_from_flat(flat, ids, valid, n=n, k=k, mode="mod"),
        words=k // 32))
    np.testing.assert_array_equal(got, ref)


def test_no_bool_occupancy_on_append_path():
    """Acceptance: the sketch is packed-word end to end — the store keeps
    no (n+1, k) bool occupancy buffer, and the packed replica is exactly
    1/8th of the bool bytes the PR-3 fold held."""
    store = cov.ShardedDeviceRRStore(100, sketch_k=128)
    assert not hasattr(store, "_occ")
    assert store._sk_words.dtype == np.uint32
    assert store.sketch_bytes() * 8 == store.sketch_rows * store.sketch_k
    store.append_batch((np.array([[1, 2, 3]]), np.array([3])))
    assert not hasattr(store, "_occ")
    assert store._sk_words.dtype == np.uint32


def test_mesh1_solver_defaults_record_sharding():
    from repro.graph import csr as csr_mod, generators, weights
    from repro.core.imm import IMMSolver
    src, dst = generators.erdos_renyi(30, 120, seed=0)
    g = weights.wc_weights(csr_mod.from_edges(src, dst, 30))
    from repro.core.problem import IMProblem
    solver = IMMSolver(g, engine="queue", batch=32)
    stats = solver.solve(IMProblem(k=2, eps=0.5, max_theta=64)).stats
    assert stats.mesh_shape == (1,)
    assert stats.pool_sharding == "samples:1"
    assert stats.per_device_pool_bytes == \
        solver.store.capacity * (4 + 4 + 1)


# --------------------------------------- 8-way mesh parity (subprocess)

PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import coverage as cov
from repro.graph import csr as csr_mod, generators, weights
from repro.core.imm import IMMSolver
from repro.core.problem import IMProblem

assert len(jax.devices()) == 8
mesh8 = Mesh(np.asarray(jax.devices()), ("samples",))
n, k = 50, 6

def batches():
    r = np.random.default_rng(7)
    out = []
    for _ in range(4):
        lens = r.integers(0, 8, 61)          # empty rows + odd row count
        w = max(int(lens.max()), 1)
        nodes = np.zeros((61, w), np.int64)
        for i, ln in enumerate(lens):
            if ln:
                nodes[i, :ln] = r.choice(n, size=ln, replace=False)
        out.append((nodes, lens))
    return out

# identical pool on a 1-device and an 8-device mesh: every backend must be
# bit-identical, for every sketch size, all under the transfer guard
for sketch_k in (32, 256, None):
    d1 = cov.ShardedDeviceRRStore(n, capacity=8, sketch_k=sketch_k)
    d8 = cov.ShardedDeviceRRStore(n, capacity=64, sketch_k=sketch_k,
                                  mesh=mesh8)
    with jax.transfer_guard("disallow"):
        for b in batches():
            d1.append_batch(b)
            d8.append_batch(b)
        assert d1.n_rr == d8.n_rr and d1.n_elems == d8.n_elems
        if sketch_k is not None:
            s1, s8 = jax.device_get((d1.sketch_words(), d8.sketch_words()))
            assert np.array_equal(np.asarray(s1), np.asarray(s8)), \
                "incremental sketch fold diverged across mesh sizes"
        for method in ("flat", "bitset"):
            r1, r8 = d1.select(k, method=method), d8.select(k, method=method)
            a, b_ = jax.device_get(((r1.seeds, r1.gains, r1.frac),
                                    (r8.seeds, r8.gains, r8.frac)))
            assert np.array_equal(a[0], b_[0]), (method, a[0], b_[0])
            assert np.array_equal(a[1], b_[1]) and a[2] == b_[2], method
        c1 = cov.select_seeds_celf(d1, k)
        c8 = cov.select_seeds_celf(d8, k)
        a, b_ = jax.device_get(((c1.seeds, c1.gains, c1.frac),
                                (c8.seeds, c8.gains, c8.frac)))
        assert np.array_equal(a[0], b_[0]), ("celf", sketch_k, a[0], b_[0])
        assert np.array_equal(a[1], b_[1]) and a[2] == b_[2]

# full solve: same engine stream into a sharded vs single-device pool
src, dst = generators.erdos_renyi(60, 300, seed=6)
g = weights.wc_weights(csr_mod.from_edges(src, dst, 60))
res = {}
for mesh in (None, mesh8):
    solver = IMMSolver(g, engine="queue", batch=64, seed=3,
                       selection="celf-sketch", mesh=mesh)
    with jax.transfer_guard("disallow"):
        r = solver.solve(IMProblem(k=4, eps=0.5, max_theta=256))
    res[r.stats.pool_sharding] = (r.seeds.tolist(), round(r.spread, 6))
assert res["samples:1"] == res["samples:8"], res
print("OK", res["samples:8"])
"""


def test_sharded_backends_bit_identical_to_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", PARITY_SCRIPT], env=env,
                       capture_output=True, text=True, cwd="/root/repo",
                       timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "OK" in r.stdout
