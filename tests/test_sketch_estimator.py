"""The exact store's coverage sketch: Δocc lower bounds (exact when the
bucketing is injective), the incremental fold against its flat rebuild
under both bucketings, linear counting, and the union-popcount kernel."""
import numpy as np
import jax
import pytest

from repro.core import coverage as cov, sketch as sk


def _random_pool(rng, n, batches=4, count=60, max_len=8, sketch_k=None,
                 sketch_mode="mod"):
    dev = cov.DeviceRRStore(n, capacity=8, sketch_k=sketch_k,
                            sketch_mode=sketch_mode)
    rr_all = []
    for _ in range(batches):
        lens = rng.integers(1, max_len, count)
        nodes = np.zeros((count, int(lens.max())), np.int64)
        for i, ln in enumerate(lens):
            nodes[i, :ln] = rng.choice(n, size=ln, replace=False)
        dev.append_batch((nodes, lens))
        rr_all += [nodes[j, :lens[j]].tolist() for j in range(count)]
    return dev, rr_all


# ------------------------------------------------ sketch estimator bounds

def test_sketch_gains_are_lower_bounds_and_exact_when_wide():
    """Δocc(v | ∅) <= exact Occur[v] always; equality when the bucketing is
    injective (sketch_k >= n_rr, mod hashing)."""
    rng = np.random.default_rng(5)
    n = 30
    for sketch_k, exact in ((256, True), (32, False)):
        dev, rr_all = _random_pool(rng, n, batches=2, count=50,
                                   sketch_k=sketch_k)
        occur = np.zeros(n, np.int64)
        for rr in rr_all:
            for v in rr:
                occur[v] += 1
        cov_sk = jax.device_put(np.zeros(dev.sketch_k // 32, np.uint32))
        deltas = np.asarray(jax.device_get(
            sk.union_gains(dev.sketch_words(), cov_sk)))[:n]
        assert (deltas <= occur).all()
        if exact:
            np.testing.assert_array_equal(deltas, occur)


def test_sketch_from_flat_matches_incremental():
    """A sketch rebuilt from the live flat pool equals the incrementally
    maintained one (same bucketing, same row ids), under the default
    ``"mod"`` bucketing and the Knuth-multiplicative ``"mix"`` one."""
    for mode, seed, n, count, max_len in (("mod", 9, 35, 20, 8),
                                          ("mix", 21, 40, 40, 7)):
        dev, _ = _random_pool(np.random.default_rng(seed), n, batches=3,
                              count=count, max_len=max_len, sketch_k=64,
                              sketch_mode=mode)
        occ = sk.sketch_from_flat(dev._flat[0], dev._ids[0], dev._valid[0],
                                  n=n, k=dev.sketch_k, mode=mode)
        rebuilt = sk.pack_sketch(occ, words=dev.sketch_k // 32)
        np.testing.assert_array_equal(np.asarray(rebuilt),
                                      np.asarray(dev.sketch_words()))


def test_linear_count_estimator():
    assert sk.linear_count(0, 64) == pytest.approx(0.0)
    # small occupancy ~ cardinality; high occupancy corrects upward
    assert sk.linear_count(4, 256) == pytest.approx(4.0, rel=0.02)
    assert sk.linear_count(60, 64) > 60
    assert np.isfinite(sk.linear_count(64, 64))


def test_union_popcount_kernel_matches_numpy():
    from repro.kernels import ops as kops
    rng = np.random.default_rng(13)
    rows, w = 37, 4
    words = rng.integers(0, 2**32, (rows, w), dtype=np.uint64).astype(np.uint32)
    covw = rng.integers(0, 2**32, (w,), dtype=np.uint64).astype(np.uint32)
    got = np.asarray(kops.sketch_union_popcount(words, covw))
    expect = np.array([
        bin(int.from_bytes((words[i] | covw).tobytes(), "little")).count("1")
        for i in range(rows)])
    np.testing.assert_array_equal(got, expect)
