"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs. pure-jnp ref oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


RNG = np.random.default_rng(0)


@pytest.mark.parametrize("r,l", [(16, 128), (100, 128), (257, 256),
                                 (1024, 512), (7, 384)])
def test_membership_sweep(r, l):
    rows = jnp.asarray(RNG.integers(0, 50, size=(r, l)), jnp.int32)
    lens = jnp.asarray(RNG.integers(0, l + 1, size=r), jnp.int32)
    for u in (0, 7, 49, 1000):
        got = ops.membership_rows(rows, lens, u)
        want = ref.membership_rows_ref(rows, lens, u)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("e", [128, 1000, 4096, 65536, 37])
@pytest.mark.parametrize("seed", [0, 1, 123456789])
def test_bernoulli_bitexact_sweep(e, seed):
    w = jnp.asarray(RNG.uniform(size=e), jnp.float32)
    got = ops.bernoulli_edges(w, seed)
    want = ref.bernoulli_edges_ref(w, seed)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bernoulli_statistics():
    """Mean keep-rate ~= p; streams differ across seeds."""
    e = 1 << 16
    for p in (0.1, 0.5, 0.9):
        w = jnp.full((e,), p, jnp.float32)
        keep = np.asarray(ops.bernoulli_edges(w, 7))
        assert abs(keep.mean() - p) < 4.5 * np.sqrt(p * (1 - p) / e)
    k1 = np.asarray(ops.bernoulli_edges(jnp.full((e,), 0.5, jnp.float32), 1))
    k2 = np.asarray(ops.bernoulli_edges(jnp.full((e,), 0.5, jnp.float32), 2))
    assert 0.4 < (k1 != k2).mean() < 0.6  # independent streams


def test_bernoulli_lane_independence():
    """Adjacent counters are uncorrelated (avalanche sanity)."""
    e = 1 << 16
    keep = np.asarray(ops.bernoulli_edges(jnp.full((e,), 0.5, jnp.float32), 3))
    a, b = keep[:-1], keep[1:]
    agree = (a == b).mean()
    assert 0.45 < agree < 0.55


@pytest.mark.parametrize("b,n", [(4, 32), (8, 128), (33, 1024), (128, 4096)])
def test_pack_bits_sweep(b, n):
    bits = jnp.asarray(RNG.integers(0, 2, size=(b, n)).astype(bool))
    got = ops.pack_bits(bits)
    want = ref.pack_bits_ref(bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,w", [(4, 4), (64, 32), (100, 100), (257, 8)])
def test_bitset_binary_and_popcount_sweep(b, w):
    a = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, w), dtype=np.uint32))
    c = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, w), dtype=np.uint32))
    np.testing.assert_array_equal(np.asarray(ops.bitset_or(a, c)),
                                  np.asarray(ref.bitset_or_ref(a, c)))
    np.testing.assert_array_equal(np.asarray(ops.bitset_andnot(a, c)),
                                  np.asarray(ref.bitset_andnot_ref(a, c)))
    got = np.asarray(ops.popcount_words(a))
    want = np.asarray(ref.popcount_words_ref(a))
    np.testing.assert_array_equal(got, want)
    # cross-check against python popcount
    assert got[0, 0] == bin(int(np.asarray(a)[0, 0])).count("1")


@pytest.mark.parametrize("b,w", [(8, 4), (64, 8), (100, 16), (16, 1)])
def test_occur_from_bitset_sweep(b, w):
    words = jnp.asarray(RNG.integers(0, 2 ** 32, size=(b, w), dtype=np.uint32))
    got = np.asarray(ops.occur_from_bitset(words))
    want = np.asarray(ref.occur_from_bitset_ref(words))
    np.testing.assert_array_equal(got, want)
    # equivalence with bool unpack + sum
    unpacked = np.zeros((b, w * 32), dtype=np.int32)
    wnp = np.asarray(words)
    for i in range(b):
        for j in range(w):
            for t in range(32):
                unpacked[i, j * 32 + t] = (int(wnp[i, j]) >> t) & 1
    np.testing.assert_array_equal(got, unpacked.sum(axis=0))


def test_membership_kernel_drives_coverage():
    """Kernel membership == the coverage module's segment-based scan."""
    from repro.core import coverage as cov
    rng = np.random.default_rng(5)
    n = 40
    rr = [rng.choice(n, size=int(rng.integers(1, 10)), replace=False).tolist()
          for _ in range(200)]
    l = 16
    rows = np.full((200, l), n, np.int32)
    lens = np.zeros(200, np.int32)
    for i, r in enumerate(rr):
        rows[i, :len(r)] = r
        lens[i] = len(r)
    store = cov.build_store(rr, n)
    for u in (0, 5, 39):
        hit_kernel = np.asarray(ops.membership_rows(
            jnp.asarray(rows), jnp.asarray(lens), u))
        match = (np.asarray(store.rr_flat) == u) & np.asarray(store.valid)
        hit_flat = np.zeros(200, bool)
        np.logical_or.at(hit_flat, np.asarray(store.rr_ids)[match], True)
        np.testing.assert_array_equal(hit_kernel, hit_flat)


@pytest.mark.parametrize("s,bq,bk", [(16, 8, 8), (32, 8, 16), (64, 64, 32)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_sweep(s, bq, bk, dtype):
    import jax
    dt = jnp.dtype(dtype)
    b, h, d = 2, 3, 16
    q = jax.random.normal(jax.random.key(1), (b, s, h, d)).astype(dt)
    k = jax.random.normal(jax.random.key(2), (b, s, h, d)).astype(dt)
    v = jax.random.normal(jax.random.key(3), (b, s, h, d)).astype(dt)
    got = ops.flash_attention(q, k, v, bq=bq, bk=bk)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    import jax
    b, s, h, d = 1, 32, 2, 8
    q = jax.random.normal(jax.random.key(1), (b, s, h, d))
    k = jax.random.normal(jax.random.key(2), (b, s, h, d))
    v = jax.random.normal(jax.random.key(3), (b, s, h, d))
    got = ops.flash_attention(q, k, v, causal=False, bq=8, bk=8)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_sketch_kernels_defer_interpret_to_ops_policy(monkeypatch):
    # regression: the sketch kernel used to hardcode interpret=True, so the
    # compiled Mosaic path was unreachable on accelerator backends.  It
    # must resolve interpret=None through the shared ops policy (per-call >
    # module override > env > backend default) like every other kernel.
    from repro.core import sketch as core_sk
    from repro.kernels import sketch as sk
    seen = []
    real = ops.resolve_interpret

    def recorder(flag=None):
        seen.append(flag)
        return real(flag)

    monkeypatch.setattr(ops, "resolve_interpret", recorder)
    words = core_sk.scatter_or_bits(
        jnp.zeros((8, 2), jnp.uint32), jnp.asarray([1, 3, 99], jnp.int32),
        jnp.asarray([0, 33, 5], jnp.int32))
    got = np.asarray(words)
    assert got[1, 0] == 1 and got[3, 1] == 2   # bit 0 / bit 33
    assert got.sum() == 3                       # oob row 99 dropped

    cov_words = jnp.asarray(np.asarray([1, 0], np.uint32))
    cnt = sk.sketch_union_popcount(words, cov_words)
    assert seen == [None]           # default defers to the shared policy
    seen.clear()
    cnt = sk.sketch_union_popcount(words, cov_words, interpret=True)
    assert seen == [True]           # explicit flag still wins
    want = np.asarray([np.uint32(r[0] | 1).bit_count() + r[1].bit_count()
                       for r in got], np.int32)
    np.testing.assert_array_equal(np.asarray(cnt), want)
