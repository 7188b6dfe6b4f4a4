"""Model-zoo unit tests (reduced configs, CPU): shapes, NaNs, invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import transformer as T
from repro.models import gnn, deepfm, embedding


def test_rms_and_nonparam_norm():
    x = jax.random.normal(jax.random.key(0), (4, 8)) * 3 + 1
    y = L.rms_norm(x, jnp.zeros(8))
    assert np.allclose(np.mean(np.asarray(y) ** 2, -1), 1.0, atol=1e-4)
    z = L.nonparametric_layer_norm(x)
    assert np.allclose(np.asarray(z).mean(-1), 0.0, atol=1e-5)
    assert np.allclose(np.asarray(z).std(-1), 1.0, atol=1e-3)


def test_rope_preserves_norm_and_relative():
    x = jax.random.normal(jax.random.key(1), (2, 6, 4, 8))
    pos = jnp.broadcast_to(jnp.arange(6)[None], (2, 6))
    y = A.apply_rope(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)
    # relative property: <R(p)q, R(p+d)k> depends only on d
    q = jax.random.normal(jax.random.key(2), (1, 1, 1, 8))
    k = jax.random.normal(jax.random.key(3), (1, 1, 1, 8))
    def dot_at(p, d):
        qr = A.apply_rope(q, jnp.asarray([[p]]))
        kr = A.apply_rope(k, jnp.asarray([[p + d]]))
        return float((qr * kr).sum())
    assert abs(dot_at(0, 3) - dot_at(10, 3)) < 1e-4


def test_gqa_causality():
    """Perturbing future tokens must not change past outputs."""
    cfg = dict(n_heads=4, n_kv_heads=2, head_dim=8)
    p = A.gqa_init(jax.random.key(0), 16, 4, 2, 8)
    x = jax.random.normal(jax.random.key(1), (1, 6, 16))
    pos = jnp.arange(6)[None]
    out1, _ = A.gqa_apply(p, x, pos, **cfg)
    x2 = x.at[0, 4:].add(1.0)
    out2, _ = A.gqa_apply(p, x2, pos, **cfg)
    np.testing.assert_allclose(np.asarray(out1[0, :4]),
                               np.asarray(out2[0, :4]), atol=1e-5)


def test_sliding_window_masks_far_tokens():
    p = A.gqa_init(jax.random.key(0), 16, 4, 4, 8)
    x = jax.random.normal(jax.random.key(1), (1, 10, 16))
    pos = jnp.arange(10)[None]
    kw = dict(n_heads=4, n_kv_heads=4, head_dim=8)
    out_w, _ = A.gqa_apply(p, x, pos, window=2, **kw)
    # perturb token 0: with window=2, token 9 cannot see it
    x2 = x.at[0, 0].add(5.0)
    out_w2, _ = A.gqa_apply(p, x2, pos, window=2, **kw)
    np.testing.assert_allclose(np.asarray(out_w[0, 9]),
                               np.asarray(out_w2[0, 9]), atol=1e-5)
    # but with global attention it can
    out_g, _ = A.gqa_apply(p, x, pos, window=None, **kw)
    out_g2, _ = A.gqa_apply(p, x2, pos, window=None, **kw)
    assert np.abs(np.asarray(out_g[0, 9]) - np.asarray(out_g2[0, 9])).max() > 1e-4


def test_mla_shapes_and_causality():
    mcfg = A.MLAConfig(n_heads=4, q_lora_rank=12, kv_lora_rank=8,
                       qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    p = A.mla_init(jax.random.key(0), 16, mcfg)
    x = jax.random.normal(jax.random.key(1), (2, 5, 16))
    pos = jnp.broadcast_to(jnp.arange(5)[None], (2, 5))
    out, (c_kv, k_rope, _) = A.mla_apply(p, x, pos, mcfg)
    assert out.shape == (2, 5, 16)
    assert c_kv.shape == (2, 5, 8)          # latent cache, not per-head
    assert k_rope.shape == (2, 5, 1, 4)
    x2 = x.at[:, 3:].add(1.0)
    out2, _ = A.mla_apply(p, x2, pos, mcfg)
    np.testing.assert_allclose(np.asarray(out[:, :3]),
                               np.asarray(out2[:, :3]), atol=1e-5)


def test_moe_routes_and_shapes():
    cfg = M.MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared=1,
                      capacity_factor=2.0)
    p = M.moe_init(jax.random.key(0), 16, cfg)
    x = jax.random.normal(jax.random.key(1), (4, 6, 16))
    y, aux = M.moe_apply(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0


def test_moe_capacity_one_expert_degenerate():
    """top-1 of 1 expert with big capacity == plain FFN + shared."""
    cfg = M.MoEConfig(n_experts=1, top_k=1, d_ff_expert=32, n_shared=0,
                      capacity_factor=4.0)
    p = M.moe_init(jax.random.key(0), 16, cfg)
    x = jax.random.normal(jax.random.key(1), (2, 3, 16))
    y, _ = M.moe_apply(p, x, cfg)
    expert0 = jax.tree.map(lambda a: a[0], p["experts"])
    want = L.ffn(expert0, x.reshape(-1, 16)).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)


TINY = dict(n_layers=4, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
            d_ff=64, vocab=128)


@pytest.mark.parametrize("variant", ["qwen2", "olmo", "gemma3", "deepseek",
                                     "llama4"])
def test_tiny_lm_forward_and_loss(variant):
    kw = dict(TINY)
    if variant == "qwen2":
        cfg = T.LMConfig(name="tiny-qwen2", qkv_bias=True, **kw)
    elif variant == "olmo":
        cfg = T.LMConfig(name="tiny-olmo", norm="nonparam",
                         tie_embeddings=False, **kw)
    elif variant == "gemma3":
        cfg = T.LMConfig(name="tiny-gemma3", act="geglu",
                         local_global=(1, 4), **kw)
    elif variant == "deepseek":
        cfg = T.LMConfig(
            name="tiny-deepseek",
            mla=A.MLAConfig(n_heads=4, q_lora_rank=16, kv_lora_rank=8,
                            qk_nope_head_dim=8, qk_rope_head_dim=4,
                            v_head_dim=8),
            moe=M.MoEConfig(n_experts=4, top_k=2, d_ff_expert=32, n_shared=1,
                            router_score="sigmoid", capacity_factor=2.0),
            n_dense_layers=1, d_ff_dense=64, mtp=True, **kw)
    else:
        cfg = T.LMConfig(
            name="tiny-llama4",
            moe=M.MoEConfig(n_experts=4, top_k=1, d_ff_expert=32, n_shared=1,
                            router_score="sigmoid", capacity_factor=2.0), **kw)
    params = T.lm_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 10), 0, cfg.vocab)
    hidden, aux, _ = T.lm_backbone(params, cfg, tokens)
    assert hidden.shape == (2, 10, cfg.d_model)
    logits = T.lm_logits(params, cfg, hidden)
    assert logits.shape == (2, 10, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    loss = T.lm_loss(params, cfg, tokens)
    assert np.isfinite(float(loss))
    # gradients flow
    g = jax.grad(lambda p: T.lm_loss(p, cfg, tokens))(params)
    gnorm = sum(float((x ** 2).sum()) for x in jax.tree.leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0


def test_decode_matches_forward():
    """serve_step token-by-token reproduces the full-forward logits."""
    cfg = T.LMConfig(name="tiny-qwen2", qkv_bias=True, **TINY)
    params = T.lm_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    hidden, _, _ = T.lm_backbone(params, cfg, tokens)
    full_logits = T.lm_logits(params, cfg, hidden)
    caches = T.init_cache(cfg, batch=2, max_len=16)
    for t in range(8):
        logits, caches = T.serve_step(params, cfg, tokens[:, t:t + 1], caches,
                                      jnp.int32(t))
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               atol=2e-4, rtol=1e-4)


def test_decode_matches_forward_gemma_pattern():
    cfg = T.LMConfig(name="tiny-gemma3", act="geglu", local_global=(1, 4),
                     **TINY)
    params = T.lm_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(2), (1, 8), 0, cfg.vocab)
    hidden, _, _ = T.lm_backbone(params, cfg, tokens)
    full_logits = T.lm_logits(params, cfg, hidden)
    caches = T.init_cache(cfg, batch=1, max_len=8)
    for t in range(8):
        logits, caches = T.serve_step(params, cfg, tokens[:, t:t + 1], caches,
                                      jnp.int32(t))
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full_logits[:, -1]),
                               atol=2e-4, rtol=1e-4)


# ----------------------------------------------------------------- GNN/rec

def _toy_graph(n=12, m=40, seed=0):
    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    mask = jnp.ones(m, bool)
    return src, dst, mask


def test_gat_shapes():
    cfg = gnn.GATConfig(n_layers=2, d_hidden=8, n_heads=8, d_in=20,
                        n_classes=7)
    p = gnn.gat_init(jax.random.key(0), cfg)
    src, dst, mask = _toy_graph()
    x = jax.random.normal(jax.random.key(1), (12, 20))
    out = gnn.gat_apply(p, cfg, x, src, dst, mask)
    assert out.shape == (12, 7)
    assert np.isfinite(np.asarray(out)).all()


def test_gin_sum_aggregation_counts():
    """GIN with identity-ish MLP distinguishes node degree (sum agg)."""
    cfg = gnn.GINConfig(n_layers=1, d_hidden=4, d_in=1, n_classes=2)
    p = gnn.gin_init(jax.random.key(0), cfg)
    src = jnp.asarray([0, 1, 2], jnp.int32)
    dst = jnp.asarray([3, 3, 3], jnp.int32)
    mask = jnp.ones(3, bool)
    x = jnp.ones((4, 1))
    out = gnn.gin_apply(p, cfg, x, src, dst, mask)
    assert out.shape == (4, 2)


def test_egnn_equivariance():
    """Rotating+translating inputs rotates+translates coordinate outputs."""
    cfg = gnn.EGNNConfig(n_layers=2, d_hidden=16, d_in=8)
    p = gnn.egnn_init(jax.random.key(0), cfg)
    src, dst, mask = _toy_graph(n=10, m=30, seed=2)
    h = jax.random.normal(jax.random.key(1), (10, 8))
    x = jax.random.normal(jax.random.key(2), (10, 3))
    # random rotation via QR
    q, _ = np.linalg.qr(np.random.default_rng(3).normal(size=(3, 3)))
    q = jnp.asarray(q * np.sign(np.linalg.det(q)), jnp.float32)
    t = jnp.asarray([1.0, -2.0, 0.5])
    h1, x1 = gnn.egnn_apply(p, cfg, h, x, src, dst, mask)
    h2, x2 = gnn.egnn_apply(p, cfg, h, x @ q.T + t, src, dst, mask)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-3)
    np.testing.assert_allclose(np.asarray(x1 @ q.T + t), np.asarray(x2),
                               atol=1e-3)


def test_graphcast_residual_stack():
    cfg = gnn.GraphCastConfig(n_layers=3, d_hidden=16, d_in=10, d_out=10)
    p = gnn.graphcast_init(jax.random.key(0), cfg)
    src, dst, mask = _toy_graph(n=15, m=50, seed=4)
    x = jax.random.normal(jax.random.key(1), (15, 10))
    out = gnn.graphcast_apply(p, cfg, x, src, dst, mask)
    assert out.shape == (15, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_embedding_bag_modes():
    table = jnp.asarray(np.arange(20, dtype=np.float32).reshape(10, 2))
    ids = jnp.asarray([0, 1, 2, 5], jnp.int32)
    seg = jnp.asarray([0, 0, 1, 1], jnp.int32)
    out = embedding.embedding_bag(table, ids, seg, 2, mode="sum")
    np.testing.assert_allclose(np.asarray(out),
                               [[2., 4.], [14., 16.]])
    out = embedding.embedding_bag(table, ids, seg, 2, mode="mean")
    np.testing.assert_allclose(np.asarray(out), [[1., 2.], [7., 8.]])


def test_sharded_lookup_matches_take():
    import subprocess, sys, os
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.models.embedding import sharded_lookup
table = jnp.asarray(np.random.default_rng(0).normal(size=(32, 4)),
                    jnp.float32)
ids = jnp.asarray([0, 5, 31, 8, 17, 16], jnp.int32)
mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("model",))
fn = shard_map(lambda t, i: sharded_lookup(t, i, "model"), mesh=mesh,
               in_specs=(P("model", None), P()), out_specs=P())
out = fn(table, ids)
np.testing.assert_allclose(np.asarray(out),
                           np.asarray(jnp.take(table, ids, axis=0)),
                           rtol=1e-6)
print("OK")
"""
    env = dict(os.environ); env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, cwd="/root/repo",
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]


def test_deepfm_forward_and_fm_term():
    vocabs = tuple([50] * 5)
    cfg = deepfm.DeepFMConfig(n_sparse=5, embed_dim=4, mlp_dims=(16, 8),
                              field_vocabs=vocabs, n_dense_feats=3)
    p = deepfm.deepfm_init(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 50, (6, 5))
                      + cfg.field_offsets[None, :], jnp.int32)
    dense_x = jnp.asarray(rng.normal(size=(6, 3)), jnp.float32)
    logits = deepfm.deepfm_logits(p, cfg, ids, dense_x)
    assert logits.shape == (6,)
    labels = jnp.asarray(rng.integers(0, 2, 6), jnp.float32)
    loss = deepfm.deepfm_loss(p, cfg, ids, dense_x, labels)
    assert np.isfinite(float(loss))
    g = jax.grad(lambda pp: deepfm.deepfm_loss(pp, cfg, ids, dense_x,
                                               labels))(p)
    assert np.isfinite(sum(float((x ** 2).sum())
                           for x in jax.tree.leaves(g)))


def test_retrieval_topk():
    rng = np.random.default_rng(1)
    cand = jnp.asarray(rng.normal(size=(1000, 8)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    vals, idx = deepfm.retrieval_topk(q, cand, 10)
    scores = np.asarray(cand) @ np.asarray(q)
    np.testing.assert_allclose(np.asarray(vals), np.sort(scores)[::-1][:10],
                               rtol=1e-5)
