"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(42)


@pytest.mark.parametrize("B,N,D,k", [
    (8, 512, 128, 10),
    (3, 300, 64, 5),       # off-tile shapes exercise padding
    (16, 1024, 256, 16),
    (1, 512, 32, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_knn_topk_matches_oracle(B, N, D, k, dtype):
    kq, kd = jax.random.split(jax.random.fold_in(KEY, B * N + D))
    xq = jax.random.normal(kq, (B, D), dtype)
    xdb = jax.random.normal(kd, (N, D), dtype)
    d2k, idxk = ops.knn_topk(xq, xdb, k=k, interpret=True)
    d2r, idxr = ref.knn_topk_ref(xq, xdb, k)
    np.testing.assert_allclose(d2k, d2r, rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-4)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(idxk), np.asarray(idxr))


@pytest.mark.parametrize("n,m1,K,m2", [
    (8, 512, 5, 10),
    (4, 1000, 8, 50),      # the paper's 1000-item scenario
    (8, 2048, 3, 128),     # MAX_KERNEL_M2 boundary
    (2, 600, 1, 1),
])
def test_fused_rank_matches_oracle(n, m1, K, m2):
    ks = jax.random.split(jax.random.fold_in(KEY, n * m1 + K), 3)
    u = jax.random.normal(ks[0], (n, m1))
    a = jax.random.normal(ks[1], (n, K, m1))
    lam = jnp.abs(jax.random.normal(ks[2], (n, K)))
    vk, ik = ops.fused_rank(u, a, lam, m2=m2, interpret=True)
    vr, ir = ref.fused_rank_ref(u, a, lam, m2)
    np.testing.assert_allclose(vk, vr, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))


def test_fused_rank_xla_fallback_large_m2():
    ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ks[0], (4, 512))
    a = jax.random.normal(ks[1], (4, 2, 512))
    lam = jnp.abs(jax.random.normal(ks[2], (4, 2)))
    vk, ik = ops.fused_rank(u, a, lam, m2=256)     # > MAX_KERNEL_M2 -> XLA
    vr, ir = ref.fused_rank_ref(u, a, lam, 256)
    np.testing.assert_allclose(vk, vr, rtol=1e-5, atol=1e-5)


def test_knn_predict_kernel_matches_reference_predictor():
    from repro.core.predictors import knn_predict
    ks = jax.random.split(KEY, 3)
    X_db = jax.random.normal(ks[0], (256, 16))
    lam_db = jnp.abs(jax.random.normal(ks[1], (256, 4)))
    X = jax.random.normal(ks[2], (8, 16))
    got = ops.knn_predict_kernel(X_db, lam_db, X, k=10, interpret=True)
    want = knn_predict(X_db, lam_db, X, k=10)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_embedding_bag_model_twin():
    """models.recsys.embedding_bag (take+segment_sum) == oracle."""
    from repro.models.recsys import embedding_bag as model_bag
    ks = jax.random.split(KEY, 3)
    table = jax.random.normal(ks[0], (64, 8))
    idx = jax.random.randint(ks[1], (8, 6), -1, 64)
    a = model_bag(table, idx)
    b = ref.embedding_bag_ref(table, idx)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
