"""The single-grid KNN predict+rank+audit kernel
(kernels.knn_topk.knn_rank_audited_pallas) vs its two oracles:

  * the PR 4 two-kernel chain (knn_lambda_pallas -> rank_audited_pallas,
    λ̂ through an HBM buffer) — BITWISE on every RankingOutput field
    including λ̂, at matched tile geometry, because the fused grid runs
    the chain's own merge/flush bodies (_db_slab_merge, _idw_lambda_flush,
    _merge_scored_tile, _audit_flush);
  * the two-stage predictor.predict(X) -> rank_given_lambda oracle —
    exact on perm/compliant (score gaps dwarf the λ̂ perturbation on
    these problems); utility/exposure within core.ranking's
    PARITY_RTOL/ATOL (the kernel flush and the jnp audit reduce in a
    different order), λ̂ to tight tolerance (per-slab vs one-matmul
    distance accumulation differs in the last ulp).

Plus the geometry battery the kernel's phased grid makes interesting:
slab sizes that do and do not divide n_train, bucket-padded engine
micro-batches, the m2 = MAX_KERNEL_M2 edge, exact-match neighbours
sitting in a slab past the first, and slab/tile-width invariance of λ̂.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictors import KNNLambdaPredictor
from repro.core.ranking import PARITY_ATOL, PARITY_RTOL, rank_given_lambda
from repro.kernels import ops
from repro.kernels.fused_rank import MAX_KERNEL_M2

KEY = jax.random.key(29)

FIELDS = ("perm", "utility", "exposure", "compliant")
EXACT = ("perm", "compliant")
N_TRAIN = 600


def _problem(n, m1, K, m2, d=12, n_train=N_TRAIN, k=5, salt=0):
    ks = jax.random.split(jax.random.fold_in(KEY, n * m1 + m2 + salt), 7)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    b = jnp.abs(jax.random.normal(ks[2], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[3], (n, m2)))
    X = jax.random.normal(ks[4], (n, d))
    X_tr = jax.random.uniform(ks[5], (n_train, d))
    lam_tr = jnp.abs(jax.random.normal(ks[6], (n_train, K)))
    return u, a, b, gamma, X, KNNLambdaPredictor.fit(X_tr, lam_tr, k=k)


def _assert_oracle(got, want, rows=slice(None), msg=""):
    for field in FIELDS:
        g = np.asarray(getattr(got, field))[rows]
        w = np.asarray(getattr(want, field))[rows]
        err = f"single-grid vs oracle broke on {field} {msg}"
        if field in EXACT:
            np.testing.assert_array_equal(g, w, err_msg=err)
        else:
            np.testing.assert_allclose(g, w, rtol=PARITY_RTOL,
                                       atol=PARITY_ATOL, err_msg=err)


def _assert_parity(got, chain, want, msg=""):
    for field in FIELDS + ("lam",):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(chain, field)),
            err_msg=f"single-grid vs chain broke on {field} {msg}")
    _assert_oracle(got, want, msg=msg)


@pytest.mark.parametrize("tile_n", [200, 600, 160, 512])
def test_single_grid_parity_across_slab_sizes(tile_n):
    """tile_n in {200, 600} divides n_train = 600; {160, 512} does not
    (the db pads with far-away rows). All four give the chain's answer
    bitwise and the oracle's fields within the parity contract."""
    n, m1, K, m2 = 11, 700, 4, 16
    u, a, b, gamma, X, pred = _problem(n, m1, K, m2)
    got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                   interpret=True, tile_n=tile_n)
    chain = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                     interpret=True, tile_n=tile_n,
                                     knn_chain=True)
    want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
    _assert_parity(got, chain, want, msg=f"[tile_n={tile_n}]")
    np.testing.assert_allclose(
        np.asarray(got.lam), np.asarray(pred.predict(X)),
        rtol=1e-5, atol=1e-6, err_msg=f"λ̂ drifted [tile_n={tile_n}]")


def test_single_grid_wide_batch_tile():
    """A batch that fills the 32-wide resident query tile (the default
    above 32 rows, matching the chain's knn_lambda_tile_q) — plus a
    ragged row count so the last tile is phantom-padded."""
    n, m1, K, m2 = 40, 512, 3, 10
    u, a, b, gamma, X, pred = _problem(n, m1, K, m2, salt=1)
    got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                   interpret=True)
    chain = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                     interpret=True, knn_chain=True)
    want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
    _assert_parity(got, chain, want, msg="[wide tile]")


def test_single_grid_m2_kernel_edge():
    """m2 = MAX_KERNEL_M2: the widest rank scratch the kernel path
    serves — one slot before the XLA fallback takes over."""
    n, m1, K, m2 = 8, 1024, 3, MAX_KERNEL_M2
    u, a, b, gamma, X, pred = _problem(n, m1, K, m2, salt=2)
    got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                   interpret=True)
    chain = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                     interpret=True, knn_chain=True)
    want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
    _assert_parity(got, chain, want, msg="[m2 edge]")


def test_single_grid_bucket_padded_batch():
    """An engine-style micro-batch: phantom rows, NEG_FILL candidate
    padding, and a constraint tier WIDER than the predictor's output —
    the padded constraints must price at exactly 0.0 (zero lam_db
    columns through the flush-step weighting), phantom rows must audit to
    zero utility and trivial compliance."""
    from repro.serving import Scenario, assemble_batch, bucket_for, make_request

    d, K_pred = 10, 4
    rng = np.random.default_rng(7)
    sc = Scenario("cov", m1=300, m2=20, K=K_pred, tag="arch", d_cov=d)
    reqs = [make_request(rng, sc, rid) for rid in range(5)]
    bucket = bucket_for(m1=max(r.u.shape[0] for r in reqs), m2=20,
                        K=8, tag="arch", batch=8)    # padded K tier + rows
    staged = assemble_batch(reqs, bucket, d_cov=d)
    u, a = jnp.asarray(staged["u"]), jnp.asarray(staged["a"])
    b, gamma = jnp.asarray(staged["b"]), jnp.asarray(staged["gamma"])
    X = jnp.asarray(staged["X"])
    X_tr = jnp.asarray(rng.uniform(0, 1, (64, d)), jnp.float32)
    lam_tr = jnp.asarray(np.abs(rng.normal(size=(64, K_pred))), jnp.float32)
    pred = KNNLambdaPredictor.fit(X_tr, lam_tr, k=5)

    got = ops.predict_rank_audited(X, pred, u, a, b, gamma,
                                   m2=bucket.m2, interpret=True)
    lam = jnp.pad(pred.predict(X), ((0, 0), (0, bucket.K - K_pred)))
    want = rank_given_lambda(u, a, b, lam, gamma, m2=bucket.m2)
    n_real = len(reqs)
    _assert_oracle(got, want, rows=slice(None, n_real),
                   msg="(padded KNN micro-batch)")
    # the bucket-padded constraint columns price at exactly zero
    np.testing.assert_array_equal(np.asarray(got.lam)[:, K_pred:], 0.0)
    # phantom rows: zero gamma -> zero utility, trivially compliant
    np.testing.assert_array_equal(np.asarray(got.utility[n_real:]), 0.0)
    assert bool(np.all(np.asarray(got.compliant[n_real:])))


def test_exact_match_neighbour_inside_later_slab():
    """A query that coincides with a db row whose global index lands in
    a slab PAST the first (index > tile_n): the exact-match override at
    the λ̂ flush must return that row's training value even though the
    match was merged k slabs into the sweep (sklearn 'distance'
    semantics, relative test)."""
    n, m1, K, m2, tile_n = 8, 512, 3, 8, 128
    u, a, b, gamma, X, pred = _problem(n, m1, K, m2, salt=3)
    # rows 0/1 coincide with db rows in slab 2 and the final slab
    X = X.at[0].set(pred.X_db[300])
    X = X.at[1].set(pred.X_db[N_TRAIN - 1])
    got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                   interpret=True, tile_n=tile_n)
    np.testing.assert_allclose(np.asarray(got.lam[0]),
                               np.asarray(pred.lam_db[300]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.lam[1]),
                               np.asarray(pred.lam_db[N_TRAIN - 1]),
                               rtol=1e-4, atol=1e-5)
    # and the full output still matches the chain bitwise, the oracle
    # within the parity contract
    chain = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                     interpret=True, tile_n=tile_n,
                                     knn_chain=True)
    want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
    _assert_parity(got, chain, want, msg="[exact match]")


def test_lambda_slab_size_invariance():
    """Slab geometry is a traffic knob, not semantics: λ̂ agrees across
    slab sizes (the tile_q-invariance contract of the chain's knn_lambda
    kernel, inherited by the fused grid)."""
    n, m1, K, m2 = 16, 512, 3, 8
    u, a, b, gamma, X, pred = _problem(n, m1, K, m2, salt=4)
    lams = [
        np.asarray(ops.predict_rank_audited(
            X, pred, u, a, b, gamma, m2=m2, interpret=True,
            tile_n=tile_n).lam)
        for tile_n in (128, 200, 600)
    ]
    np.testing.assert_allclose(lams[0], lams[1], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(lams[0], lams[2], rtol=1e-6, atol=1e-7)


def test_lambda_batch_tile_width_invariance():
    """The narrow (8) and wide (32) resident query tiles give the same
    λ̂ and the same ranking fields."""
    n, m1, K, m2 = 40, 512, 3, 8
    u, a, b, gamma, X, pred = _problem(n, m1, K, m2, salt=5)
    narrow = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                      interpret=True, tile_b=8)
    wide = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                    interpret=True, tile_b=32)
    for field in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(narrow, field)),
            np.asarray(getattr(wide, field)),
            err_msg=f"batch-tile width changed {field}")
    np.testing.assert_allclose(np.asarray(narrow.lam), np.asarray(wide.lam),
                               rtol=1e-6, atol=1e-7)


def test_knn_rank_audited_rejects_bad_shapes():
    """The kernel wrapper keeps the KNN contract (n_train >= k) and the
    row-consistency checks loud."""
    n, m1, K, m2 = 8, 512, 3, 8
    u, a, b, gamma, X, _ = _problem(n, m1, K, m2, salt=6)
    with pytest.raises(ValueError, match="n_train"):
        ops.knn_rank_audited(X, jnp.zeros((4, 12)), jnp.zeros((4, K)),
                             u, a, b, gamma, k=10, m2=m2, interpret=True)
    with pytest.raises(ValueError, match="shadow prices"):
        ops.knn_rank_audited(X, jnp.zeros((64, 12)), jnp.zeros((64, K + 2)),
                             u, a, b, gamma, k=5, m2=m2, interpret=True)
    with pytest.raises(ValueError, match="covariate rows"):
        ops.knn_rank_audited(X[:4], jnp.zeros((64, 12)), jnp.zeros((64, K)),
                             u, a, b, gamma, k=5, m2=m2, interpret=True)
