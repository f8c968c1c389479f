"""Single-sweep predict+rank+audit (kernels.ops.predict_rank_audited)
vs the two-stage oracle `predictor.predict(X)` -> `rank_given_lambda`,
for all four predictor families.

Parity contract (core.ranking PARITY_RTOL / PARITY_ATOL, asserted
here): perm and compliant exact; utility and exposure within the
tolerance, since the kernel's flush and the jnp audit reduce in a
different order. λ̂ per family:
  * linear / mean — the affine prologue folded into the rank kernel
    runs the same jnp.dot + max ops as LinearLambdaPredictor.predict;
    the dot may still reduce in another order, so λ̂ is held to the
    tolerance (the covariate-free mean stays bitwise);
  * knn — the fused inverse-distance weighting agrees within the
    tolerance (per-tile vs one-matmul distance accumulation); selection
    still agrees exactly on these fixed-seed problems (score gaps are
    orders of magnitude above the λ̂ perturbation);
  * mlp — λ̂ stays XLA inside the same executable, within the
    tolerance.

Plus: bucket-padded micro-batches (phantom rows, padded K tier), the
m2 = MAX_KERNEL_M2 edge, the m2 > MAX_KERNEL_M2 XLA fallback, and the
fused KNN λ kernel against its oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictors import (
    KNNLambdaPredictor,
    LinearLambdaPredictor,
    MeanLambdaPredictor,
    MLPLambdaPredictor,
)
from repro.core.ranking import PARITY_ATOL, PARITY_RTOL, rank_given_lambda
from repro.kernels import ops, ref
from repro.kernels.fused_rank import MAX_KERNEL_M2

KEY = jax.random.key(11)

FIELDS = ("perm", "utility", "exposure", "compliant")
EXACT = ("perm", "compliant")

D_COV = 12


def _problem(n, m1, K, m2, d=D_COV, salt=0):
    ks = jax.random.split(jax.random.fold_in(KEY, n * m1 + K + salt), 7)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    b = jnp.abs(jax.random.normal(ks[2], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[3], (n, m2)))
    X = jax.random.normal(ks[4], (n, d))
    X_tr = jax.random.uniform(ks[5], (48, d))
    lam_tr = jnp.abs(jax.random.normal(ks[6], (48, K)))
    return u, a, b, gamma, X, X_tr, lam_tr


def _families(X_tr, lam_tr):
    return {
        "linear": LinearLambdaPredictor.fit(X_tr, lam_tr),
        "mean": MeanLambdaPredictor.fit(X_tr, lam_tr),
        "knn": KNNLambdaPredictor.fit(X_tr, lam_tr, k=5),
        "mlp": MLPLambdaPredictor.fit(X_tr, lam_tr, num_steps=25),
    }


def _assert_fields_equal(got, want, rows=slice(None), msg=""):
    for field in FIELDS:
        g = np.asarray(getattr(got, field))[rows]
        w = np.asarray(getattr(want, field))[rows]
        err = f"predict+rank parity broke on {field} {msg}"
        if field in EXACT:
            np.testing.assert_array_equal(g, w, err_msg=err)
        else:
            np.testing.assert_allclose(g, w, rtol=PARITY_RTOL,
                                       atol=PARITY_ATOL, err_msg=err)


@pytest.mark.parametrize("n,m1,K,m2", [
    (8, 512, 5, 10),
    (3, 700, 2, 8),                 # off-tile n and m1 exercise padding
    (8, 1024, 3, MAX_KERNEL_M2),    # m2 edge: the largest kernel path
])
def test_predict_rank_matches_two_stage_oracle(n, m1, K, m2):
    u, a, b, gamma, X, X_tr, lam_tr = _problem(n, m1, K, m2)
    for name, pred in _families(X_tr, lam_tr).items():
        got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                       interpret=True)
        want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
        _assert_fields_equal(got, want, msg=f"[{name}]")
        # in-kernel prologue / KNN weighting vs eager predict(): λ̂ to a
        # few ulp (the dot's reduction order is the compiler's choice)
        np.testing.assert_allclose(
            np.asarray(got.lam), np.asarray(want.lam),
            rtol=PARITY_RTOL, atol=PARITY_ATOL,
            err_msg=f"λ̂ parity broke for {name}")


def test_mean_family_preserves_unclamped_negative_lambda():
    """The mean predictor broadcasts mean_lam verbatim (no clamp); the
    prologue's relu must stay OFF for it — a synthetic negative mean
    would otherwise be silently zeroed and the parity would hide it."""
    n, m1, K, m2 = 8, 512, 3, 8
    u, a, b, gamma, X, X_tr, _ = _problem(n, m1, K, m2, salt=1)
    lam_tr = jax.random.normal(jax.random.fold_in(KEY, 5), (48, K)) - 0.5
    pred = MeanLambdaPredictor.fit(X_tr, lam_tr)
    assert bool(jnp.any(pred.mean_lam < 0))     # the case under test
    got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                   interpret=True)
    want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
    _assert_fields_equal(got, want)
    np.testing.assert_array_equal(np.asarray(got.lam), np.asarray(want.lam))


def test_predict_rank_bucket_padded_batch():
    """An engine-style padded micro-batch on the covariate path:
    phantom rows (X = 0), NEG_FILL candidate padding, a K tier wider
    than the predictor's output — parity with the two-stage oracle on
    the whole padded problem, zero audit on phantom rows."""
    from repro.serving import Scenario, assemble_batch, bucket_for, make_request

    d, K_pred = 10, 4
    rng = np.random.default_rng(2)
    sc = Scenario("cov", m1=300, m2=20, K=K_pred, tag="arch", d_cov=d)
    reqs = [make_request(rng, sc, rid) for rid in range(5)]
    bucket = bucket_for(m1=max(r.u.shape[0] for r in reqs), m2=20,
                       K=8, tag="arch", batch=8)     # padded K tier + rows
    staged = assemble_batch(reqs, bucket, d_cov=d)
    u = jnp.asarray(staged["u"])
    a = jnp.asarray(staged["a"])
    b = jnp.asarray(staged["b"])
    gamma = jnp.asarray(staged["gamma"])
    X = jnp.asarray(staged["X"])
    X_tr = jnp.asarray(rng.uniform(0, 1, (32, d)), jnp.float32)
    lam_tr = jnp.asarray(np.abs(rng.normal(size=(32, K_pred))), jnp.float32)

    for pred in (LinearLambdaPredictor.fit(X_tr, lam_tr),
                 KNNLambdaPredictor.fit(X_tr, lam_tr, k=5)):
        got = ops.predict_rank_audited(X, pred, u, a, b, gamma,
                                       m2=bucket.m2, interpret=True)
        lam = jnp.pad(pred.predict(X), ((0, 0), (0, bucket.K - K_pred)))
        want = rank_given_lambda(u, a, b, lam, gamma, m2=bucket.m2)
        n_real = len(reqs)
        _assert_fields_equal(got, want, rows=slice(None, n_real),
                             msg="(padded covariate batch)")
        # phantom rows: zero gamma -> zero utility, trivially compliant
        np.testing.assert_array_equal(np.asarray(got.utility[n_real:]), 0.0)
        assert bool(np.all(np.asarray(got.compliant[n_real:])))


def test_affine_prologue_lane_padded_ragged_d_exact():
    """The TPU lane-alignment path: padding a ragged covariate dim d to
    the 128-lane boundary with zero X/W columns must leave every output
    bitwise unchanged (trailing zeros append exactly-0.0 terms at the
    end of the prologue dot's reduction). The pad is gated OFF on the
    interpret path by default — forcing it on here proves the gate is
    caution about reduction-order, not a correctness requirement."""
    n, m1, K, m2 = 8, 512, 3, 10
    d = 12                                     # ragged: pads to 128
    u, a, b, gamma, X, X_tr, lam_tr = _problem(n, m1, K, m2, d=d, salt=7)
    for pred in (LinearLambdaPredictor.fit(X_tr, lam_tr),
                 MeanLambdaPredictor.fit(X_tr, lam_tr)):
        plain = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                         interpret=True)
        padded = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                          interpret=True, pad_lanes=True)
        for field in FIELDS + ("lam",):
            np.testing.assert_array_equal(
                np.asarray(getattr(padded, field)),
                np.asarray(getattr(plain, field)),
                err_msg=f"lane padding changed {field} for "
                        f"{type(pred).__name__}")
        want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
        _assert_fields_equal(padded, want)


def test_predict_rank_xla_fallback_large_m2():
    """m2 > MAX_KERNEL_M2 routes to the two-stage XLA oracle: the
    dispatcher must reproduce ref.rank_audited_ref on the predictor's
    own λ̂, bitwise, for every family. (ref ↔ rank_given_lambda parity
    under matched numerics is tests/test_rank_audited.py's job; eager
    vs jit'd score epilogues may legitimately swap last-ulp-tied
    neighbours, so the oracle here is the same eager program the
    fallback runs.)"""
    n, m1, K, m2 = 4, 700, 3, MAX_KERNEL_M2 + 72
    u, a, b, gamma, X, X_tr, lam_tr = _problem(n, m1, K, m2, salt=2)
    for name, pred in _families(X_tr, lam_tr).items():
        got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2)
        _, idx, utility, exposure, compliant = ref.rank_audited_ref(
            u, a, b, pred.predict(X).astype(jnp.float32), gamma, m2)
        np.testing.assert_array_equal(
            np.asarray(got.perm), np.asarray(idx),
            err_msg=f"fallback perm broke [{name}]")
        np.testing.assert_array_equal(
            np.asarray(got.utility), np.asarray(utility),
            err_msg=f"fallback utility broke [{name}]")
        np.testing.assert_array_equal(
            np.asarray(got.exposure), np.asarray(exposure),
            err_msg=f"fallback exposure broke [{name}]")
        np.testing.assert_array_equal(
            np.asarray(got.compliant), np.asarray(compliant),
            err_msg=f"fallback compliance broke [{name}]")


def test_predict_rank_shared_broadcast_forms():
    """(K, m1) a, (K,) b, (m2,) gamma broadcast exactly like the
    two-stage path."""
    n, m1, K, m2 = 6, 512, 4, 16
    u, a, b, gamma, X, X_tr, lam_tr = _problem(n, m1, K, m2, salt=3)
    pred = LinearLambdaPredictor.fit(X_tr, lam_tr)
    got = ops.predict_rank_audited(X, pred, u, a[0], b[0], gamma[0],
                                   m2=m2, interpret=True)
    want = rank_given_lambda(u, a[0], b[0], pred.predict(X), gamma[0], m2=m2)
    _assert_fields_equal(got, want)


def test_predict_rank_rejects_too_wide_predictor():
    """A predictor emitting more shadow prices than the problem has
    constraint rows is a configuration error, not silence."""
    n, m1, K, m2 = 8, 512, 2, 8
    u, a, b, gamma, X, X_tr, _ = _problem(n, m1, K, m2, salt=4)
    lam_tr = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 9), (48, 5)))
    pred = LinearLambdaPredictor.fit(X_tr, lam_tr)      # 5 > K = 2
    with pytest.raises(ValueError, match="shadow prices"):
        ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                 interpret=True)
    # the XLA fallback branch raises the same purposeful error
    gamma_big = jnp.abs(jax.random.normal(
        jax.random.fold_in(KEY, 10), (n, MAX_KERNEL_M2 + 8)))
    with pytest.raises(ValueError, match="shadow prices"):
        ops.predict_rank_audited(X, pred, u, a, b, gamma_big,
                                 m2=MAX_KERNEL_M2 + 8)


def test_predict_rank_rejects_row_count_mismatch():
    """X with fewer rows than u must be a loud error — the kernel path
    pads X for tiling and would otherwise intercept-serve the
    uncovered rows."""
    n, m1, K, m2 = 8, 512, 3, 8
    u, a, b, gamma, X, X_tr, lam_tr = _problem(n, m1, K, m2, salt=6)
    pred = LinearLambdaPredictor.fit(X_tr, lam_tr)
    with pytest.raises(ValueError, match="covariate rows"):
        ops.predict_rank_audited(X[:4], pred, u, a, b, gamma, m2=m2,
                                 interpret=True)


def test_knn_lambda_rejects_too_small_db():
    """n_train < k errors like every other KNN path instead of letting
    the far-away padding rows into the top-k."""
    with pytest.raises(ValueError, match="n_train"):
        ops.knn_lambda(jnp.zeros((4, 3)), jnp.zeros((4, 3)),
                       jnp.zeros((4, 2)), k=10, interpret=True)


# ---------------------------------------------------------------------------
# The fused KNN λ kernel on its own
# ---------------------------------------------------------------------------


def test_knn_lambda_kernel_matches_ref_and_predictor():
    """knn_lambda (payload-carried weighting at the flush step) agrees
    with its argsort oracle and with core.predictors.knn_predict,
    including the exact-match override (query == db row)."""
    from repro.core.predictors import knn_predict

    ks = jax.random.split(jax.random.fold_in(KEY, 21), 3)
    X_db = jax.random.normal(ks[0], (600, 16))
    lam_db = jnp.abs(jax.random.normal(ks[1], (600, 5)))
    Xq = jnp.concatenate([jax.random.normal(ks[2], (9, 16)), X_db[:3]])
    got = ops.knn_lambda(Xq, X_db, lam_db, k=10, interpret=True)
    want_ref = ref.knn_lambda_ref(Xq, X_db, lam_db, 10)
    want_pred = knn_predict(X_db, lam_db, Xq, k=10)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_pred),
                               rtol=1e-5, atol=1e-6)
    # exact-match rows return the training value (sklearn semantics)
    np.testing.assert_allclose(np.asarray(got[-3:]), np.asarray(lam_db[:3]),
                               rtol=1e-4, atol=1e-5)


def test_knn_lambda_tile_q_selection_consistent():
    """The wide (tile_q=32) and narrow (tile_q=8) query tilings give
    the same λ̂ — tile geometry is a traffic knob, not semantics."""
    ks = jax.random.split(jax.random.fold_in(KEY, 22), 2)
    X_db = jax.random.normal(ks[0], (256, 8))
    lam_db = jnp.abs(jax.random.normal(ks[1], (256, 3)))
    Xq = jax.random.normal(jax.random.fold_in(KEY, 23), (40, 8))
    wide = ops.knn_lambda(Xq, X_db, lam_db, k=5, tile_q=32, interpret=True)
    narrow = ops.knn_lambda(Xq, X_db, lam_db, k=5, tile_q=8, interpret=True)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(narrow),
                               rtol=1e-6, atol=1e-7)
