"""The quantized KNN db sweep (int8 / bf16 packed slabs, exact f32
survivor re-score) vs its oracles, end to end:

  * selection parity: knn_quant_scan (XLA scan twin) and the Pallas
    quantized kernels vs kernels.ref.knn_quant_select_ref /
    knn_quant_lambda_ref — BITWISE on the selected neighbour set and
    the margin-guard flags, exact-on-x̃ by construction, at slab sizes
    that do and do not divide n_train;
  * the full-RankingOutput contract: ops.predict_rank_audited on a
    quantized predictor vs the COMPILED f32 oracle — the parity target
    the paper's serving path actually guarantees (perm / compliant
    exact, utility / exposure / λ̂ within core.ranking's
    PARITY_RTOL/ATOL: the kernel flush reduces in another order);
  * adversarial near-ties planted inside the quantization error fire
    the margin guard (observability for forced fallbacks);
  * degenerate all-identical db rows: every distance ties, selection
    must collapse to the lowest global indices, bitwise vs the oracle;
  * a lossless-grid db (values on the 0.5 grid, absmax planted per
    slab): the int8 predictor's RankingOutput equals the f32
    predictor's bitwise INCLUDING λ̂;
  * refresh hygiene: quantized() state round-trips through
    state_fields/with_state, and unquantized predictors keep their
    2-key state.

The property layer (hypothesis, import-guarded like test_refresh.py)
pins the bitwise-selection invariant under random geometry: the
quantized sweep's survivor re-score selects the same neighbour set as
the full-precision-on-x̃ oracle, always.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictors import (
    KNNLambdaPredictor,
    knn_predict_quant,
    knn_quant_scan,
    pack_knn_db,
    predictor_state,
    state_fields,
    with_state,
)
from repro.core.ranking import PARITY_ATOL, PARITY_RTOL
from repro.kernels import ops, ref
from repro.kernels.common import PAD_Y2, QUANT_EXTRA, dequant_rows
from repro.kernels.knn_topk import knn_lambda_quant_pallas

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                              # pragma: no cover
    given = None

KEY = jax.random.key(31)
N_TRAIN, D, K = 600, 12, 4
FIELDS = ("perm", "utility", "exposure", "compliant")


def _db(n=N_TRAIN, d=D, salt=0):
    ks = jax.random.split(jax.random.fold_in(KEY, salt), 2)
    X_db = jax.random.normal(ks[0], (n, d), jnp.float32)
    lam_db = jnp.abs(jax.random.normal(ks[1], (n, K), jnp.float32))
    return X_db, lam_db


def _queries(b=16, d=D, salt=1):
    return jax.random.normal(jax.random.fold_in(KEY, 1000 + salt),
                             (b, d), jnp.float32)


def _rank_problem(n, m1, m2, salt=2):
    ks = jax.random.split(jax.random.fold_in(KEY, 2000 + salt), 4)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    b = 0.1 * jnp.abs(jax.random.normal(ks[2], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[3], (n, m2)))
    return u, a, b, gamma


# ---------------------------------------------------------------------------
# Selection parity: scan twin and kernel vs the quantized oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["int8", "bf16"])
@pytest.mark.parametrize("slab", [200, 512])     # divides / pads N_TRAIN
def test_quant_scan_matches_oracle_bitwise(mode, slab):
    X_db, _ = _db()
    Xq = _queries()
    X_q, q_scale, y2_q = pack_knn_db(X_db, mode=mode, slab=slab)
    d2, idx, guard = knn_quant_scan(X_q, q_scale, y2_q, Xq, k=5, mode=mode)
    d2_r, idx_r, guard_r = ref.knn_quant_select_ref(
        Xq, X_q, q_scale, y2_q, 5, mode=mode)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_r))
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2_r))
    np.testing.assert_array_equal(np.asarray(guard), np.asarray(guard_r))


@pytest.mark.parametrize("mode", ["int8", "bf16"])
@pytest.mark.parametrize("slab", [200, 512])
def test_quant_kernel_lambda_matches_oracle(mode, slab):
    X_db, lam_db = _db()
    Xq = _queries()
    X_q, q_scale, y2_q = pack_knn_db(X_db, mode=mode, slab=slab)
    lam_pad = jnp.pad(lam_db, ((0, X_q.shape[0] - lam_db.shape[0]), (0, 0)))
    lam, guard = knn_lambda_quant_pallas(
        Xq, X_q, q_scale, y2_q, lam_pad, k=5, mode=mode,
        tile_q=8, tile_n=slab, interpret=True)
    lam_r, guard_r = ref.knn_quant_lambda_ref(
        Xq, X_q, q_scale, y2_q, lam_db, 5, mode=mode)
    # λ̂ to 1-ulp: the kernel's per-slab accumulation and the oracle's
    # one-shot einsum differ in reduction layout, nothing else
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam_r),
                               rtol=2e-7, atol=2e-7)
    np.testing.assert_array_equal(np.asarray(guard), np.asarray(guard_r))


def test_quant_pad_rows_never_selected():
    """slab=512 pads 600 db rows to 1024: the 424 phantom rows carry
    PAD_Y2 and must never enter any top-k."""
    X_db, _ = _db()
    X_q, q_scale, y2_q = pack_knn_db(X_db, mode="int8", slab=512)
    assert X_q.shape[0] == 1024
    assert np.asarray(y2_q)[N_TRAIN:].min() == np.float32(PAD_Y2)
    _, idx, _ = knn_quant_scan(X_q, q_scale, y2_q, _queries(b=32),
                               k=5 + QUANT_EXTRA - 1, mode="int8")
    assert int(np.asarray(idx).max()) < N_TRAIN


# ---------------------------------------------------------------------------
# Full-RankingOutput contract through the serving dispatcher
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["int8", "bf16"])
def test_predict_rank_audited_quant_parity(mode):
    X_db, lam_db = _db()
    base = KNNLambdaPredictor.fit(np.asarray(X_db), np.asarray(lam_db), k=5)
    pred = base.quantized(mode=mode, slab=200)
    n, m1, m2 = 16, 96, 8
    X = _queries(b=n)
    u, a, b, gamma = _rank_problem(n, m1, m2)
    got = ops.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2)
    # the oracle under jit: eager jnp.sum reduces in a different order
    # than the compiled audit (1 ulp in utility); the serving contract
    # is vs the compiled program
    want = jax.jit(lambda *t: ref.predict_rank_audited_ref(
        *t[:1], pred, *t[1:], m2))(X, u, a, b, gamma)
    w = dict(zip(("vals", "perm", "utility", "exposure", "compliant",
                  "lam"), want))
    for f in ("perm", "compliant"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(w[f]), err_msg=f)
    for f in ("utility", "exposure"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, f)), np.asarray(w[f]),
            rtol=PARITY_RTOL, atol=PARITY_ATOL, err_msg=f)
    np.testing.assert_allclose(np.asarray(got.lam), np.asarray(w["lam"]),
                               rtol=2e-7, atol=2e-7)


def test_lossless_grid_int8_equals_f32_bitwise():
    """Values on the 0.5 grid with the absmax planted per slab make
    every slab scale exactly 0.5 — int8 reconstructs the db bitwise,
    so the quantized RankingOutput (λ̂ included) must equal f32's."""
    rng = np.random.default_rng(5)
    X_ll = np.clip(np.round(rng.uniform(-63.0, 63.0, (N_TRAIN, D)) * 2.0)
                   / 2.0, -63.5, 63.5).astype(np.float32)
    X_ll[::200] = 63.5
    lam_db = np.abs(rng.normal(size=(N_TRAIN, K))).astype(np.float32)
    base = KNNLambdaPredictor.fit(X_ll, lam_db, k=5)
    quant = base.quantized(mode="int8", slab=200)
    got_db = dequant_rows(quant.X_q[:N_TRAIN],
                          jnp.repeat(quant.q_scale[:, 0], 200)[:N_TRAIN,
                                                               None])
    np.testing.assert_array_equal(np.asarray(got_db), X_ll)
    n, m1, m2 = 16, 96, 8
    X = jnp.asarray(np.round(rng.uniform(-10, 10, (n, D)) * 2.0)
                    .astype(np.float32) / 2.0)
    u, a, b, gamma = _rank_problem(n, m1, m2, salt=6)
    o32 = ops.predict_rank_audited(X, base, u, a, b, gamma, m2=m2)
    oq = ops.predict_rank_audited(X, quant, u, a, b, gamma, m2=m2)
    for f in FIELDS + ("lam",):
        np.testing.assert_array_equal(
            np.asarray(getattr(o32, f)), np.asarray(getattr(oq, f)),
            err_msg=f)


# ---------------------------------------------------------------------------
# Guard observability: forced fallbacks and degenerate geometry
# ---------------------------------------------------------------------------


def test_adversarial_near_tie_fires_guard():
    """Rows k-1 and k planted closer together than the query's
    quantization error: the margin guard MUST flag those queries (the
    exact re-score already served the right answer — the guard is the
    observability signal the fleet alarms on)."""
    rng = np.random.default_rng(11)
    X_db = rng.normal(size=(N_TRAIN, D)).astype(np.float32) * 40.0
    q = rng.normal(size=(D,)).astype(np.float32) * 40.0
    # plant a shell of rows at nearly identical distance from q
    for i in range(8):
        v = rng.normal(size=(D,)).astype(np.float32)
        v /= np.linalg.norm(v)
        X_db[i] = q + v * (1.0 + 1e-4 * i)
    lam_db = np.abs(rng.normal(size=(N_TRAIN, K))).astype(np.float32)
    X_q, q_scale, y2_q = pack_knn_db(jnp.asarray(X_db), mode="int8",
                                     slab=200)
    Xq = jnp.asarray(np.repeat(q[None, :], 8, axis=0))
    _, _, guard = knn_quant_scan(X_q, q_scale, y2_q, Xq, k=5, mode="int8")
    assert int(np.asarray(guard).sum()) >= 1
    # and the flagged selection still matches the exact-on-x̃ oracle
    _, idx, _ = knn_quant_scan(X_q, q_scale, y2_q, Xq, k=5, mode="int8")
    _, idx_r, _ = ref.knn_quant_select_ref(Xq, X_q, q_scale, y2_q, 5,
                                           mode="int8")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_r))


def test_all_identical_rows_select_lowest_indices():
    """Every db row identical -> every distance ties -> the selection
    must collapse to [0..k-1] (ties to the lowest global index), and
    the guard fires on the all-tied boundary."""
    X_db = jnp.ones((256, D), jnp.float32) * 3.0
    X_q, q_scale, y2_q = pack_knn_db(X_db, mode="int8", slab=64)
    Xq = _queries(b=8, salt=9)
    d2, idx, guard = knn_quant_scan(X_q, q_scale, y2_q, Xq, k=5,
                                    mode="int8")
    np.testing.assert_array_equal(
        np.asarray(idx), np.broadcast_to(np.arange(5), (8, 5)))
    d2_r, idx_r, guard_r = ref.knn_quant_select_ref(
        Xq, X_q, q_scale, y2_q, 5, mode="int8")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_r))
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2_r))
    np.testing.assert_array_equal(np.asarray(guard), np.asarray(guard_r))
    assert int(np.asarray(guard).sum()) == 8   # gap 0 <= any error bound


# ---------------------------------------------------------------------------
# Refresh/state hygiene for the packed representation
# ---------------------------------------------------------------------------


def test_quantized_state_roundtrip_and_unquantized_stays_2key():
    X_db, lam_db = _db()
    base = KNNLambdaPredictor.fit(np.asarray(X_db), np.asarray(lam_db),
                                  k=5)
    assert state_fields(base) == ("X_db", "lam_db")
    quant = base.quantized(mode="int8", slab=200)
    assert set(state_fields(quant)) == {"X_db", "lam_db", "X_q",
                                        "q_scale", "y2_q"}
    st_ = predictor_state(quant)
    back = with_state(quant, st_)
    lam_a = np.asarray(knn_predict_quant(
        quant.X_q, quant.q_scale, quant.y2_q, quant.lam_db, _queries(),
        k=5, mode="int8"))
    lam_b = np.asarray(knn_predict_quant(
        back.X_q, back.q_scale, back.y2_q, back.lam_db, _queries(),
        k=5, mode="int8"))
    np.testing.assert_array_equal(lam_a, lam_b)


# ---------------------------------------------------------------------------
# Property layer (hypothesis; skipped visibly when unavailable)
# ---------------------------------------------------------------------------


if given is not None:
    settings.register_profile("ci_quant", max_examples=25, deadline=None)
    settings.load_profile("ci_quant")

    @given(st.integers(0, 10 ** 6), st.sampled_from([64, 100]),
           st.sampled_from(["int8", "bf16"]))
    def test_quant_selection_bitwise_invariant(seed, slab, mode):
        """THE invariant the tentpole rests on: for any db/query draw,
        the quantized sweep + exact f32 survivor re-score selects the
        same neighbour set, in the same order, as the full-precision
        oracle on the dequantized db x̃ — bitwise, including guard."""
        rng = np.random.default_rng(seed)
        n = int(rng.integers(40, 200))
        X_db = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32)
                           * rng.uniform(0.1, 30.0))
        Xq = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
        X_q, q_scale, y2_q = pack_knn_db(X_db, mode=mode, slab=slab)
        d2, idx, guard = knn_quant_scan(X_q, q_scale, y2_q, Xq, k=5,
                                        mode=mode)
        d2_r, idx_r, guard_r = ref.knn_quant_select_ref(
            Xq, X_q, q_scale, y2_q, 5, mode=mode)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_r))
        np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2_r))
        np.testing.assert_array_equal(np.asarray(guard),
                                      np.asarray(guard_r))
else:                                            # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed — property layer "
                             "runs in CI (pip install .[dev])")
    def test_quant_property_layer_requires_hypothesis():
        pytest.importorskip("hypothesis")
