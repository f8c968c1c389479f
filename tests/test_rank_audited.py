"""Fused rank+audit kernel vs the rank_given_lambda oracle: perm and
compliant exact, utility and exposure within the PARITY_RTOL/ATOL
contract of core.ranking (reduction order differs), across a shape sweep,
bucket-padded serving batches (trailing-zero gamma rows, phantom rows),
the m2 = MAX_KERNEL_M2 edge, and the XLA fallback — plus the payload
topk_merge primitive and the tune_eps tie-break regression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ranking import (
    EPS_GRID,
    PARITY_ATOL,
    PARITY_RTOL,
    rank_given_lambda,
    tune_eps,
)
from repro.kernels import ops
from repro.kernels.common import NEG_INF, topk_merge
from repro.kernels.fused_rank import MAX_KERNEL_M2

KEY = jax.random.key(7)

FIELDS = ("perm", "utility", "exposure", "compliant")
EXACT = ("perm", "compliant")


def _problem(n, m1, K, m2, salt=0):
    ks = jax.random.split(jax.random.fold_in(KEY, n * m1 + K + salt), 5)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    lam = jnp.abs(jax.random.normal(ks[2], (n, K)))
    b = jnp.abs(jax.random.normal(ks[3], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[4], (n, m2)))
    return u, a, b, lam, gamma


def _assert_parity(got, want, rows=slice(None), msg="rank+audit parity"):
    for field in FIELDS:
        g = np.asarray(getattr(got, field))[rows]
        w = np.asarray(getattr(want, field))[rows]
        if field in EXACT:
            np.testing.assert_array_equal(g, w, err_msg=f"{msg} broke on {field}")
        else:
            np.testing.assert_allclose(g, w, rtol=PARITY_RTOL, atol=PARITY_ATOL,
                                       err_msg=f"{msg} broke on {field}")


def _assert_bitwise(got, want):
    for field in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=f"rank+audit parity broke on {field}")


@pytest.mark.parametrize("n,m1,K,m2", [
    (8, 512, 5, 10),
    (4, 1000, 8, 50),              # the paper's 1000-item scenario
    (8, 2048, 3, MAX_KERNEL_M2),   # m2 edge: the largest kernel path
    (2, 600, 1, 1),
    (3, 700, 2, 8),                # off-tile n and m1 exercise padding
])
def test_rank_audited_matches_oracle_bitwise(n, m1, K, m2):
    u, a, b, lam, gamma = _problem(n, m1, K, m2)
    got = ops.rank_audited(u, a, b, lam, gamma, m2=m2, interpret=True)
    want = rank_given_lambda(u, a, b, lam, gamma, m2=m2)
    _assert_parity(got, want)
    # sanity: the audit actually discriminates on these problems
    assert np.asarray(want.compliant).ndim == 1


def test_rank_audited_shared_broadcast_forms():
    """(K, m1) a, (K,) b, (m2,) gamma broadcast exactly like the oracle."""
    u, a, b, lam, gamma = _problem(6, 512, 4, 16)
    got = ops.rank_audited(u, a[0], b[0], lam, gamma[0], m2=16,
                           interpret=True)
    want = rank_given_lambda(u, a[0], b[0], lam, gamma[0], m2=16)
    _assert_parity(got, want)


def test_rank_audited_bucket_padded_batch():
    """An engine-style padded micro-batch: phantom rows, NEG_FILL
    candidate padding, zero constraint rows, trailing-zero gamma —
    kernel and oracle agree on the whole padded problem."""
    from repro.serving import assemble_batch, bucket_for, make_request
    from repro.serving.traffic import DEFAULT_MIX

    rng = np.random.default_rng(0)
    reqs = [make_request(rng, DEFAULT_MIX[0], rid) for rid in range(5)]
    bucket = bucket_for(m1=max(r.u.shape[0] for r in reqs),
                        m2=reqs[0].m2, K=reqs[0].a.shape[0],
                        tag="_lam", batch=8)        # 3 phantom rows
    staged = assemble_batch(reqs, bucket)
    u = jnp.asarray(staged["u"])
    a = jnp.asarray(staged["a"])
    b = jnp.asarray(staged["b"])
    lam = jnp.asarray(staged["lam"])
    gamma = jnp.asarray(staged["gamma"])
    assert float(gamma[0, -1]) == 0.0 or bucket.m2 == reqs[0].m2

    got = ops.rank_audited(u, a, b, lam, gamma, m2=bucket.m2, interpret=True)
    want = rank_given_lambda(u, a, b, lam, gamma, m2=bucket.m2)
    n_real = len(reqs)
    _assert_parity(got, want, rows=slice(None, n_real),
                   msg="padded-batch parity")
    # phantom rows (u uniformly NEG_FILL == the merge's init sentinel):
    # their perm is unspecified — every candidate ties with the empty
    # running buffer — and the engine unpads them away before results
    # leave. The AUDIT outputs still agree exactly: zero gamma makes
    # utility/exposure exactly 0.0 and compliance trivially true.
    for field in ("utility", "exposure", "compliant"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field))[n_real:],
            np.asarray(getattr(want, field))[n_real:],
            err_msg=f"phantom-row audit parity broke on {field}")
    np.testing.assert_array_equal(np.asarray(got.utility[n_real:]), 0.0)


def test_rank_audited_trailing_zero_gamma_rows():
    """Per-request gamma rows with zeroed trailing slots (bucket-padded
    m2) leave utility/exposure identical to the unpadded problem."""
    n, m1, K, m2_real, m2_pad = 4, 512, 3, 10, 16
    u, a, b, lam, gamma = _problem(n, m1, K, m2_real)
    gamma_pad = jnp.pad(gamma, ((0, 0), (0, m2_pad - m2_real)))
    got = ops.rank_audited(u, a, b, lam, gamma_pad, m2=m2_pad,
                           interpret=True)
    want = rank_given_lambda(u, a, b, lam, gamma, m2=m2_real)
    np.testing.assert_array_equal(
        np.asarray(got.perm[:, :m2_real]), np.asarray(want.perm))
    np.testing.assert_allclose(
        np.asarray(got.utility), np.asarray(want.utility),
        rtol=PARITY_RTOL, atol=PARITY_ATOL)
    np.testing.assert_allclose(
        np.asarray(got.exposure), np.asarray(want.exposure),
        rtol=PARITY_RTOL, atol=PARITY_ATOL)
    np.testing.assert_array_equal(
        np.asarray(got.compliant), np.asarray(want.compliant))


def test_rank_audited_xla_fallback_large_m2():
    n, m1, K, m2 = 4, 700, 3, MAX_KERNEL_M2 + 72
    u, a, b, lam, gamma = _problem(n, m1, K, m2)
    got = ops.rank_audited(u, a, b, lam, gamma, m2=m2)   # > MAX -> XLA
    want = rank_given_lambda(u, a, b, lam, gamma, m2=m2)
    _assert_bitwise(got, want)


def test_rank_given_lambda_kernel_backend_route():
    """backend='kernel' emits the same RankingOutput as the jnp path."""
    u, a, b, lam, gamma = _problem(8, 512, 4, 12, salt=3)
    want = rank_given_lambda(u, a, b, lam, gamma, m2=12)
    got = rank_given_lambda(u, a, b, lam, gamma, m2=12, backend="kernel")
    _assert_parity(got, want)
    with pytest.raises(ValueError):
        rank_given_lambda(u, a, b, lam, gamma, m2=12, backend="nope")


def test_topk_merge_payload_carry():
    """Payload columns follow their winners through the streaming merge
    exactly, including across the running-buffer boundary."""
    k, B, T = 4, 3, 16
    ks = jax.random.split(KEY, 4)
    run_v = jnp.sort(jax.random.normal(ks[0], (B, k)), axis=-1)[:, ::-1]
    run_i = jnp.arange(k)[None, :].repeat(B, 0)
    tile_v = jax.random.normal(ks[1], (B, T))
    tile_i = 100 + jnp.arange(T)[None, :].repeat(B, 0)
    run_p = {"u": run_v * 2.0, "a": jnp.stack([run_v, -run_v], axis=1)}
    tile_p = {"u": tile_v * 2.0, "a": jnp.stack([tile_v, -tile_v], axis=1)}
    out_v, out_i, out_p = topk_merge(run_v, run_i, tile_v, tile_i, k,
                                     run_payload=run_p, tile_payload=tile_p)
    # oracle: top-k of the union, payload = f(value) must track winners
    cand_v = np.concatenate([run_v, tile_v], axis=-1)
    order = np.argsort(-cand_v, axis=-1, kind="stable")[:, :k]
    want_v = np.take_along_axis(cand_v, order, axis=-1)
    np.testing.assert_array_equal(np.asarray(out_v), want_v)
    np.testing.assert_array_equal(np.asarray(out_p["u"]), want_v * 2.0)
    np.testing.assert_array_equal(np.asarray(out_p["a"][:, 0]), want_v)
    np.testing.assert_array_equal(np.asarray(out_p["a"][:, 1]), -want_v)


def test_topk_merge_no_payload_unchanged():
    """The payload-free signature still returns the 2-tuple contract."""
    run_v = jnp.full((2, 3), NEG_INF)
    run_i = jnp.zeros((2, 3), jnp.int32)
    tile_v = jnp.asarray([[1.0, 3.0, 2.0, 0.0]] * 2)
    tile_i = jnp.arange(4)[None, :].repeat(2, 0)
    out = topk_merge(run_v, run_i, tile_v, tile_i, 3)
    assert len(out) == 2
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  [[1, 2, 0]] * 2)


# ---------------------------------------------------------------------------
# tune_eps tie-breaking (ascending grid regression)
# ---------------------------------------------------------------------------

def test_eps_grid_is_ascending():
    assert list(EPS_GRID) == sorted(EPS_GRID)
    assert EPS_GRID[0] == 0.0 and EPS_GRID[1] == pytest.approx(1e-4)


def test_tune_eps_flat_landscape_keeps_smallest_eps():
    """eps = 0 ties the two candidates (violation); every eps > 0 breaks
    the tie toward the constrained item (zero violation, FLAT in eps).
    The documented rule — ties -> smaller eps — demands the smallest
    positive grid point, 1e-4; a descending or i*10^-j-ordered sweep
    would return 0.1."""
    u = jnp.asarray([[1.5, 1.0]])
    a = jnp.asarray([[[0.0, 1.0]]])
    b = jnp.asarray([[0.5]])
    lam = jnp.asarray([[0.5]])      # eps=0: s = [1.5, 1.5] -> exact tie
    gamma = jnp.asarray([1.0])
    # sanity: eps=0 -> tie -> item 0 -> violated; eps>0 -> item 1 -> ok
    out0 = rank_given_lambda(u, a, b, lam, gamma, m2=1, eps=0.0)
    assert not bool(out0.compliant[0])
    out1 = rank_given_lambda(u, a, b, lam, gamma, m2=1, eps=0.1)
    assert bool(out1.compliant[0])
    assert tune_eps(u, a, b, lam, gamma, m2=1) == pytest.approx(1e-4)


def test_tune_eps_all_flat_returns_zero():
    """Fully flat landscape (b = 0: always compliant) -> eps stays 0.0."""
    u, a, b, lam, gamma = _problem(2, 128, 2, 4)
    b0 = jnp.zeros_like(b)
    assert tune_eps(u, a, b0, lam, gamma[0], m2=4) == 0.0
