"""A bucket executable's one output slab (repro.serving.outputs).

Every bucket executable returns its five outputs packed into one int32
array, which the completion side copies home once and unpacks into
numpy views. The packing must be exact: unpacked outputs are bitwise
the unpacked body's own, NaN payloads, -0.0, infinities and large
`perm` values included, for every executor and every kind of bucket
(raw λ, an affine predictor, KNN), and a served stream's answers are
bitwise those of the unpacked body called on the same batches.
"""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.constraints import dcg_discount
from repro.core.predictors import KNNLambdaPredictor, LinearLambdaPredictor
from repro.core.ranking import RankingOutput
from repro.serving import (
    LAM_TAG,
    RankRequest,
    ServingEngine,
    assemble_batch,
    unpad_result,
)
from repro.serving.outputs import OutputSlab

EXECUTORS = ("xla", "fused")        # fused runs its kernels interpreted
TAGS = (LAM_TAG, "linear", "knn")
D_COV, K, BATCH = 6, 3, 4
FIELDS = ("perm", "utility", "exposure", "compliant", "lam")


def _engine(executor, depth=0):
    rng = np.random.default_rng(1)
    X = rng.normal(size=(40, D_COV)).astype(np.float32)
    lam = np.abs(rng.normal(size=(40, K))).astype(np.float32)
    eng = ServingEngine(max_batch=BATCH, max_wait_ms=1e9, executor=executor,
                        pipeline_depth=depth)
    eng.register_predictor("linear", LinearLambdaPredictor.fit(X, lam),
                           d_cov=D_COV)
    eng.register_predictor("knn", KNNLambdaPredictor.fit(X, lam, k=4),
                           d_cov=D_COV)
    return eng


def _requests(tag, n, seed=0):
    """`n` requests of one bucket (m1 70-120, m2 5) under `tag`."""
    rng = np.random.default_rng(seed)
    out = []
    for rid in range(n):
        m1, m2 = int(rng.integers(70, 121)), 5
        req = dict(rid=rid, u=rng.uniform(1, 5, m1).astype(np.float32),
                   a=(rng.random((K, m1)) < 0.3).astype(np.float32),
                   b=np.full(K, 0.2, np.float32), m2=m2,
                   gamma=np.asarray(dcg_discount(m2), np.float32))
        if tag == LAM_TAG:
            lam = np.abs(rng.normal(size=K)).astype(np.float32)
            out.append(RankRequest(lam=lam, **req))
        else:
            X = rng.normal(size=D_COV).astype(np.float32)
            out.append(RankRequest(X=X, tag=tag, **req))
    return out


def _bits(x) -> np.ndarray:
    """The raw bits of an output (bool and int32 as they are)."""
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


def _assert_bitwise(got: RankingOutput, want: RankingOutput):
    for name in FIELDS:
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=name)


def _with_specials(out: RankingOutput) -> RankingOutput:
    """`out` with NaN payloads, signed zeros, infinities and large
    `perm` values planted in every field that can hold them."""
    specials = np.array([0x7FC00001, 0xFFFFFFFF, 0x7F800001, 0x80000000,
                         0x7F800000, 0xFF800000, 0x00000001], np.uint32)
    specials = specials.view(np.float32)      # NaNs, -0.0, ±inf, denormal

    def plant(x):
        x = np.array(x)
        flat = x.reshape(-1)
        n = min(flat.size, specials.size)
        flat[:n] = specials[:n]
        return jnp.asarray(x)

    perm = np.array(out.perm)
    perm[0, :3] = (np.iinfo(np.int32).max, np.iinfo(np.int32).min, -1)
    perm[-1, -1] = 2**31 - 2
    compliant = np.array(out.compliant)
    compliant[0], compliant[-1] = True, False
    return replace(out, perm=jnp.asarray(perm), utility=plant(out.utility),
                   exposure=plant(out.exposure),
                   compliant=jnp.asarray(compliant), lam=plant(out.lam))


@pytest.mark.parametrize("tag", TAGS)
@pytest.mark.parametrize("executor", EXECUTORS)
def test_pack_then_unpack_is_bitwise(executor, tag):
    eng = _engine(executor)
    reqs = _requests(tag, BATCH)
    eng.warmup(reqs)
    bucket = eng.bucket_of(reqs[0])
    slab = eng._slabs[bucket]
    assert slab == OutputSlab(m2=bucket.m2, K=bucket.K)
    assert slab.width == bucket.m2 + 1 + bucket.K + 1 + bucket.K
    body, _ = eng._bucket_body(bucket)

    def args():
        staged = assemble_batch(reqs, bucket, d_cov=eng._dcov(bucket))
        return eng._args(bucket, staged)

    want = jax.jit(body)(*args())
    packed = np.asarray(eng._exec[bucket](*args()))
    assert packed.dtype == np.int32
    assert packed.shape == (bucket.batch, slab.width)
    _assert_bitwise(slab.unpack(packed), want)       # the executable itself

    special = _with_specials(want)
    back = slab.unpack(np.asarray(jax.jit(slab.pack)(special)))
    _assert_bitwise(back, special)
    assert np.isnan(back.utility[0]) and np.signbit(back.exposure.flat[3])
    eng.close()


@pytest.mark.parametrize("tag", TAGS)
@pytest.mark.parametrize("executor", EXECUTORS)
def test_served_answers_are_the_unpacked_body_bitwise(executor, tag):
    """A stream served through the pipeline answers, row by row, what
    the unpacked body gives for the same batches."""
    eng = _engine(executor, depth=1)
    reqs = _requests(tag, 3 * BATCH - 1, seed=7)
    results = {r.rid: r for r in eng.serve_stream(reqs)}
    eng.close()
    bucket = eng.bucket_of(reqs[0])
    body = jax.jit(eng._bucket_body(bucket)[0])
    # one bucket, max_wait_ms never reached: capacity flushes of BATCH
    # requests in arrival order, then the rest at drain
    for start in range(0, len(reqs), BATCH):
        batch = reqs[start:start + BATCH]
        staged = assemble_batch(batch, bucket, d_cov=eng._dcov(bucket))
        want = jax.tree_util.tree_map(np.asarray,
                                      body(*eng._args(bucket, staged)))
        for i, req in enumerate(batch):
            got = results[req.rid]
            perm, utility, exposure, compliant = unpad_result(want, i, req)
            np.testing.assert_array_equal(got.perm, perm)
            assert _bits(np.float32(got.utility)) == _bits(np.float32(utility))
            np.testing.assert_array_equal(_bits(got.exposure), _bits(exposure))
            assert got.compliant == compliant
            np.testing.assert_array_equal(
                _bits(got.lam), _bits(want.lam[i, :K]))


def test_pack_refuses_an_output_it_cannot_hold():
    slab = OutputSlab(m2=8, K=4)
    out = RankingOutput(perm=jnp.zeros((2, 8), jnp.int32),
                        utility=jnp.zeros(2), exposure=jnp.zeros((2, 4)),
                        compliant=jnp.zeros(2, bool), lam=jnp.zeros((2, 4)))
    assert slab.pack(out).shape == (2, slab.width)
    with pytest.raises(ValueError, match="utility"):
        slab.pack(replace(out, utility=jnp.zeros(2, jnp.int32)))
    with pytest.raises(ValueError, match="lam"):
        slab.pack(replace(out, lam=jnp.zeros((2, 3))))


def test_layout_columns_tile_the_slab():
    slab = OutputSlab(m2=64, K=8)
    assert slab.width == 82
    assert tuple(name for name, *_ in slab.fields()) == FIELDS
    cols = [c for *_, cols in slab.fields()
            for c in np.arange(82)[cols].reshape(-1)]
    assert cols == list(range(82))
