"""Streaming serving engine: bucket geometry, padding equivalence, and
the no-recompile contract.

The heart of the subsystem is an exactness claim — padding a request
into its shape bucket must not change perm/utility/exposure/compliance
— and a performance claim — a mixed-shape stream compiles nothing after
warmup. Both are asserted here; the recompile assertion goes through
the engine's per-bucket jit cache sizes (1 == exactly the warmed
executable).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.constraints import dcg_discount
from repro.core.predictors import (
    KNNLambdaPredictor,
    LinearLambdaPredictor,
    MeanLambdaPredictor,
)
from repro.core.ranking import PARITY_ATOL, PARITY_RTOL, rank_given_lambda
from repro.serving import (
    LAM_TAG,
    RankRequest,
    Scenario,
    ServingEngine,
    bucket_for,
    ceil_pow2,
    k_tier,
    make_stream,
)

# ---------------------------------------------------------------------------
# Bucket geometry
# ---------------------------------------------------------------------------


def test_ceil_pow2_boundaries():
    assert ceil_pow2(128, 128) == 128       # exact boundary: no inflation
    assert ceil_pow2(129, 128) == 256       # one past: next power of two
    assert ceil_pow2(1, 128) == 128         # floor applies
    assert ceil_pow2(1024, 128) == 1024


def test_k_tier_and_oversize_fallback():
    assert k_tier(3) == 4
    assert k_tier(4) == 4                   # exact tier boundary
    assert k_tier(5) == 8
    assert k_tier(32) == 32
    assert k_tier(40) == 64                 # oversize: pow2 fallback


def test_bucket_for_clamps_and_validates():
    b = bucket_for(m1=100, m2=100, K=2, tag=LAM_TAG, batch=8)
    assert b.m1 == 128 and b.m2 == 128      # m2 ceiling clamped to m1 ceiling
    with pytest.raises(ValueError):
        bucket_for(m1=50, m2=51, K=2, tag=LAM_TAG, batch=8)
    b2 = bucket_for(m1=500, m2=50, K=5, tag="x", batch=16)
    assert (b2.m1, b2.m2, b2.K, b2.batch) == (512, 64, 8, 16)


# ---------------------------------------------------------------------------
# Padding equivalence: engine result == direct unpadded hot path
# ---------------------------------------------------------------------------


def _direct(req, lam):
    """Unbatched, unpadded reference through the core online path."""
    return rank_given_lambda(
        jnp.asarray(req.u)[None], jnp.asarray(req.a)[None],
        jnp.asarray(req.b)[None], jnp.asarray(lam)[None],
        jnp.asarray(req.gamma), m2=req.m2, eps=1e-4)


def _check_match(result, ref):
    np.testing.assert_array_equal(result.perm, np.asarray(ref.perm[0]))
    np.testing.assert_allclose(result.utility, float(ref.utility[0]),
                               rtol=1e-5)
    np.testing.assert_allclose(result.exposure, np.asarray(ref.exposure[0]),
                               rtol=1e-5, atol=1e-6)
    assert result.compliant == bool(ref.compliant[0])


def test_pad_unpad_roundtrip_matches_unbatched():
    reqs = make_stream(n_requests=24, seed=11)   # all carry lam directly
    eng = ServingEngine(max_batch=8, max_wait_ms=1.0)
    by_rid = {r.rid: r for r in eng.serve_stream(reqs)}
    assert len(by_rid) == len(reqs)
    for req in reqs:
        _check_match(by_rid[req.rid], _direct(req, req.lam))


def test_pad_unpad_roundtrip_predictor_path():
    rng = np.random.default_rng(3)
    d, K = 12, 5
    X_db = rng.normal(size=(100, d)).astype(np.float32)
    lam_db = np.abs(rng.normal(size=(100, K))).astype(np.float32)
    knn = KNNLambdaPredictor.fit(X_db, lam_db, k=5)
    eng = ServingEngine(max_batch=4, max_wait_ms=1.0)
    eng.register_predictor("arch", knn, d_cov=d)
    mix = (Scenario("s", m1=300, m2=30, K=K, tag="arch", d_cov=d),)
    reqs = make_stream(mix, n_requests=12, seed=5)
    by_rid = {r.rid: r for r in eng.serve_stream(reqs)}
    for req in reqs:
        lam = np.asarray(knn.predict(jnp.asarray(req.X)[None]))[0]
        _check_match(by_rid[req.rid], _direct(req, lam))


def test_fused_executor_matches_xla_executor():
    reqs = make_stream(n_requests=8, seed=7)
    res_x = {r.rid: r for r in ServingEngine(
        max_batch=4, max_wait_ms=1.0, executor="xla").serve_stream(reqs)}
    res_f = {r.rid: r for r in ServingEngine(
        max_batch=4, max_wait_ms=1.0, executor="fused").serve_stream(reqs)}
    for rid in res_x:
        # core.ranking's parity contract: the fused rank+audit kernel
        # mirrors the XLA audit op for op, so selection and compliance
        # match exactly; its sums reduce in another order, so utility
        # and exposure match to a few ulp
        np.testing.assert_array_equal(res_f[rid].perm, res_x[rid].perm)
        np.testing.assert_allclose(res_f[rid].exposure, res_x[rid].exposure,
                                   rtol=PARITY_RTOL, atol=PARITY_ATOL)
        np.testing.assert_allclose(res_f[rid].utility, res_x[rid].utility,
                                   rtol=PARITY_RTOL, atol=PARITY_ATOL)
        assert res_f[rid].compliant == res_x[rid].compliant


# ---------------------------------------------------------------------------
# Flush triggers (sync engine: pipeline_depth=0 makes submit/poll return
# the flushed batch inline, so the trigger -> result mapping is exact)
# ---------------------------------------------------------------------------


def _tiny_request(rid, m1=64, m2=8, K=2):
    rng = np.random.default_rng(rid)
    return RankRequest(
        rid=rid, u=rng.uniform(1, 5, m1).astype(np.float32),
        a=(rng.random((K, m1)) < 0.3).astype(np.float32),
        b=np.zeros(K, np.float32), m2=m2,
        lam=np.zeros(K, np.float32),
        gamma=np.asarray(dcg_discount(m2), np.float32))


def test_capacity_flush_fires_on_full_batch():
    eng = ServingEngine(max_batch=4, max_wait_ms=1e9, pipeline_depth=0)
    out = []
    for i in range(4):
        out += eng.submit(_tiny_request(i), now=0.0)
    assert sorted(r.rid for r in out) == [0, 1, 2, 3]
    assert eng.metrics.capacity_flushes == 1


def test_capacity_flush_retires_async_with_pipeline():
    """Same stream through the pipelined engine: the capacity flush
    dispatches without blocking and the batch retires by drain time."""
    eng = ServingEngine(max_batch=4, max_wait_ms=1e9, pipeline_depth=2)
    out = []
    for i in range(4):
        out += eng.submit(_tiny_request(i), now=0.0)
    out += eng.drain()
    assert sorted(r.rid for r in out) == [0, 1, 2, 3]
    assert eng.metrics.capacity_flushes == 1


def test_deadline_flush_fires_on_max_wait():
    eng = ServingEngine(max_batch=4, max_wait_ms=2.0, pipeline_depth=0)
    assert eng.submit(_tiny_request(0), now=0.0) == []
    assert eng.poll(now=0.001) == []            # 1 ms: under deadline
    out = eng.poll(now=0.003)                   # 3 ms: over deadline
    assert [r.rid for r in out] == [0]
    assert eng.metrics.deadline_flushes == 1
    assert out[0].wait_ms > 0


def test_drain_flushes_everything():
    eng = ServingEngine(max_batch=8, max_wait_ms=1e9, pipeline_depth=0)
    for i in range(3):
        eng.submit(_tiny_request(i))
    out = eng.drain()
    assert len(out) == 3 and eng.metrics.drain_flushes == 1


# ---------------------------------------------------------------------------
# The no-recompile contract (acceptance criterion)
# ---------------------------------------------------------------------------


def test_mixed_stream_no_recompiles_after_warmup():
    """>= 256 requests, >= 2 archs, >= 3 (m1, m2) geometries: after
    warmup, zero recompilations — via the engine counter AND the
    per-bucket jit cache sizes."""
    rng = np.random.default_rng(0)
    d = 16
    knn = KNNLambdaPredictor.fit(
        rng.normal(size=(64, d)).astype(np.float32),
        np.abs(rng.normal(size=(64, 5))).astype(np.float32), k=5)
    mean = MeanLambdaPredictor.fit(
        np.zeros((4, d), np.float32),
        np.abs(rng.normal(size=(4, 3))).astype(np.float32))
    eng = ServingEngine(max_batch=16, max_wait_ms=2.0)
    eng.register_predictor("sasrec", knn, d_cov=d)
    eng.register_predictor("deepfm", mean, d_cov=d)
    mix = (
        Scenario("feed", m1=500, m2=50, K=5, weight=3.0,
                 tag="sasrec", d_cov=d),
        Scenario("strip", m1=1000, m2=20, K=3, weight=2.0,
                 tag="deepfm", d_cov=d),
        Scenario("notif", m1=120, m2=8, K=3, weight=1.0),     # raw-lam arch
        Scenario("retrieval", m1=2000, m2=50, K=8, weight=1.0),
    )
    reqs = make_stream(mix, n_requests=256, seed=9)
    assert len({(eng.bucket_of(r).m1, eng.bucket_of(r).m2)
                for r in reqs}) >= 3
    assert len({eng.bucket_of(r).tag for r in reqs}) >= 2

    eng.warmup(reqs)
    compiles_at_warmup = eng.metrics.compiles
    results = []
    for r in reqs:
        results += eng.submit(r)
        results += eng.poll()
    results += eng.drain()

    assert len(results) == 256
    assert eng.metrics.compiles == compiles_at_warmup
    assert eng.metrics.compiles_post_warmup == 0
    assert eng.metrics.oversize_requests == 0
    # jit cache stats: exactly the one warmed executable per bucket
    sizes = eng.jit_cache_sizes()
    assert sizes and all(v == 1 for v in sizes.values()), sizes


def test_oversize_request_is_served_and_counted():
    """A geometry outside the warmed lattice still gets served (new
    bucket compiled on demand) and is visible in the metrics."""
    eng = ServingEngine(max_batch=4, max_wait_ms=1.0)
    small = [_tiny_request(i) for i in range(8)]
    eng.warmup(small)
    for r in small:
        eng.submit(r)
    eng.drain()
    assert eng.metrics.compiles_post_warmup == 0
    big = _tiny_request(99, m1=5000, m2=64, K=40)   # oversize K -> pow2 tier
    eng.submit(big)
    out = eng.drain()
    assert [r.rid for r in out] == [99]
    assert eng.metrics.oversize_requests == 1
    assert eng.metrics.compiles_post_warmup == 1
    _check_match(out[0], _direct(big, big.lam))


class _CountingPredictor:
    """Delegating predictor that counts PYTHON invocations of predict.
    Inside a jit'd bucket executable, predict runs once per TRACE
    (warmup) and never again — a per-batch count increase would mean λ̂
    was being dispatched as a separate device program."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def predict(self, X):
        self.calls += 1
        return self.inner.predict(X)


@pytest.mark.parametrize("executor", ["xla", "fused"])
def test_covariate_stream_single_dispatch_per_batch(executor):
    """The single-dispatch contract (acceptance criterion): a
    covariate-carrying stream executes EXACTLY ONE device dispatch per
    flushed micro-batch — λ̂ prediction lives inside the bucket
    executable (kernels.ops.predict_rank_audited), never as a second
    program. The assertions with teeth: the per-bucket jit caches hold
    exactly the one warmed executable (a retracing predict path would
    grow them), and the predictor's Python predict() is never
    re-entered after warmup (an eager or separately-jitted predict
    would re-enter it per flush). The executable-call counter is the
    accounting surface those facts certify."""
    rng = np.random.default_rng(4)
    d, K = 10, 4
    knn = KNNLambdaPredictor.fit(
        rng.normal(size=(96, d)).astype(np.float32),
        np.abs(rng.normal(size=(96, K))).astype(np.float32), k=5)
    counting = _CountingPredictor(MeanLambdaPredictor.fit(
        np.zeros((4, d), np.float32),
        np.abs(rng.normal(size=(4, K))).astype(np.float32)))
    eng = ServingEngine(max_batch=8, max_wait_ms=2.0, executor=executor)
    eng.register_predictor("knn_arch", knn, d_cov=d)
    eng.register_predictor("counted_arch", counting, d_cov=d)
    mix = (
        Scenario("feed", m1=300, m2=20, K=K, weight=2.0,
                 tag="knn_arch", d_cov=d),
        Scenario("strip", m1=600, m2=10, K=K, weight=1.0,
                 tag="counted_arch", d_cov=d),
    )
    reqs = make_stream(mix, n_requests=48, seed=13)
    assert all(r.X is not None for r in reqs)    # covariate-only stream

    eng.warmup(reqs)
    calls_after_warmup = counting.calls
    results = eng.serve_stream(reqs)
    assert len(results) == 48

    m = eng.metrics
    assert m.batches > 0
    assert m.executable_calls == m.batches       # one dispatch per flush
    assert m.summary()["dispatches_per_batch"] == 1.0
    assert m.compiles_post_warmup == 0
    sizes = eng.jit_cache_sizes()
    assert sizes and all(v == 1 for v in sizes.values()), sizes
    # predict() was traced into the executable, not dispatched per batch
    assert counting.calls == calls_after_warmup
    # kernel-launch accounting: every fused-executor batch carries ONE
    # Pallas kernel launch — the KNN buckets included, now that the
    # single-grid predict+rank+audit kernel replaced the two-kernel
    # chain; the xla executor launches none.
    if executor == "fused":
        assert m.kernel_launches == m.batches
        assert m.summary()["kernel_launches_per_batch"] == 1.0
    else:
        assert m.kernel_launches == 0

    # and the answers are the two-stage oracle's, per request
    by_rid = {r.rid: r for r in results}
    for req in reqs:
        pred = knn if req.tag == "knn_arch" else counting.inner
        lam = np.asarray(pred.predict(jnp.asarray(req.X)[None]))[0]
        _check_match(by_rid[req.rid], _direct(req, lam))
    eng.close()


def test_fused_predictor_executor_matches_xla_executor():
    """xla and fused executors agree on a covariate stream — the fused
    path's in-kernel λ̂ prologue (linear/mean) and fused KNN weighting
    produce the same results the two-stage XLA body does."""
    rng = np.random.default_rng(6)
    d, K = 8, 3
    lin = LinearLambdaPredictor.fit(
        jnp.asarray(rng.uniform(0, 1, (64, d)), jnp.float32),
        jnp.asarray(np.abs(rng.normal(size=(64, K))), jnp.float32))
    mix = (Scenario("cov", m1=260, m2=16, K=K, tag="lin", d_cov=d),)
    reqs = make_stream(mix, n_requests=16, seed=3)
    res = {}
    for executor in ("xla", "fused"):
        eng = ServingEngine(max_batch=4, max_wait_ms=1.0, executor=executor)
        eng.register_predictor("lin", lin, d_cov=d)
        res[executor] = {r.rid: r for r in eng.serve_stream(reqs)}
        eng.close()
    for rid in res["xla"]:
        np.testing.assert_array_equal(res["fused"][rid].perm,
                                      res["xla"][rid].perm)
        np.testing.assert_array_equal(res["fused"][rid].exposure,
                                      res["xla"][rid].exposure)
        assert res["fused"][rid].utility == res["xla"][rid].utility
        assert res["fused"][rid].compliant == res["xla"][rid].compliant


def test_predictor_with_too_few_outputs_is_rejected():
    """A predictor cannot price constraints it was not fit for; serving
    them with lam=0 must be an error, not silence."""
    rng = np.random.default_rng(1)
    knn = KNNLambdaPredictor.fit(
        rng.normal(size=(16, 4)).astype(np.float32),
        np.abs(rng.normal(size=(16, 2))).astype(np.float32), k=3)
    eng = ServingEngine(max_batch=4)
    eng.register_predictor("arch", knn, d_cov=4)
    req = _tiny_request(0, K=5)
    req = RankRequest(rid=0, u=req.u, a=req.a, b=req.b, m2=req.m2,
                      X=np.zeros(4, np.float32), tag="arch", gamma=req.gamma)
    with pytest.raises(ValueError, match="shadow prices"):
        eng.submit(req)


def test_metrics_summary_shape():
    eng = ServingEngine(max_batch=8, max_wait_ms=1.0)
    eng.serve_stream(make_stream(n_requests=32, seed=2))
    s = eng.metrics.summary()
    assert s["results"] == 32
    assert 0.0 < s["fill_rate"] <= 1.0
    for q in ("p50", "p95", "p99"):
        assert np.isfinite(s["latency_ms"][q])
    assert 0.0 <= s["compliance"] <= 1.0


# ---------------------------------------------------------------------------
# Paced open-loop load generation (serving.traffic)
# ---------------------------------------------------------------------------


def test_poisson_arrivals_statistics():
    from repro.serving import poisson_arrivals

    arr = poisson_arrivals(4096, qps=100.0, seed=3)
    assert arr.shape == (4096,)
    assert np.all(np.diff(arr) > 0)                 # strictly increasing
    gaps = np.diff(np.concatenate([[0.0], arr]))
    assert abs(gaps.mean() - 0.01) < 0.001          # mean gap ~ 1/qps
    with pytest.raises(ValueError):
        poisson_arrivals(8, qps=0.0)


def test_serve_open_loop_virtual_clock():
    """Open-loop pacing under a deterministic virtual clock: every
    request is submitted at (never before) its scheduled arrival, all
    results come back, and the lag profile is reported."""
    from repro.serving import poisson_arrivals, serve_open_loop

    t = [0.0]

    def clock():
        return t[0]

    def sleep(dt):
        t[0] += dt

    reqs = [_tiny_request(rid) for rid in range(24)]
    arrivals = poisson_arrivals(len(reqs), qps=2000.0, seed=1)
    eng = ServingEngine(max_batch=4, max_wait_ms=0.5, pipeline_depth=0,
                        clock=clock)
    eng.warmup(reqs)
    results, stats = serve_open_loop(eng, reqs, arrivals,
                                     clock=clock, sleep=sleep)
    assert sorted(r.rid for r in results) == list(range(24))
    assert stats["wall_s"] >= float(arrivals[-1])   # pacing was honored
    assert stats["lag_ms"]["max"] >= 0.0
    assert set(stats["lag_ms"]) == {"mean", "p50", "p99", "max", "last"}
    # the virtual clock only advances via sleep(), so submissions can
    # never run ahead of schedule
    assert stats["lag_ms"]["mean"] >= 0.0


class _FakeEngine:
    """Deterministic serve_open_loop stand-in on a virtual clock:
    submit() consumes `cost_s` of clock time (pure engine
    backpressure) and the lag samples fed to observe_submission_lag
    are recorded for inspection."""

    def __init__(self, t, cost_s=0.0):
        self.t = t
        self.cost_s = cost_s
        self.fed = []

    def poll(self):
        return []

    def submit(self, req):
        self.t[0] += self.cost_s
        return [req]

    def drain(self):
        return []

    def observe_submission_lag(self, lag_ms):
        self.fed.append(lag_ms)


def test_open_loop_pacing_overshoot_is_drift_not_queue_lag():
    """Regression (frozen-clock trace): sleep-granularity overshoot
    used to be charged to the engine's submission-lag profile, tripping
    the saturation detector on pacing jitter. Decomposed, it lands
    entirely in drift_ms — queue_lag_ms stays exactly zero, and the
    admission controller is fed those zeros."""
    from repro.serving import serve_open_loop

    t = [0.0]
    overshoot = 1e-3

    def sleep(dt):                              # timer overshoots 1 ms
        t[0] += dt + overshoot

    eng = _FakeEngine(t, cost_s=0.0)            # engine is instantaneous
    n = 16
    arrivals = 0.01 * np.arange(1, n + 1)       # 10 ms gaps >> overshoot
    _, stats = serve_open_loop(eng, list(range(n)), arrivals,
                               clock=lambda: t[0], sleep=sleep)
    assert stats["queue_lag_ms"]["max"] == 0.0  # nothing charged to engine
    assert stats["drift_ms"]["max"] >= overshoot * 1e3
    assert stats["lag_ms"]["max"] == stats["drift_ms"]["max"]
    assert eng.fed == [0.0] * n                 # controller sees no lag


def test_open_loop_backpressure_is_queue_lag_not_drift():
    """The converse trace: a saturated engine (each submit consumes 2x
    the arrival gap) accumulates lateness that is pure queueing — it
    lands entirely in queue_lag_ms, grows over the stream (the
    saturation telltale), and is exactly what feeds the controller."""
    from repro.serving import serve_open_loop

    t = [0.0]

    def sleep(dt):                              # exact virtual timer
        t[0] += dt

    eng = _FakeEngine(t, cost_s=0.02)           # 20 ms service, 10 ms gaps
    n = 16
    arrivals = 0.01 * np.arange(1, n + 1)
    _, stats = serve_open_loop(eng, list(range(n)), arrivals,
                               clock=lambda: t[0], sleep=sleep)
    assert stats["drift_ms"]["max"] == 0.0      # no pacing jitter charged
    assert stats["queue_lag_ms"]["last"] > 0.0
    assert stats["queue_lag_ms"]["last"] == stats["queue_lag_ms"]["max"]
    # lateness at entry grows ~(cost - gap) = 10 ms per request
    np.testing.assert_allclose(eng.fed, 10.0 * np.arange(n), atol=1e-6)
    assert stats["lag_ms"]["last"] == stats["queue_lag_ms"]["last"]


def test_serve_open_loop_length_mismatch_rejected():
    from repro.serving import serve_open_loop

    eng = ServingEngine(max_batch=4, pipeline_depth=0)
    with pytest.raises(ValueError, match="arrival times"):
        serve_open_loop(eng, [_tiny_request(0)], np.asarray([0.0, 1.0]))
    with pytest.raises(ValueError, match="empty request stream"):
        serve_open_loop(eng, [], np.asarray([]))
