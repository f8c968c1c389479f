"""The engine's trace spans on the profiler's clock, and
the bucket executables' names.

A small mixed stream (raw-lam and KNN-predictor buckets) is served under
`jax.profiler.start_trace` and read back with `ProfileData`: every
flushed batch has one of each batch span carrying its `batch` id, the
jit call lies inside its flush on the submitting thread and holds the
runtime's copies of the staged arrays to the device, the worker's spans
lie on the worker's thread, and each request's `engine.build_result`
names its `rid` and its batch.
"""

from collections import Counter, defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.constraints import dcg_discount
from repro.core.predictors import KNNLambdaPredictor
from repro.serving import RankRequest, ServingEngine
from repro.serving.engine import executable_name

# every span of the engine and the stats it carries (docs/serving.md)
SPANS = {
    "engine.enqueue": ("rid", "bucket"),
    "engine.flush": ("batch", "bucket", "trigger", "rows"),
    "engine.fill_staging": ("batch",),
    "engine.dispatch": ("batch", "bytes"),
    "pipeline.backpressure": ("batch",),
    "pipeline.device_wait": ("batch",),
    "pipeline.d2h": ("batch", "bytes", "copies"),
    "engine.build_result": ("rid", "batch"),
}
BATCH_SPANS = ("engine.fill_staging", "engine.dispatch",
               "pipeline.backpressure", "pipeline.device_wait",
               "pipeline.d2h")
D_COV, K = 6, 3


def _request(rid, rng, *, m1, tag=None):
    m2 = 8
    req = dict(rid=rid, u=rng.uniform(1, 5, m1).astype(np.float32),
               a=(rng.random((K, m1)) < 0.3).astype(np.float32),
               b=np.zeros(K, np.float32), m2=m2,
               gamma=np.asarray(dcg_discount(m2), np.float32))
    if tag is None:
        return RankRequest(lam=np.zeros(K, np.float32), **req)
    return RankRequest(X=rng.normal(size=D_COV).astype(np.float32),
                       tag=tag, **req)


def _engine(**kw):
    rng = np.random.default_rng(0)
    eng = ServingEngine(max_batch=4, max_wait_ms=1e9, **kw)
    knn = KNNLambdaPredictor.fit(
        rng.normal(size=(32, D_COV)).astype(np.float32),
        np.abs(rng.normal(size=(32, K))).astype(np.float32), k=4)
    eng.register_predictor("knn", knn, d_cov=D_COV)
    reqs = [_request(i, rng, m1=(64, 200)[i % 2],
                     tag=("knn", None, None)[i % 3]) for i in range(22)]
    eng.warmup(reqs)
    return eng, reqs


def _start_trace(trace_dir) -> None:
    """As the on-chip benchmark traces: host spans, no Python tracer."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _events(trace_dir: Path, names=SPANS):
    """Every engine span (or event of ``names``) as (name, thread, start,
    end, stats); a thread is its line's index (every Python thread's
    line is named 'python')."""
    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, thread, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    eng, reqs = _engine(pipeline_depth=1)
    trace_dir = tmp_path_factory.mktemp("trace")
    _start_trace(trace_dir)
    try:
        results = []
        for r in reqs:
            results += eng.submit(r)
        results += eng.drain()
    finally:
        jax.profiler.stop_trace()
    eng.close()
    buckets = {b.name: b for b in eng._warmed}
    return (_events(trace_dir, (*SPANS, "DevicePut")), reqs, results,
            buckets)


def test_every_span_carries_its_stats(traced):
    events, *_ = traced
    assert {name for name, *_ in events} == {*SPANS, "DevicePut"}
    for name, _, _, _, stats in events:
        assert tuple(stats) == SPANS.get(name, ()), name


def test_each_batch_has_one_of_each_batch_span(traced):
    events, reqs, results, _ = traced
    flushes = [st for name, *_, st in events if name == "engine.flush"]
    ids = [st["batch"] for st in flushes]
    assert len(ids) == len(set(ids)) >= len(reqs) // 4
    assert sum(st["rows"] for st in flushes) == len(reqs)
    for span_name in BATCH_SPANS:
        got = Counter(st["batch"] for name, *_, st in events
                      if name == span_name)
        assert got == Counter(ids), span_name


def test_dispatch_bytes_are_the_staged_arrays(traced):
    events, _, _, buckets = traced
    bucket_of = {st["batch"]: buckets[st["bucket"]]
                 for name, *_, st in events if name == "engine.flush"}
    for name, *_, st in events:
        if name != "engine.dispatch":
            continue
        bk = bucket_of[st["batch"]]
        last = bk.K if bk.tag == "_lam" else D_COV      # lam | X
        per_row = bk.m1 + bk.K * bk.m1 + bk.K + bk.m2 + last
        assert st["bytes"] == 4 * bk.batch * per_row


def test_call_lies_inside_its_flush_and_holds_the_copies(traced):
    events, *_ = traced
    flush = {st["batch"]: (th, s, e) for name, th, s, e, st in events
             if name == "engine.flush"}
    calls = [(th, s, e) for name, th, s, e, _ in events
             if name == "engine.dispatch"]
    puts = [(th, s, e) for name, th, s, e, _ in events if name == "DevicePut"]
    # the jit call copies its five staged arrays, on its own thread
    for th, s, e in calls:
        assert sum(p_th == th and s <= p_s <= p_e <= e
                   for p_th, p_s, p_e in puts) == 5
    for name, th, s, e, st in events:
        f_th, f_s, f_e = flush[st.get("batch", -1)] if "batch" in st \
            else (None, None, None)
        if name in ("engine.fill_staging", "engine.dispatch",
                    "pipeline.backpressure"):
            assert th == f_th and f_s <= s <= e <= f_e, name
        elif name in ("pipeline.device_wait", "pipeline.d2h"):
            assert th != f_th, name                 # the pipeline worker


def test_build_result_names_its_request_and_batch(traced):
    events, reqs, results, _ = traced
    rows = {st["batch"]: st["rows"] for name, *_, st in events
            if name == "engine.flush"}
    bucket_of_batch = {st["batch"]: st["bucket"] for name, *_, st in events
                       if name == "engine.flush"}
    bucket_of_rid = {st["rid"]: st["bucket"] for name, *_, st in events
                     if name == "engine.enqueue"}
    built = [st for name, *_, st in events if name == "engine.build_result"]
    assert sorted(st["rid"] for st in built) == [r.rid for r in reqs]
    assert Counter(st["batch"] for st in built) == Counter(rows)
    for st in built:
        assert bucket_of_batch[st["batch"]] == bucket_of_rid[st["rid"]]
    by_rid = {r.rid: r.bucket for r in results}
    assert by_rid == bucket_of_rid


def test_sync_engine_records_the_same_batch_spans(tmp_path):
    eng, reqs = _engine(pipeline_depth=0)
    _start_trace(tmp_path)
    try:
        for r in reqs:
            eng.submit(r)
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    events = _events(tmp_path)
    per_batch = defaultdict(Counter)
    for name, *_, st in events:
        if "batch" in st:
            per_batch[st["batch"]][name] += 1
    assert len(per_batch) >= len(reqs) // 4
    for counts in per_batch.values():
        # no pipeline: no backpressure, the rest once each
        assert all(counts[n] == 1 for n in BATCH_SPANS
                   if n != "pipeline.backpressure")
        assert counts["pipeline.backpressure"] == 0


def test_device_wait_blocks_only_under_a_trace(tmp_path, monkeypatch):
    import repro.serving.engine as engine_mod

    eng, reqs = _engine(pipeline_depth=0)
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(engine_mod.jax, "block_until_ready",
                        lambda x: waits.append(1) or real(x))
    for r in reqs[:8]:
        eng.submit(r)
    eng.drain()
    assert waits == []          # no trace: the copies home do the waiting
    _start_trace(tmp_path)
    try:
        for r in reqs[8:16]:
            eng.submit(r)
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    assert waits and len(waits) == len({st["batch"] for name, *_, st in
                              _events(tmp_path) if name == "engine.flush"})
    eng.close()


class _CopySpy:
    """numpy, counting the conversions of device arrays to host arrays:
    each is one copy home."""

    def __init__(self):
        self.copies = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *args, **kw):
        self.copies += isinstance(x, jax.Array)
        return np.asarray(x, *args, **kw)


@pytest.mark.parametrize("depth", [0, 1])
def test_a_batch_comes_home_in_one_copy(tmp_path, monkeypatch, depth):
    """Sync and async: one device-to-host copy a materialized batch,
    and `pipeline.d2h` says so: `copies` 1, `bytes` the whole slab."""
    import repro.serving.engine as engine_mod

    eng, reqs = _engine(pipeline_depth=depth)
    spy = _CopySpy()
    monkeypatch.setattr(engine_mod, "np", spy)
    _start_trace(tmp_path)
    try:
        for r in reqs:
            eng.submit(r)
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    eng.close()
    events = _events(tmp_path)
    bucket_of = {st["batch"]: st["bucket"] for name, *_, st in events
                 if name == "engine.flush"}
    slabs = {b.name: (b, eng._slabs[b]) for b in eng._warmed}
    d2h = [st for name, *_, st in events if name == "pipeline.d2h"]
    assert spy.copies == len(d2h) == len(bucket_of) == eng.metrics.batches
    for st in d2h:
        bk, slab = slabs[bucket_of[st["batch"]]]
        assert st["copies"] == 1
        assert st["bytes"] == 4 * bk.batch * slab.width


def test_executables_are_named_after_their_buckets():
    eng, _ = _engine(pipeline_depth=1)
    compiled = eng.compiled_executables()
    assert len(compiled) == 4                 # 2 tags x 2 m1 corners
    for bk in eng._warmed:
        name = executable_name(bk)
        assert name.startswith("serve_") and name.isidentifier()
        assert f"_m1_{bk.m1}_m2_{bk.m2}_K_{bk.K}_B_{bk.batch}" in name
        assert f"HloModule jit_{name}" in compiled[bk.name].as_text()
    eng.close()
