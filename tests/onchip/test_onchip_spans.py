"""span_reduce on hand-made profiles, and the span metrics' readers on a
trace that a small engine writes on the CPU."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmarks.onchip import layout, span_reduce

SPAN_METRICS = ("h2d_ms", "h2d_mb", "d2h_ms", "build_us",
                "inflight_idle_pct")


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _profile(program=True):
    """Window [1000, 2000). Device 0 runs ops in [900, 1100) and
    [1500, 1600); device 1 in [1200, 1900), which inflight idle ignores.
    A batch is in flight [1050, 1300) on the submitting thread (its jit
    call, which starts two copies to the device) and [1250, 1700) on the
    worker (device wait, d2h). The runtime's threads carry the copies:
    the call's second copy is two transfers, the last done at 1390."""
    sub = [_ev("bench.window", 1000, 1000),
           _ev("TpuClient::LinearizeIntoImpl", 960, 10, _p=10),  # no call
           _ev("engine.flush", 950, 400, batch=7, bucket="b", rows=3),
           _ev("engine.dispatch", 1050, 250, batch=7, bytes=600),
           _ev("TpuClient::LinearizeIntoImpl", 1060, 20, _p=11),
           _ev("TpuClient::LinearizeIntoImpl", 1100, 20, _p=12),
           _ev("engine.dispatch", 2100, 50, batch=8, bytes=999),  # after
           _ev("engine.build_result", 1800, 40, rid=1, batch=7),
           _ev("engine.build_result", 1980, 40, rid=2, batch=7),
           _ev("bench.poll", 1800, 300)]
    worker = [_ev("pipeline.device_wait", 1250, 300, batch=7),
              _ev("pipeline.d2h", 1550, 150, batch=7, bytes=40),
              _ev("pipeline.d2h", 100, 150, batch=6, bytes=40)]  # before
    runtime = [_ev("H2D Dispatch", 965, 10, _c=10),
               _ev("tpu::System::TransferToDevice", 966, 8, _p=20),
               _ev("H2D Dispatch", 1070, 50, _c=11),
               _ev("tpu::System::TransferToDevice", 1075, 35, _p=21),
               _ev("H2D Dispatch", 1130, 70, _c=12),
               _ev("tpu::System::TransferToDevice", 1135, 25, _p=22),
               _ev("tpu::System::TransferToDevice", 1165, 30, _p=23)]
    done = "tpu::System::TransferToDevice=>IssueEvent=>Done"
    events = [_ev(done, 980, 10, _c=20), _ev(done, 1150, 10, _c=21),
              _ev(done, 1240, 10, _c=22), _ev(done, 1380, 10, _c=23)]
    if not program:
        sub = [ev for ev in sub
               if not ev.name.startswith(span_reduce.PROGRAM_SPANS)]
        worker = []
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=sub), NS(name="python", events=worker),
        NS(name="pjrt-tpu-tasks", events=runtime),
        NS(name="EventFDAsyncWorker", events=events)])

    def dev(i, ops):
        return NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=[])])

    return NS(planes=[
        host,
        dev(0, [_ev("%fusion.1 = f32[8]{0} fusion(f32[8] %x)", 900, 200),
                _ev("%fusion.2 = f32[8]{0} fusion(f32[8] %x)", 1500, 100)]),
        dev(1, [_ev("%fusion.1 = f32[8]{0} fusion(f32[8] %x)", 1200, 700)]),
        NS(name="Task Environment", lines=[])])


def test_spans_are_clipped_to_the_window():
    r = span_reduce.reduce_spans(_profile())
    assert r["window_s"] == pytest.approx(1e-6)
    sp = r["spans"]
    assert set(sp) == {"engine.flush", "engine.dispatch",
                       "engine.build_result", "pipeline.device_wait",
                       "pipeline.d2h"}
    # the flush straddles the window's start: [1000, 1350)
    assert sp["engine.flush"]["dur_s"] == pytest.approx([350e-9])
    # one dispatch and one d2h in the window, the others outside it
    assert sp["engine.dispatch"]["n"] == 1 and sp["pipeline.d2h"]["n"] == 1
    # the last result build straddles the window's end: [1980, 2000)
    assert sp["engine.build_result"]["dur_s"] == pytest.approx(
        [40e-9, 20e-9])


def test_bytes_sum_over_the_window_spans():
    sp = span_reduce.reduce_spans(_profile())["spans"]
    assert sp["engine.dispatch"]["bytes"] == 600
    assert sp["pipeline.d2h"]["bytes"] == 40
    assert sp["engine.flush"]["bytes"] == 0


def test_h2d_runs_from_the_call_to_its_last_transfer_done():
    # the call's two copies, not the one before it: the second copy's
    # later transfer is done at 1390, 340 ns after the call started
    assert span_reduce.reduce_spans(_profile())["h2d_s"] == pytest.approx(
        [340e-9])


def test_a_copy_with_an_unfinished_transfer_has_no_done_time():
    dispatches = {"t": [(10, 50, 1), (60, 90, 2)]}
    transfers = {"t": [(12, 20, 5), (30, 40, 6), (65, 70, 7),
                       (95, 99, 8)]}                # the last in no dispatch
    got = span_reduce._copies_done(dispatches, transfers,
                                   {5: 45, 6: 80, 8: 100})
    assert got == {1: 80, 2: None}


def test_inflight_idle_is_device_idle_inside_in_flight_spans():
    r = span_reduce.reduce_spans(_profile())
    # device 0 idle in the window: [1100, 1500) and [1600, 2000); in
    # flight (both threads, union): [1050, 1700); intersection
    # [1100, 1500) + [1600, 1700) = 500 ns
    assert r["inflight_idle_s"] == pytest.approx(500e-9)


def test_intersect_of_interval_lists():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45)]
    assert span_reduce._intersect(a, b) == [(5, 10), (20, 25), (28, 30),
                                            (40, 45)]
    assert span_reduce._intersect(a, []) == []


def test_a_program_without_spans_reduces_to_nothing(monkeypatch):
    r = span_reduce.reduce_spans(_profile(program=False))
    assert r["spans"] == {} and r["h2d_s"] == []
    assert r["inflight_idle_s"] is None
    monkeypatch.setattr(span_reduce, "of_record", lambda rec: r)
    for name in SPAN_METRICS:
        assert layout.metric_reader(name)({"trace": {}}) is None


def test_readers_of_the_hand_made_profile(monkeypatch):
    r = span_reduce.reduce_spans(_profile())
    monkeypatch.setattr(span_reduce, "of_record", lambda rec: r)
    read = {name: layout.metric_reader(name)({"trace": {}})
            for name in SPAN_METRICS}
    assert read["h2d_ms"] == pytest.approx(340e-6)
    assert read["h2d_mb"] == pytest.approx(600 / 1e6)
    assert read["d2h_ms"] == pytest.approx(150e-6)
    assert read["build_us"] == pytest.approx(30e-3)
    assert read["inflight_idle_pct"] == pytest.approx(50.0)


def test_an_untraced_run_reads_nothing():
    for name in SPAN_METRICS:
        assert layout.metric_reader(name)({"trace": None}) is None


def test_readers_of_a_cpu_engine_trace(tmp_path, monkeypatch):
    """A small engine stream traced as run.py traces it: the readers
    find the trace under TRACE_DIR and read the staged bytes back."""
    import jax

    from repro.core.constraints import dcg_discount
    from repro.serving import RankRequest, ServingEngine

    m1, m2, K, B = 128, 8, 4, 4
    rng = np.random.default_rng(0)
    reqs = [RankRequest(rid=i, u=rng.uniform(1, 5, m1).astype(np.float32),
                        a=(rng.random((K, m1)) < 0.3).astype(np.float32),
                        b=np.zeros(K, np.float32), m2=m2,
                        lam=np.zeros(K, np.float32),
                        gamma=np.asarray(dcg_discount(m2), np.float32))
            for i in range(12)]
    eng = ServingEngine(max_batch=B, max_wait_ms=1e9)
    eng.warmup(reqs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for r in reqs:
                eng.submit(r)
            eng.drain()
    finally:
        jax.profiler.stop_trace()
    eng.close()
    monkeypatch.setattr(span_reduce, "TRACE_DIR", tmp_path)
    rec = {"trace": {"window_s": 1.0}}
    # u, a, b, gamma, lam of one bucket, 3 batches
    staged = 4 * B * (m1 + K * m1 + K + m2 + K)
    assert layout.metric_reader("h2d_mb")(rec) == pytest.approx(staged / 1e6)
    assert span_reduce.span_of(rec, "engine.dispatch")["n"] == 3
    assert span_reduce.span_of(rec, "engine.build_result")["n"] == 12
    for name in ("d2h_ms", "build_us"):
        assert layout.metric_reader(name)(rec) > 0
    # no TPU runtime and no device plane on the CPU: no transfer done
    # events and nothing to intersect
    assert layout.metric_reader("h2d_ms")(rec) is None
    assert layout.metric_reader("inflight_idle_pct")(rec) is None
