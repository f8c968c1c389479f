"""The client's arithmetic: the request plan, the window's record, and
the end-to-end metrics read from it."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import numpy as np
import pytest

from benchmarks.onchip import layout, loadgen, record
from repro.serving import Bucket, RankResult, Shed

MIX = {"name": "t", "arrivals": "poisson", "rate_rps": 1000.0,
       "m1": [512, 1024], "predictor": "knn", "pool": 64, "lead_in_s": 0.5}


def test_plan_offers_the_same_work_for_every_seed():
    a = loadgen.make_plan(MIX, 1, 2.0)
    b = loadgen.make_plan(MIX, 2**33 + 7, 2.0)
    assert len(a.due) == len(b.due) == 500 + 2000
    assert a.n_lead == b.n_lead == 500
    assert sorted(a.m1) == sorted(b.m1)          # same sizes, other order
    assert not np.array_equal(a.m1, b.m1)
    win = slice(a.n_lead, None)
    assert sorted(a.user[win]) == sorted(b.user[win])   # same users too
    # and the same (user, m1) pairs, so the same pages to rank
    assert (sorted(zip(a.user[win], a.m1[win]))
            == sorted(zip(b.user[win], b.m1[win])))
    assert np.bincount(a.user[win], minlength=64).min() >= 2000 // 64
    assert a.m1.min() >= 512 and a.m1.max() <= 1024
    for p in (a, b):
        assert np.all(np.diff(p.due) >= 0)
        assert p.due[:p.n_lead].max() < 0.5 <= p.due[p.n_lead:].min()
        assert p.due.max() < 2.5
    c = loadgen.make_plan(MIX, 1, 2.0)
    assert np.array_equal(a.due, c.due) and np.array_equal(a.user, c.user)


def test_an_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError):
        loadgen.make_plan({**MIX, "arrivals": "onoff"}, 3, 1.0)


def _result(rid, *, compliant=True, rung=0, wait=1.0):
    return RankResult(rid=rid, perm=np.arange(2), utility=1.0,
                      exposure=np.ones(1), compliant=compliant,
                      bucket="t/m1=128/m2=8/K=4/B=4", latency_ms=0.0,
                      wait_ms=wait, rung=rung, lam=np.zeros(1))


def _record(lat_ms, answers, *, seconds=1.0, budget_ms=50.0):
    n = len(answers)
    plan = loadgen.Plan(due=np.linspace(0.5, 1.4, n + 1)[:n + 1],
                        user=np.zeros(n + 1, int),
                        m1=np.full(n + 1, 512), n_lead=1, lead_s=0.5,
                        window_s=seconds)
    t_due = 100.0 + plan.due
    t_recv = t_due.copy()
    t_recv[1:] += np.asarray(lat_ms) / 1e3
    drive = loadgen.Drive(t0=100.0, t_due=t_due, t_sub=t_due + 1e-4,
                          t_recv=t_recv, queue_lag=np.zeros(n + 1),
                          answers=[_result(-1)] + list(answers), errors=[])
    metrics = record.RecordingMetrics()
    return record.build_record(
        drive, plan, metrics, t_open=100.5, t_close=101.5,
        budget_ms=budget_ms, setup_s=3.0, chips=1, max_batch=4,
        predictor_of=lambda b: "knn", shape_of=lambda b, p: {},
        routes={"knn": "knn_rank_audited"}, counters={},
        peaks=layout.peaks_for("TPU v5 lite"),
        padding={"real_flops": 1, "padded_flops": 2})


def _read(name, rec):
    return layout.metric_reader(name)(rec)


def test_sheds_and_failures_enter_the_tail_at_the_window_length():
    # 200 requests: 97 answered in 10 ms, 2 shed, 101 never answered
    answers = [_result(i) for i in range(97)]
    answers += [Shed(rid=97, bucket="b", predicted_ms=80.0,
                     budget_ms=50.0),
                Shed(rid=98, bucket="b", predicted_ms=80.0,
                     budget_ms=50.0)] + [None] * 101
    rec = _record([10.0] * 200, answers)
    assert (rec["attempted"], rec["served"], rec["shed"],
            rec["failed"]) == (200, 97, 2, 101)
    lat = np.array([10.0] * 97 + [1000.0] * 103)
    assert rec["lat_ms"] == pytest.approx(lat)
    # more than half never answered in time: the median is the window
    assert _read("p50_ms", rec) == pytest.approx(np.percentile(lat, 50))
    assert _read("p50_ms", rec) == 1000.0
    assert _read("gen_lag_p99_ms", rec) == pytest.approx(0.1)


def test_goodput_and_compliance_count_over_attempted():
    answers = [_result(i, compliant=i % 2 == 0) for i in range(90)]
    answers += [Shed(rid=90 + i, bucket="b", predicted_ms=80.0,
                     budget_ms=50.0) for i in range(10)]
    lat = [10.0] * 80 + [60.0] * 10 + [0.0] * 10    # 10 answered late
    rec = _record(lat, answers, seconds=2.0)
    assert _read("goodput_rps", rec) == pytest.approx(80 / 2.0)
    assert _read("compliant_share", rec) == pytest.approx(100.0 * 45 / 100)
    assert _read("setup_s", rec) == 3.0
    assert _read("padding_ratio", rec) == 2.0


def test_queue_wait_tail():
    answers = [_result(i, wait=float(i)) for i in range(100)]
    rec = _record([5.0] * 100, answers)
    assert _read("queue_wait_p99_ms", rec) == pytest.approx(
        np.percentile(np.arange(100.0), 99))


def test_batches_and_work_are_cut_to_the_window():
    metrics = record.RecordingMetrics()
    bk = Bucket(tag="sasrec", m1=512, m2=64, K=8, batch=32)
    fill = {"real_cells": 1, "padded_cells": 1}
    for t, rows in ((100.2, 3), (100.6, 32), (101.0, 16), (101.7, 8)):
        metrics.on_dispatch(bk, rows, "capacity", fill, assembly_ms=0.5,
                            dispatch_ms=0.1, depth=0, t_now=t)
        metrics.on_retire(2.0, t + 0.002)
    drive = loadgen.Drive(t0=100.0, t_due=np.array([100.6]),
                          t_sub=np.array([100.6]),
                          t_recv=np.array([100.61]),
                          queue_lag=np.zeros(1), answers=[_result(0)],
                          errors=[])
    plan = loadgen.Plan(due=np.array([0.6]), user=np.zeros(1, int),
                        m1=np.array([512]), n_lead=0, lead_s=0.5,
                        window_s=1.0)
    counters = {"step": lambda s: {"flops": 2e9, "bytes": 1e6},
                "knn_rank_audited": lambda s: {"flops": 1e9,
                                               "bytes": 819e6}}
    rec = record.build_record(
        drive, plan, metrics, t_open=100.5, t_close=101.5,
        budget_ms=50.0, setup_s=1.0, chips=1, max_batch=32,
        predictor_of=lambda b: "knn", shape_of=lambda b, p: {},
        routes={"knn": "knn_rank_audited"}, counters=counters,
        peaks=layout.peaks_for("TPU v5 lite"),
        padding={"real_flops": 1, "padded_flops": 1})
    assert [b["rows"] for b in rec["batches"]] == [32, 16]
    assert rec["work"]["knn_rank_audited"]["batches"] == 2
    # bytes bound: 819 MB at 819 GB/s is 1 ms a batch
    assert rec["work"]["knn_rank_audited"]["least_s"] == pytest.approx(2e-3)
    assert _read("launch_to_done_ms", rec) == pytest.approx(2.0)
    assert _read("assembly_ms", rec) == pytest.approx(0.5)
    rec["trace"] = {"window_s": 1.0, "devices": 1, "busy_s": 0.25,
                    "kernel_s": {"knn_rank_audited": 0.004},
                    "kernel_n": {"knn_rank_audited": 2.0},
                    "modules": 2.0, "module_s": 0.005}
    assert _read("device_idle", rec) == pytest.approx(75.0)
    assert _read("kernel_us", rec) == pytest.approx(2000.0)
    assert _read("knn_rank_audited_roofline", rec) == pytest.approx(50.0)
    # step: 2 GFLOP at 197 TFLOP/s over 2.5 ms a step
    assert _read("step_mfu", rec) == pytest.approx(
        100.0 * (2e9 / 197e12) / 2.5e-3)
