"""trace_reduce on a trace recorded on a TPU v5e (0.3 s of
sasrec-feed.steady traffic at 400 req/s), and on a hand-made profile."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import gzip
from types import SimpleNamespace as NS

import pytest

from benchmarks.onchip import trace_reduce

DATA = Path(__file__).parent / "data"
KERNELS = ["knn_rank_audited"]


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    out.write_bytes(gzip.decompress(
        (DATA / "sasrec_feed_0.3s.xplane.pb.gz").read_bytes()))
    return trace_reduce.reduce_trace(out, KERNELS)


def test_chip_trace_window_and_steps(chip_trace):
    r = chip_trace
    assert r["window_s"] == pytest.approx(0.3007, abs=1e-3)
    assert r["devices"] == 1
    assert r["modules"] == 52                  # one executable a batch
    assert r["kernel_n"] == {"knn_rank_audited": 52}
    assert 0 < r["kernel_s"]["knn_rank_audited"] <= r["module_s"]
    assert r["module_s"] <= r["busy_s"] * 1.001
    assert 0 < r["busy_s"] < r["window_s"]


def test_chip_trace_breakdown(chip_trace):
    top = trace_reduce.top_ops(chip_trace)
    assert 0 < len(top) <= 10
    assert top[0][0].startswith("%knn_rank_audited_pallas")
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = chip_trace["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(name.startswith(("bench.", "no client span"))
               for name, _ in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)
    idle = chip_trace["window_s"] - chip_trace["busy_s"]
    assert sum(s for _, s in gaps) <= idle + 1e-9


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def _profile():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 1000, 1000),
        _ev("bench.submit", 1000, 100),
        _ev("bench.wait", 1500, 400),
        _ev("other", 1100, 10)]), NS(name="worker", events=[
        _ev("np.asarray(jax.Array)", 1300, 2_000_000),
        _ev("short", 1200, 500)])])

    def dev(i, ops, mods):
        return NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=mods)])

    d0 = dev(0, [_ev("%knn_rank_audited_pallas.1 = f32[32,64]{1,0} "
                     "custom-call(f32[32,50] %x)", 900, 300),
                 _ev("%all-reduce.2 = f32[8]{0} all-reduce(f32[8] %y)",
                     1150, 100),
                 _ev("%copy.3 = f32[8]{0} copy(f32[8] "
                     "%knn_rank_audited_pallas.1)", 1700, 100)],
             [_ev("jit_fn(1)", 900, 900)])
    d1 = dev(1, [_ev("%fusion.1 = f32[8]{0} fusion(f32[8] %z)", 1400, 200)],
             [_ev("jit_fn(1)", 1400, 200)])
    return NS(planes=[host, d0, d1, NS(name="Task Environment", lines=[])])


def test_hand_made_profile():
    r = trace_reduce.reduce_profile(_profile(), KERNELS)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["devices"] == 2
    # device 0 busy [1000, 1250) + [1700, 1800) = 350 ns, device 1 200 ns
    assert r["busy_s"] == pytest.approx(275e-9)
    # the kernel is counted once, clipped to the window: 200 ns on one
    # device, averaged over two
    assert r["kernel_s"] == {"knn_rank_audited": pytest.approx(100e-9)}
    assert r["kernel_n"] == {"knn_rank_audited": 0.5}
    assert r["modules"] == 1.0
    # device 0's gaps: [1250, 1700) in bench.wait, [1800, 2000) in it too
    # and the long host event on another thread, not the short one
    assert r["idle_gaps"] == [
        ["bench.wait > np.asarray(jax.Array)", pytest.approx(450e-9)],
        ["bench.wait > np.asarray(jax.Array)", pytest.approx(200e-9)]]


def test_collective_time_of_the_hand_made_profile():
    from benchmarks.onchip import layout

    p = _profile()
    # device 1 also runs the halves of an asynchronous all-gather and a
    # fusion named after one; a copy that merely reads a collective's
    # output is no collective
    p.planes[2].lines[0].events += [
        _ev("%all-gather-start.1 = (f32[32,64]{1,0}, f32[128,64]{1,0}) "
            "all-gather-start(f32[32,64]{1,0} %v)", 1100, 40),
        _ev("%all-gather-done.1 = f32[128,64]{1,0} "
            "all-gather-done((f32[32,64]{1,0}, f32[128,64]{1,0}) "
            "%all-gather-start.1)", 1200, 60),
        _ev("%all-gather-fusion.2 = f32[8]{0} fusion(f32[8] %w)", 1600, 20),
        _ev("%copy.9 = f32[128,64]{1,0} copy(f32[128,64]{1,0} "
            "%all-gather-done.1)", 1700, 30)]
    r = trace_reduce.reduce_profile(p, KERNELS)
    # all-reduce 100 ns on device 0, 40 + 60 + 20 ns on device 1
    assert r["collective_s"] == pytest.approx((100e-9 + 120e-9) / 2)
    read = layout.metric_reader("collective_us")
    assert read({"trace": r}) == pytest.approx(1e6 * 110e-9 / 1.0)


def test_a_trace_without_collectives_has_no_collective_time(chip_trace):
    from benchmarks.onchip import layout

    assert chip_trace["collective_s"] == 0.0
    assert layout.metric_reader("collective_us")({"trace": chip_trace}) is None


def test_a_trace_without_the_window_is_refused():
    p = _profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(p, KERNELS)
