"""requests/scored.py, the requests of a configuration that names no
module of its own, builds the same requests as the harness always has,
and hands the reference the requests' own arrays, bit for bit, in both
configurations (a moved request would move every metric of a cell)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmarks.onchip import deployment, layout, loadgen
from repro.serving import RankRequest

D_COV = {"sasrec": 50, "mind": 256}


def _pool(cfg, n_pool):
    """A pool of the configuration's shapes, from a fixed key."""
    rng = np.random.default_rng(7)
    n, K, m2 = cfg["candidates"], cfg["K"], cfg["m2"]
    return deployment.Pool(
        U=rng.standard_normal((n_pool, n), np.float32),
        X=rng.standard_normal((n_pool, D_COV[cfg["arch"]]), np.float32),
        gamma=(1.0 / np.log2(np.arange(m2) + 2.0)).astype(np.float32),
        topics=(rng.random((K, n)) < 0.15).astype(np.float32),
        b=np.full(K, 0.08, np.float32))


def _requests_as_built_before(setup, pool, mix, users, m1s, rid0=0):
    """The harness's requests as ``deployment.make_requests`` built
    them before the requests/ modules."""
    m2 = setup.dep.m2
    reqs = []
    for i, (user, m1) in enumerate(zip(users, m1s)):
        m1 = int(m1)
        m2r = min(m2, m1)
        reqs.append(RankRequest(
            rid=rid0 + i, u=pool.U[user, :m1], a=pool.topics[:, :m1],
            b=pool.b, m2=m2r, X=pool.X[user], tag=setup.cfg["arch"],
            gamma=pool.gamma[:m2r]))
    return reqs


def _same(x, y):
    return (np.asarray(x).dtype == np.asarray(y).dtype
            and np.array_equal(x, y))


@pytest.mark.parametrize("cell", ["sasrec-feed.steady",
                                  "mind-retrieval.steady"])
def test_scored_requests_are_the_harness_s_own(cell):
    bench = layout.load_benchmark()
    entry = layout.find_cell(bench, cell)
    cfg = layout.load_config(entry["config"])
    mix = layout.load_traffic(entry["traffic"])
    assert "requests" not in cfg
    scored = layout.request_module(cfg)
    assert Path(scored.__file__).name == "scored.py"
    pool = _pool(cfg, int(mix["pool"]))
    setup = NS(cfg=cfg, dep=NS(m2=int(cfg["m2"])))
    plan = loadgen.make_plan(mix, 2**31 + 9, 0.25)
    warm = deployment.warm_m1s(mix)
    for users, m1s, rid0 in ((plan.user, plan.m1, 0),
                             ([0] * len(warm), warm, -len(warm))):
        got = scored.make(setup, pool, mix, users, m1s, rid0=rid0)
        want = _requests_as_built_before(setup, pool, mix, users, m1s,
                                         rid0=rid0)
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            assert (g.rid, g.m2, g.tag, g.lam) == (w.rid, w.m2, w.tag, w.lam)
            for f in ("u", "a", "b", "X", "gamma"):
                assert _same(getattr(g, f), getattr(w, f)), f
        inputs = scored.inputs(setup, pool, got)
        for inp, w in zip(inputs, want):
            assert set(inp) == {"u", "a", "b", "gamma", "X", "m2", "m1"}
            assert (inp["m1"], inp["m2"]) == (w.u.shape[0], w.m2)
            for f in ("u", "a", "b", "X", "gamma"):
                assert _same(inp[f], getattr(w, f)), f


def test_warm_up_reaches_every_bucket_corner_of_the_mix():
    mix = layout.load_traffic("retrieval_steady")
    assert deployment.warm_m1s(mix) == [32768, 65536]
    assert deployment.warm_m1s({"m1": [512, 1024]}) == [512, 1024]
    assert deployment.warm_m1s({"m1": [100, 300]}) == [100, 128, 256, 300]
