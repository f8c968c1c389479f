"""``correct`` on the CPU at a tiny size: a sound run is correct; a run
with its served path broken underneath is not, for each fault the cells
can have; and the control (the reference one precision step down) fails
the configured limits.

The harness's look for a chip is skipped: ``run_cell`` is driven with
the CPU device, the XLA executor and the smoke widths of sasrec."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import copy
import shutil

import jax
import numpy as np
import pytest

from benchmarks.onchip import control, deployment, layout, loadgen, run
from repro.serving import RankResult

CELL = {"name": "sasrec-feed.steady", "chips": 1}
DATA = Path(__file__).parent / "data"


def tiny_config(name="sasrec-feed"):
    cfg = copy.deepcopy(layout.load_config(name))
    # covariates of width 8 over 4,096 offline users: neighbours close
    # enough that the control's three-pass distances swap some of them,
    # as the full-size database's do on the chip
    cfg["widths"] = {"n_items": 1000, "embed_dim": 8, "n_blocks": 2,
                     "n_heads": 1, "seq_len": 12}
    cfg.update(offline_users=4096, offline_candidates=256, candidates=256,
               check_sample=64)
    cfg["serve"]["executor"] = "xla"
    return cfg


def tiny_mix():
    mix = dict(layout.load_traffic("feed_steady"))
    mix.update(rate_rps=200, m1=[100, 256], pool=64, lead_in_s=0.2)
    return mix


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    path = tmp_path_factory.mktemp("offline")
    deployment.build(tiny_config(), cache=path)     # fills the cache once
    return path


@pytest.fixture(scope="module")
def compiles():
    return run.CompileCounter()


def run_tiny(cache, compiles, seed=2**31 + 3):
    return run.run_cell(layout.load_benchmark(), CELL, tiny_config(),
                        tiny_mix(), seed=seed, seconds=0.5, trace=False,
                        devices=jax.devices(), cache=cache,
                        compiles=compiles,
                        peaks=layout.peaks_for("TPU v5 lite"),
                        log=lambda msg: None)


def test_sound_run_is_correct(cache, compiles):
    res = run_tiny(cache, compiles)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 100 and res["failed"] == 0
    assert list(res["checks"]) == ["lam_err", "page_err",
                                   "compliant_mismatch", "shape_errors",
                                   "unanswered", "db_rows_misplaced",
                                   "db_noncompliant"]
    assert set(res["metrics"]) == {"setup_s", "p50_ms", "compliant_share"}
    assert list(res)[-1] == "checks"


def _unpad_fault(monkeypatch, alter):
    import repro.serving.engine as engine_mod

    orig = engine_mod.unpad_result

    def broken(out, i, req):
        return alter(req, *orig(out, i, req))

    monkeypatch.setattr(engine_mod, "unpad_result", broken)


def _answer_altered(req, perm, util, expo, comp):
    perm = perm.copy()
    perm[0] = int(np.argmin(req.u))          # the worst candidate on top
    return perm, util, expo, comp


def _exposure_altered(req, perm, util, expo, comp):
    return perm, util, expo * 1.001, comp


def _page_cut(req, perm, util, expo, comp):
    return perm[:-1], util, expo, comp


@pytest.mark.parametrize("fault,number", [
    (_answer_altered, "page_err"),
    (_exposure_altered, "page_err"),
    (_page_cut, "shape_errors"),
])
def test_an_altered_answer_is_not_correct(cache, compiles, monkeypatch,
                                          fault, number):
    _unpad_fault(monkeypatch, fault)
    res = run_tiny(cache, compiles)
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]


def test_a_wrong_predictor_is_not_correct(cache, compiles, monkeypatch):
    from repro.core.predictors import KNNLambdaPredictor

    orig = KNNLambdaPredictor.fit

    def nine(X, lam, k=10):
        return orig(X, lam, k=k - 1)         # one neighbour short

    monkeypatch.setattr(KNNLambdaPredictor, "fit", staticmethod(nine))
    res = run_tiny(cache, compiles)
    assert not res["correct"]
    c = res["checks"]["lam_err"]
    assert c["value"] > c["limit"]


def _rows_shifted(X, lam):
    return np.roll(np.asarray(X), 1, axis=0), lam


def _duals_unsolved(X, lam):
    return X, np.zeros_like(np.asarray(lam))     # the solver's start


@pytest.mark.parametrize("fault,number", [
    (_rows_shifted, "db_rows_misplaced"),
    (_duals_unsolved, "db_noncompliant"),
])
def test_a_wrong_database_is_not_correct(cache, compiles, monkeypatch,
                                         fault, number):
    """The served path and the reference share the λ-database, so a
    database that is wrong row by row only shows against the offline
    users' own LPs."""
    from repro.core.predictors import KNNLambdaPredictor

    orig = KNNLambdaPredictor.fit

    def broken(X, lam, k=10):
        return orig(*fault(X, lam), k=k)

    monkeypatch.setattr(KNNLambdaPredictor, "fit", staticmethod(broken))
    res = run_tiny(cache, compiles)
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]


def test_an_answer_that_never_comes_is_not_correct(cache, compiles,
                                                    monkeypatch):
    from repro.serving.pipeline import PendingBatch

    orig = PendingBatch.results

    def lossy(self):
        return [r for r in orig(self) if r.rid % 2 == 0]

    monkeypatch.setattr(PendingBatch, "results", lossy)
    res = run_tiny(cache, compiles)
    assert not res["correct"]
    assert res["failed"] > 0
    assert res["checks"]["unanswered"]["value"] == res["failed"]


def _tree_with(tmp_path, files):
    """A copy of the benchmark's directory with ``files`` (relative path
    -> text) added; (its path, the bytes of what was there before)."""
    here = tmp_path / "onchip"
    shutil.copytree(layout.HERE, here,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    for rel, text in files.items():
        (here / rel).write_text(text)
    return here, before


def run_in(here, cfg, cache, compiles):
    return run.run_cell(layout.load_benchmark(), CELL, cfg, tiny_mix(),
                        seed=2**31 + 3, seconds=0.5, trace=False,
                        devices=jax.devices(), cache=cache,
                        compiles=compiles,
                        peaks=layout.peaks_for("TPU v5 lite"),
                        log=lambda msg: None, here=here)


def test_a_configuration_brings_its_own_requests_and_step(
        tmp_path, cache, compiles, monkeypatch):
    """A later configuration (a backbone on the served path) adds its
    requests module and its step's work counts as new files; the
    harness runs it with no edit to a file that was there."""
    from benchmarks.onchip import record

    here, before = _tree_with(tmp_path, {
        "requests/echo.py": (
            "from benchmarks.onchip import layout\n"
            "_scored = layout.request_module({})\n"
            "make, inputs = _scored.make, _scored.inputs\n"),
        "work/tower.py": (
            "def count(shape):\n"
            "    w = shape['widths']['embed_dim']\n"
            "    return {'flops': 2 * shape['B'] * shape['m1'] * w,\n"
            "            'bytes': 4 * w}\n")})
    cfg = {**tiny_config(), "requests": "echo",
           "step": ["knn_rank_audited", "tower"]}
    recs = []
    build = record.build_record

    def keep(*a, **kw):
        recs.append(build(*a, **kw))
        return recs[-1]

    monkeypatch.setattr(record, "build_record", keep)
    res = run_in(here, cfg, cache, compiles)
    assert res["correct"], res["checks"]
    work = recs[0]["work"]
    assert work["step"]["batches"] == work["knn_rank_audited"]["batches"] > 0
    tower = work["step"]["flops"] - work["knn_rank_audited"]["flops"]
    shapes = [b["bucket"] for b in recs[0]["batches"]]
    assert tower == sum(2 * 32 * int(b.split("m1=")[1].split("/")[0]) * 8
                        for b in shapes)
    for p, data in before.items():       # nothing that was there changed
        assert p.read_bytes() == data


def test_the_reference_reads_the_requests_module_s_inputs(
        tmp_path, cache, compiles):
    """A module that plants an offset in the utilities it hands the
    reference makes a sound run incorrect: the reference compares
    against the module's inputs, not the request's arrays."""
    here, _ = _tree_with(tmp_path, {
        "requests/requests_offset_u.py":
            (DATA / "requests_offset_u.py").read_text()})
    cfg = {**tiny_config(), "requests": "requests_offset_u"}
    res = run_in(here, cfg, cache, compiles)
    assert not res["correct"]
    c = res["checks"]["page_err"]
    assert c["value"] > c["limit"]
    assert res["checks"]["lam_err"]["value"] <= res["checks"]["lam_err"][
        "limit"]


def test_the_control_fails_the_limits(cache, compiles):
    cfg, mix = tiny_config(), tiny_mix()
    reqmod = layout.request_module(cfg)
    setup = deployment.build(cfg, cache=cache)
    pool = deployment.score_pool(setup, int(mix["pool"]))
    engine, metrics, _ = run.start_engine(setup, pool, mix, reqmod)
    plan = loadgen.make_plan(mix, 5, 0.5)
    reqs = reqmod.make(setup, pool, mix, plan.user, plan.m1)
    drive, _ = run.serve_plan(engine, metrics, reqs, plan, budget_s=0.05,
                              trace=False, compiles=compiles)
    engine.close()
    pairs = [(q, a) for q, a in zip(reqs[plan.window],
                                    drive.answers[plan.window])
             if isinstance(a, RankResult)]
    q = reqmod.inputs(setup, pool, [p[0] for p in pairs])
    a = [p[1] for p in pairs]
    limits = layout.load_config("sasrec-feed")["limits"]
    program = control.readings(cfg, q, a, setup.X_db, setup.lam_db,
                               control=False)
    ok, _ = reference_judge(program["numbers"], limits)
    assert ok, program
    ctl = control.readings(cfg, q, a, setup.X_db, setup.lam_db,
                           control=True)
    ok, checks = reference_judge(ctl["numbers"], limits)
    assert not ok, checks


def reference_judge(numbers, limits):
    from benchmarks.onchip import reference

    return reference.judge(numbers, limits)
