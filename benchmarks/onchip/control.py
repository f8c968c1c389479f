"""The control of the correctness comparison, and the readings its
limits are set from.

The control is the plain reference put in the program's place and
computed one step below the precision the configuration states
(float32 with matmuls at ``highest``): every dot product in ``high``
precision, three bf16 passes (x_hi y_hi + x_hi y_lo + x_lo y_hi,
accumulated in float32), which is what a later change might be tempted
to switch the distance or score products to. The emulation is explicit,
so it computes the same on the chip and on a CPU.

    python3 benchmarks/onchip/control.py --workload sasrec-feed.steady \
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 3

One process: the cell's set-up once; then per seed a short window at
the cell's own load through the engine, its answers compared with the
reference as a run does (the program's reading), and for the control
seeds the control's answers to the same sample compared the same way
(the control's reading). One JSON line per seed and side.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from benchmarks.onchip import layout, reference  # noqa: E402


@dataclass
class Answer:
    """A control answer, shaped like the program's RankResult."""

    perm: np.ndarray
    utility: float
    exposure: np.ndarray
    compliant: bool
    lam: np.ndarray
    rung: int


def _dot3(x, y):
    """x @ y in three bf16 passes with float32 accumulation."""
    import jax.numpy as jnp

    bf = jnp.bfloat16
    xh = x.astype(bf)
    yh = y.astype(bf)
    xl = (x - xh.astype(jnp.float32)).astype(bf)
    yl = (y - yh.astype(jnp.float32)).astype(bf)

    def d(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)

    return d(xh, yh) + d(xh, yl) + d(xl, yh)


def _control_fn(k: int, m2: int, eps: float, tol: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(X, X_db, y2, lam_db, u, a, b, gamma):
        # KNN λ̂
        x2 = jnp.sum(X * X, axis=1, keepdims=True)
        d2 = jnp.maximum(x2 - 2.0 * _dot3(X, X_db.T) + y2[None, :], 0.0)
        neg, idx = jax.lax.top_k(-d2, k)
        dk = -neg
        scale = x2 + y2[idx]
        exact = dk <= 1e-6 * scale
        w = jnp.where(jnp.any(exact, axis=1, keepdims=True),
                      exact.astype(jnp.float32),
                      1.0 / jnp.sqrt(jnp.maximum(dk, 1e-30)))
        w = w / jnp.sum(w, axis=1, keepdims=True)
        lam = jnp.einsum("bk,bkj->bj", w, lam_db[idx])
        # scores, top-m2 and the audit, every product in three passes
        s = u + (1.0 + eps) * _dot3(lam[:, None, :], a)[:, 0, :]
        _, perm = jax.lax.top_k(s, m2)
        u_sel = jnp.take_along_axis(u, perm, axis=1)
        a_sel = jnp.take_along_axis(a, perm[:, None, :], axis=2)
        util = _dot3(u_sel[:, None, :], gamma[:, :, None])[:, 0, 0]
        expo = _dot3(a_sel, gamma[:, :, None])[:, :, 0]
        comp = jnp.all(expo >= b - tol, axis=1)
        return perm, util, expo, comp, lam

    return fn


def control_answers(inputs, db_X, db_lam, *, k: int, eps: float,
                    tol: float, block: int = 32) -> list[Answer]:
    """The control's answer to each request of plain ``inputs`` (as
    ``reference.compare`` takes them), by the KNN predictor. Requests
    are padded to a common candidate count (utilities -1e30, attributes
    0), ``block`` at a time."""
    import jax.numpy as jnp

    X_db = jnp.asarray(db_X, jnp.float32)
    lam_db = jnp.asarray(db_lam, jnp.float32)
    y2 = jnp.sum(X_db * X_db, axis=1)
    K = len(inputs[0]["b"])
    m1p = 1 << (max(r["m1"] for r in inputs) - 1).bit_length()
    m2 = max(r["m2"] for r in inputs)
    fn = _control_fn(k, m2, eps, tol)
    out = []
    for s in range(0, len(inputs), block):
        rb = inputs[s:s + block]
        n = len(rb)
        u = np.full((block, m1p), -1e30, np.float32)
        a = np.zeros((block, K, m1p), np.float32)
        gamma = np.zeros((block, m2), np.float32)
        b = np.zeros((block, K), np.float32)
        X = np.zeros((block, X_db.shape[1]), np.float32)
        for i, r in enumerate(rb):
            m1 = r["m1"]
            u[i, :m1] = r["u"]
            a[i, :, :m1] = r["a"]
            gamma[i, :r["m2"]] = r["gamma"]
            b[i] = r["b"]
            X[i] = r["X"]
        perm, util, expo, comp, lam = (np.asarray(v) for v in fn(
            X, X_db, y2, lam_db, u, a, b, gamma))
        for i, r in enumerate(rb[:n]):
            out.append(Answer(perm=perm[i, :r["m2"]],
                              utility=float(util[i]), exposure=expo[i],
                              compliant=bool(comp[i]), lam=lam[i], rung=0))
    return out


def readings(cfg, inputs, answers, db_X, db_lam, *, control: bool) -> dict:
    """The comparison's numbers for the program's answers to requests of
    plain ``inputs`` or, with ``control``, for the control's answers to
    the same requests."""
    if control:
        answers = control_answers(
            inputs, db_X, db_lam,
            k=int(cfg["knn_k"]), eps=float(cfg["eps"]),
            tol=float(cfg["audit_tol"]))
    db = reference.LamDB(db_X, db_lam)
    return reference.compare(inputs, answers, db, k=int(cfg["knn_k"]),
                             eps=float(cfg["eps"]),
                             tol=float(cfg["audit_tol"]),
                             tie_rtol=float(cfg["knn_tie_rtol"]))


def main(argv=None) -> int:
    from benchmarks.onchip import deployment, loadgen, run
    from repro.serving import RankResult

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    opts = ap.parse_args(argv)
    bench = layout.load_benchmark()
    cell = layout.find_cell(bench, opts.workload)
    cfg = layout.load_config(cell["config"])
    mix = layout.load_traffic(cell["traffic"])
    run.configure_jax(cfg)
    devices = run.tpu_devices(int(cell["chips"]))
    compiles = run.CompileCounter()
    reqmod = layout.request_module(cfg)
    setup = deployment.build(cfg)
    pool = deployment.score_pool(setup, int(mix["pool"]))
    control_seeds = {int(s) for s in opts.control_seeds.split(",") if s}
    for seed in (int(s) for s in opts.seeds.split(",")):
        engine, metrics, _ = run.start_engine(setup, pool, mix, reqmod)
        plan = loadgen.make_plan(mix, seed, opts.seconds)
        requests = reqmod.make(setup, pool, mix, plan.user, plan.m1)
        drive, _ = run.serve_plan(
            engine, metrics, requests, plan,
            budget_s=cfg["serve"]["budget_ms"] / 1e3, trace=False,
            compiles=compiles)
        engine.close()
        win = plan.window
        answers = drive.answers[win]
        served_idx = [i for i, a in enumerate(answers)
                      if isinstance(a, RankResult)]
        rng = np.random.default_rng([seed, 1])
        sample = run.check_sample(rng, plan.m1[win], served_idx,
                                  int(cfg["check_sample"]))
        window = requests[win]
        inputs = reqmod.inputs(setup, pool, [window[i] for i in sample])
        sampled = [answers[i] for i in sample]
        sides = [False] + ([True] if seed in control_seeds else [])
        for control in sides:
            t = time.perf_counter()
            r = readings(cfg, inputs, sampled, setup.X_db, setup.lam_db,
                         control=control)
            print(json.dumps({
                "workload": opts.workload, "seed": seed,
                "side": "control" if control else "program",
                "attempted": len(window), "served": len(served_idx),
                **r, "seconds": time.perf_counter() - t,
                "device": {"platform": devices[0].platform,
                           "kind": devices[0].device_kind,
                           "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
