"""Find a cell's knee: the highest offered rate the served path sustains.

    python3 benchmarks/onchip/sweep.py --workload sasrec-feed.steady \
        --rates 1000,2000,4000 --seconds 5 --seed 3

One process: the cell's set-up once, then for each rate of the ladder
a fresh engine (warmed up on the cell's buckets) driven open-loop by
the cell's traffic mix at that rate, for a lead-in and ``--seconds`` of
window. Per rate it prints one JSON line: the p50 and p99 of latency
from scheduled arrival (sheds and failures entering at the window's
length), the share of requests answered within the budget, the shed
share, and the client's queue lag (lateness already there
when a request's pacing began) at the window's end and its p99. The knee
is the highest rate up to which every rate of the ladder had at least
99 % of requests answered within the budget and a queue lag that does
not grow.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.onchip import layout, run  # noqa: E402


def rate_point(setup, pool, mix, rate: float, *, seed: int, seconds: float,
               compiles, reqmod) -> dict:
    """One rate of the ladder, served with the requests module
    ``reqmod``."""
    import numpy as np

    from benchmarks.onchip import loadgen
    from repro.serving import RankResult, Shed

    engine, metrics, _ = run.start_engine(setup, pool, mix, reqmod)
    plan = loadgen.make_plan(mix, seed, seconds, rate=rate)
    reqs = reqmod.make(setup, pool, mix, plan.user, plan.m1)
    budget_ms = setup.cfg["serve"]["budget_ms"]
    drive, _ = run.serve_plan(engine, metrics, reqs, plan,
                              budget_s=budget_ms / 1e3, trace=False,
                              compiles=compiles)
    engine.close()
    win = plan.window
    answers = drive.answers[win]
    served = np.array([isinstance(a, RankResult) for a in answers])
    shed = np.array([isinstance(a, Shed) for a in answers])
    lat = np.where(served, (drive.t_recv[win] - drive.t_due[win]) * 1e3,
                   plan.window_s * 1e3)
    q = drive.queue_lag[win] * 1e3
    return {"rate_rps": rate, "attempted": len(answers),
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "within_budget": float(np.mean(served & (lat <= budget_ms))),
            "shed_share": float(shed.mean()),
            "queue_lag_last_ms": float(q[-1]),
            "queue_lag_p99_ms": float(np.percentile(q, 99)),
            "batches": len(metrics.exec_ms),
            "errors": len(drive.errors)}


def knee(points: list[dict], *, max_lag_ms: float = 20.0) -> float | None:
    """The highest rate of the ladder up to which every rate had at
    least 99 % of requests answered within the budget and a client
    queue lag under ``max_lag_ms`` at the window's end (not growing)."""
    best = None
    for p in sorted(points, key=lambda p: p["rate_rps"]):
        if p["within_budget"] < 0.99 or p["queue_lag_last_ms"] >= max_lag_ms:
            break
        best = p["rate_rps"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, req/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    opts = ap.parse_args(argv)
    bench = layout.load_benchmark()
    cell = layout.find_cell(bench, opts.workload)
    cfg = layout.load_config(cell["config"])
    mix = layout.load_traffic(cell["traffic"])
    run.configure_jax(cfg)
    devices = run.tpu_devices(int(cell["chips"]))
    log = run.Log(f"{devices[0].platform} {devices[0].device_kind} "
                  f"x{len(devices)}")
    compiles = run.CompileCounter()

    from benchmarks.onchip import deployment

    reqmod = layout.request_module(cfg)
    setup = deployment.build(cfg)
    pool = deployment.score_pool(setup, int(mix["pool"]))
    log(f"setup {time.perf_counter() - T_PROC:.1f} s")
    points = []
    for rate in (float(r) for r in opts.rates.split(",")):
        p = rate_point(setup, pool, mix, rate, seed=opts.seed,
                       seconds=opts.seconds, compiles=compiles,
                       reqmod=reqmod)
        points.append(p)
        print(json.dumps({"workload": opts.workload, **p}), flush=True)
    print(json.dumps({"workload": opts.workload, "knee_rps": knee(points),
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
