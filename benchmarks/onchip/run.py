"""One run of one benchmark cell on the chip.

    python3 benchmarks/onchip/run.py --workload sasrec-feed.steady \
        --seed 7 --seconds 20 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``).
One process: set-up (backbone weights, the offline λ-database from its
cache, the request pool, engine warm-up of the cell's buckets), an
unmeasured lead-in, the measured window of ``--seconds`` driven
open-loop through ``ServingEngine.submit / poll / drain``, then, off
the clock, the comparison of a seeded sample of the window's answers
with the plain reference (``reference.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and prints its per-layer
metrics instead. The last line of standard output is the result, as
JSON; the numbers compared with the reference, each beside its limit,
are the last lines of standard error and the result's last key.

Exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.onchip import layout  # noqa: E402

JAX_CACHE = HERE / ".cache" / "jax"
TRACE_DIR = HERE / ".cache" / "trace"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Log:
    """stderr lines, each naming the device it ran on."""

    def __init__(self, tag: str = ""):
        self.tag = tag

    def __call__(self, msg: str) -> None:
        print(f"[{self.tag}] {msg}", file=sys.stderr, flush=True)


def configure_jax(cfg: dict) -> None:
    """The compile cache at its fixed in-checkout path (whatever the
    environment says: the path is part of the cache's key), every
    program cached, and the matmul precision the configuration states."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    prec = cfg["serve"].get("matmul_precision")
    if prec:
        jax.config.update("jax_default_matmul_precision", prec)


def tpu_devices(chips: int) -> list:
    """The chips this cell needs, or exit non-zero."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run: no TPU (JAX found {devs[0].platform})")
    if len(devs) < chips:
        raise SystemExit(f"run: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


class CompileCounter:
    """XLA backend compiles in this process (JAX monitoring events)."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def kernel_names(here: Path = HERE) -> list[str]:
    """The kernels the trace is searched for: every work/ count but the
    whole step's."""
    return sorted(p.stem for p in (Path(here) / "work").glob("*.py")
                  if p.stem != "step")


def device_dict(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def check_sample(rng, m1s, served_idx, n: int):
    """A seeded sample of the answered window requests (``m1s`` each
    window request's candidate count, from the plan), with the one of
    most candidates always in it."""
    import numpy as np

    served_idx = np.asarray(served_idx)
    if served_idx.size <= n:
        return served_idx
    pick = rng.choice(served_idx, n - 1, replace=False)
    longest = max(served_idx, key=lambda i: m1s[i])
    return np.unique(np.append(pick, longest))


def shape_errors(cfg: dict, m1s, answers, served_idx) -> int:
    """Served answers whose page breaks their request's geometry: m1
    from the plan, m2 and K from the configuration."""
    from benchmarks.onchip import reference

    m2, K = int(cfg["m2"]), int(cfg["K"])
    return sum(1 for i in served_idx
               if not reference.shape_ok(int(m1s[i]), min(m2, int(m1s[i])),
                                         K, answers[i]))


def start_engine(setup, pool, mix, reqmod):
    """The configuration's engine, warmed up on the mix's buckets (and
    their degradation rungs) with the requests module ``reqmod``:
    (engine, metrics, warmup report)."""
    from benchmarks.onchip import deployment, record

    metrics = record.RecordingMetrics()
    engine = deployment.make_engine(setup, metrics)
    m1s = deployment.warm_m1s(mix)
    t = time.perf_counter()
    warm = engine.warmup(reqmod.make(setup, pool, mix, [0] * len(m1s), m1s,
                                     rid0=-len(m1s)))
    setup.seconds["warmup"] = time.perf_counter() - t
    return engine, metrics, warm


def serve_plan(engine, metrics, requests, plan, *, budget_s: float,
               trace: bool, compiles):
    """Drive the plan open-loop (traced when ``trace``); (drive, marks)
    with marks["open"/"close"] = (clock, real flops, padded flops,
    compiles) as the window opened and closed."""
    import jax

    from benchmarks.onchip import loadgen

    marks = {"gc_pauses": []}
    gc_start = []

    def on_gc(phase, info):
        if phase == "start":
            gc_start.append(time.perf_counter())
        elif gc_start:
            marks["gc_pauses"].append((info["generation"],
                                       time.perf_counter() - gc_start.pop()))

    def on_window(opening: bool) -> None:
        marks["open" if opening else "close"] = (
            time.perf_counter(), metrics.real_flops, metrics.padded_flops,
            compiles.count)

    # the set-up heap (pool, request plan, engine) is frozen out of the
    # cyclic collector, so a collection scans only what serving makes
    gc.collect()
    gc.freeze()
    gc.callbacks.append(on_gc)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        drive = loadgen.drive_open_loop(engine, requests, plan,
                                        budget_s=budget_s, trace=trace,
                                        on_window=on_window)
    finally:
        if trace:
            jax.profiler.stop_trace()
        gc.callbacks.remove(on_gc)
        gc.unfreeze()
    return drive, marks


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, devices, t_proc: float = T_PROC,
             log=None, compiles: CompileCounter | None = None,
             peaks: dict | None = None, here: Path = HERE,
             cache: Path | None = None) -> dict:
    """Set up, warm up, drive the lead-in and the window, compare; the
    result dict (the contract's last line). ``cache`` moves the offline
    λ-database cache (tests)."""
    import numpy as np

    from benchmarks.onchip import deployment, loadgen, record, reference
    from benchmarks.onchip import trace_reduce
    from repro.serving import RankResult

    log = log or Log()
    compiles = compiles or CompileCounter()
    chips = int(cell["chips"])
    if peaks is None:
        peaks = layout.peaks_for(devices[0].device_kind, here)
    srv = cfg["serve"]

    reqmod = layout.request_module(cfg, here)
    step_count = layout.step_counter(cfg, here)
    setup = deployment.build(cfg, cache=cache or deployment.CACHE)
    pool = deployment.score_pool(setup, int(mix["pool"]))
    engine, metrics, warm = start_engine(setup, pool, mix, reqmod)
    plan = loadgen.make_plan(mix, seed, seconds)
    requests = reqmod.make(setup, pool, mix, plan.user, plan.m1)
    log(f"setup: offline cache {'hit' if setup.offline_hit else 'miss'} "
        f"seconds {json.dumps(setup.seconds)} buckets {warm['buckets']} "
        f"requests {len(requests)} (lead-in {plan.n_lead}) rate "
        f"{mix['rate_rps']} req/s")
    drive, marks = serve_plan(engine, metrics, requests, plan,
                              budget_s=srv["budget_ms"] / 1e3, trace=trace,
                              compiles=compiles)
    setup_s = float(drive.t_due[0] - t_proc)
    device = device_dict(devices, chips)
    engine.close()
    t_open, rf0, pf0, c0 = marks["open"]
    t_close, rf1, pf1, c1 = marks["close"]
    red = None
    if trace:
        red = trace_reduce.reduce_trace(TRACE_DIR, kernel_names(here))
    K_pred = int(setup.lam_db.shape[1])
    n_db, d_cov = setup.X_db.shape

    def predictor_of(bucket):
        return "knn"

    def shape_of(bucket, pred):
        return record.batch_shape(bucket, predictor=pred, d=int(d_cov),
                                  n_db=int(n_db),
                                  k=int(cfg["knn_k"]),
                                  k_pred=K_pred, widths=cfg["widths"])

    routes = layout.work_module("step", here).ROUTES
    counters = {name: layout.work_counter(name, here)
                for name in kernel_names(here)}
    counters["step"] = step_count
    rec = record.build_record(
        drive, plan, metrics, t_open=t_open, t_close=t_close,
        budget_ms=srv["budget_ms"], setup_s=setup_s, chips=chips,
        max_batch=srv["max_batch"], predictor_of=predictor_of,
        shape_of=shape_of, routes=routes, counters=counters,
        peaks=peaks, padding={"real_flops": rf1 - rf0,
                              "padded_flops": pf1 - pf0},
        trace=red)
    pauses = marks["gc_pauses"]
    q_lag = drive.queue_lag[plan.window] * 1e3
    log(f"window: attempted {rec['attempted']} served {rec['served']} shed "
        f"{rec['shed']} failed {rec['failed']} degraded {rec['degraded']} "
        f"batches {len(rec['batches'])} compiles_in_window {c1 - c0} "
        f"latency_ms p99 {np.percentile(rec['lat_ms'], 99):.3f} max "
        f"{rec['lat_ms'].max():.3f} "
        f"errors {drive.errors[:3]} queue_lag_ms p99 "
        f"{np.percentile(q_lag, 99):.3f} max {q_lag.max():.3f} at "
        f"{plan.due[plan.window][int(q_lag.argmax())] - plan.lead_s:.2f} s "
        f"launch_to_done_ms max {max(metrics.exec_ms, default=0.0):.2f} "
        f"gc_pauses {len(pauses)} "
        f"max_s {max((p for _, p in pauses), default=0.0):.4f} gen2 "
        f"{sum(1 for g, _ in pauses if g == 2)}")

    # off the clock: free the engine; take the sampled requests' inputs
    # and rebuild a sample of the offline users' LPs on the backbone,
    # free it, then compare
    win = plan.window
    answers = drive.answers[win]
    m1s = plan.m1[win]
    reqs = requests[win]
    del engine, metrics, drive, requests
    gc.collect()
    t = time.perf_counter()
    served_idx = [i for i, a in enumerate(answers)
                  if isinstance(a, RankResult)]
    rng = np.random.default_rng([seed, 1])
    sample = check_sample(rng, m1s, served_idx, int(cfg["check_sample"]))
    inputs = reqmod.inputs(setup, pool, [reqs[i] for i in sample])
    db_rows, db_U, db_X = deployment.offline_rows(
        setup, np.random.default_rng([seed, 2]), int(cfg["db_sample"]))
    gamma_off, topics_off, b_off = deployment.offline_surface(cfg)
    setup.dep = None
    del reqs
    gc.collect()
    db = reference.LamDB(setup.X_db, setup.lam_db)
    lim = cfg["limits"]
    cmp = reference.compare(
        inputs, [answers[i] for i in sample],
        db, k=int(cfg["knn_k"]), eps=float(cfg["eps"]),
        tol=float(cfg["audit_tol"]), tie_rtol=float(cfg["knn_tie_rtol"]),
        unanswered=rec["failed"])
    cmp["numbers"]["shape_errors"] = float(
        shape_errors(cfg, m1s, answers, served_idx))
    dbc = reference.check_db(db, db_rows, db_U, db_X, gamma=gamma_off,
                             topics=topics_off, b=b_off,
                             eps=float(cfg["eps"]),
                             tol=float(cfg["audit_tol"]))
    cmp["numbers"].update(dbc["numbers"])
    cmp["counts"].update(dbc["counts"])
    ok, checks = reference.judge(cmp["numbers"], lim)
    correct = bool(ok and cmp["counts"]["compared"] > 0)
    log(f"reference: {json.dumps(cmp['counts'])} seconds "
        f"{time.perf_counter() - t:.3f}")

    kind = "per_layer" if trace else "end_to_end"
    out_metrics = {}
    for m in layout.cell_metrics(bench, cell["name"], kind):
        value = layout.metric_reader(m["name"], here)(rec)
        if value is not None:
            out_metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": out_metrics,
              "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(red),
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    opts = parse_args(argv)
    bench = layout.load_benchmark()
    cell = layout.find_cell(bench, opts.workload)
    cfg = layout.load_config(cell["config"])
    mix = layout.load_traffic(cell["traffic"])
    configure_jax(cfg)
    devices = tpu_devices(int(cell["chips"]))
    log = Log(f"{devices[0].platform} {devices[0].device_kind} "
              f"x{len(devices)}")
    result = run_cell(bench, cell, cfg, mix, seed=opts.seed,
                      seconds=opts.seconds, trace=bool(opts.trace),
                      devices=devices, log=log)
    log(f"result: correct {result['correct']} metrics "
        f"{json.dumps(result['metrics'])}")
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
