"""Online-stage latency: the paper's < 50 ms claim, measured.

Three measurement modes (docs/benchmarks.md walks through them):

  * direct: the full online hot path — predict lambda via KNN over the
    train database, adjust scores, take the top-m2 — end to end under
    jit, per (m1, K, m2, batch) problem size. The paper's headline
    (>= 500 objects, >= 5 constraints inside 50 ms on a 2015 quad-core
    CPU) is checked directly.

  * engine: the same mixed-shape request stream served through the
    streaming engine (repro.serving) twice — synchronous
    (pipeline_depth=0: every flush blocks on its own transfer) and
    pipelined (pipeline_depth=1 double buffering) — reported side by
    side: per-request p50/p95/p99 (enqueue -> result) from a
    deadline-driven run, saturated wall-clock throughput and the
    pipelined/sync speedup from paired interleaved trials, compliance,
    bucket fill rate, and recompiles after warmup
    (must stay 0). Both modes must produce identical perms per rid
    (verified here, not just in tests). This is the fleet-relevant
    number: the deployed system sees a stream, not a fixed batch.

    Measurement notes (full discussion in docs/benchmarks.md):
    - throughput trials submit back-to-back with a frozen arrival
      clock, so the capacity-flush batch structure is identical across
      modes and trials — the comparison never measures two different
      batchings;
    - trials are paired and interleaved (sync, pipelined, sync, ...)
      and summarized by the median of per-pair ratios, which cancels
      the machine-load drift that dominates small CI boxes;
    - on a CPU-only host the engine comparison runs in a subprocess
      with XLA's intra-op threading disabled
      (--xla_cpu_multi_thread_eigen=false): host/device overlap only
      exists when device execution does not consume every host core,
      which is the deployment reality on any accelerator backend. On
      a 2-core CI container with XLA spanning both cores, sync and
      pipelined are both CPU-bound on identical total work and the
      comparison measures scheduler noise instead of the pipeline.

  * frontier (`--frontier` / `--only frontier`): p99 latency vs OFFERED
    load, paced open-loop — Poisson arrivals at target QPS fractions of
    the measured closed-loop capacity (`serving.traffic.poisson_arrivals`
    + `serve_open_loop`). Closed-loop drivers cannot offer more load
    than the server absorbs, so they never see queueing delay; the
    open-loop sweep reports the tail below saturation and marks the
    rows past it. Each row also reports the deadline-hit rate against
    the 50 ms budget next to p99, and saturation is detected on the
    decomposed QUEUE lag (pacing clock-drift excluded).

  * deadline (`--only deadline`): the admission-control health gate
    (`check_deadline`) — zero missed deadlines at <= 0.8x detected
    saturation with admission on, an admission-off baseline that
    misses past saturation, and a forced-degrade pass whose rung-1
    compliance cost comes from the fused-kernel audit outputs. Writes
    BENCH_deadline.json with `--json`; AssertionError on regression.

  * refresh (`--only refresh`): the λ-refresh hot-swap health gate
    (`check_refresh`) — real telemetry drives >= 2 mid-stream swaps
    with zero recompiles, per-bucket jit caches pinned at the warmed
    executable, one dispatch per flushed batch, every epoch bitwise
    identical to a cold engine started on that epoch's published
    state, and rollback restoring the last-good generation bitwise.
    Also times the refresh publish (drain + update + device_put +
    fenced swap). Writes BENCH_refresh.json with `--json`.

  * drift (`--only drift`): the drift regression gate (`check_drift`)
    — under 8x mid-stream constraint tightening, refresh-on must
    strictly reduce accumulated exposure shortfall vs the frozen
    predictor with zero recompiles, and must be a bitwise no-op on a
    compliant stationary stream. Writes BENCH_drift.json with
    `--json`.

  * quant (`--only quant`): the quantized-serving gate (`check_quant`)
    — an engine on an int8-quantized KNN predictor serves a lossless
    stream bitwise identical to the f32 engine (perm, utility,
    exposure, compliant per request), with exactly one kernel launch
    per flushed micro-batch and zero post-warmup recompiles on both
    sides. Writes BENCH_quant_serve.json with `--json`.

  * fleet (`--only fleet`): the fault-tolerance gate (`check_fleet`) —
    a 3-replica FleetRouter serves a 512-request mixed stream under
    the seeded chaos plan (crash-at-batch-k, heartbeat blackhole,
    slow replica, partial-drain kill), armed AFTER a fault-free
    prefix + refresh so an epoch checkpoint exists before the first
    crash. Asserts zero orphaned futures, zero lost requests, every
    rid served exactly once, the restarted replica resuming at the
    last-good checkpointed epoch (not cold), zero post-warmup
    recompiles on every incarnation, and p99 within the latency
    budget x a CI tolerance. Writes BENCH_fleet.json with `--json`.

  * lattice (`--only lattice`): the adaptive-lattice gate
    (`check_lattice`) — one engine serves a skewed two-phase
    multi-surface stream while a LatticeLane learns bucket corners
    from the shape histogram and re-warms them in detected troughs.
    Asserts >= 2 detector-gated mid-stream swaps with ZERO
    dispatch-path compiles, measured padding waste (padded/real sweep
    FLOPs) cut >= 1.5x vs a power-of-two engine on identical chunks,
    per-epoch results bitwise-equal to a cold engine built on that
    epoch's lattice, and a poisoned proposal rolling back to
    last-good without pausing the stream. Writes BENCH_lattice.json
    with `--json`.

Usage:

  python -m benchmarks.latency_serve \\
      [--quick] [--frontier] [--json OUT] \\
      [--only direct|engine|frontier|deadline|refresh|drift|quant|lattice]

`--json OUT` additionally writes a machine-readable
BENCH_latency_serve.json (medians, geometry, backend — see
benchmarks.common.write_bench_json) so the serving-latency trajectory
is trackable across PRs; CI uploads it as an artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Record, save_json, timed, write_bench_json
from repro.core.constraints import dcg_discount
from repro.core.predictors import (
    KNNLambdaPredictor,
    MeanLambdaPredictor,
    knn_predict,
    with_state,
)
from repro.core.ranking import rank_given_lambda
from repro.data.synthetic import DriftSpec
from repro.checkpoint import CheckpointStore
from repro.serving import (
    DEFAULT_MIX,
    AdmissionController,
    FaultInjector,
    FaultPlan,
    FleetRouter,
    HealthConfig,
    Lattice,
    LatticeLane,
    RefreshLane,
    Scenario,
    ServingEngine,
    Shed,
    TroughDetector,
    make_drift_stream,
    make_stream,
    poisson_arrivals,
    serve_open_loop,
)

LATENCY_BUDGET_MS = 50.0

# Engine-comparison child process marker + the dedicated-device-core
# XLA config it runs under (see module docstring).
_CHILD_ENV = "REPRO_ENGINE_BENCH_CHILD"
_DEDICATED_CORE_FLAGS = "--xla_cpu_multi_thread_eigen=false"


def _serve_fn(m2):
    @jax.jit
    def serve(X, u, a, b, X_db, lam_db):
        lam_hat = knn_predict(X_db, lam_db, X, k=10)
        return rank_given_lambda(u, a, b, lam_hat, dcg_discount(m2), m2=m2)
    return serve


def run(*, sizes=((1000, 5, 50), (1000, 5, 1000), (10000, 8, 50),
                  (100_000, 5, 50)),
        batches=(1, 512), n_db=10_000, d_cov=20, verbose=True):
    rows = []
    for m1, K, m2 in sizes:
        for B in batches:
            key = jax.random.key(m1 + B)
            ks = jax.random.split(key, 5)
            X = jax.random.normal(ks[0], (B, d_cov))
            u = jax.random.uniform(ks[1], (B, m1), minval=1, maxval=5)
            a = (jax.random.uniform(ks[2], (B, K, m1)) < 0.1).astype(jnp.float32)
            b = 0.03 * jnp.sum(dcg_discount(m2)) * jnp.ones((K,))
            X_db = jax.random.normal(ks[3], (n_db, d_cov))
            lam_db = jnp.abs(jax.random.normal(ks[4], (n_db, K)))
            serve = _serve_fn(m2)
            us = timed(lambda: serve(X, u, a, b, X_db, lam_db).perm, iters=5)
            rows.append({
                "m1": m1, "K": K, "m2": m2, "batch": B,
                "us_total": us, "us_per_user": us / B,
                "within_50ms": bool(us / 1e3 <= LATENCY_BUDGET_MS),
            })
            if verbose:
                r = rows[-1]
                print(f"serve m1={m1:6d} K={K} m2={m2:4d} B={B:4d} "
                      f"{r['us_total']/1e3:8.2f} ms/batch "
                      f"({r['us_per_user']:8.1f} us/user) "
                      f"<=50ms: {r['within_50ms']}", flush=True)
    save_json("latency_serve", rows)
    return rows


def _saturated_serve(engine, requests):
    """Back-to-back submission with a frozen arrival clock: the
    capacity-flush batch structure is deterministic (identical across
    modes/trials), so wall clock measures execution, not batching."""
    t0 = time.perf_counter()
    out = []
    for r in requests:
        out += engine.submit(r, now=0.0)
    out += engine.drain()
    return out, time.perf_counter() - t0


def _perms_of(results):
    return {r.rid: np.asarray(r.perm) for r in results}


def _perms_equal(a, b):
    return sorted(a) == sorted(b) and all(
        np.array_equal(a[rid], b[rid]) for rid in a)


def _run_engine_inproc(*, n_requests, max_batch, max_wait_ms, scenarios,
                       seed, depths, trials, verbose):
    requests = make_stream(scenarios, n_requests=n_requests, seed=seed)
    engines, rows = {}, []
    for depth in depths:
        engines[depth] = ServingEngine(max_batch=max_batch,
                                       max_wait_ms=max_wait_ms,
                                       pipeline_depth=depth)
        engines[depth].warmup(requests)

    # latency profile: one deadline-driven pass (real arrival clock),
    # metrics snapshotted before the throughput trials pollute them.
    latency, perms = {}, {}
    for depth, eng in engines.items():
        results = eng.serve_stream(requests)
        latency[depth] = eng.metrics.summary()
        perms[depth] = _perms_of(results)

    # throughput: paired interleaved trials over the frozen-clock
    # saturated stream; per-pair ratios cancel machine-load drift.
    walls = {d: [] for d in depths}
    diverged = set()
    for _ in range(max(1, trials)):
        for depth, eng in engines.items():
            out, wall = _saturated_serve(eng, requests)
            walls[depth].append(wall)
            if not _perms_equal(_perms_of(out), perms[depths[0]]):
                diverged.add(depth)
    base = depths[0]
    for depth in depths:
        s = latency[depth]
        ratios = sorted(ws / wp for ws, wp in zip(walls[base], walls[depth]))
        wall_med = statistics.median(walls[depth])
        identical = (_perms_equal(perms[depth], perms[base])
                     and depth not in diverged)
        rows.append({
            "mode": "sync" if depth == 0 else f"pipelined(depth={depth})",
            "pipeline_depth": depth,
            "n_requests": n_requests,
            "scenarios": [sc.name for sc in scenarios],
            "max_batch": max_batch, "max_wait_ms": max_wait_ms,
            "trials": trials,
            "buckets": s["buckets_used"],
            "compiles_post_warmup": s["compiles_post_warmup"],
            "fill_rate": s["fill_rate"],
            "p50_ms": s["latency_ms"]["p50"],
            "p95_ms": s["latency_ms"]["p95"],
            "p99_ms": s["latency_ms"]["p99"],
            "wall_median_s": round(wall_med, 4),
            "throughput_rps": round(n_requests / wall_med, 1),
            "speedup_vs_sync": round(statistics.median(ratios), 2),
            "speedup_spread": [round(ratios[0], 2), round(ratios[-1], 2)],
            "queue_depth_max": s["pipeline"]["queue_depth_max"],
            "perms_match_baseline": bool(identical),
            "compliance": s["compliance"],
            "within_50ms": bool(s["latency_ms"]["p99"] <= LATENCY_BUDGET_MS),
        })
        if verbose:
            r = rows[-1]
            print(f"engine[{r['mode']:18s}] n={n_requests} "
                  f"p50 {r['p50_ms']:6.2f} p95 {r['p95_ms']:6.2f} "
                  f"p99 {r['p99_ms']:6.2f} ms  "
                  f"{r['throughput_rps']:7.1f} req/s "
                  f"(median {r['speedup_vs_sync']:.2f}x, spread "
                  f"{r['speedup_spread'][0]:.2f}-{r['speedup_spread'][1]:.2f})"
                  f"  perms_match {r['perms_match_baseline']}  "
                  f"recompiles {r['compiles_post_warmup']}", flush=True)
    for eng in engines.values():
        eng.close()
    return rows


def run_engine(*, n_requests=512, max_batch=32, max_wait_ms=2.0,
               scenarios=DEFAULT_MIX, seed=0, depths=(0, 1), trials=7,
               dedicated_device_core=True, verbose=True):
    """Mixed-shape stream through the engine, sync vs pipelined.

    depths[0] is the baseline (0 = synchronous); every other depth is
    reported with its paired-median speedup over that baseline and
    checked for identical perms per rid.

    With dedicated_device_core=True (default) on a CPU backend, the
    whole comparison re-runs in a subprocess with XLA intra-op
    threading disabled so device execution models an accelerator that
    does not consume host cores (both modes run under the SAME flags;
    see module docstring). Pass False to measure in-process under
    whatever XLA config is already loaded.
    """
    use_child = (dedicated_device_core
                 and not os.environ.get(_CHILD_ENV)
                 and jax.default_backend() == "cpu")
    if not use_child:
        rows = _run_engine_inproc(
            n_requests=n_requests, max_batch=max_batch,
            max_wait_ms=max_wait_ms, scenarios=scenarios, seed=seed,
            depths=depths, trials=trials, verbose=verbose)
        if not os.environ.get(_CHILD_ENV):
            save_json("latency_serve_engine", rows)
        return rows

    cfg = dict(n_requests=n_requests, max_batch=max_batch,
               max_wait_ms=max_wait_ms, seed=seed, depths=list(depths),
               trials=trials, verbose=verbose,
               scenarios=[vars(sc) for sc in scenarios])
    env = dict(os.environ)
    env[_CHILD_ENV] = "1"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                        + _DEDICATED_CORE_FLAGS).strip()
    with tempfile.NamedTemporaryFile("r", suffix=".json") as out_f:
        subprocess.run(
            [sys.executable, "-m", "benchmarks.latency_serve",
             "--engine-child", out_f.name, "--engine-config",
             json.dumps(cfg)],
            env=env, check=True)
        rows = json.load(open(out_f.name))
    save_json("latency_serve_engine", rows)
    return rows


def run_frontier(*, n_requests=512,
                 load_fracs=(0.25, 0.5, 0.7, 0.85, 1.0, 1.2, 2.0),
                 max_batch=32, max_wait_ms=2.0, scenarios=DEFAULT_MIX,
                 seed=0, pipeline_depth=1, verbose=True):
    """The latency/throughput frontier: p99 vs OFFERED load, paced
    open-loop (Poisson arrivals at a target QPS — serving.traffic).

    A closed-loop (back-to-back) driver can only ever measure the
    saturated operating point; real deployments run below saturation
    and care about the tail there. The sweep first probes saturated
    capacity with one closed-loop pass, then offers Poisson traffic at
    fractions of it. Below saturation p99 is batching + service time
    (deadline-bounded); past it, queueing delay dominates and achieved
    throughput caps at capacity — `saturated` marks those rows.
    """
    requests = make_stream(scenarios, n_requests=n_requests, seed=seed)

    def fresh_engine():
        eng = ServingEngine(max_batch=max_batch, max_wait_ms=max_wait_ms,
                            pipeline_depth=pipeline_depth)
        eng.warmup(requests)
        return eng

    probe = fresh_engine()
    _, wall = _saturated_serve(probe, requests)
    probe.close()
    capacity = n_requests / wall
    if verbose:
        print(f"frontier: closed-loop capacity ~ {capacity:.1f} req/s",
              flush=True)

    rows = []
    for frac in load_fracs:
        qps = capacity * frac
        eng = fresh_engine()
        arrivals = poisson_arrivals(n_requests, qps, seed=seed + 1)
        results, ol = serve_open_loop(eng, requests, arrivals)
        s = eng.metrics.summary()
        dl = s["deadline"]
        eng.close()
        # Saturation telltale: QUEUEING lag at the last submission —
        # lateness carried into an arrival by earlier submits blocking
        # on engine backpressure. serve_open_loop separates this from
        # pacing clock-drift (sleep-granularity overshoot), so the
        # detector no longer trips on timer jitter on a loaded host.
        # Threshold: 10 arrival slots or 5 ms, whichever is larger.
        lag_thresh_ms = max(5.0, 1e4 / qps)
        saturated = ol["queue_lag_ms"]["last"] > lag_thresh_ms
        rows.append({
            "offered_qps": round(qps, 1),
            "offered_frac_of_capacity": frac,
            "achieved_qps": round(ol["achieved_qps"], 1),
            "capacity_qps": round(capacity, 1),
            "n_requests": n_requests,
            "max_batch": max_batch, "max_wait_ms": max_wait_ms,
            "pipeline_depth": pipeline_depth,
            "p50_ms": s["latency_ms"]["p50"],
            "p95_ms": s["latency_ms"]["p95"],
            "p99_ms": s["latency_ms"]["p99"],
            "queue_lag_ms_last": round(ol["queue_lag_ms"]["last"], 3),
            "drift_ms_p99": round(ol["drift_ms"]["p99"], 3),
            "submit_lag_ms_p99": round(ol["lag_ms"]["p99"], 3),
            "submit_lag_ms_last": round(ol["lag_ms"]["last"], 3),
            "deadline_hit_rate": dl["hit_rate"],
            "deadline_misses": dl["misses"],
            "sheds": dl["sheds"],
            "degrades": dl["degrades"],
            "fill_rate": s["fill_rate"],
            "compiles_post_warmup": s["compiles_post_warmup"],
            "saturated": bool(saturated),
            "within_50ms": bool(s["latency_ms"]["p99"] <= LATENCY_BUDGET_MS),
        })
        if verbose:
            r = rows[-1]
            print(f"frontier offered {r['offered_qps']:8.1f} req/s "
                  f"({frac:4.2f}x cap)  achieved {r['achieved_qps']:8.1f}  "
                  f"p50 {r['p50_ms']:6.2f}  p95 {r['p95_ms']:6.2f}  "
                  f"p99 {r['p99_ms']:7.2f} ms  queue_lag_last "
                  f"{r['queue_lag_ms_last']:7.2f} ms  "
                  f"hit_rate {r['deadline_hit_rate']:.3f}  "
                  f"saturated {r['saturated']}", flush=True)
    save_json("latency_frontier", rows)
    return rows


def _deadline_mix(seed):
    """Quick synthetic mix for the deadline gate: a KNN-served surface
    with a mean-predictor degradation rung, plus a raw-lam surface."""
    rng = np.random.default_rng(seed)
    d, K = 12, 4
    knn = KNNLambdaPredictor.fit(
        rng.normal(size=(64, d)).astype(np.float32),
        np.abs(rng.normal(size=(64, K))).astype(np.float32), k=5)
    mean = MeanLambdaPredictor.fit(
        np.zeros((4, d), np.float32),
        np.abs(rng.normal(size=(4, K))).astype(np.float32))
    mix = (Scenario("feed_knn", m1=300, m2=24, K=K, weight=2.0,
                    tag="knn", d_cov=d),
           Scenario("notif_lam", m1=120, m2=8, K=3, weight=1.0))
    return mix, knn, mean, d


def run_deadline(*, n_requests=256, max_batch=16, max_wait_ms=2.0,
                 seed=0, pipeline_depth=1, verbose=True):
    """Deadline-hit-rate frontier for the admission health gate.

    Probes closed-loop capacity on the quick synthetic mix, fixes a
    feasible per-request budget (max(50 ms, 5x the low-load p99) —
    generous enough that below-saturation service can always make it,
    so a miss means the engine queued past the deadline, not that the
    budget was impossible), then measures:

      * admission ON  at 0.5x and 0.8x capacity — the gate requires
        ZERO deadline misses (sheds/degrades are the controller doing
        its job and are reported, not failed);
      * admission OFF at 2.5x capacity — the baseline must show misses
        past saturation (otherwise the gate proves nothing);
      * a deterministic forced-degrade pass (KNN rungs poisoned with a
        huge observed service time) — degraded buckets must serve from
        rung 1 and report their compliance cost from the fused-kernel
        audit outputs.
    """
    mix, knn, mean, d = _deadline_mix(seed)
    requests = make_stream(mix, n_requests=n_requests, seed=seed)

    def fresh(admission, budget_s):
        eng = ServingEngine(max_batch=max_batch, max_wait_ms=max_wait_ms,
                            pipeline_depth=pipeline_depth,
                            admission=admission, default_budget_s=budget_s)
        eng.register_predictor("knn", knn, d_cov=d)
        eng.register_predictor("mean", mean, d_cov=d)
        eng.set_degradation_ladder("knn", ["mean"])
        eng.warmup(requests)
        return eng

    probe = fresh(None, 1.0)
    _, wall = _saturated_serve(probe, requests)
    probe.close()
    capacity = n_requests / wall

    eng = fresh(None, 1.0)                      # low-load budget reference
    arrivals = poisson_arrivals(n_requests, 0.3 * capacity, seed=seed + 1)
    serve_open_loop(eng, requests, arrivals)
    p99_low = eng.metrics.summary()["latency_ms"]["p99"]
    eng.close()
    budget_ms = max(LATENCY_BUDGET_MS, 5.0 * p99_low)
    if verbose:
        print(f"deadline: capacity ~ {capacity:.1f} req/s, low-load p99 "
              f"{p99_low:.2f} ms -> budget {budget_ms:.1f} ms", flush=True)

    # The overload baseline needs a stream long enough that queueing
    # lateness actually exceeds the budget: at frac x capacity the last
    # arrival is ~ (n/capacity)(1 - 1/frac) seconds late, so size n for
    # ~2 budgets of accumulated lateness (bounded for beefy hosts).
    n_over = int(min(20_000, max(
        n_requests, np.ceil(2.0 * (budget_ms / 1e3) * capacity / 0.6))))
    requests_over = make_stream(mix, n_requests=n_over, seed=seed)

    rows = []
    for frac, use_admission in ((0.5, True), (0.8, True), (2.5, False)):
        reqs = requests if use_admission else requests_over
        eng = fresh(AdmissionController() if use_admission else None,
                    budget_ms / 1e3)
        arrivals = poisson_arrivals(len(reqs), capacity * frac,
                                    seed=seed + 2)
        # deadlines anchored at scheduled ARRIVAL (absolute stamps):
        # lateness the generator accumulates blocking on backpressure
        # counts against the budget, as a caller-side SLA would.
        results, ol = serve_open_loop(eng, reqs, arrivals,
                                      deadline_budget_s=budget_ms / 1e3)
        dl = eng.metrics.deadline_summary()
        served = sum(1 for r in results if not isinstance(r, Shed))
        eng.close()
        rows.append({
            "admission": use_admission,
            "offered_frac_of_capacity": frac,
            "offered_qps": round(capacity * frac, 1),
            "capacity_qps": round(capacity, 1),
            "budget_ms": round(budget_ms, 1),
            "n_requests": len(reqs),
            "served": served,
            "deadline_hit_rate": dl["hit_rate"],
            "deadline_misses": dl["misses"],
            "sheds": dl["sheds"],
            "degrades": dl["degrades"],
            "queue_lag_ms_last": round(ol["queue_lag_ms"]["last"], 3),
            "rungs": dl["rungs"],
        })
        if verbose:
            r = rows[-1]
            print(f"deadline[admission={'on ' if use_admission else 'off'}] "
                  f"{frac:4.2f}x cap  served {r['served']:4d}  "
                  f"hit_rate {r['deadline_hit_rate']:.3f}  "
                  f"misses {r['deadline_misses']:3d}  "
                  f"sheds {r['sheds']:3d}  degrades {r['degrades']:3d}",
                  flush=True)

    # deterministic forced-degrade pass: every KNN rung predicted late
    for r in requests:
        r.deadline = None       # drop the open-loop runs' absolute stamps
    ctrl = AdmissionController()
    eng = fresh(ctrl, budget_ms / 1e3)
    for b in eng._warmed:
        if b.tag == "knn":
            ctrl.observe_service(b.name, 1e6)
    eng.serve_stream(requests, warmup=False)
    dl = eng.metrics.deadline_summary()
    eng.close()
    degrade = {
        "degrades": dl["degrades"],
        "sheds": dl["sheds"],
        "rung1_served": dl["rungs"].get("1", {}).get("served", 0),
        "rung1_compliance": dl["rungs"].get("1", {}).get(
            "compliance", float("nan")),
        "rung1_mean_shortfall": dl["rungs"].get("1", {}).get(
            "mean_shortfall", float("nan")),
    }
    if verbose:
        print(f"deadline[forced degrade] degrades {degrade['degrades']} "
              f"rung1_served {degrade['rung1_served']} "
              f"rung1_compliance {degrade['rung1_compliance']} "
              f"rung1_mean_shortfall {degrade['rung1_mean_shortfall']}",
              flush=True)
    out = {"capacity_qps": round(capacity, 1),
           "budget_ms": round(budget_ms, 1),
           "rows": rows, "degrade": degrade}
    save_json("latency_deadline", out)
    return out


def check_deadline(*, quick=False, verbose=True):
    """Admission health gate (kernel_bench-style: AssertionError on
    regression): zero missed deadlines below 80% of detected
    saturation with admission on; the admission-off baseline must miss
    past saturation; degraded buckets must report compliance cost."""
    kw = dict(n_requests=160) if quick else {}
    res = run_deadline(verbose=verbose, **kw)
    for r in res["rows"]:
        if r["admission"] and r["offered_frac_of_capacity"] <= 0.8:
            assert r["deadline_misses"] == 0, (
                f"deadline gate: {r['deadline_misses']} misses at "
                f"{r['offered_frac_of_capacity']}x capacity with admission "
                f"on (budget {r['budget_ms']} ms)")
            assert r["served"] > 0, (
                "deadline gate: admission shed the entire below-saturation "
                "stream — the controller is overpredicting")
    baseline = [r for r in res["rows"] if not r["admission"]]
    assert baseline and any(r["deadline_misses"] > 0 for r in baseline), (
        "deadline gate: the admission-off overload baseline shows no "
        "misses — the gate is not exercising saturation")
    dg = res["degrade"]
    assert dg["degrades"] > 0 and dg["rung1_served"] > 0, (
        f"deadline gate: forced-degrade pass served nothing from rung 1 "
        f"({dg})")
    assert np.isfinite(dg["rung1_mean_shortfall"]), (
        "deadline gate: rung 1 reported no compliance cost")
    print("# deadline acceptance (0 misses <= 0.8x capacity with "
          "admission, baseline misses past saturation, degraded rungs "
          "report compliance cost): PASS")
    return res


def records_deadline(res):
    recs = [Record(
        name=f"serve_deadline/admission={'on' if r['admission'] else 'off'}"
             f"/frac={r['offered_frac_of_capacity']}",
        us_per_call=float("nan"),
        derived={"hit_rate": r["deadline_hit_rate"],
                 "misses": r["deadline_misses"],
                 "sheds": r["sheds"], "degrades": r["degrades"],
                 "served": r["served"], "budget_ms": r["budget_ms"],
                 "capacity_qps": r["capacity_qps"]})
        for r in res["rows"]]
    dg = res["degrade"]
    recs.append(Record(
        name="serve_deadline/forced_degrade",
        us_per_call=float("nan"),
        derived={"degrades": dg["degrades"],
                 "rung1_served": dg["rung1_served"],
                 "rung1_compliance": dg["rung1_compliance"],
                 "rung1_mean_shortfall": dg["rung1_mean_shortfall"]}))
    return recs


REFRESH_TAG = "arch"
REFRESH_D, REFRESH_K = 10, 4


def _refresh_engine(pred, *, max_batch=8, pipeline_depth=1):
    """Deterministic refresh-gate engine: max_wait_ms=1e9 means the
    deadline flush never fires, so batch composition is a pure function
    of the stream (capacity flushes + end-of-stream drain) and hot- vs
    cold-engine runs are bitwise comparable without a frozen clock."""
    eng = ServingEngine(max_batch=max_batch, max_wait_ms=1e9,
                        pipeline_depth=pipeline_depth)
    eng.register_predictor(REFRESH_TAG, pred, d_cov=REFRESH_D)
    return eng


def _bitwise_same(got, ref):
    return (np.array_equal(got.perm, ref.perm)
            and np.array_equal(got.exposure, ref.exposure)
            and got.utility == ref.utility
            and got.compliant == ref.compliant)


def run_refresh(*, n_requests=192, chunk=32, max_batch=8, seed=0,
                verbose=True):
    """Hot-swap health probe for the online λ-refresh lane.

    Serves a shortfall-heavy stationary stream in chunks with a
    `lane.refresh()` between chunks (real telemetry -> real swaps),
    then checks the zero-recompile contract the tests prove, on the
    benchmark box: swaps happened, compiles_post_warmup stayed 0,
    per-bucket jit caches stayed at exactly the warmed executable,
    executable_calls stayed one per flushed micro-batch — and each
    epoch's results are BITWISE what a cold engine started from that
    epoch's published state serves. A final rollback() must republish
    the pre-swap generation bitwise. Also times the refresh publish
    (drain + update rule + device_put + fenced swap), the number that
    has to stay tiny for the lane to ride the serving box.
    """
    rng = np.random.default_rng(seed)
    pred = KNNLambdaPredictor.fit(
        rng.normal(size=(96, REFRESH_D)).astype(np.float32),
        np.abs(rng.normal(size=(96, REFRESH_K))).astype(np.float32), k=5)
    reqs = make_drift_stream(
        DriftSpec(kind="none"), tag=REFRESH_TAG, n_requests=n_requests,
        m1=128, m2=16, K=REFRESH_K, d_cov=REFRESH_D, b_frac=0.25,
        seed=seed)

    eng = _refresh_engine(pred, max_batch=max_batch)
    lane = RefreshLane(eng, eta=0.5, min_samples=8)
    eng.warmup(reqs)

    # epoch -> host copy of the published state, for cold-engine replay
    states = {0: jax.device_get(eng.predictor_state_of(REFRESH_TAG))}
    chunks, swap_us = [], []
    for i in range(0, len(reqs), chunk):
        got = eng.serve_stream(reqs[i:i + chunk], warmup=False)
        chunks.append((eng.predictor_epoch(REFRESH_TAG),
                       reqs[i:i + chunk], got))
        t0 = time.perf_counter()
        rep = lane.refresh()[REFRESH_TAG]
        dt = time.perf_counter() - t0
        if rep["swapped"]:
            swap_us.append(dt * 1e6)
            states[rep["epoch"]] = jax.device_get(
                eng.predictor_state_of(REFRESH_TAG))

    m = eng.metrics
    sizes = eng.jit_cache_sizes()
    swaps = m.refresh_summary()["swaps"]

    # hot-vs-cold parity, per epoch: refresh() runs between chunks and
    # serve_stream drains fully, so every chunk is entirely one epoch.
    parity_ok = True
    for epoch in sorted({e for e, _, _ in chunks}):
        cold = _refresh_engine(with_state(pred, states[epoch]),
                               max_batch=max_batch)
        for e, creqs, got in chunks:
            if e != epoch:
                continue
            ref = {r.rid: r for r in cold.serve_stream(creqs)}
            parity_ok &= all(_bitwise_same(r, ref[r.rid]) for r in got)
        cold.close()

    # rollback republishes the pre-swap generation (as a NEW epoch —
    # the fence applies to rollback too) bitwise.
    pre_rollback_epoch = eng.predictor_epoch(REFRESH_TAG)
    rollback_ok = False
    if swaps >= 1:
        t0 = time.perf_counter()
        rb_epoch = lane.rollback(REFRESH_TAG)
        rollback_us = (time.perf_counter() - t0) * 1e6
        prev = states[pre_rollback_epoch - 1]
        now = jax.device_get(eng.predictor_state_of(REFRESH_TAG))
        rollback_ok = (rb_epoch == pre_rollback_epoch + 1
                       and set(now) == set(prev)
                       and all(np.array_equal(now[k], prev[k])
                               for k in now))
    else:
        rollback_us = float("nan")

    out = {
        "n_requests": n_requests,
        "swaps": swaps,
        "final_epoch": eng.predictor_epoch(REFRESH_TAG),
        "compiles_post_warmup": m.compiles_post_warmup,
        "executable_calls": m.executable_calls,
        "batches": m.batches,
        "jit_cache_sizes": dict(sizes),
        "parity_ok": bool(parity_ok),
        "rollback_ok": bool(rollback_ok),
        "swap_us_p50": (round(statistics.median(swap_us), 1)
                        if swap_us else float("nan")),
        "rollback_us": round(rollback_us, 1),
    }
    eng.close()
    if verbose:
        print(f"refresh: swaps {out['swaps']}  epoch {out['final_epoch']}  "
              f"compiles_post_warmup {out['compiles_post_warmup']}  "
              f"exec_calls/batches {out['executable_calls']}/"
              f"{out['batches']}  swap_p50 {out['swap_us_p50']} us  "
              f"parity {out['parity_ok']}  rollback {out['rollback_ok']}",
              flush=True)
    save_json("latency_refresh", out)
    return out


def check_refresh(*, quick=False, verbose=True):
    """Refresh-lane health gate (kernel_bench-style: AssertionError on
    regression): real telemetry must drive >= 2 hot swaps with zero
    recompiles and one dispatch per batch, every epoch must serve
    bitwise what a cold engine on that state serves, and rollback must
    restore the last-good generation bitwise."""
    kw = dict(n_requests=128) if quick else {}
    res = run_refresh(verbose=verbose, **kw)
    assert res["swaps"] >= 2, (
        f"refresh gate: only {res['swaps']} swaps — the shortfall-heavy "
        f"stream should force repeated refreshes")
    assert res["compiles_post_warmup"] == 0, (
        f"refresh gate: {res['compiles_post_warmup']} recompiles after "
        f"warmup — a swap broke the frozen-shape contract")
    assert all(v == 1 for v in res["jit_cache_sizes"].values()), (
        f"refresh gate: jit cache grew past the warmed executable: "
        f"{res['jit_cache_sizes']}")
    assert res["executable_calls"] == res["batches"], (
        f"refresh gate: {res['executable_calls']} executable calls for "
        f"{res['batches']} batches — a swap added a dispatch")
    assert res["parity_ok"], (
        "refresh gate: hot-swapped serving diverged from a cold engine "
        "started on the published state")
    assert res["rollback_ok"], (
        "refresh gate: rollback did not restore the pre-swap state "
        "bitwise")
    print("# refresh acceptance (>= 2 hot swaps, 0 recompiles, 1 "
          "dispatch/batch, hot == cold bitwise per epoch, rollback "
          "restores last-good): PASS")
    return res


def records_refresh(res):
    return [Record(
        name=f"serve_refresh/hot_swap/n={res['n_requests']}",
        us_per_call=res["swap_us_p50"],
        derived={"swaps": res["swaps"],
                 "final_epoch": res["final_epoch"],
                 "compiles_post_warmup": res["compiles_post_warmup"],
                 "executable_calls": res["executable_calls"],
                 "batches": res["batches"],
                 "parity_ok": res["parity_ok"],
                 "rollback_ok": res["rollback_ok"],
                 "rollback_us": res["rollback_us"]})]


def run_drift(*, n_requests=256, chunk=32, seed=10, verbose=True):
    """Drift regression: refresh-on vs refresh-off under mid-stream
    constraint tightening, plus the stationarity control.

    The KNN predictor is fit in the compliant era (zero-λ database);
    the stream tightens its thresholds 8x between 25% and 75% of the
    stream. Refresh-off keeps serving the stale λ̂ and accumulates
    exposure shortfall against the requests' REAL thresholds;
    refresh-on folds the dual-subgradient telemetry back between
    chunks and must strictly reduce it — with zero recompiles. On a
    stationary stream with no dual pressure (compliant, and served
    with λ̂ = 0 so the symmetric decay side of the gate is quiet too)
    the lane must publish nothing and serving must stay bitwise
    identical to refresh-off.
    """
    def shortfall_run(reqs, *, refresh_on, eta=1.0, knn_scale=0.0,
                      knn_seed=9):
        rng = np.random.default_rng(knn_seed)
        pred = KNNLambdaPredictor.fit(
            rng.normal(size=(64, REFRESH_D)).astype(np.float32),
            knn_scale * np.abs(rng.normal(
                size=(64, REFRESH_K))).astype(np.float32), k=5)
        eng = _refresh_engine(pred, pipeline_depth=0)
        lane = (RefreshLane(eng, eta=eta, min_samples=8)
                if refresh_on else None)
        eng.warmup(reqs)
        results = []
        for i in range(0, len(reqs), chunk):
            results += eng.serve_stream(reqs[i:i + chunk], warmup=False)
            if lane is not None:
                lane.refresh()
        by_rid = {r.rid: r for r in reqs}
        shortfall = sum(
            float(np.clip(by_rid[r.rid].b - r.exposure, 0.0, None).sum())
            for r in results)
        m = eng.metrics
        out = {"shortfall": round(shortfall, 4),
               "swaps": m.refresh_summary()["swaps"],
               "compiles_post_warmup": m.compiles_post_warmup,
               "results": results}
        eng.close()
        return out

    spec = DriftSpec(kind="tighten", magnitude=8.0, start=0.25, end=0.75)
    reqs = make_drift_stream(
        spec, tag=REFRESH_TAG, n_requests=n_requests, m1=128, m2=16,
        K=REFRESH_K, d_cov=REFRESH_D, b_frac=0.03, seed=seed)
    off = shortfall_run(reqs, refresh_on=False)
    on = shortfall_run(reqs, refresh_on=True)

    # stationarity control: compliant stream, refresh must be a no-op
    stat = make_drift_stream(
        DriftSpec(kind="none"), tag=REFRESH_TAG, n_requests=96, m1=128,
        m2=16, K=REFRESH_K, d_cov=REFRESH_D, topic_rate=0.45,
        b_frac=0.01, seed=seed + 1)
    # knn_scale=0.0: a compliant stream served with positive λ̂ now
    # legitimately publishes (decay pressure relaxes over-satisfied
    # constraints), so the bitwise-neutrality control serves unpriced
    s_off = shortfall_run(stat, refresh_on=False, knn_scale=0.0,
                          knn_seed=seed + 2)
    s_on = shortfall_run(stat, refresh_on=True, knn_scale=0.0,
                         knn_seed=seed + 2)
    ref = {r.rid: r for r in s_off["results"]}
    neutral = (s_on["swaps"] == 0
               and all(_bitwise_same(r, ref[r.rid])
                       for r in s_on["results"]))

    out = {
        "n_requests": n_requests,
        "drift": {"kind": spec.kind, "magnitude": spec.magnitude,
                  "start": spec.start, "end": spec.end},
        "shortfall_off": off["shortfall"],
        "shortfall_on": on["shortfall"],
        "shortfall_ratio": round(on["shortfall"]
                                 / max(off["shortfall"], 1e-12), 4),
        "swaps_on": on["swaps"],
        "compiles_post_warmup": (off["compiles_post_warmup"]
                                 + on["compiles_post_warmup"]),
        "stationary_neutral": bool(neutral),
        "stationary_swaps": s_on["swaps"],
    }
    if verbose:
        print(f"drift[{spec.kind} x{spec.magnitude}] shortfall "
              f"off {out['shortfall_off']:.2f} -> on "
              f"{out['shortfall_on']:.2f} (ratio "
              f"{out['shortfall_ratio']:.3f}, {out['swaps_on']} swaps)  "
              f"stationary_neutral {out['stationary_neutral']}",
              flush=True)
    save_json("latency_drift", out)
    return out


def check_drift(*, quick=False, verbose=True):
    """Drift health gate (AssertionError on regression): refresh-on
    strictly reduces accumulated shortfall under tighten drift with
    zero recompiles, and is bitwise neutral on a compliant stationary
    stream."""
    kw = dict(n_requests=160) if quick else {}
    res = run_drift(verbose=verbose, **kw)
    assert res["shortfall_on"] < res["shortfall_off"], (
        f"drift gate: refresh-on shortfall {res['shortfall_on']} did not "
        f"beat refresh-off {res['shortfall_off']}")
    assert res["swaps_on"] >= 1, (
        "drift gate: refresh-on published nothing under drift")
    assert res["compiles_post_warmup"] == 0, (
        f"drift gate: {res['compiles_post_warmup']} recompiles after "
        f"warmup across the drift runs")
    assert res["stationary_neutral"], (
        f"drift gate: refresh was not a bitwise no-op on the "
        f"no-pressure stationary stream ({res['stationary_swaps']} "
        f"swaps)")
    print("# drift acceptance (refresh-on < refresh-off shortfall under "
          "tighten drift, 0 recompiles, bitwise-neutral when "
          "stationary): PASS")
    return res


def records_drift(res):
    return [Record(
        name=f"serve_drift/{res['drift']['kind']}"
             f"/mag={res['drift']['magnitude']}/n={res['n_requests']}",
        us_per_call=float("nan"),
        derived={"shortfall_off": res["shortfall_off"],
                 "shortfall_on": res["shortfall_on"],
                 "shortfall_ratio": res["shortfall_ratio"],
                 "swaps_on": res["swaps_on"],
                 "compiles_post_warmup": res["compiles_post_warmup"],
                 "stationary_neutral": res["stationary_neutral"]})]


QUANT_TAG, QUANT_D, QUANT_K, QUANT_SLAB = "quant_arch", 12, 4, 32


def run_quant(*, n_requests=96, n_db=96, max_batch=8, seed=21,
              verbose=True):
    """Serve one stream through TWO fused-executor engines — one on the
    f32 KNN predictor, one on its int8-quantized twin — and compare the
    served results request by request. The train db is LOSSLESS under
    int8 (values on the 0.5 grid inside [-63.5, 63.5] with the absmax
    planted in every slab, so each slab scale is exactly 0.5): the
    quantized sweep then reconstructs the db bitwise and every served
    field (perm, utility, exposure, compliant) must match the f32
    engine exactly — the 'unchanged RankingOutput' contract measured at
    the serving boundary rather than the kernel boundary."""
    rng = np.random.default_rng(seed)
    X_db = np.clip(np.round(rng.uniform(
        -63.0, 63.0, size=(n_db, QUANT_D)) * 2.0) / 2.0, -63.5, 63.5)
    X_db[::QUANT_SLAB] = 63.5            # every slab sees the absmax
    lam_db = np.abs(rng.normal(size=(n_db, QUANT_K))).astype(np.float32)
    base = KNNLambdaPredictor.fit(X_db.astype(np.float32), lam_db, k=5)
    quant = base.quantized(mode="int8", slab=QUANT_SLAB)

    mix = (Scenario("feed", m1=300, m2=16, K=QUANT_K, tag=QUANT_TAG,
                    d_cov=QUANT_D),)
    reqs = make_stream(mix, n_requests=n_requests, seed=seed + 1)
    served, metrics = {}, {}
    for name, pred in (("f32", base), ("int8", quant)):
        with ServingEngine(max_batch=max_batch, max_wait_ms=1e9,
                           executor="fused") as eng:
            eng.register_predictor(QUANT_TAG, pred, d_cov=QUANT_D)
            eng.warmup(reqs)
            results = eng.serve_stream(reqs, warmup=False)
            m = eng.metrics
            served[name] = {r.rid: r for r in results}
            metrics[name] = {
                "batches": m.batches,
                "launches_per_batch": (m.kernel_launches / m.batches
                                       if m.batches else float("nan")),
                "compiles_post_warmup": m.compiles_post_warmup,
                "p50_ms": m.summary()["latency_ms"]["p50"]}
    bitwise = (sorted(served["f32"]) == sorted(served["int8"])
               and all(_bitwise_same(served["int8"][rid],
                                     served["f32"][rid])
                       for rid in served["f32"]))
    out = {"n_requests": n_requests, "n_db": n_db, "slab": QUANT_SLAB,
           "bitwise_vs_f32": bool(bitwise), "metrics": metrics}
    if verbose:
        print(f"# quant serve: int8 engine bitwise == f32 engine: "
              f"{bitwise}; launches/batch "
              f"f32={metrics['f32']['launches_per_batch']:.2f} "
              f"int8={metrics['int8']['launches_per_batch']:.2f}; "
              f"recompiles f32={metrics['f32']['compiles_post_warmup']} "
              f"int8={metrics['int8']['compiles_post_warmup']}",
              flush=True)
    return out


def check_quant(*, quick=False, verbose=True):
    """Quantized-serving health gate (AssertionError on regression):
    the int8 engine serves the lossless stream bitwise identical to
    the f32 engine, both keep exactly one kernel launch per flushed
    micro-batch, and neither recompiles after warmup."""
    kw = dict(n_requests=48) if quick else {}
    res = run_quant(verbose=verbose, **kw)
    assert res["bitwise_vs_f32"], (
        "quant gate: int8 engine diverged from the f32 engine on a "
        "lossless db (served RankingOutput must be unchanged)")
    for name, m in res["metrics"].items():
        assert m["launches_per_batch"] == 1.0, (
            f"quant gate: {name} engine at {m['launches_per_batch']} "
            f"kernel launches per batch (expected exactly 1.0)")
        assert m["compiles_post_warmup"] == 0, (
            f"quant gate: {name} engine recompiled "
            f"{m['compiles_post_warmup']}x after warmup")
    print("# quant serve acceptance (int8 engine bitwise == f32 engine"
          ", 1 launch/batch, 0 recompiles): PASS")
    return res


def records_quant(res):
    m = res["metrics"]
    return [Record(
        name=f"serve_quant/n={res['n_requests']}/db={res['n_db']}"
             f"/slab={res['slab']}",
        us_per_call=float("nan"),
        derived={"bitwise_vs_f32": res["bitwise_vs_f32"],
                 "p50_ms_f32": m["f32"]["p50_ms"],
                 "p50_ms_int8": m["int8"]["p50_ms"],
                 "launches_per_batch": m["int8"]["launches_per_batch"],
                 "compiles_post_warmup":
                     m["int8"]["compiles_post_warmup"]})]


FLEET_TAG = "fleet_arch"
FLEET_D, FLEET_K = 12, 4


def _fleet_step_clock(step_s=1e-3):
    """Router clock for the fleet gate: advances a fixed step per call,
    so health deadlines and restart backoff depend on the CALL pattern
    (deterministic given the stream + plan), not wall time. Engines
    keep their real clocks — the p99 the gate reports is real."""
    t = [0.0]

    def clock():
        t[0] += step_s
        return t[0]
    return clock


def run_fleet(*, n_requests=512, max_batch=8, seed=17, slow_ms=1.0,
              ckpt_dir=None, verbose=True):
    """Chaos probe for the replica fleet (serving/fleet.py).

    Builds a 3-replica FleetRouter (each replica a full engine +
    RefreshLane + per-replica CheckpointStore), serves a fault-free
    prefix (n/4 requests) and runs one refresh so the busiest
    replica publishes AND checkpoints epoch 1, then arms the seeded
    chaos plan — crash-at-batch-k and a partial-drain kill on the
    primary of the busiest bucket, a heartbeat blackhole + poisoned
    swap on the second, injected latency on the third — and serves
    the remaining 3n/4 through the failures, with periodic refreshes
    so the poisoned swap actually fires. The fault schedule is
    derived from `seed` and the router runs on a step clock, so the
    same failures replay on every box."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(96, FLEET_D)).astype(np.float32)
    lam = np.abs(rng.normal(size=(96, FLEET_K))).astype(np.float32)
    if ckpt_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="fleet-gate-")
        ckpt_dir = tmp.name
    else:
        tmp = None

    def factory(name):
        eng = ServingEngine(max_batch=max_batch, max_wait_ms=1e9,
                            pipeline_depth=1)
        eng.register_predictor(
            FLEET_TAG, KNNLambdaPredictor.fit(X, lam, k=5), d_cov=FLEET_D)
        store = CheckpointStore(os.path.join(ckpt_dir, name), keep_last=3)
        lane = RefreshLane(eng, eta=0.5, min_samples=8, checkpoint=store)
        return eng, lane

    mix = (Scenario("feed", m1=128, m2=16, K=FLEET_K, tag=FLEET_TAG,
                    d_cov=FLEET_D, m1_jitter=0.0, b_frac=0.25, weight=2.0,
                    surface="feed"),
           Scenario("strip", m1=192, m2=16, K=FLEET_K, tag=FLEET_TAG,
                    d_cov=FLEET_D, m1_jitter=0.0, b_frac=0.25, weight=1.0,
                    surface="strip"))
    reqs = make_stream(mix, n_requests=n_requests, seed=seed + 1)

    router = FleetRouter(
        factory, 3, clock=_fleet_step_clock(),
        health=HealthConfig(suspect_after_s=0.02, dead_after_s=0.5),
        heartbeat_interval_s=float("inf"),
        backoff_base_s=0.02, backoff_cap_s=0.2, seed=seed)
    router.warmup(reqs)

    def serve(chunk, out):
        for r in chunk:
            out += router.submit(r)
            out += router.poll()
            router.tick()

    # ---- fault-free prefix: a checkpointed epoch must exist before
    # the first planned crash, or "restart resumes at last-good λ̂"
    # would be vacuous.
    results = []
    prefix = n_requests // 4
    serve(reqs[:prefix], results)
    results += router.drain()
    # crash target: the primary of the busiest bucket — the chaos plan
    # is keyed by name, the ring decides who that is.
    order = []
    for r in reqs:
        name = router.replicas[
            router._owners(router._bucket_key(r))[0]].name
        if name not in order:
            order.append(name)
    order += [rep.name for rep in router.replicas if rep.name not in order]
    pre = router.refresh()[order[0]][FLEET_TAG]
    assert pre["swapped"] and pre["checkpointed"], (
        f"fleet gate setup: prefix refresh did not checkpoint: {pre}")
    last_good = pre["epoch"]

    # ---- arm the chaos plan and serve the remainder through it
    plan = FaultPlan.chaos(order, seed=seed, slow_ms=slow_ms)
    router.fault_plan = plan
    for rep in router.replicas:
        rep.injector = FaultInjector(plan.faults_for(rep.name), rep.name)
        if rep.lane is not None:
            rep.lane.publish_filter = (
                lambda tag, state, inj=rep.injector: inj.poison_state(state))
    router.arm_faults()
    rest = reqs[prefix:]
    refused = 0
    step = max(1, len(rest) // 3)
    for i in range(0, len(rest), step):
        serve(rest[i:i + step], results)
        for reports in router.refresh().values():   # poisoned swap fires
            rep = reports.get(FLEET_TAG, {})        # on a planned index
            refused += int(str(rep.get("reason", "")).startswith("refused"))
    results += router.drain()

    s = router.fleet_summary()
    served = sorted(r.rid for r in results if not isinstance(r, Shed))
    crash_rep = next(r for r in router.replicas if r.name == order[0])
    restored = (crash_rep.restore_history[0].get(FLEET_TAG)
                if crash_rep.restore_history else None)
    out = {
        "n_requests": n_requests,
        "replicas": 3,
        "exactly_once": served == list(range(n_requests)),
        "orphaned_futures": s["orphaned_futures"],
        "lost": s["lost"],
        "crashes": s["crashes"],
        "restarts": s["restarts"],
        "failovers": s["failovers"],
        "hedges": s["hedges"],
        "duplicates_deduped": s["duplicates_deduped"],
        "retries": s["retries"],
        "heartbeats_missed": s["heartbeats_missed"],
        "poisoned_swaps_refused": refused,
        "last_good_epoch": last_good,
        "restored_epoch": restored,
        "compiles_post_warmup": sum(
            rep.engine.metrics.compiles_post_warmup
            for rep in router.replicas),
        "p50_ms": s.get("latency_ms", {}).get("p50", float("nan")),
        "p99_ms": s.get("latency_ms", {}).get("p99", float("nan")),
    }
    router.close()
    if tmp is not None:
        tmp.cleanup()
    if verbose:
        print(f"fleet: served {len(served)}/{n_requests}  crashes "
              f"{out['crashes']}  restarts {out['restarts']}  failovers "
              f"{out['failovers']}  hedges {out['hedges']}  retries "
              f"{out['retries']}  lost {out['lost']}  orphans "
              f"{out['orphaned_futures']}  restored epoch "
              f"{out['restored_epoch']} (last-good {out['last_good_epoch']})"
              f"  p99 {out['p99_ms']:.2f} ms", flush=True)
    save_json("latency_fleet", out)
    return out


# CI boxes are noisy shared CPUs, and a supervised restart re-warms
# (recompiles) the replica's bucket subset on the caller thread, which
# stalls requests queued on the HEALTHY replicas for the duration —
# so the fleet gate checks the budget with a generous multiple. The
# tight per-request budget is the deadline gate's job; here the p99
# bound only catches pathological stalls (a failover path that
# serializes the fleet, a drain that spins) well past that restart
# pause.
FLEET_P99_TOLERANCE = 40.0


def check_fleet(*, quick=False, verbose=True):
    """Fleet fault-tolerance gate (AssertionError on regression): under
    the full seeded chaos plan, every request is served exactly once,
    nothing is lost or orphaned, the crashed replica restarts and
    resumes at the last-good checkpointed epoch, no incarnation
    recompiles after warmup, and p99 stays within budget x tolerance."""
    kw = dict(n_requests=256) if quick else {}
    res = run_fleet(verbose=verbose, **kw)
    assert res["exactly_once"], (
        "fleet gate: served rids != submitted rids (dropped or "
        "duplicated requests)")
    assert res["orphaned_futures"] == 0, (
        f"fleet gate: {res['orphaned_futures']} fleet futures never "
        f"settled")
    assert res["lost"] == 0, (
        f"fleet gate: {res['lost']} requests exhausted their retry "
        f"budget")
    assert res["crashes"] >= 1 and res["restarts"] >= 1, (
        f"fleet gate: chaos plan did not exercise crash+restart "
        f"(crashes={res['crashes']}, restarts={res['restarts']})")
    assert res["restored_epoch"] == res["last_good_epoch"], (
        f"fleet gate: restarted replica resumed at epoch "
        f"{res['restored_epoch']}, expected last-good "
        f"{res['last_good_epoch']} (cold restart?)")
    assert res["compiles_post_warmup"] == 0, (
        f"fleet gate: {res['compiles_post_warmup']} recompiles after "
        f"warmup across the fleet — a failover path hit a cold bucket")
    budget = LATENCY_BUDGET_MS * FLEET_P99_TOLERANCE
    assert res["p99_ms"] <= budget, (
        f"fleet gate: p99 {res['p99_ms']:.1f} ms over {budget:.0f} ms "
        f"(budget x {FLEET_P99_TOLERANCE:g} CI tolerance)")
    print("# fleet acceptance (exactly-once under chaos, 0 orphans, "
          "0 lost, restart resumes last-good epoch, 0 recompiles, "
          "p99 within tolerance): PASS")
    return res


def records_fleet(res):
    return [Record(
        name=f"serve_fleet/chaos/n={res['n_requests']}"
             f"/replicas={res['replicas']}",
        us_per_call=res["p99_ms"] * 1e3,
        derived={"p50_ms": res["p50_ms"], "p99_ms": res["p99_ms"],
                 "exactly_once": res["exactly_once"],
                 "orphaned_futures": res["orphaned_futures"],
                 "lost": res["lost"], "crashes": res["crashes"],
                 "restarts": res["restarts"],
                 "failovers": res["failovers"],
                 "hedges": res["hedges"], "retries": res["retries"],
                 "restored_epoch": res["restored_epoch"],
                 "compiles_post_warmup": res["compiles_post_warmup"]})]


# Skewed multi-surface mixes for the lattice gate: nominal candidate
# counts sit just past a power-of-two boundary (540 -> 1024,
# 300 -> 512, 140 -> 256) with jitter tight enough that the WHOLE
# jitter range stays in that ceiling's bucket, so the static lattice
# pads 2-3x while the learned corners hug the traffic. Phase 1 shifts
# the heavy surface (a feed redesign doubling its candidate pool past
# the NEXT boundary, 1100 -> 2048) so the second swap has something
# genuinely new to learn.
LATTICE_MIX_P0 = (
    Scenario("feed_a", m1=540, m2=10, K=3, weight=4.0, m1_jitter=0.05,
             surface="feed"),
    Scenario("strip_a", m1=300, m2=8, K=5, weight=2.0, m1_jitter=0.1,
             surface="strip"),
    Scenario("notif_a", m1=140, m2=6, K=3, weight=1.0, m1_jitter=0.05,
             surface="notif"),
)
LATTICE_MIX_P1 = (
    Scenario("feed_b", m1=1100, m2=12, K=3, weight=4.0, m1_jitter=0.05,
             surface="feed"),
    Scenario("strip_a", m1=300, m2=8, K=5, weight=2.0, m1_jitter=0.1,
             surface="strip"),
)


def _lattice_engine(*, max_batch=8, pipeline_depth=1, lattice=None):
    """Deterministic lattice-gate engine (same trick as the refresh
    gate: max_wait_ms=1e9 kills the deadline flush, so batch
    composition is a pure function of the stream and two engines
    serving the same chunks are bitwise comparable)."""
    return ServingEngine(max_batch=max_batch, max_wait_ms=1e9,
                         pipeline_depth=pipeline_depth, lattice=lattice)


def run_lattice(*, chunk=64, max_batch=8, seed=29, pipeline_depth=1,
                verbose=True):
    """Adaptive-lattice health probe.

    Serves the skewed two-phase mix in chunks on one engine with a
    LatticeLane attached: chunk c0 builds the shape histogram on the
    boot power-of-two lattice, a detector-gated re-warm flips to
    learned corners (swap 1), c1 is measured adaptive; c2 switches to
    the phase-1 mix (new shapes fall back to warmed power-of-two
    buckets — out-of-lattice traffic degrades, never compiles), a
    second re-warm learns the shifted mix (swap 2), c3 is measured
    adaptive again. A separate power-of-two engine serves IDENTICAL
    c1+c3 chunks as the padding-waste baseline. Then a poisoned
    proposal (an m2 > m1 corner) exercises the rollback path, and a
    final chunk proves the served stream never paused.

    Checks, per the refined no-recompile contract: zero compiles on
    the dispatch path across both swaps (jit caches frozen at the
    warmed executables; cache growth only inside shadow-warm windows),
    one executable call per flushed batch, every chunk's results
    bitwise-equal to a COLD engine constructed directly on that
    chunk's lattice epoch, and adaptive padding waste (padded/real
    sweep FLOPs) at least 1.5x lower than the power-of-two baseline's
    on the measured chunks.
    """
    s0, s1, s2, s3, s4 = seed, seed + 1, seed + 2, seed + 3, seed + 4
    c0 = make_stream(LATTICE_MIX_P0, n_requests=chunk, seed=s0)
    c1 = make_stream(LATTICE_MIX_P0, n_requests=chunk, seed=s1)
    c2 = make_stream(LATTICE_MIX_P1, n_requests=chunk, seed=s2)
    c3 = make_stream(LATTICE_MIX_P1, n_requests=chunk, seed=s3)
    c4 = make_stream(LATTICE_MIX_P1, n_requests=max_batch, seed=s4)
    for i, r in enumerate(c1 + c2 + c3 + c4):
        r.rid = 10_000 + i            # distinct rids across chunks
    full = c0 + c1 + c2 + c3 + c4

    eng = _lattice_engine(max_batch=max_batch,
                          pipeline_depth=pipeline_depth)
    lane = LatticeLane(
        eng, max_executables=8, min_samples=32,
        detector=TroughDetector(rate_threshold_qps=50.0,
                                lag_threshold_ms=5.0, patience_s=0.25))
    # warm on the FULL stream: every power-of-two bucket either phase
    # reaches is compiled up front, so post-swap out-of-lattice
    # fallbacks are warm too — the zero-dispatch-compile guarantee
    # covers the WHOLE run, swaps and phase shift included.
    eng.warmup(full)

    chunks = []                        # (lattice_epoch, lattice, reqs, got)
    stamps_ok = True

    def serve_chunk(reqs):
        nonlocal stamps_ok
        got = eng.serve_stream(reqs, warmup=False)
        epoch = eng.lattice_epoch()
        stamps_ok &= all(r.lattice_epoch == epoch for r in got)
        chunks.append((epoch, eng.lattice(), reqs, got))
        return got

    def trough_rewarm():
        """Detector-gated re-warm, as the background lane would run it:
        quiet for longer than the patience window -> trough -> propose
        + shadow-warm + flip."""
        now = eng.clock()
        early = lane.maybe_rewarm(now + 0.1)       # patience not yet met
        later = lane.maybe_rewarm(now + 1.0)       # quiet >= patience
        return early, later

    def flops():
        return (eng.metrics.real_flops, eng.metrics.padded_flops)

    serve_chunk(c0)
    no_trough, swap1 = trough_rewarm()
    f0 = flops()
    serve_chunk(c1)                    # measured adaptive (epoch 1)
    f1 = flops()
    serve_chunk(c2)                    # phase shift: pow2 fallbacks
    _, swap2 = trough_rewarm()
    f2 = flops()
    serve_chunk(c3)                    # measured adaptive (epoch 2)
    f3 = flops()

    # rollback: a poisoned proposal (m2 > m1 is not a well-posed
    # ranking corner) must fail validation BEFORE anything flips
    epoch_before = eng.lattice_epoch()
    lane.propose = lambda: Lattice(corners=((64, 128, 4),))
    rollback_rep = lane.rewarm()
    del lane.propose
    rollback_ok = (not rollback_rep["swapped"]
                   and eng.lattice_epoch() == epoch_before
                   and eng.metrics.lattice_rollbacks >= 1)
    got4 = serve_chunk(c4)             # stream uninterrupted after it
    rollback_ok = rollback_ok and len(got4) == len(c4)

    m = eng.metrics
    sizes = eng.jit_cache_sizes()

    # measured padding waste on the adaptive chunks ONLY (c1 under
    # epoch 1, c3 under epoch 2 — c2 deliberately excluded: it is the
    # phase-shift chunk serving out-of-lattice shapes on the pow2
    # fallback) vs a power-of-two engine serving the SAME chunks
    adaptive_waste = (
        ((f1[1] - f0[1]) + (f3[1] - f2[1]))
        / ((f1[0] - f0[0]) + (f3[0] - f2[0])))
    base = _lattice_engine(max_batch=max_batch,
                           pipeline_depth=pipeline_depth)
    base.warmup(full)
    bf0 = (base.metrics.real_flops, base.metrics.padded_flops)
    base.serve_stream(c1, warmup=False)
    base.serve_stream(c3, warmup=False)
    bf1 = (base.metrics.real_flops, base.metrics.padded_flops)
    pow2_waste = (bf1[1] - bf0[1]) / (bf1[0] - bf0[0])
    base_cpw = base.metrics.compiles_post_warmup
    base.close()

    # per-epoch parity: each chunk vs a COLD engine built directly on
    # that chunk's lattice (the boot pow2 lattice for epoch 0)
    parity_ok = True
    for _, lattice, creqs, got in chunks:
        cold = _lattice_engine(max_batch=max_batch,
                               pipeline_depth=pipeline_depth,
                               lattice=lattice)
        ref = {r.rid: r for r in cold.serve_stream(creqs)}
        parity_ok &= all(_bitwise_same(r, ref[r.rid]) for r in got)
        cold.close()

    out = {
        "n_requests": len(full),
        "chunk": chunk,
        "swaps": m.lattice_swaps,
        "final_epoch": eng.lattice_epoch(),
        "corners": [list(map(list, c[1].corners or ()))
                    for c in chunks if c[1].adaptive][-1:],
        "detector_gated": bool(no_trough["reason"] == "no-trough"
                               and swap1["swapped"] and swap2["swapped"]),
        "compiles_post_warmup": m.compiles_post_warmup,
        "base_compiles_post_warmup": base_cpw,
        "shadow_compiles": m.shadow_compiles,
        "shadow_warm_ms_p50": m._pct(m.shadow_warm_ms)["p50"],
        "executable_calls": m.executable_calls,
        "batches": m.batches,
        "jit_cache_sizes": dict(sizes),
        "lattice_rollbacks": m.lattice_rollbacks,
        "rollback_ok": bool(rollback_ok),
        "parity_ok": bool(parity_ok),
        "stamps_ok": bool(stamps_ok),
        "padding_waste_pow2": round(pow2_waste, 4),
        "padding_waste_adaptive": round(adaptive_waste, 4),
        "waste_improvement": round(pow2_waste / adaptive_waste, 4),
        "epoch_of_chunk": [c[0] for c in chunks],
    }
    eng.close()
    if verbose:
        print(f"lattice: swaps {out['swaps']}  epoch {out['final_epoch']}  "
              f"waste pow2 {out['padding_waste_pow2']} vs adaptive "
              f"{out['padding_waste_adaptive']} "
              f"({out['waste_improvement']}x)  "
              f"compiles_post_warmup {out['compiles_post_warmup']}  "
              f"shadow {out['shadow_compiles']}  "
              f"parity {out['parity_ok']}  rollback {out['rollback_ok']}",
              flush=True)
    save_json("latency_lattice", out)
    return out


def check_lattice(*, quick=False, verbose=True):
    """Adaptive-lattice health gate (kernel_bench-style: AssertionError
    on regression): the traffic-learned lattice must cut measured
    padding waste >= 1.5x vs power-of-two on the skewed mix, across
    >= 2 detector-gated mid-stream swaps with ZERO dispatch-path
    compiles (cache growth only inside shadow-warm windows), one
    dispatch per batch, per-epoch serving bitwise-equal to a cold
    engine on that epoch's lattice, and a poisoned proposal rolling
    back with the served stream uninterrupted."""
    kw = dict(chunk=48) if quick else {}
    res = run_lattice(verbose=verbose, **kw)
    assert res["swaps"] >= 2, (
        f"lattice gate: only {res['swaps']} lattice swaps — the two-phase "
        f"mix should force a re-warm per phase")
    assert res["detector_gated"], (
        "lattice gate: the trough detector did not gate the re-warms "
        "(no-trough refusal then patience-window swap)")
    assert res["waste_improvement"] >= 1.5, (
        f"lattice gate: adaptive lattice only cut padding waste "
        f"{res['waste_improvement']}x (pow2 {res['padding_waste_pow2']} "
        f"vs adaptive {res['padding_waste_adaptive']}) — need >= 1.5x")
    assert res["compiles_post_warmup"] == 0, (
        f"lattice gate: {res['compiles_post_warmup']} dispatch-path "
        f"compiles — the refined contract allows cache growth only in "
        f"shadow-warm windows")
    assert res["shadow_compiles"] >= 1, (
        "lattice gate: no shadow compiles recorded — the swaps served "
        "stale executables?")
    assert all(v == 1 for v in res["jit_cache_sizes"].values()), (
        f"lattice gate: jit cache grew past the warmed executable: "
        f"{res['jit_cache_sizes']}")
    assert res["executable_calls"] == res["batches"], (
        f"lattice gate: {res['executable_calls']} executable calls for "
        f"{res['batches']} batches — a swap added a dispatch")
    assert res["parity_ok"], (
        "lattice gate: post-swap serving diverged bitwise from a cold "
        "engine warmed directly on that epoch's lattice")
    assert res["stamps_ok"], (
        "lattice gate: a served row's lattice_epoch stamp disagreed "
        "with the lattice generation live at its dispatch")
    assert res["rollback_ok"], (
        "lattice gate: poisoned proposal did not roll back to last-good "
        "with the stream uninterrupted")
    print("# lattice acceptance (>= 1.5x waste cut, >= 2 detector-gated "
          "swaps, 0 dispatch-path compiles, hot == cold bitwise per "
          "epoch, poisoned proposal rolls back): PASS")
    return res


def records_lattice(res):
    return [Record(
        name=f"serve_lattice/rewarm/n={res['n_requests']}"
             f"/chunk={res['chunk']}",
        us_per_call=res["shadow_warm_ms_p50"] * 1e3,
        derived={"swaps": res["swaps"],
                 "final_epoch": res["final_epoch"],
                 "waste_pow2": res["padding_waste_pow2"],
                 "waste_adaptive": res["padding_waste_adaptive"],
                 "waste_improvement": res["waste_improvement"],
                 "compiles_post_warmup": res["compiles_post_warmup"],
                 "shadow_compiles": res["shadow_compiles"],
                 "executable_calls": res["executable_calls"],
                 "batches": res["batches"],
                 "parity_ok": res["parity_ok"],
                 "rollback_ok": res["rollback_ok"],
                 "detector_gated": res["detector_gated"]})]


def records(rows):
    return [Record(
        name=f"serve/m1={r['m1']}/K={r['K']}/m2={r['m2']}/B={r['batch']}",
        us_per_call=r["us_total"],
        derived={"us_per_user": round(r["us_per_user"], 1),
                 "within_50ms": r["within_50ms"]})
        for r in rows]


def records_frontier(rows):
    return [Record(
        name=f"serve_frontier/offered={r['offered_qps']}qps"
             f"/frac={r['offered_frac_of_capacity']}",
        us_per_call=r["p99_ms"] * 1e3,
        derived={"p50_ms": r["p50_ms"], "p95_ms": r["p95_ms"],
                 "p99_ms": r["p99_ms"],
                 "achieved_qps": r["achieved_qps"],
                 "deadline_hit_rate": r["deadline_hit_rate"],
                 "deadline_misses": r["deadline_misses"],
                 "queue_lag_ms_last": r["queue_lag_ms_last"],
                 "saturated": r["saturated"],
                 "within_50ms": r["within_50ms"]})
        for r in rows]


def records_engine(rows):
    return [Record(
        name=f"serve_engine/{r['mode']}/n={r['n_requests']}"
             f"/B={r['max_batch']}/wait={r['max_wait_ms']}ms",
        us_per_call=r["p50_ms"] * 1e3,
        derived={"p50_ms": r["p50_ms"], "p95_ms": r["p95_ms"],
                 "p99_ms": r["p99_ms"], "fill": r["fill_rate"],
                 "throughput_rps": r["throughput_rps"],
                 "speedup_vs_sync": r["speedup_vs_sync"],
                 "perms_match": r["perms_match_baseline"],
                 "recompiles_post_warmup": r["compiles_post_warmup"],
                 "within_50ms": r["within_50ms"]})
        for r in rows]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: small direct sweep, 256-request stream")
    ap.add_argument("--only", default="all",
                    choices=["all", "direct", "engine", "frontier",
                             "deadline", "refresh", "drift", "quant",
                             "fleet", "lattice"])
    ap.add_argument("--frontier", action="store_true",
                    help="also sweep p99 vs offered load (paced open-loop "
                         "Poisson arrivals below/around saturation)")
    ap.add_argument("--trials", type=int, default=None,
                    help="paired throughput trials (default 7; quick 3)")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="write BENCH_latency_serve.json to OUT (a "
                         "directory, or an explicit *.json path)")
    ap.add_argument("--engine-child", metavar="OUT_JSON",
                    help=argparse.SUPPRESS)     # internal: subprocess mode
    ap.add_argument("--engine-config", metavar="JSON",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.engine_child:                       # dedicated-core subprocess
        from repro.serving import Scenario
        cfg = json.loads(args.engine_config)
        cfg["depths"] = tuple(cfg["depths"])
        cfg["scenarios"] = tuple(Scenario(**sc) for sc in cfg["scenarios"])
        rows = run_engine(**cfg)
        with open(args.engine_child, "w") as f:
            json.dump(rows, f)
        return

    if args.only == "deadline":
        # the admission health gate writes its own BENCH_deadline.json
        # (never the engine step's BENCH_latency_serve.json — the two
        # run as separate CI steps against the same artifact dir).
        res = check_deadline(quick=args.quick)
        recs = records_deadline(res)
        for rec in recs:
            print(rec.csv())
        if args.json:
            write_bench_json(args.json, "deadline", recs,
                             meta={"quick": args.quick})
        return

    if args.only == "refresh":
        # the refresh-lane health gate writes its own BENCH_refresh.json
        res = check_refresh(quick=args.quick)
        recs = records_refresh(res)
        for rec in recs:
            print(rec.csv())
        if args.json:
            write_bench_json(args.json, "refresh", recs,
                             meta={"quick": args.quick})
        return

    if args.only == "drift":
        # the drift regression gate writes its own BENCH_drift.json
        res = check_drift(quick=args.quick)
        recs = records_drift(res)
        for rec in recs:
            print(rec.csv())
        if args.json:
            write_bench_json(args.json, "drift", recs,
                             meta={"quick": args.quick})
        return

    if args.only == "quant":
        # the quantized-serving gate writes its own BENCH_quant_serve.json
        res = check_quant(quick=args.quick)
        recs = records_quant(res)
        for rec in recs:
            print(rec.csv())
        if args.json:
            write_bench_json(args.json, "quant_serve", recs,
                             meta={"quick": args.quick})
        return

    if args.only == "fleet":
        # the fleet fault-tolerance gate writes its own BENCH_fleet.json
        res = check_fleet(quick=args.quick)
        recs = records_fleet(res)
        for rec in recs:
            print(rec.csv())
        if args.json:
            write_bench_json(args.json, "fleet", recs,
                             meta={"quick": args.quick})
        return

    if args.only == "lattice":
        # the adaptive-lattice gate writes its own BENCH_lattice.json
        res = check_lattice(quick=args.quick)
        recs = records_lattice(res)
        for rec in recs:
            print(rec.csv())
        if args.json:
            write_bench_json(args.json, "lattice", recs,
                             meta={"quick": args.quick})
        return

    all_recs = []
    if args.only in ("all", "direct"):
        kw = (dict(sizes=((1000, 5, 50), (10000, 8, 50)), batches=(1, 64),
                   n_db=2000) if args.quick else {})
        for rec in records(run(**kw)):
            all_recs.append(rec)
            print(rec.csv())
    if args.frontier or args.only == "frontier":
        fkw = (dict(n_requests=192, load_fracs=(0.5, 0.85, 2.0))
               if args.quick else {})
        for rec in records_frontier(run_frontier(**fkw)):
            all_recs.append(rec)
            print(rec.csv())
    engine_rows = None
    if args.only in ("all", "engine"):
        ekw = (dict(n_requests=320, trials=3) if args.quick else {})
        if args.trials is not None:
            ekw["trials"] = args.trials
        engine_rows = run_engine(**ekw)
        for rec in records_engine(engine_rows):
            all_recs.append(rec)
            print(rec.csv())
    if args.json:           # artifact lands even if acceptance exits 1
        write_bench_json(args.json, "latency_serve", all_recs,
                         meta={"quick": args.quick, "only": args.only})
    if engine_rows is not None:
        rows = engine_rows
        piped = [r for r in rows if r["pipeline_depth"] > 0]
        correct = (all(r["perms_match_baseline"] for r in rows)
                   and all(r["compiles_post_warmup"] == 0 for r in rows))
        fast = any(r["speedup_vs_sync"] >= 1.2 for r in piped)
        if not correct:
            print("# pipeline acceptance: FAIL (results diverged or "
                  "recompiled after warmup)")
            raise SystemExit(1)
        if fast:
            print("# pipeline acceptance (>=1.2x, identical perms, "
                  "0 recompiles): PASS")
        else:
            # correctness holds; the speedup shortfall on a loaded CI
            # box is measurement noise, not a result change -> warn.
            print("# pipeline acceptance: WARN — correctness PASS, "
                  f"median speedup "
                  f"{max(r['speedup_vs_sync'] for r in piped):.2f}x < 1.2x "
                  "(noisy/starved host? see docs/benchmarks.md)")


if __name__ == "__main__":
    main()
