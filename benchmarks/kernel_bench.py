"""Kernel-path benchmarks: oracle (XLA) paths timed on CPU, kernel HBM
models derived analytically — plus a kernel-health gate for CI.

interpret=True Pallas runs execute the kernel body in Python per grid
step — meaningful for CORRECTNESS, meaningless for wall time. So here we
time the XLA oracle path (what the CPU actually runs) and report, per
kernel, the analytic HBM-traffic ratio oracle/kernel — the quantity the
TPU kernel improves (validated against the dry-run roofline for the
paper cells in EXPERIMENTS.md §Perf).

The rank+audit section compares the two ways of producing a complete
RankingOutput from the kernel path:

  baseline  rank kernel, then a separate post-rank XLA audit program
            that re-reads u/a: gathers the (K+1)*m2 selected values
            back out of HBM via a materialized (n, K, m2) int32 index
            tensor (the pre-fusion serving code, kept here as the
            measured baseline);
  fused     the rank+audit kernel: the merge carries the selected
            values as VMEM payload, the audit runs at the flush step,
            and the audit's HBM traffic collapses to the gamma/b reads
            and the tiny outputs.

Both the analytic audit-traffic ratio and the measured wall-time delta
between the corresponding XLA programs (two dispatches + index
materialization vs one fused program with a broadcast gather) are
reported.

The predict+rank section does the same for the λ-predictor handoff the
single-sweep dispatcher (kernels.ops.predict_rank_audited) deletes:

  baseline  TWO device programs — a predict executable whose λ̂ (and,
            for KNN, whose (B, n_train) distance matrix) round-trips
            HBM, then a separate rank+audit executable that reads λ̂
            back;
  fused     ONE program: affine predictors fold into the rank kernel's
            VMEM prologue, KNN fuses its weighting into the db sweep's
            flush step, and λ̂ never exists between programs.

The knn_fused section covers the single-grid KNN kernel
(`kernels/knn_topk.knn_rank_audited_pallas`) specifically: the HBM
traffic model for the three ways of serving a KNN micro-batch (XLA
chunked predict -> rank with its per-slab d2 materializations; the PR 4
two-kernel chain with its λ̂ HBM round-trip; the single grid), and the
measured two-dispatch-vs-one wall of the corresponding XLA stand-in
programs.

`python -m benchmarks.kernel_bench --quick` is the CI smoke: small
shapes, plus `check_rank_audited`, `check_predict_rank` and
`check_knn_fused` — hard gates that fail the build if interpret-mode
parity with the predict-then-rank oracle breaks, if the dispatchers
stop engaging the kernels for kernel-eligible shapes, if the
m2 > MAX_KERNEL_M2 fallbacks stop engaging, or if the serving engine's
KNN buckets stop recording exactly one kernel launch per flushed
micro-batch. `--json OUT` writes machine-readable
BENCH_kernel_bench.json / BENCH_knn_fused.json (medians, geometry,
backend) for the cross-PR perf trajectory; CI uploads both as
artifacts. `--budget-s` bounds the --quick wall clock: blowing it
fails the job with a named per-section timing table instead of the
runner's silent timeout.
"""

from __future__ import annotations

import argparse
import os
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Record, timed, write_bench_json
from repro.core.ranking import AUDIT_TOL, PARITY_ATOL, PARITY_RTOL
from repro.kernels import ref


_PARITY_FIELDS = ("perm", "utility", "exposure", "compliant")


def _assert_parity(got, want, msg, fields=_PARITY_FIELDS):
    """core.ranking's kernel parity contract: perm / compliant exact,
    float outputs within PARITY_RTOL / PARITY_ATOL. `want` is a
    RankingOutput or a dict of its fields."""
    for f in fields:
        g = np.asarray(getattr(got, f))
        w = np.asarray(want[f] if isinstance(want, dict) else getattr(want, f))
        if f in ("perm", "compliant"):
            np.testing.assert_array_equal(g, w, err_msg=f"{msg} broke on {f}")
        else:
            np.testing.assert_allclose(g, w, rtol=PARITY_RTOL,
                                       atol=PARITY_ATOL,
                                       err_msg=f"{msg} broke on {f}")


def _rank_audit_problem(n, m1, K, m2):
    ks = jax.random.split(jax.random.key(7), 5)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.1).astype(jnp.float32)
    lam = jnp.abs(jax.random.normal(ks[2], (n, K)))
    b = jnp.abs(jax.random.normal(ks[3], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[4], (n, m2)))
    return u, a, b, lam, gamma


def _xla_audit_epilogue(u, a, b, gamma, idx):
    """The pre-fusion post-rank audit, verbatim: gather the selected
    values back out of u/a through a materialized (n, K, m2) index
    tensor, then einsum against gamma. Kept as the measured baseline."""
    u_sel = jnp.take_along_axis(u, idx, axis=-1)
    utility = jnp.einsum("nm,nm->n", u_sel, gamma)
    a_sel = jnp.take_along_axis(
        a, idx[:, None, :].repeat(a.shape[1], axis=1), axis=-1)
    exposure = jnp.einsum("nkm,nm->nk", a_sel, gamma)
    compliant = jnp.all(exposure >= b - AUDIT_TOL, axis=-1)
    return utility, exposure, compliant


def _audit_traffic_model(K: int, m2: int) -> dict:
    """Per-request HBM bytes of the audit step alone (rank traffic is
    identical on both sides: read u/a once, write the top-m2 pairs).

      XLA epilogue: read back idx (m2 i32), materialize the broadcast
      (K, m2) i32 index tensor (write + read), random-gather the
      (K+1)*m2 selected f32 values out of the HBM-resident u/a
      (counted at the 4-byte compulsory floor — real gathers touch a
      full cache line per hit), read gamma/b, write the audit outputs.

      fused kernel: the (K+1)*m2 selected values are already in VMEM
      scratch when the flush step runs — the audit's only HBM traffic
      is reading gamma/b and writing the audit outputs.
    """
    out_bytes = (1 + K + 1) * 4                    # utility, exposure, compliant
    gb_bytes = (m2 + K) * 4                        # gamma + b reads
    xla = (m2 * 4                                  # idx read-back
           + 2 * K * m2 * 4                        # materialized index tensor
           + (K + 1) * m2 * 4                      # gathered u/a values
           + gb_bytes + out_bytes)
    fused = gb_bytes + out_bytes
    return {"audit_xla_bytes": xla, "audit_fused_bytes": fused,
            "audit_ratio_xla_over_fused": round(xla / fused, 3)}


def run_rank_audit(n, m1, K, m2, *, iters=7):
    """rank-vs-rank+audit at one problem shape. Three measurements:

    * end-to-end: (rank program; audit program) — two dispatches, the
      audit re-reading u/a — vs the single fused XLA program. Both
      sides share the dominant argsort, so this delta is small and
      noise-prone on a busy host; reported for completeness.
    * audit step isolated: the post-rank XLA epilogue alone vs the
      flush-equivalent arithmetic the fused kernel adds (the shared
      audit on already-selected (K+1)*m2 values, no gather, no index
      materialization). This is precisely the work fusion deletes /
      keeps, and is the robust measured win.
    * the analytic per-request audit HBM-traffic model.
    """
    from repro.core.ranking import audit_selected

    u, a, b, lam, gamma = _rank_audit_problem(n, m1, K, m2)
    rank_j = jax.jit(lambda u, a, lam: ref.fused_rank_ref(u, a, lam, m2)[1])
    audit_j = jax.jit(_xla_audit_epilogue)
    fused_j = jax.jit(
        lambda u, a, b, lam, gamma: ref.rank_audited_ref(
            u, a, b, lam, gamma, m2)[2])
    flush_j = jax.jit(
        lambda u_sel, a_sel, gamma, b: audit_selected(
            u_sel, a_sel, gamma, b, tol=AUDIT_TOL)[0])

    idx = jax.block_until_ready(rank_j(u, a, lam))
    u_sel = jnp.take_along_axis(u, idx, axis=-1)
    a_sel = jnp.take_along_axis(a, idx[:, None, :], axis=-1)

    base_us = timed(lambda: audit_j(u, a, b, gamma, rank_j(u, a, lam))[0],
                    iters=iters)
    fused_us = timed(lambda: fused_j(u, a, b, lam, gamma), iters=iters)
    epi_us = timed(lambda: audit_j(u, a, b, gamma, idx)[0], iters=iters)
    flush_us = timed(lambda: flush_j(u_sel, a_sel, gamma, b), iters=iters)
    model = _audit_traffic_model(K, m2)
    return {
        "name": f"rank_audit/m1={m1}/K={K}/m2={m2}/n={n}",
        "us": fused_us,
        "derived": {
            **model,
            "us_baseline_end_to_end": round(base_us, 1),
            "wall_end_to_end": round(base_us / fused_us, 3),
            "us_audit_epilogue": round(epi_us, 1),
            "us_audit_flush_equiv": round(flush_us, 1),
            "wall_audit_step": round(epi_us / flush_us, 3),
        },
    }


def _fit_predictors(K, d, n_db, seed=11):
    from repro.core.predictors import KNNLambdaPredictor, LinearLambdaPredictor

    ks = jax.random.split(jax.random.key(seed), 2)
    X_tr = jax.random.uniform(ks[0], (n_db, d))
    lam_tr = jnp.abs(jax.random.normal(ks[1], (n_db, K)))
    return {"linear": LinearLambdaPredictor.fit(X_tr, lam_tr),
            "knn": KNNLambdaPredictor.fit(X_tr, lam_tr, k=10)}


def _predict_traffic_model(family: str, B: int, N: int, D: int,
                           K: int) -> dict:
    """Per-batch HBM bytes of the predict stage + λ̂ handoff alone
    (rank+audit traffic is identical on both sides).

      two-dispatch: the predict program's own traffic, plus λ̂ written
      out by program 1 and read back by program 2 (2·B·K floats). For
      KNN the predict program also materializes the (B, N) distance
      matrix (write + read around the top-k) — the paper-scale killer.

      fused: λ̂ never exists in HBM. Affine families re-read X/W (they
      were reading them anyway); the KNN kernel re-streams the db once
      per resident query tile (tile_q = 32 when the batch allows) and
      keeps distances, weights, and λ̂ in VMEM.
    """
    if family == "linear":
        xla = (B * D + K * D + 2 * B * K) * 4
        fused = (B * D + K * D) * 4
    else:
        from repro.kernels.ops import knn_lambda_tile_q

        sweeps = -(-B // knn_lambda_tile_q(B))
        xla = (N * D + 2 * B * N + 2 * B * K) * 4
        fused = sweeps * N * D * 4
    return {"predict_xla_bytes": xla, "predict_fused_bytes": fused,
            "predict_ratio_xla_over_fused": round(xla / fused, 3)}


def run_predict_rank(n, m1, K, m2, *, d=20, n_db=8192, iters=7):
    """predict+rank+audit at one problem shape, per predictor family.

    Measured (CPU XLA stand-ins): the two-dispatch baseline — a jit'd
    predict program, then a jit'd rank+audit program reading λ̂ back —
    vs the single fused program. Both sides share the dominant
    rank work, so the wall delta isolates the dispatch + λ̂ (and KNN
    d2-matrix) round-trip the fusion deletes. The analytic per-batch
    traffic model for the predict stage rides along.
    """
    u, a, b, _, gamma = _rank_audit_problem(n, m1, K, m2)
    X = jax.random.normal(jax.random.key(23), (n, d))
    rows = []
    for family, pred in _fit_predictors(K, d, n_db).items():
        predict_j = jax.jit(pred.predict)
        rank_j = jax.jit(
            lambda u, a, b, lam, gamma: ref.rank_audited_ref(
                u, a, b, lam, gamma, m2)[2])
        fused_j = jax.jit(
            lambda X, u, a, b, gamma: ref.predict_rank_audited_ref(
                X, pred, u, a, b, gamma, m2)[2])
        two_us = timed(lambda: rank_j(u, a, b, predict_j(X), gamma),
                       iters=iters)
        one_us = timed(lambda: fused_j(X, u, a, b, gamma), iters=iters)
        model = _predict_traffic_model(family, n, n_db, d, K)
        rows.append({
            "name": f"predict_rank/{family}/m1={m1}/K={K}/m2={m2}"
                    f"/n={n}/n_db={n_db}",
            "us": one_us,
            "derived": {
                **model,
                "us_two_dispatch": round(two_us, 1),
                "wall_two_over_one": round(two_us / one_us, 3),
            },
        })
    return rows


def _knn_fused_traffic_model(B: int, N: int, D: int, K: int,
                             m1: int, m2: int, k: int = 10) -> dict:
    """Per-micro-batch HBM bytes of the three ways to serve a KNN
    bucket, at the tile geometry the dispatcher actually runs
    (kernels.common.TILE_B batch rows resident per db sweep):

      xla_chunked  predict program (knn_predict_chunked: every db slab's
                   (B, chunk) d2 block materializes — write + read —
                   summing to 2·B·N floats across the sweep, plus the db
                   stream) writes λ̂ out; rank+audit program reads λ̂
                   back and streams u/a. Two dispatches.
      chain        PR 4: knn_lambda kernel (db streamed once per
                   resident query tile, d2 never leaves VMEM) writes λ̂
                   to HBM; rank_audited kernel reads it back and
                   streams u/a. One executable, two kernel launches,
                   one λ̂ round-trip.
      single_grid  this PR: one kernel launch; the λ̂ round-trip is
                   gone (the (B, K) lam output is written once as
                   observability, never read back) and so is the
                   second launch's pipeline drain/fill.

    Rank-side traffic (u/a streamed once + outputs) is identical
    everywhere and included so the ratios reflect whole micro-batches.
    """
    from repro.kernels.ops import knn_lambda_tile_q

    # db sweeps per micro-batch: one per resident query tile, at the
    # same tile rule the dispatcher runs (32-wide when the batch fills)
    sweeps = -(-B // knn_lambda_tile_q(B))
    db_stream = sweeps * N * (D + K) * 4       # db rows + λ rows, per sweep
    rank_stream = B * (K + 1) * m1 * 4         # u + a, read once
    outputs = B * (2 * m2 + K + K + 2) * 4     # vals/idx/util/expo/comp/lam
    lam_rt = 2 * B * K * 4                     # λ̂ write + read back
    d2_slabs = 2 * B * N * 4                   # chunked-scan d2 blocks
    # the XLA path streams the db once and gathers only the B·k
    # selected λ rows (the kernels stream the full (N, K) λ database
    # per sweep instead — payload ride-along trades λ bytes for never
    # touching HBM with d2/idx)
    xla = (d2_slabs + N * D * 4 + B * k * K * 4
           + lam_rt + rank_stream + outputs)
    chain = db_stream + lam_rt + rank_stream + outputs
    fused = db_stream + rank_stream + outputs
    return {
        "knn_xla_bytes": xla, "knn_chain_bytes": chain,
        "knn_fused_bytes": fused,
        "ratio_xla_over_fused": round(xla / fused, 3),
        "ratio_chain_over_fused": round(chain / fused, 3),
        "lam_roundtrip_bytes_eliminated": lam_rt,
        "kernel_launches_chain": 2, "kernel_launches_fused": 1,
    }


def run_knn_fused(n, m1, K, m2, *, d=20, n_db=8192, k=10, iters=7):
    """The single-grid KNN kernel's section: the three-way traffic
    model above, plus a measured CPU stand-in for the dispatch overhead
    the fusion deletes — the two-dispatch XLA baseline (a jit'd
    knn_predict_chunked program, then a jit'd rank+audit program
    reading λ̂ back) against the same math as ONE jit program. Both
    sides run the slab-streaming predictor, so the wall delta isolates
    the λ̂ handoff + second dispatch; interpret-mode Pallas wall time
    would be meaningless (see module docstring)."""
    from repro.core.predictors import KNNLambdaPredictor, knn_predict_chunked

    u, a, b, _, gamma = _rank_audit_problem(n, m1, K, m2)
    ks = jax.random.split(jax.random.key(29), 3)
    X = jax.random.normal(ks[0], (n, d))
    X_tr = jax.random.uniform(ks[1], (n_db, d))
    lam_tr = jnp.abs(jax.random.normal(ks[2], (n_db, K)))
    pred = KNNLambdaPredictor.fit(X_tr, lam_tr, k=k)

    chunk = min(2048, n_db)
    predict_j = jax.jit(lambda X: knn_predict_chunked(
        pred.X_db, pred.lam_db, X, k=k, chunk=chunk))
    rank_j = jax.jit(
        lambda u, a, b, lam, gamma: ref.rank_audited_ref(
            u, a, b, lam, gamma, m2)[2])
    one_j = jax.jit(
        lambda X, u, a, b, gamma: ref.rank_audited_ref(
            u, a, b, knn_predict_chunked(
                pred.X_db, pred.lam_db, X, k=k, chunk=chunk),
            gamma, m2)[2])
    two_us = timed(lambda: rank_j(u, a, b, predict_j(X), gamma), iters=iters)
    one_us = timed(lambda: one_j(X, u, a, b, gamma), iters=iters)
    model = _knn_fused_traffic_model(n, n_db, d, K, m1, m2, k=k)
    return {
        "name": f"knn_fused/m1={m1}/K={K}/m2={m2}/n={n}/n_db={n_db}",
        "us": one_us,
        "derived": {
            **model,
            "us_two_dispatch": round(two_us, 1),
            "wall_two_over_one": round(two_us / one_us, 3),
        },
    }


def _knn_quant_traffic_model(B: int, N: int, D: int, K: int,
                              *, slab: int = 512, k: int = 10) -> dict:
    """Per-micro-batch db-sweep HBM bytes, f32 vs quantized storage.

    The sweep streams, per resident query tile, the db rows plus the λ
    payload. Quantization changes only the ROW stream: f32 rows are
    N·D·4 bytes; int8 rows are N·D·1 plus the exact |x̃|² sidecar
    (N·4) and the per-slab scales (N/slab · 4); bf16 rows are N·D·2
    plus the same sidecars. The λ payload (N·K·4) and the tiny
    survivor re-score traffic (B·(k+QUANT_EXTRA)·D·4, already in VMEM
    as kernel payload — counted 0 here) are identical across modes, so
    the headline ratio is reported on the row stream (what the
    tentpole optimizes) and the whole-sweep ratio alongside."""
    from repro.kernels.ops import knn_lambda_tile_q

    sweeps = -(-B // knn_lambda_tile_q(B))
    sidecar = N * 4 + -(-N // slab) * 4           # y2_q + per-slab scales
    rows_f32 = sweeps * N * D * 4
    rows_int8 = sweeps * (N * D * 1 + sidecar)
    rows_bf16 = sweeps * (N * D * 2 + sidecar)
    lam_stream = sweeps * N * K * 4
    return {
        "db_rows_f32_bytes": rows_f32,
        "db_rows_int8_bytes": rows_int8,
        "db_rows_bf16_bytes": rows_bf16,
        "rows_ratio_f32_over_int8": round(rows_f32 / rows_int8, 3),
        "rows_ratio_f32_over_bf16": round(rows_f32 / rows_bf16, 3),
        "sweep_ratio_f32_over_int8": round(
            (rows_f32 + lam_stream) / (rows_int8 + lam_stream), 3),
        "kernel_launches_quant": 1,
    }


def run_knn_quant(n, m1, K, m2, *, d=20, n_db=8192, k=10, iters=7):
    """Quantized-db sweep section: the storage-traffic model above plus
    a measured CPU stand-in — the jitted XLA quant-scan path
    (predictors.knn_predict_quant: int8 slab sweep + exact survivor
    re-score) against the f32 chunked scan on the same db. CPU wall
    does not see the MXU/HBM win (interpret-mode Pallas would be
    meaningless, and XLA CPU widens int8 dots anyway), so the wall
    numbers are recorded for trajectory, not gated; the byte model is
    what CI gates (check_knn_quant)."""
    from repro.core.predictors import (
        KNNLambdaPredictor, knn_predict_chunked, knn_predict_quant)

    ks = jax.random.split(jax.random.key(37), 3)
    X = jax.random.normal(ks[0], (n, d))
    X_tr = jax.random.uniform(ks[1], (n_db, d))
    lam_tr = jnp.abs(jax.random.normal(ks[2], (n_db, K)))
    pred = KNNLambdaPredictor.fit(X_tr, lam_tr, k=k)
    slab = min(512, n_db)
    predq = pred.quantized(mode="int8", slab=slab)

    f32_j = jax.jit(lambda X: knn_predict_chunked(
        pred.X_db, pred.lam_db, X, k=k, chunk=slab))
    q_j = jax.jit(lambda X: knn_predict_quant(
        predq.X_q, predq.q_scale, predq.y2_q, predq.lam_db, X, k=k,
        mode="int8"))
    f32_us = timed(lambda: f32_j(X), iters=iters)
    q_us = timed(lambda: q_j(X), iters=iters)
    model = _knn_quant_traffic_model(n, n_db, d, K, slab=slab, k=k)
    return {
        "name": f"knn_quant/d={d}/K={K}/n={n}/n_db={n_db}",
        "us": q_us,
        "derived": {
            **model,
            "us_f32_scan": round(f32_us, 1),
            "wall_f32_over_quant": round(f32_us / q_us, 3),
        },
    }


def check_knn_quant() -> None:
    """Quantized-db kernel health gate (CI smoke): raises on any
    regression.

    1. parity sweep: the int8 engine path (quantized predictor through
       ops.predict_rank_audited — the quantized single-grid kernel)
       matches the quantized oracle (ref.predict_rank_audited_ref over
       the same packed arrays) BITWISE on perm/utility/exposure/
       compliant (λ̂ to 1-ulp) for both quant modes, at a slab dividing
       n_train and one that does not — INCLUDING a db with planted
       near-ties that force the margin-guard fallback (guard fires,
       selection still exact).
    2. lossless bitwise: on an int8-representable db the int8 engine's
       RankingOutput — λ̂ included — is bit-for-bit the f32 engine's.
    3. launches: the quantized route engages the quantized single-grid
       kernel exactly once per batch (kernel_launches_per_batch == 1.0
       in a fused-executor engine serving a quantized predictor).
    4. bytes: the storage model gives int8 >= 2x fewer db-row bytes
       than f32 at every swept geometry.
    """
    import repro.kernels.ops as ops_mod
    from repro.core.predictors import KNNLambdaPredictor
    from repro.kernels.ops import knn_rank_audited
    from repro.serving import Scenario, ServingEngine, make_stream

    n, m1, K, m2, d, n_db = 8, 640, 4, 16, 12, 600
    ks = jax.random.split(jax.random.key(41), 7)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    b = jnp.abs(jax.random.normal(ks[2], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[3], (n, m2)))
    X = jax.random.normal(ks[4], (n, d))
    X_tr = np.asarray(jax.random.uniform(ks[5], (n_db, d)))
    lam_tr = jnp.abs(jax.random.normal(ks[6], (n_db, K)))

    # planted near-tie: two db rows closer together than the query-
    # quantization error around query 0's neighbourhood — forces the
    # margin guard on at least one row of the parity sweep
    X_adv = X_tr.copy()
    X_adv[50] = np.asarray(X[0]) + 0.31
    X_adv[51] = X_adv[50] + 1e-4

    for X_base in (X_tr, X_adv):
        base = KNNLambdaPredictor.fit(
            X_base.astype(np.float32), lam_tr, k=5)
        for mode in ("int8", "bf16"):
            for slab in (200, 512):        # divides 600 / does not
                pred = base.quantized(mode=mode, slab=slab)
                got = ops_mod.predict_rank_audited(
                    X, pred, u, a, b, gamma, m2=m2)
                # the oracle under jit: eager jnp.sum reduces in a
                # different order than the compiled audit (1-ulp in
                # utility), and the contract is vs the COMPILED oracle
                want = jax.jit(
                    lambda X_, u_, a_, b_, g_, p_=pred:
                    ref.predict_rank_audited_ref(
                        X_, p_, u_, a_, b_, g_, m2))(X, u, a, b, gamma)
                w = dict(zip(("vals", "perm", "utility", "exposure",
                              "compliant", "lam"), want))
                _assert_parity(got, w, f"quant parity ({mode}, slab={slab})")
                np.testing.assert_allclose(
                    np.asarray(got.lam), np.asarray(w["lam"]),
                    rtol=2e-7, atol=2e-7,
                    err_msg=f"quant λ̂ drifted ({mode}, slab={slab})")

    # forced fallback is observable: the adversarial db fires the guard
    adv = KNNLambdaPredictor.fit(
        X_adv.astype(np.float32), lam_tr, k=5).quantized(
            mode="int8", slab=200)
    _, guard = knn_rank_audited(
        X, adv.X_db, adv.lam_db, u, a, b, gamma, k=5, m2=m2,
        quant="int8", X_q=adv.X_q, q_scale=adv.q_scale, y2_q=adv.y2_q,
        tile_n=200, return_guard=True)
    if int(np.asarray(guard).sum()) < 1:
        raise AssertionError(
            "quant guard regression: planted near-tie did not force "
            "the margin-guard fallback")

    # lossless db -> int8 engine bitwise == f32 engine (λ̂ included)
    rng = np.random.default_rng(7)
    X_ll = np.clip(np.round(rng.uniform(-63.0, 63.0, size=(n_db, d))
                            * 2.0) / 2.0, -63.5, 63.5)
    X_ll[::200] = 63.5                      # every slab hits the absmax
    ll = KNNLambdaPredictor.fit(X_ll.astype(np.float32), lam_tr, k=5)
    llq = ll.quantized(mode="int8", slab=200)
    X_q32 = jnp.asarray(np.round(
        rng.uniform(-10, 10, size=(n, d)) * 2.0).astype(np.float32) / 2.0)
    o32 = ops_mod.predict_rank_audited(X_q32, ll, u, a, b, gamma, m2=m2)
    oq = ops_mod.predict_rank_audited(X_q32, llq, u, a, b, gamma, m2=m2)
    for f in ("perm", "utility", "exposure", "compliant", "lam"):
        np.testing.assert_array_equal(
            np.asarray(getattr(o32, f)), np.asarray(getattr(oq, f)),
            err_msg=f"lossless int8-vs-f32 engine broke on {f}")

    # fused-executor engine on a quantized predictor: exactly one
    # kernel launch and one executable call per flushed micro-batch
    knn = KNNLambdaPredictor.fit(
        rng.normal(size=(96, d)).astype(np.float32),
        np.abs(rng.normal(size=(96, K))).astype(np.float32),
        k=5).quantized(mode="int8", slab=32)
    with _count_kernel_calls(
            {"quant": "knn_rank_audited_quant_pallas"}) as calls:
        with ServingEngine(max_batch=8, max_wait_ms=2.0,
                           executor="fused") as eng:
            eng.register_predictor("knn_arch", knn, d_cov=d)
            mix = (Scenario("feed", m1=300, m2=16, K=K, tag="knn_arch",
                            d_cov=d),)
            reqs = make_stream(mix, n_requests=24, seed=3)
            eng.warmup(reqs)
            results = eng.serve_stream(reqs)
            m = eng.metrics
            if len(results) != 24 or m.batches == 0:
                raise AssertionError("quant engine smoke failed to serve")
            if m.kernel_launches / m.batches != 1.0:
                raise AssertionError(
                    f"quant launch accounting: "
                    f"{m.kernel_launches / m.batches} launches/batch "
                    f"(expected exactly 1.0)")
            if m.executable_calls != m.batches:
                raise AssertionError(
                    f"quant dispatch: {m.executable_calls} executable "
                    f"calls for {m.batches} batches")
    if calls["quant"] < 1:
        raise AssertionError(
            "quant dispatch regression: the fused engine never engaged "
            "the quantized single-grid kernel")

    # storage model: >= 2x fewer db-row bytes at every geometry
    for (BB, NN, DD, KK) in ((32, 16384, 20, 5), (64, 65536, 64, 8)):
        mdl = _knn_quant_traffic_model(BB, NN, DD, KK)
        if mdl["rows_ratio_f32_over_int8"] < 2.0:
            raise AssertionError(
                f"quant traffic regression: f32/int8 db-row byte ratio "
                f"{mdl['rows_ratio_f32_over_int8']} < 2.0 at "
                f"B={BB} N={NN} D={DD}")
    print("# knn_quant acceptance (bitwise parity incl. forced "
          "fallbacks, lossless int8==f32 engine, 1 launch/batch, "
          ">=2x db-row bytes): PASS")


def run(quick: bool = False):
    rows = []
    key = jax.random.key(0)

    # fused_rank: oracle materializes s (read+write) vs kernel streaming
    n, m1, K, m2 = (64, 100_000, 5, 50) if not quick else (16, 10_000, 5, 50)
    ks = jax.random.split(key, 3)
    u = jax.random.uniform(ks[0], (n, m1))
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.1).astype(jnp.float32)
    lam = jnp.abs(jax.random.normal(ks[2], (n, K)))
    f = jax.jit(lambda u, a, lam: ref.fused_rank_ref(u, a, lam, m2))
    us = timed(lambda: f(u, a, lam)[0], iters=3)
    compulsory = (K + 1) * m1 * 4          # read u + a once
    oracle_traffic = (K + 1) * m1 * 4 + 2 * m1 * 4  # + write s + read s
    rows.append({"name": f"fused_rank/m1={m1}/K={K}", "us": us,
                 "derived": {"hbm_ratio_oracle_over_kernel":
                             round(oracle_traffic / compulsory, 3)}})

    # rank+audit: fused kernel vs kernel + post-rank XLA audit epilogue,
    # at the retrieval shape (huge m1) and the serving-bucket shape
    # (engine micro-batch: the lattice cell the fused executor dispatches).
    shapes = ([(16, 10_000, 5, 50), (64, 2048, 8, 64)] if quick
              else [(64, 100_000, 5, 50), (256, 2048, 8, 128)])
    for n_ra, m1_ra, K_ra, m2_ra in shapes:
        rows.append(run_rank_audit(n_ra, m1_ra, K_ra, m2_ra))

    # predict+rank+audit: two-dispatch predict->rank vs one fused
    # program, at an engine micro-batch shape (covariate traffic)
    pr_shapes = ([(32, 2048, 5, 32, 20, 4096)] if quick
                 else [(32, 2048, 5, 32, 20, 16384),
                       (64, 8192, 8, 50, 20, 65536)])
    for n_pr, m1_pr, K_pr, m2_pr, d_pr, ndb_pr in pr_shapes:
        rows += run_predict_rank(n_pr, m1_pr, K_pr, m2_pr,
                                 d=d_pr, n_db=ndb_pr)

    # knn_fused: the single-grid KNN kernel vs the two-kernel chain vs
    # the XLA chunked path, at engine micro-batch shapes
    kf_shapes = ([(32, 2048, 5, 32, 20, 4096)] if quick
                 else [(32, 2048, 5, 32, 20, 16384),
                       (64, 8192, 8, 50, 20, 65536)])
    for n_kf, m1_kf, K_kf, m2_kf, d_kf, ndb_kf in kf_shapes:
        rows.append(run_knn_fused(n_kf, m1_kf, K_kf, m2_kf,
                                  d=d_kf, n_db=ndb_kf))

    # knn_quant: int8/bf16 db storage vs f32 — the row-stream byte
    # model plus the XLA quant-scan wall stand-in
    for n_kf, m1_kf, K_kf, m2_kf, d_kf, ndb_kf in kf_shapes:
        rows.append(run_knn_quant(n_kf, m1_kf, K_kf, m2_kf,
                                  d=d_kf, n_db=ndb_kf))

    # knn_topk: oracle materializes the (B, N) distance matrix
    B, N, D, k = (256, 65536, 20, 10) if not quick else (64, 8192, 20, 10)
    kq, kd = jax.random.split(key)
    xq = jax.random.normal(kq, (B, D))
    xdb = jax.random.normal(kd, (N, D))
    g = jax.jit(lambda xq, xdb: ref.knn_topk_ref(xq, xdb, k))
    us = timed(lambda: g(xq, xdb)[0], iters=3)
    kernel_traffic = N * D * 4             # stream db once (q tile resident)
    oracle_traffic = N * D * 4 + 2 * B * N * 4   # + write/read d2
    rows.append({"name": f"knn_topk/B={B}/N={N}", "us": us,
                 "derived": {"hbm_ratio_oracle_over_kernel":
                             round(oracle_traffic / kernel_traffic, 3)}})

    return rows


@contextmanager
def _count_kernel_calls(mapping: dict):
    """Monkeypatch-count Pallas kernel engagements through the ops
    dispatchers: ``mapping`` is {label: attribute name on
    repro.kernels.ops}; yields the live {label: count} dict. The shared
    scaffolding of every health gate below — wrappers restore on exit,
    so a failing gate can never leak a counting shim into later
    sections."""
    import repro.kernels.ops as ops_mod

    calls = {label: 0 for label in mapping}
    real = {label: getattr(ops_mod, attr) for label, attr in mapping.items()}

    def counting(label, fn):
        def wrapped(*args, **kwargs):
            calls[label] += 1
            return fn(*args, **kwargs)
        return wrapped

    for label, attr in mapping.items():
        setattr(ops_mod, attr, counting(label, real[label]))
    try:
        yield calls
    finally:
        for label, attr in mapping.items():
            setattr(ops_mod, attr, real[label])


def check_rank_audited() -> None:
    """Kernel-health gate (CI smoke): raises on any regression.

    1. interpret-mode parity: the rank+audit kernel's outputs match the
       rank_given_lambda oracle BITWISE (perm/utility/exposure/compliant).
    2. dispatch: the default path actually engages the Pallas kernel for
       kernel-eligible m2 (a silently-engaging fallback would keep tests
       green while TPU hosts quietly run the slow path).
    3. fallback: m2 > MAX_KERNEL_M2 routes to the XLA oracle, and its
       outputs match the oracle too.
    """
    import repro.kernels.ops as ops_mod
    from repro.core.ranking import rank_given_lambda

    n, m1, K, m2 = 8, 640, 4, 16
    ks = jax.random.split(jax.random.key(3), 5)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    lam = jnp.abs(jax.random.normal(ks[2], (n, K)))
    b = jnp.abs(jax.random.normal(ks[3], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[4], (n, m2)))

    with _count_kernel_calls({"kernel": "rank_audited_pallas"}) as calls:
        got = ops_mod.rank_audited(u, a, b, lam, gamma, m2=m2)
        big = ops_mod.rank_audited(
            u, a, b, lam, jnp.abs(jax.random.normal(ks[4], (n, 256))), m2=256)
    if calls["kernel"] != 1:
        raise AssertionError(
            f"kernel dispatch regression: rank_audited_pallas engaged "
            f"{calls['kernel']} times across (kernel-eligible, fallback) "
            f"calls, expected exactly 1")

    want = rank_given_lambda(u, a, b, lam, gamma, m2=m2)
    _assert_parity(got, want, "rank+audit interpret parity")
    want_big = rank_given_lambda(
        u, a, b, lam, jnp.abs(jax.random.normal(ks[4], (n, 256))), m2=256)
    for field in ("perm", "utility", "exposure", "compliant"):
        np.testing.assert_array_equal(
            np.asarray(getattr(big, field)),
            np.asarray(getattr(want_big, field)),
            err_msg=f"rank+audit XLA fallback parity broke on {field}")
    print("# rank+audit health: kernel engaged, interpret parity within "
          "the parity contract, fallback parity bitwise -> PASS")


def check_predict_rank() -> None:
    """Predict+rank+audit health gate (CI smoke): raises on regression.

    1. interpret-mode parity: ops.predict_rank_audited matches
       predictor.predict(X) -> rank_given_lambda for every family —
       BITWISE for the affine prologue (linear/mean) and the
       in-executable MLP; λ̂ to tight tolerance for the fused KNN
       weighting (selection/audit still exact on this problem).
    2. dispatch: kernel-eligible shapes actually engage the fused
       kernels (the affine-prologue kernel for linear/mean; the KNN λ
       kernel chained into the rank+audit kernel for knn).
    3. fallback: m2 > MAX_KERNEL_M2 engages no kernel and matches the
       two-stage XLA oracle.
    """
    import repro.kernels.ops as ops_mod
    from repro.core.predictors import (
        KNNLambdaPredictor,
        LinearLambdaPredictor,
        MeanLambdaPredictor,
        MLPLambdaPredictor,
    )
    from repro.core.ranking import rank_given_lambda

    n, m1, K, m2, d = 8, 640, 4, 16, 12
    ks = jax.random.split(jax.random.key(17), 7)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    b = jnp.abs(jax.random.normal(ks[2], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[3], (n, m2)))
    X = jax.random.normal(ks[4], (n, d))
    X_tr = jax.random.uniform(ks[5], (48, d))
    lam_tr = jnp.abs(jax.random.normal(ks[6], (48, K)))
    families = {
        "linear": LinearLambdaPredictor.fit(X_tr, lam_tr),
        "mean": MeanLambdaPredictor.fit(X_tr, lam_tr),
        "knn": KNNLambdaPredictor.fit(X_tr, lam_tr, k=5),
        "mlp": MLPLambdaPredictor.fit(X_tr, lam_tr, num_steps=20),
    }

    with _count_kernel_calls({
            "linear": "linear_rank_audited_pallas",
            "knn_fused": "knn_rank_audited_pallas",
            "rank": "rank_audited_pallas"}) as calls:
        got = {name: ops_mod.predict_rank_audited(
                   X, pred, u, a, b, gamma, m2=m2)
               for name, pred in families.items()}
        gamma_big = jnp.abs(jax.random.normal(ks[3], (n, 256)))
        big = ops_mod.predict_rank_audited(
            X, families["linear"], u, a, b, gamma_big, m2=256)

    # knn engages the single-grid kernel; only mlp still chains into a
    # standalone rank kernel
    want_calls = {"linear": 2, "knn_fused": 1, "rank": 1}
    if calls != want_calls:
        raise AssertionError(
            f"predict+rank dispatch regression: kernel engagement "
            f"{calls}, expected {want_calls} (fallback must engage none)")

    for name, pred in families.items():
        want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
        _assert_parity(got[name], want, f"predict+rank parity [{name}]",
                       fields=_PARITY_FIELDS + ("lam",))

    _, idx_w, util_w, expo_w, comp_w, _ = ref.predict_rank_audited_ref(
        X, families["linear"], u, a, b, gamma_big, 256)
    for field, want_f in (("perm", idx_w), ("utility", util_w),
                          ("exposure", expo_w), ("compliant", comp_w)):
        np.testing.assert_array_equal(
            np.asarray(getattr(big, field)), np.asarray(want_f),
            err_msg=f"predict+rank XLA fallback parity broke on {field}")
    print("# predict+rank health: kernels engaged per family, outputs "
          "and λ̂ within the parity contract, fallback parity -> PASS")


def check_knn_fused() -> None:
    """Single-grid KNN kernel health gate (CI smoke): raises on any
    regression.

    1. parity: ops.predict_rank_audited on a KNN predictor — the
       single-grid knn_rank_audited_pallas — matches the PR 4
       two-kernel chain (knn_chain=True, matched tile geometry)
       BITWISE on every RankingOutput field INCLUDING λ̂, and matches
       the rank_given_lambda oracle exactly on
       perm/utility/exposure/compliant (λ̂ to tight tolerance — the
       per-slab distance accumulation differs from the oracle's
       one-matmul form in the last ulp).
    2. dispatch: the kernel-eligible shape engages the single-grid
       kernel exactly once and the chain kernels not at all; the
       m2 > MAX_KERNEL_M2 fallback engages none.
    3. engine accounting: a fused-executor engine serving a KNN
       covariate stream records exactly ONE kernel launch AND one
       executable call per flushed micro-batch post-warmup
       (EngineMetrics.kernel_launches / executable_calls).
    """
    import repro.kernels.ops as ops_mod
    from repro.core.predictors import KNNLambdaPredictor
    from repro.core.ranking import rank_given_lambda
    from repro.serving import Scenario, ServingEngine, make_stream

    n, m1, K, m2, d = 8, 640, 4, 16, 12
    ks = jax.random.split(jax.random.key(31), 7)
    u = jax.random.uniform(ks[0], (n, m1), minval=1.0, maxval=5.0)
    a = (jax.random.uniform(ks[1], (n, K, m1)) < 0.15).astype(jnp.float32)
    b = jnp.abs(jax.random.normal(ks[2], (n, K)))
    gamma = jnp.abs(jax.random.normal(ks[3], (n, m2)))
    X = jax.random.normal(ks[4], (n, d))
    X_tr = jax.random.uniform(ks[5], (600, d))
    lam_tr = jnp.abs(jax.random.normal(ks[6], (600, K)))
    pred = KNNLambdaPredictor.fit(X_tr, lam_tr, k=5)

    gamma_big = jnp.abs(jax.random.normal(ks[3], (n, 256)))
    with _count_kernel_calls({
            "fused": "knn_rank_audited_pallas",
            "chain_knn": "knn_lambda_pallas",
            "chain_rank": "rank_audited_pallas"}) as calls:
        got = ops_mod.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2)
        fast_calls = dict(calls)
        big = ops_mod.predict_rank_audited(X, pred, u, a, b, gamma_big,
                                           m2=256)
        fallback_calls = dict(calls)

    if fast_calls != {"fused": 1, "chain_knn": 0, "chain_rank": 0}:
        raise AssertionError(
            f"knn_fused dispatch regression: kernel engagement "
            f"{fast_calls}, expected the single grid exactly once")
    if fallback_calls != fast_calls:
        raise AssertionError(
            f"knn_fused fallback regression: m2 > MAX_KERNEL_M2 engaged "
            f"kernels {fallback_calls} (expected {fast_calls})")

    chain = ops_mod.predict_rank_audited(X, pred, u, a, b, gamma, m2=m2,
                                         knn_chain=True)
    for field in ("perm", "utility", "exposure", "compliant", "lam"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(chain, field)),
            err_msg=f"single-grid vs two-kernel chain broke on {field}")
    want = rank_given_lambda(u, a, b, pred.predict(X), gamma, m2=m2)
    _assert_parity(got, want, "single-grid vs oracle")
    np.testing.assert_allclose(
        np.asarray(got.lam), np.asarray(want.lam), rtol=1e-5, atol=1e-6,
        err_msg="single-grid λ̂ drifted from the predictor")
    want_big = rank_given_lambda(u, a, b, pred.predict(X), gamma_big, m2=256)
    for field in ("perm", "utility", "exposure", "compliant"):
        np.testing.assert_array_equal(
            np.asarray(getattr(big, field)),
            np.asarray(getattr(want_big, field)),
            err_msg=f"knn_fused XLA fallback parity broke on {field}")

    rng = np.random.default_rng(5)
    knn = KNNLambdaPredictor.fit(
        rng.normal(size=(96, d)).astype(np.float32),
        np.abs(rng.normal(size=(96, K))).astype(np.float32), k=5)
    with ServingEngine(max_batch=8, max_wait_ms=2.0,
                       executor="fused") as eng:
        eng.register_predictor("knn_arch", knn, d_cov=d)
        mix = (Scenario("feed", m1=300, m2=16, K=K, tag="knn_arch",
                        d_cov=d),)
        reqs = make_stream(mix, n_requests=24, seed=3)
        eng.warmup(reqs)
        results = eng.serve_stream(reqs)
        m = eng.metrics
        if len(results) != 24 or m.batches == 0:
            raise AssertionError("knn_fused engine smoke did not serve")
        if (m.kernel_launches != m.batches
                or m.executable_calls != m.batches):
            raise AssertionError(
                f"knn_fused engine accounting regression: "
                f"{m.batches} batches but {m.executable_calls} "
                f"executable calls / {m.kernel_launches} kernel "
                f"launches (want exactly one of each per batch)")
    print("# knn_fused health: single grid engaged (chain kernels idle), "
          "bitwise vs chain, oracle parity, fallback clean, engine "
          "1 launch/batch -> PASS")


def records(rows):
    return [Record(name=f"kernel/{r['name']}", us_per_call=r["us"],
                   derived=r["derived"]) for r in rows]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized shapes + the kernel health gates")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="write BENCH_kernel_bench.json / "
                         "BENCH_knn_fused.json to OUT (a directory, or "
                         "an explicit *.json path for the main file)")
    ap.add_argument("--budget-s", type=float, default=300.0,
                    help="--quick wall-clock budget: exceeding it fails "
                         "the run with a per-section timing table "
                         "(instead of the CI runner's silent timeout)")
    args = ap.parse_args()

    sections: list[tuple[str, float]] = []

    def section(name, fn):
        t0 = time.perf_counter()
        out = fn()
        sections.append((name, time.perf_counter() - t0))
        return out

    section("check_rank_audited", check_rank_audited)   # hard gates:
    section("check_predict_rank", check_predict_rank)   # raise on
    section("check_knn_fused", check_knn_fused)         # regression
    section("check_knn_quant", check_knn_quant)
    rows = section("bench_sweep", lambda: run(quick=args.quick))
    recs = records(rows)
    for rec in recs:
        print(rec.csv())
    if args.json:
        write_bench_json(args.json, "kernel_bench", recs,
                         meta={"quick": args.quick})
        kf_recs = [r for r in recs if "/knn_fused/" in r.name]
        out_dir = (args.json if not args.json.endswith(".json")
                   else (os.path.dirname(args.json) or "."))
        write_bench_json(out_dir, "knn_fused", kf_recs,
                         meta={"quick": args.quick})
        kq_recs = [r for r in recs if "/knn_quant/" in r.name]
        write_bench_json(out_dir, "knn_quant", kq_recs,
                         meta={"quick": args.quick})
    ras = [r for r in rows if r["name"].startswith("rank_audit/")]
    if any(r["derived"]["audit_ratio_xla_over_fused"] <= 1.0 for r in ras):
        raise SystemExit("# rank+audit acceptance: FAIL — audit traffic "
                         "model does not favor the fused kernel")
    best = max(r["derived"]["wall_audit_step"] for r in ras)
    if best >= 1.0:
        print(f"# rank+audit acceptance: PASS — audit traffic ratio "
              f"{max(r['derived']['audit_ratio_xla_over_fused'] for r in ras)}"
              f"x, measured audit-step wall win up to {best:.1f}x over the "
              f"XLA epilogue")
    else:
        # parity + traffic model hold; a wall-time shortfall on a noisy
        # shared host is measurement jitter, not a dataflow change.
        print(f"# rank+audit acceptance: WARN — traffic model holds but "
              f"measured audit-step wall win {best:.2f}x < 1.0x "
              f"(noisy host?)")
    prs = [r for r in rows if r["name"].startswith("predict_rank/")]
    if any(r["derived"]["predict_ratio_xla_over_fused"] <= 1.0 for r in prs):
        raise SystemExit("# predict+rank acceptance: FAIL — predict "
                         "traffic model does not favor the fused path")
    best_pr = max(r["derived"]["wall_two_over_one"] for r in prs)
    if best_pr >= 1.0:
        print(f"# predict+rank acceptance: PASS — predict traffic ratio up "
              f"to "
              f"{max(r['derived']['predict_ratio_xla_over_fused'] for r in prs)}"
              f"x, two-dispatch/fused wall up to {best_pr:.2f}x")
    else:
        print(f"# predict+rank acceptance: WARN — traffic model holds but "
              f"measured two-dispatch/fused wall {best_pr:.2f}x < 1.0x "
              f"(noisy host?)")
    kfs = [r for r in rows if r["name"].startswith("knn_fused/")]
    # bytes compared unrounded: the chain/fused edge is the λ̂
    # round-trip (small but strictly positive) plus the deleted second
    # kernel launch; the xla/fused edge is the d2 slab materialization
    if any(r["derived"]["knn_fused_bytes"] >= r["derived"]["knn_chain_bytes"]
           or (r["derived"]["knn_fused_bytes"]
               >= r["derived"]["knn_xla_bytes"]) for r in kfs):
        raise SystemExit("# knn_fused acceptance: FAIL — traffic model "
                         "does not favor the single-grid kernel over the "
                         "chain AND the XLA chunked path")
    best_kf = max(r["derived"]["wall_two_over_one"] for r in kfs)
    print(f"# knn_fused acceptance: PASS — xla/fused traffic up to "
          f"{max(r['derived']['ratio_xla_over_fused'] for r in kfs)}x, "
          f"chain/fused {max(r['derived']['ratio_chain_over_fused'] for r in kfs)}x "
          f"(+1 fewer kernel launch), two-dispatch/fused wall up to "
          f"{best_kf:.2f}x" if best_kf >= 1.0 else
          f"# knn_fused acceptance: WARN — traffic model holds but "
          f"measured wall {best_kf:.2f}x < 1.0x (noisy host?)")

    # --- wall-clock budget: a growing bench suite must fail loudly, ---
    # --- with names, not eat the CI runner's timeout silently       ---
    total = sum(s for _, s in sections)
    width = max(len(n) for n, _ in sections)
    print(f"# section timings (budget {args.budget_s:.0f}s, "
          f"{'enforced' if args.quick else 'informational'}):")
    for name, secs in sections + [("TOTAL", total)]:
        print(f"#   {name:<{width}}  {secs:7.1f}s")
    if args.quick and total > args.budget_s:
        raise SystemExit(
            f"# kernel_bench budget: FAIL — --quick took {total:.1f}s "
            f"> {args.budget_s:.0f}s; trim the slowest section above "
            f"or raise --budget-s deliberately")


if __name__ == "__main__":
    main()
