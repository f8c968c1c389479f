"""Serve the constrained-ranking path once on a TPU, at full width.

    python3 chip_smoke.py               # one chip: the fused executor
    python3 chip_smoke.py --four-chips  # four chips: the dist executor

One chip: the serve driver's own pieces (repro.launch.serve), in this
process, at sasrec's published config (10^6 x 50 item table, 2 blocks,
seq 50) with a few random-weight training steps, a 65,536-user offline
dual solve + KNN fit, and 512 requests of up to 1,024 candidates
(m1 jitter 0.5, K = 5, m2 = 50, batch 32) through the `fused` executor
with admission on. Every fused bucket executable must hold a Pallas
kernel (``tpu_custom_call``), no bucket may compile after warmup, every
batch must be one kernel launch, and the served rankings must agree
with the `xla` engine run at full f32 matmul precision, which serves
each request on the predictor (KNN or its mean rung) that served it
(``compare_engines``).

--four-chips runs only the same stream plus a retrieval-width surface
(m1 = 65,536): first through the one-chip fused engine, then through
ServingEngine(executor="dist") on a 1 x 4 ("data", "model") mesh,
checks that every device holds its shard of u / a, and compares the
two.

Exits non-zero and prints no result when JAX finds no TPU. The last
line of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

# the served run: the serve driver's flags, fixed
SERVE_ARGV = ["--arch", "sasrec", "--requests", "512", "--candidates", "1024",
              "--m1-jitter", "0.5", "--constraints", "5", "--m2", "50",
              "--max-batch", "32", "--offline-users", "65536",
              "--train-steps", "5", "--executor", "fused", "--admission"]
RETRIEVAL_M1 = 65_536          # candidates of the --four-chips surface
RETRIEVAL_REQUESTS = 64


def log(msg: str) -> None:
    print(msg, flush=True)


def count_compiles() -> dict:
    """Running totals of XLA backend compile seconds and persistent
    cache hits / misses in this process, from JAX's monitoring events."""
    totals = {"seconds": 0.0, "programs": 0, "hits": 0, "misses": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["seconds"] += secs
            totals["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            totals["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            totals["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return totals


def _served(results) -> dict:
    from repro.serving import RankResult
    return {r.rid: r for r in results if isinstance(r, RankResult)}


def run_engine(engine, requests, *, reference: bool = False) -> dict:
    """Warm `engine` up on `requests`, serve them, close it; returns
    {"results": {rid: RankResult}, "summary", "warmup_s", "served_s"}.
    ``reference`` runs warmup and serving at full f32 matmul precision
    (the default TPU matmul rounds f32 operands to bf16), so the
    reference engine computes the plain f32 result."""
    prec = "highest" if reference else None
    with jax.default_matmul_precision(prec):
        t0 = time.perf_counter()
        engine.warmup(requests)
        t1 = time.perf_counter()
        results = engine.serve_stream(requests)
        t2 = time.perf_counter()
    engine.close()
    return {"results": _served(results), "summary": engine.metrics.summary(),
            "warmup_s": t1 - t0, "served_s": t2 - t1, "n": len(results)}


def report_run(name: str, run: dict) -> None:
    s = run["summary"]
    served = run["results"]
    degraded = sum(1 for r in served.values() if r.rung > 0)
    log(f"{name}: requests {run['n']} served {len(served)} "
        f"shed {run['n'] - len(served)} degraded {degraded}")
    log(f"{name}: warmup_s {run['warmup_s']} (compiles "
        f"{s['compiles']}) served_s {run['served_s']}")
    log(f"{name}: compiles_post_warmup {s['compiles_post_warmup']} "
        f"kernel_launches_per_batch {s['kernel_launches_per_batch']} "
        f"latency_ms {json.dumps(s['latency_ms'])}")
    if s["compiles_post_warmup"] != 0:
        raise SystemExit(f"{name}: {s['compiles_post_warmup']} compiles "
                         f"after warmup")
    if not served:
        raise SystemExit(f"{name}: served nothing")


def check_kernels(name: str, engine, run: dict) -> None:
    """Every fused bucket executable holds a compiled Pallas kernel (not
    interpret mode, not the XLA fallback), and each batch launched
    exactly one."""
    for bucket, compiled in engine.compiled_executables().items():
        has = "tpu_custom_call" in compiled.as_text()
        log(f"{name}: bucket {bucket} tpu_custom_call {has}")
        if not has:
            raise SystemExit(f"{name}: bucket {bucket} runs no kernel")
    klpb = run["summary"]["kernel_launches_per_batch"]
    if klpb != 1.0:
        raise SystemExit(f"{name}: kernel_launches_per_batch {klpb} != 1.0")


def knn_lam_tolerance(requests, knn, rids) -> dict:
    """How far a correct f32 evaluation may move each KNN-served
    request's λ̂, from a float64 host reference over the offline users:
    rid -> None where the k-th and (k+1)-th nearest users are tied
    within PARITY_RTOL of the squared norms (any f32 evaluation order
    may pick either, so λ̂ is not comparable), else the extra λ̂
    tolerance. A squared distance d2_j = |x|^2 - 2 x.y_j + |y_j|^2 held
    to PARITY_RTOL (|x|^2 + |y_j|^2) moves the inverse-distance weight
    w_j = d2_j^(-1/2) by half that relative to d2_j, and λ̂ = Σ w_j λ_j /
    Σ w_j by at most that times max_j |λ_j - λ̂|."""
    from repro.core.ranking import PARITY_RTOL
    X_db = np.asarray(knn.X_db, np.float64)
    lam_db = np.asarray(knn.lam_db, np.float64)
    y2 = np.sum(X_db * X_db, axis=1)
    by_rid = {r.rid: r for r in requests}
    out = {}
    for rid in rids:
        x = np.asarray(by_rid[rid].X, np.float64)
        x2 = float(x @ x)
        d2 = np.maximum(x2 - 2.0 * (X_db @ x) + y2, 0.0)
        near = np.argsort(d2, kind="stable")[:knn.k + 1]
        scale = x2 + y2[near]
        if d2[near[-1]] - d2[near[-2]] <= PARITY_RTOL * scale.max():
            out[rid] = None
            continue
        near, scale = near[:-1], scale[:-1]
        w = 1.0 / np.maximum(np.sqrt(d2[near]), 1e-12)
        lam_nb = lam_db[near]
        lam = w @ lam_nb / w.sum()
        kappa = float(np.max(scale / np.maximum(d2[near], 1e-30)))
        out[rid] = 0.5 * PARITY_RTOL * kappa * float(
            np.abs(lam_nb - lam).max())
    return out


def compare_engines(requests, got: dict, want: dict, knn, *,
                    eps: float = 1e-4) -> None:
    """The kernel parity contract (core.ranking) between the served
    results `got` and a reference engine's `want` on the same requests,
    each served by both with the same predictor:

    * perm equal, except where the two selections' adjusted scores
      s = u + (1 + eps) λ·a agree slot by slot within the contract's
      tolerance summed over the K + 1 terms of s (a near-tie);
    * compliant equal wherever perm is;
    * utility, exposure and λ̂ within PARITY_ATOL + PARITY_RTOL |ref|
      wherever perm is equal; a KNN λ̂ also within what f32 rounding of
      its neighbours' distances can move it (knn_lam_tolerance), and
      not compared at a k-th / (k+1)-th neighbour near-tie.
    """
    from repro.core.ranking import PARITY_ATOL, PARITY_RTOL
    by_rid = {r.rid: r for r in requests}
    common = sorted(got.keys() & want.keys())
    if len(common) != len(want):
        raise SystemExit(f"compare: the reference served {len(want)} "
                         f"requests, {len(common)} of them also served")
    knn_tol = knn_lam_tolerance(
        requests, knn, [rid for rid in common if got[rid].rung == 0])
    knn_ties = sum(1 for t in knn_tol.values() if t is None)
    score_ties, worst = 0, {"utility": 0.0, "exposure": 0.0, "lam": 0.0}
    ratio, lam_plain = 0.0, 0.0

    def close(field, g, w, extra=0.0):
        nonlocal ratio, lam_plain
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        d = np.abs(g - w)
        tol = PARITY_ATOL + PARITY_RTOL * np.abs(w)
        worst[field] = max(worst[field], float(d.max(initial=0.0)))
        ratio = max(ratio, float((d / (tol + extra)).max(initial=0.0)))
        if field == "lam":
            lam_plain = max(lam_plain, float((d / tol).max(initial=0.0)))

    for rid in common:
        g, w, req = got[rid], want[rid], by_rid[rid]
        extra = knn_tol.get(rid, 0.0)
        if extra is None:
            continue
        close("lam", g.lam, w.lam, extra)
        if not np.array_equal(g.perm, w.perm):
            lam = np.asarray(w.lam, np.float64)
            s = (np.asarray(req.u, np.float64)
                 + (1.0 + eps) * lam @ np.asarray(req.a, np.float64))
            tol = (lam.size + 1) * (PARITY_ATOL
                                    + PARITY_RTOL * np.abs(s).max())
            if np.abs(s[g.perm] - s[w.perm]).max() > tol:
                raise SystemExit(f"request {rid}: perm differs beyond a "
                                 f"near-tie")
            score_ties += 1
            continue
        if g.compliant != w.compliant:
            raise SystemExit(f"request {rid}: compliant differs")
        close("utility", g.utility, w.utility)
        close("exposure", g.exposure, w.exposure)
    log(f"compare: requests {len(requests)} compared {len(common)} "
        f"knn_rows {len(knn_tol)} score_near_ties {score_ties} "
        f"knn_near_ties {knn_ties}")
    log(f"compare: max_abs_diff utility {worst['utility']} exposure "
        f"{worst['exposure']} lam {worst['lam']} (rtol {PARITY_RTOL} atol "
        f"{PARITY_ATOL}; worst |diff| / tolerance {ratio}, λ̂ without the "
        f"KNN distance term {lam_plain})")
    if ratio > 1.0:
        raise SystemExit("compare: outside the parity tolerance")


def served_as(requests, results: dict, arch: str) -> list:
    """The requests `results` holds, each tagged with the predictor of
    the degradation rung that served it, so an engine without admission
    serves each one as it was served here."""
    ladder = (arch, f"{arch}_mean")
    return [dataclasses.replace(r, tag=ladder[results[r.rid].rung])
            for r in requests if r.rid in results]


def plain_engine(serve, args, dep, executor: str, mesh=None):
    """An engine with both ladder predictors and no admission: it
    serves every request, on the predictor its tag names."""
    eng = serve.make_engine(argparse.Namespace(**{**vars(args),
                                                  "admission": False}),
                            dep, executor=executor, mesh=mesh)
    eng.register_predictor(f"{args.arch}_mean", dep.mean, d_cov=dep.d_cov)
    return eng


def one_chip(serve, args, dep, requests) -> None:
    fused = serve.make_engine(args, dep)
    run = run_engine(fused, requests)
    report_run("fused", run)
    check_kernels("fused", fused, run)
    same = served_as(requests, run["results"], args.arch)
    ref = run_engine(plain_engine(serve, args, dep, "xla"), same,
                     reference=True)
    report_run("xla_reference", ref)
    compare_engines(same, run["results"], ref["results"], dep.knn)


def four_chips(serve, args, dep, requests) -> None:
    from repro.core.serving_dist import rank_input_shardings
    retrieval = [dataclasses.replace(r, rid=len(requests) + r.rid)
                 for r in serve.make_requests(args, dep, RETRIEVAL_REQUESTS,
                                              n_cand=RETRIEVAL_M1)]
    requests = requests + retrieval
    log(f"four_chips: {len(requests)} requests, {len(retrieval)} of them "
        f"at m1 up to {RETRIEVAL_M1}")
    # both engines without admission: every request is served, on KNN
    fused = plain_engine(serve, args, dep, "fused")
    one = run_engine(fused, requests)
    report_run("fused_one_chip", one)
    check_kernels("fused_one_chip", fused, one)

    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    dist = plain_engine(serve, args, dep, "dist", mesh)
    run = run_engine(dist, requests, reference=True)
    report_run("dist", run)
    sh = rank_input_shardings(mesh)
    for bucket, compiled in dist.compiled_executables().items():
        for name, i in (("u", 3), ("a", 4)):
            arg = compiled.input_shardings[0][i]
            ok = arg.is_equivalent_to(sh[name], 2 if name == "u" else 3)
            log(f"dist: bucket {bucket} {name} sharded {arg.spec} over "
                f"{len(arg.device_set)} devices: {ok}")
            if not ok:
                raise SystemExit(f"dist: bucket {bucket} {name} is not "
                                 f"split over the mesh")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"dist: device {d.id} bytes_in_use {stats.get('bytes_in_use')} "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    compare_engines(requests, one["results"], run["results"], dep.knn)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dist executor on a 4-chip mesh and "
                         "its comparison with the one-chip fused engine")
    opts = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform})")
    n_dev = len(jax.devices())
    want = 4 if opts.four_chips else 1
    if n_dev < want:
        raise SystemExit(f"chip_smoke: needs {want} chips, found {n_dev}")
    log(f"device_kind {dev.device_kind} device_count {n_dev}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_arch
    from repro.launch import serve
    from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

    cache = enable_compile_cache()
    cached = sum(1 for _ in Path(cache).glob("*")) if Path(cache).is_dir() \
        else 0
    origin = ("fixed in-checkout path" if cache == str(CACHE_DIR)
              else "JAX_COMPILATION_CACHE_DIR")
    log(f"compile_cache {cache} ({origin}) entries_at_start {cached}")
    compiles = count_compiles()

    t_start = time.perf_counter()
    args = serve.parse_args(SERVE_ARGV)
    cfg = get_arch(args.arch).make_config(full=True)
    log(f"widths: arch {args.arch} n_items {cfg.n_items} embed_dim "
        f"{cfg.embed_dim} n_blocks {cfg.n_blocks} n_heads {cfg.n_heads} "
        f"seq_len {cfg.seq_len} item_table_MB "
        f"{cfg.n_items * cfg.embed_dim * 4 / 1e6}")
    dep = serve.build_deployment(args, cfg)
    log(f"offline: users {args.offline_users} knn_db {tuple(dep.knn.X_db.shape)} "
        f"lam_db {tuple(dep.knn.lam_db.shape)} compliance "
        f"{dep.offline_compliance} train_s {dep.seconds['train']} "
        f"offline_s {dep.seconds['offline']}")
    log(f"serving: candidates {dep.n_cand} m1_jitter {args.m1_jitter} K "
        f"{dep.K} m2 {dep.m2} max_batch {args.max_batch} executor "
        f"{args.executor} admission {args.admission}")
    requests = serve.make_requests(args, dep, args.requests)
    if opts.four_chips:
        four_chips(serve, args, dep, requests)
    else:
        one_chip(serve, args, dep, requests)
    log(f"compile: backend_compile_s {compiles['seconds']} programs "
        f"{compiles['programs']} cache_hits {compiles['hits']} "
        f"cache_misses {compiles['misses']}")
    log(f"total_s {time.perf_counter() - t_start}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))


if __name__ == "__main__":
    main()
