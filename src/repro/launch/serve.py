"""Serving driver: ``python -m repro.launch.serve --arch <recsys-id>``.

The deployment shape of the paper's system: train (or load) a retrieval
backbone at its published config, run Algorithm 1's offline stage
(batched dual solve on a user sample + KNN predictor fit), then serve a
STREAM of heterogeneous requests through the shape-bucketed, async
double-buffered micro-batching engine (repro.serving) and report
per-request latency percentiles, compliance, pipeline overlap, and
jit-cache behaviour (steady state must not recompile). --pipeline-depth
0 serves synchronously (the pre-pipeline engine) for A/B comparison.

Backbone scoring runs as one fixed-shape jit program per arrival chunk;
each user then becomes an individual RankRequest whose candidate count
is jittered (live retrieval returns varying candidate sets), exercising
the engine's bucket lattice the way live traffic would.

  PYTHONPATH=src python -m repro.launch.serve --arch sasrec --requests 256

The pieces (parse_args, build_deployment, make_requests, make_engine,
serve) are importable: chip_smoke.py drives them in-process.
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.constraints import dcg_discount
from repro.core.dual_solver import solve_dual_batch
from repro.core.predictors import KNNLambdaPredictor, MeanLambdaPredictor
from repro.data.batches import make_deepfm_batch, make_seqrec_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.recsys import RECSYS_REGISTRY
from repro.optim import adam_init
from repro.serving import FleetRouter, RankRequest, RankResult, ServingEngine

# users per offline scoring + dual-solve program: the per-user duals are
# independent, so chunking changes no result and bounds device memory
OFFLINE_CHUNK = 8192


def _request_batch(cfg, B, seed):
    k = jax.random.key(seed)
    if cfg.kind == "deepfm":
        return make_deepfm_batch(k, batch=B, n_sparse=cfg.n_sparse,
                                 field_vocab=cfg.field_vocab)["ids"]
    return make_seqrec_batch(k, batch=B, seq_len=cfg.seq_len,
                             n_items=cfg.n_items, n_neg=1,
                             kind=cfg.kind)["seq"]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sasrec",
                    choices=["deepfm", "sasrec", "bert4rec", "mind"])
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--candidates", type=int, default=512)
    ap.add_argument("--m2", type=int, default=50)
    ap.add_argument("--constraints", type=int, default=5)
    ap.add_argument("--train-steps", type=int, default=60)
    ap.add_argument("--offline-users", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=32,
                    help="engine micro-batch capacity")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="micro-batch assembly deadline")
    ap.add_argument("--executor", default="xla", choices=["xla", "fused"])
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="in-flight micro-batch window; 1 = double "
                         "buffering, 0 = synchronous engine")
    ap.add_argument("--m1-jitter", type=float, default=0.5,
                    help="per-request candidate-count jitter in "
                         "[1-jitter, 1] * --candidates")
    ap.add_argument("--admission", action="store_true",
                    help="enable deadline-aware admission control with a "
                         "KNN -> mean degradation ladder")
    ap.add_argument("--budget-ms", type=float, default=50.0,
                    help="per-request latency budget (the paper's SLA)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a fault-tolerant FleetRouter "
                         "over N engine replicas (health-checked "
                         "consistent-hash routing, hedged retries, "
                         "supervised restart); 1 = single engine")
    return ap.parse_args(argv)


def _score(model, kind, params, req, cand_ids):
    """Backbone inference: utilities over the candidate set + user
    covariates."""
    user_in = req[:, 1:] if kind == "deepfm" else req
    return (model.retrieval_scores(params, user_in, cand_ids),
            model.user_covariates(params, req))


def surface(n_cand: int, m2: int, K: int):
    """(gamma (m2,), topic attributes a (K, n_cand), thresholds b (K,))
    of a candidate set: binary topic memberships at rate 0.15 and an
    exposure floor of 8% of the page's total discount per topic."""
    gamma = np.asarray(dcg_discount(m2), np.float32)
    topics = np.asarray(
        jax.random.uniform(jax.random.key(7), (K, n_cand)) < 0.15,
        np.float32)
    b = 0.08 * gamma.sum() * np.ones(K, np.float32)
    return gamma, topics, b


@dataclass
class Deployment:
    """A trained backbone plus Algorithm 1's offline stage: everything
    the online engines and the request stream are built from."""

    cfg: Any
    params: Any
    score: Callable            # jit (params, req, cand_ids) -> (u, X)
    knn: KNNLambdaPredictor
    mean: MeanLambdaPredictor
    n_cand: int
    m2: int
    K: int
    offline_compliance: float
    seconds: dict              # wall seconds of the set-up phases

    @property
    def d_cov(self) -> int:
        return int(self.knn.X_db.shape[1])


def build_deployment(args, cfg) -> Deployment:
    """Train the backbone for --train-steps, then solve the offline
    duals of --offline-users users (in OFFLINE_CHUNK-user programs) and
    fit the KNN and mean λ predictors on them."""
    model = RECSYS_REGISTRY[cfg.kind](cfg)
    params = model.init(jax.random.key(0))
    seconds = {}

    t0 = time.perf_counter()
    opt = adam_init(params)

    @jax.jit
    def train_step(p, o, b):
        return model.train_step(p, o, b, lr=3e-3)

    for step in range(args.train_steps):
        if cfg.kind == "deepfm":
            batch = make_deepfm_batch(jax.random.key(step), batch=64,
                                      n_sparse=cfg.n_sparse,
                                      field_vocab=cfg.field_vocab)
        else:
            batch = make_seqrec_batch(jax.random.key(step), batch=64,
                                      seq_len=cfg.seq_len,
                                      n_items=cfg.n_items, n_neg=15,
                                      kind=cfg.kind)
        params, opt, _ = train_step(params, opt, batch)
    jax.block_until_ready(params)
    seconds["train"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    n_cand = min(args.candidates, cfg.n_items)
    m2, K = min(args.m2, n_cand), args.constraints
    gamma, topics, b = surface(n_cand, m2, K)
    score = jax.jit(partial(_score, model, cfg.kind))
    cand_ids = jnp.arange(n_cand)
    chunk = min(args.offline_users, OFFLINE_CHUNK)
    X_parts, lam_parts, comp_parts = [], [], []
    for c, start in enumerate(range(0, args.offline_users, chunk)):
        n = min(chunk, args.offline_users - start)
        u_off, X_off = score(params, _request_batch(cfg, n, 10_000 + c),
                             cand_ids)
        sol = solve_dual_batch(u_off, jnp.asarray(topics), jnp.asarray(b),
                               jnp.asarray(gamma), m2=m2, num_iters=300)
        X_parts.append(X_off)
        lam_parts.append(sol.lam)
        comp_parts.append(sol.compliant)
    X_off = jnp.concatenate(X_parts)
    lam_off = jnp.concatenate(lam_parts)
    knn = KNNLambdaPredictor.fit(X_off, lam_off, k=10)
    mean = MeanLambdaPredictor.fit(X_off, lam_off)
    compliance = float(jnp.concatenate(comp_parts).mean())
    seconds["offline"] = time.perf_counter() - t0
    return Deployment(cfg=cfg, params=params, score=score, knn=knn,
                      mean=mean, n_cand=n_cand, m2=m2, K=K,
                      offline_compliance=compliance, seconds=seconds)


def make_requests(args, dep: Deployment, n_requests: int, *,
                  n_cand: int | None = None, seed: int = 20_000
                  ) -> list[RankRequest]:
    """The arrival stream: chunked backbone scoring over the first
    `n_cand` catalogue items (default: the deployment's candidate set),
    then one RankRequest per user with a jittered candidate-subset
    size in [(1 - --m1-jitter) * n_cand, n_cand]."""
    n_cand = dep.n_cand if n_cand is None else n_cand
    gamma, topics, b = surface(n_cand, dep.m2, dep.K)
    cand_ids = jnp.arange(n_cand)
    rng = np.random.default_rng(seed)
    chunk = 64
    requests = []
    m1_lo = max(dep.m2, int(n_cand * (1.0 - args.m1_jitter)))
    for c in range(-(-n_requests // chunk)):
        u, X = dep.score(dep.params, _request_batch(dep.cfg, chunk, seed + c),
                         cand_ids)
        u, X = np.asarray(u), np.asarray(X)
        for i in range(min(chunk, n_requests - c * chunk)):
            m1 = int(rng.integers(m1_lo, n_cand + 1))
            m2_req = min(dep.m2, m1)
            requests.append(RankRequest(
                rid=c * chunk + i, u=u[i, :m1], a=topics[:, :m1], b=b,
                m2=m2_req, X=X[i], tag=args.arch, gamma=gamma[:m2_req]))
    return requests


def make_engine(args, dep: Deployment, *, executor: str | None = None,
                mesh=None) -> ServingEngine:
    """One engine with the deployment's KNN predictor registered under
    the arch's tag (and, with --admission, the mean predictor as its
    degradation rung). `executor` defaults to --executor."""
    eng = ServingEngine(max_batch=args.max_batch,
                        max_wait_ms=args.max_wait_ms,
                        executor=executor or args.executor, mesh=mesh,
                        pipeline_depth=args.pipeline_depth,
                        admission=args.admission,
                        default_budget_s=args.budget_ms / 1e3)
    eng.register_predictor(args.arch, dep.knn, d_cov=dep.d_cov)
    if args.admission:
        # Cheapest rung: intercept-only predictor over the same duals.
        # Pre-warmed like every other bucket, so degrading never
        # compiles.
        eng.register_predictor(f"{args.arch}_mean", dep.mean,
                               d_cov=dep.d_cov)
        eng.set_degradation_ladder(args.arch, [f"{args.arch}_mean"])
    return eng


def serve(args, dep: Deployment, requests) -> dict:
    """Serve `requests` on one engine or a --replicas fleet; the JSON
    report."""
    report = {
        "arch": args.arch,
        "n_candidates": dep.n_cand, "m2": dep.m2, "K": dep.K,
        "executor": args.executor,
        "max_batch": args.max_batch, "max_wait_ms": args.max_wait_ms,
        "pipeline_depth": args.pipeline_depth,
        "admission": args.admission, "budget_ms": args.budget_ms,
        "replicas": args.replicas,
        "offline_compliance": round(dep.offline_compliance, 3),
    }
    if args.replicas > 1:
        # fleet path: health-checked consistent-hash routing over N
        # replica engines — each warms only its bucket subset (+ backup).
        router = FleetRouter(lambda _name=None: make_engine(args, dep),
                             args.replicas)
        warm = router.warmup(requests)
        results = router.serve_stream(requests, warmup=False)
        router.close()
        served = [r for r in results if isinstance(r, RankResult)]
        s = router.fleet_summary()
        lat = s.get("latency_ms", {"p99": float("nan")})
        report.update({
            "requests": len(results),
            "served": len(served), "shed": len(results) - len(served),
            "buckets": {n: w["buckets"] for n, w in warm.items()},
            "compiles_post_warmup": sum(
                r["compiles_post_warmup"] for r in s["replicas"].values()),
            "latency_ms": lat,
            "fleet": {k: s[k] for k in (
                "failovers", "hedges", "duplicates_deduped", "retries",
                "crashes", "restarts", "lost", "orphaned_futures")},
            "replica_states": {n: r["state"]
                               for n, r in s["replicas"].items()},
            "within_budget": bool(lat["p99"] <= args.budget_ms),
        })
    else:
        engine = make_engine(args, dep)
        warm = engine.warmup(requests)
        results = engine.serve_stream(requests)
        engine.close()
        served = [r for r in results if isinstance(r, RankResult)]
        s = engine.metrics.summary()
        report.update({
            "requests": len(results),
            "served": len(served), "shed": len(results) - len(served),
            "buckets": warm["buckets"],
            "compiles": s["compiles"],
            "compiles_post_warmup": s["compiles_post_warmup"],
            "fill_rate": s["fill_rate"],
            "latency_ms": s["latency_ms"],
            "queue_wait_ms": s["queue_wait_ms"],
            "pipeline": s["pipeline"],
            "online_compliance": s["compliance"],
            "deadline": s["deadline"],
            "within_budget": bool(s["latency_ms"]["p99"] <= args.budget_ms),
        })
    return report


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_arch(args.arch).make_config(full=True)
    dep = build_deployment(args, cfg)
    requests = make_requests(args, dep, args.requests)
    print(json.dumps(serve(args, dep, requests), indent=1))


if __name__ == "__main__":
    main()
