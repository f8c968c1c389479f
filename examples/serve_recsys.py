"""End-to-end serving driver (the paper's deployment scenario).

Trains a small SASRec retrieval backbone, fits the constrained-ranking
head (Algorithm 1 offline stage) on top of its scores/covariates, then
serves a stream of individual, shape-heterogeneous requests through the
async double-buffered micro-batching engine (repro.serving): backbone
scores -> shape bucket -> micro-batch -> KNN shadow prices ->
constrained top-k, with one pre-warmed executable per bucket so nothing
recompiles in steady state, and batch N+1 assembled while batch N's
outputs transfer back (docs/serving.md walks through the pipeline).

  PYTHONPATH=src python examples/serve_recsys.py [--requests 200]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constraints import dcg_discount
from repro.core.dual_solver import solve_dual_batch
from repro.core.predictors import KNNLambdaPredictor, MeanLambdaPredictor
from repro.data.batches import make_seqrec_batch
from repro.models.recsys import SASRec, RecsysConfig
from repro.optim import adam_init
from repro.serving import RankRequest, RankResult, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="0 = synchronous engine (pre-pipeline behavior)")
    ap.add_argument("--admission", action="store_true",
                    help="deadline-aware admission control with a "
                         "KNN -> mean degradation ladder")
    ap.add_argument("--budget-ms", type=float, default=50.0,
                    help="per-request latency budget (the paper's SLA)")
    args = ap.parse_args()

    # ---- 1. train the backbone --------------------------------------------
    cfg = RecsysConfig(kind="sasrec", n_items=2000, embed_dim=32,
                       n_blocks=2, n_heads=1, seq_len=20)
    model = SASRec(cfg)
    params = model.init(jax.random.key(0))
    opt = adam_init(params)

    @jax.jit
    def train_step(p, o, b):
        return model.train_step(p, o, b, lr=3e-3)

    print("training sasrec backbone (100 steps)...")
    for step in range(100):
        batch = make_seqrec_batch(jax.random.key(step), batch=64,
                                  seq_len=cfg.seq_len, n_items=cfg.n_items,
                                  n_neg=15, kind="sasrec")
        params, opt, metrics = train_step(params, opt, batch)
    print(f"  final loss {float(metrics['loss']):.3f}")

    # ---- 2. constrained-ranking head: offline stage -----------------------
    m1, m2, K = 512, 50, 4
    gamma = np.asarray(dcg_discount(m2), np.float32)
    cand_ids = jnp.arange(m1)
    # item topics (e.g. content categories needing exposure quotas)
    topics = np.asarray(
        (jax.random.uniform(jax.random.key(7), (K, m1)) < 0.15), np.float32)
    b = 0.08 * gamma.sum() * np.ones(K, np.float32)

    @jax.jit
    def score(params, seqs):
        return (model.retrieval_scores(params, seqs, cand_ids),
                model.user_covariates(params, seqs))

    n_offline = 256
    seqs = make_seqrec_batch(jax.random.key(1000), batch=n_offline,
                             seq_len=cfg.seq_len, n_items=cfg.n_items,
                             n_neg=1, kind="sasrec")["seq"]
    u_off, X_off = score(params, seqs)
    print(f"offline: solving {n_offline} duals (m1={m1}, K={K})...")
    sol = solve_dual_batch(u_off, jnp.asarray(topics), jnp.asarray(b),
                           jnp.asarray(gamma), m2=m2, num_iters=300)
    print(f"  offline compliance {float(sol.compliant.mean()):.2f}")
    knn = KNNLambdaPredictor.fit(X_off, sol.lam, k=10)

    # ---- 3. streaming online serving --------------------------------------
    engine = ServingEngine(max_batch=args.max_batch,
                           max_wait_ms=args.max_wait_ms,
                           pipeline_depth=args.pipeline_depth,
                           admission=args.admission,
                           default_budget_s=args.budget_ms / 1e3)
    engine.register_predictor("sasrec", knn, d_cov=cfg.embed_dim)
    if args.admission:
        mean = MeanLambdaPredictor.fit(X_off, sol.lam)
        engine.register_predictor("sasrec_mean", mean, d_cov=cfg.embed_dim)
        engine.set_degradation_ladder("sasrec", ["sasrec_mean"])

    # arrival stream: score in chunks, then one request per user with a
    # jittered candidate count (live retrieval returns varying sets).
    rng = np.random.default_rng(0)
    requests, chunk = [], 64
    for c in range(-(-args.requests // chunk)):
        seqs = make_seqrec_batch(jax.random.key(5000 + c), batch=chunk,
                                 seq_len=cfg.seq_len, n_items=cfg.n_items,
                                 n_neg=1, kind="sasrec")["seq"]
        u, X = score(params, seqs)
        u, X = np.asarray(u), np.asarray(X)
        for i in range(min(chunk, args.requests - c * chunk)):
            n_c = int(rng.integers(m1 // 2, m1 + 1))
            m2_req = min(m2, n_c)
            requests.append(RankRequest(
                rid=c * chunk + i, u=u[i, :n_c], a=topics[:, :n_c], b=b,
                m2=m2_req, X=X[i], tag="sasrec", gamma=gamma[:m2_req]))

    warm = engine.warmup(requests)
    print(f"warmed {len(warm['buckets'])} buckets "
          f"({warm['compiles']} compiles): {warm['buckets']}")

    results = engine.serve_stream(requests)
    engine.close()

    served = [r for r in results if isinstance(r, RankResult)]
    s = engine.metrics.summary()
    lat = s["latency_ms"]
    print(f"served {len(served)}/{len(results)} requests through "
          f"{s['batches']} micro-batches ({s['buckets_used']} buckets, "
          f"fill rate {s['fill_rate']:.0%}):")
    print(f"  latency  p50 {lat['p50']:7.2f} ms   p95 {lat['p95']:7.2f} ms   "
          f"p99 {lat['p99']:7.2f} ms  (per request, enqueue -> result)")
    print(f"  compliance {s['compliance']:.2f}")
    p = s["pipeline"]
    print(f"  pipeline depth {args.pipeline_depth}: max in-flight "
          f"{p['queue_depth_max']}, "
          f"exec p50 {p['exec_ms_per_batch']['p50']:.2f} ms/batch")
    print(f"  recompiles after warmup: {s['compiles_post_warmup']}")
    d = s["deadline"]
    print(f"  deadline ({args.budget_ms:.0f} ms budget): hit rate "
          f"{d['hit_rate']:.1%}, sheds {d['sheds']}, "
          f"degrades {d['degrades']}")
    print(f"  within the {args.budget_ms:.0f} ms budget: "
          f"{lat['p99'] <= args.budget_ms}")


if __name__ == "__main__":
    main()
