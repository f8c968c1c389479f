"""Multi-device semantics, run in a SUBPROCESS with 8 host devices so the
main test process keeps the single real CPU device.

Covers: distributed top-k merge == global top-k, sharded KNN == dense
KNN, compressed cross-pod psum accuracy, and one dry-run cell build on a
smoke mesh (sharding-rule plumbing under real SPMD execution).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh()                      # (2, 4) data x model

    # ---- distributed top-k == global top-k --------------------------------
    from repro.distributed.topk import sharded_knn_topk, sharded_score_topk
    key = jax.random.key(0)
    xq = jax.random.normal(key, (8, 32))
    xdb = jax.random.normal(jax.random.fold_in(key, 1), (512, 32))
    xdb_sh = jax.device_put(xdb, NamedSharding(mesh, P("model", None)))
    d2, idx = sharded_knn_topk(mesh, xq, xdb_sh, k=10)
    # dense reference
    ref_d2 = (jnp.sum(xq**2, 1, keepdims=True) - 2 * xq @ xdb.T
              + jnp.sum(xdb**2, 1)[None])
    ref_d2 = jnp.maximum(ref_d2, 0)
    ref_top = jnp.sort(ref_d2, axis=1)[:, :10]
    np.testing.assert_allclose(np.sort(np.asarray(d2), 1), ref_top,
                               rtol=1e-4, atol=1e-4)
    gathered = jnp.take_along_axis(ref_d2, idx, axis=1)
    np.testing.assert_allclose(np.sort(np.asarray(gathered), 1), ref_top,
                               rtol=1e-4, atol=1e-4)
    print("sharded_knn_topk OK")

    scores = jax.random.normal(jax.random.fold_in(key, 2), (8, 256))
    scores_sh = jax.device_put(scores, NamedSharding(mesh, P(None, "model")))
    v, i = sharded_score_topk(mesh, scores_sh, 5)
    ref_v, ref_i = jax.lax.top_k(scores, 5)
    np.testing.assert_allclose(v, ref_v, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
    print("sharded_score_topk OK")

    # ---- compressed cross-axis psum --------------------------------------
    from repro.optim.compression import compressed_psum
    x = jax.random.normal(jax.random.fold_in(key, 3), (8, 128))
    x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    out = jax.shard_map(
        lambda xs: compressed_psum(xs, "data"),
        mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
        check_vma=False)(x_sh)
    # exact psum reference: sum over the data axis groups
    ref = jnp.tile(x[:4] + x[4:], (2, 1))
    rel = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.02, rel   # int8 quantization error bound
    print("compressed_psum OK, rel err", rel)

    # ---- one dry-run cell builds, compiles AND RUNS on the smoke mesh ----
    from repro.launch.dryrun import run_cell
    from repro.launch.mesh import make_smoke_mesh
    smesh = make_smoke_mesh(multi_pod=True)
    rec = run_cell("llama3.2-1b", "train_4k", smesh, "t", smoke=True)
    assert rec["status"] == "ok", rec
    print("dryrun cell OK")

    # paper serve path executes under SPMD with real arrays
    from repro.configs.paper import PAPER_SMOKE_CELLS, build_paper, PaperConfig
    from repro.distributed.sharding import use_mesh_rules
    cell = [c for c in PAPER_SMOKE_CELLS if c.name == "serve_online"][0]
    low = build_paper(PaperConfig(), cell, smesh)
    args = [jax.tree.map(lambda s: jnp.full(s.shape, 0.25, s.dtype), a)
            for a in low.args]
    with use_mesh_rules(smesh, low.rules):
        out = jax.jit(low.fn)(*args)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(out)
               if jnp.issubdtype(x.dtype, jnp.floating))
    print("paper serve SPMD OK")

    # ---- distributed serving == dense serving (§Perf variant A) -----------
    from repro.core.predictors import knn_predict
    from repro.core.ranking import rank_given_lambda
    from repro.core.serving_dist import knn_predict_distributed, rank_distributed
    from repro.core.constraints import dcg_discount
    kk = jax.random.split(jax.random.key(9), 6)
    B, m1, K, n_db, d = 16, 64, 3, 128, 10
    X = jax.random.normal(kk[0], (B, d))
    X_db = jax.random.normal(kk[1], (n_db, d))
    lam_db = jnp.abs(jax.random.normal(kk[2], (n_db, K)))
    u = jax.random.uniform(kk[3], (B, m1))
    a = (jax.random.uniform(kk[4], (K, m1)) < 0.3).astype(jnp.float32)
    b = 0.1 * jnp.ones((K,))
    gamma = dcg_discount(8)
    lam_dense = knn_predict(X_db, lam_db, X, k=5)
    lam_dist = knn_predict_distributed(mesh, X_db, lam_db, X, k=5)
    np.testing.assert_allclose(lam_dist, lam_dense, rtol=1e-4, atol=1e-5)

    # ---- sharded QUANTIZED sweep == dense quantized predict ---------------
    # pack at a slab that divides the per-shard row count (128 rows over
    # 4 model shards -> 32/shard, slab=16): the global pack row-shards
    # cleanly, each shard holds whole slabs with their scales, and the
    # exact-on-x-tilde per-shard values make the k*shards merge bitwise
    # the dense selection.
    from repro.core.predictors import knn_predict_quant, pack_knn_db
    from repro.core.serving_dist import knn_predict_quant_distributed
    Xp_q, sc_q, y2q_q = pack_knn_db(X_db, mode="int8", slab=16)
    assert Xp_q.shape[0] == n_db  # no pad rows under this geometry
    lam_qd = knn_predict_quant(Xp_q, sc_q, y2q_q, lam_db, X, k=5,
                               mode="int8")
    lam_qdist = knn_predict_quant_distributed(
        mesh, Xp_q, sc_q, y2q_q, lam_db, X, k=5, mode="int8")
    np.testing.assert_allclose(np.asarray(lam_qdist), np.asarray(lam_qd),
                               rtol=5e-7, atol=1e-7)
    print("sharded quantized sweep OK")

    # the slab-streaming shard body vs the retired dense-matrix body:
    # the old body materialized the per-shard (B_l, n_l) distance
    # matrix; the new one streams knn_topk_scan slabs. Selection is
    # BITWISE identical (indices + gathered |x_n|^2 payload); the
    # distance VALUES may differ in the last ulp (the slab dot compiles
    # inside a scan body and XLA rounds the fused x2 - 2qx + y2 chain
    # differently there), so λ̂ is compared at 1-ulp tolerance.
    from repro.distributed.topk import distributed_top_k
    def old_dense_body(xq, xdb_local, lam_all):
        x2 = jnp.sum(xq * xq, axis=-1, keepdims=True)
        y2l = jnp.sum(xdb_local * xdb_local, axis=-1)
        d2 = jnp.maximum(x2 - 2.0 * (xq @ xdb_local.T) + y2l[None, :], 0.0)
        y2_b = jnp.broadcast_to(y2l[None, :], d2.shape)
        neg_d2, idx_g, y2_sel = distributed_top_k(-d2, 5, "model",
                                                  payload=y2_b)
        d2k = -neg_d2
        lam_nb = lam_all[idx_g]
        scale2 = x2 + y2_sel + 1e-12
        exact = d2k <= 1e-6 * scale2
        any_exact = jnp.any(exact, axis=-1, keepdims=True)
        w_inv = 1.0 / jnp.maximum(jnp.sqrt(d2k), 1e-12)
        w = jnp.where(any_exact, exact.astype(d2.dtype), w_inv)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        return idx_g, y2_sel, jnp.einsum("bk,bkc->bc", w, lam_nb)
    from repro.core.predictors import knn_topk_scan
    from repro.distributed.topk import gather_merge_top_k
    def new_selection_body(xq, xdb_local, lam_all):
        n_l = xdb_local.shape[0]
        neg_v, idx_l = knn_topk_scan(xdb_local, xq, k=5, chunk=n_l)
        y2l = jnp.sum(xdb_local * xdb_local, axis=-1)
        gidx = idx_l + jax.lax.axis_index("model") * n_l
        _, idx_g, y2_sel = gather_merge_top_k(neg_v, gidx, 5, "model",
                                              payload=y2l[idx_l])
        return idx_g, y2_sel
    specs = dict(mesh=mesh,
                 in_specs=(P("data", None), P("model", None), P()),
                 check_vma=False)
    old_idx, old_y2, old_lam = jax.shard_map(
        old_dense_body, out_specs=(P("data", None),) * 3, **specs)(
            X, X_db, lam_db)
    new_idx, new_y2 = jax.shard_map(
        new_selection_body, out_specs=(P("data", None),) * 2, **specs)(
            X, X_db, lam_db)
    np.testing.assert_array_equal(np.asarray(new_idx), np.asarray(old_idx))
    np.testing.assert_array_equal(np.asarray(new_y2), np.asarray(old_y2))
    np.testing.assert_allclose(np.asarray(lam_dist), np.asarray(old_lam),
                               rtol=5e-7, atol=1e-7)
    # multi-slab (ragged chunk) keeps the same answer
    lam_multi = knn_predict_distributed(mesh, X_db, lam_db, X, k=5, chunk=13)
    np.testing.assert_allclose(np.asarray(lam_multi), np.asarray(old_lam),
                               rtol=5e-7, atol=1e-7)
    print("slab-sweep shard body equivalence OK")
    dense = rank_given_lambda(u, a, b, lam_dense, gamma, m2=8)
    dist = rank_distributed(mesh, u, a, b, lam_dense, gamma, m2=8)
    np.testing.assert_array_equal(np.asarray(dist.perm), np.asarray(dense.perm))
    np.testing.assert_allclose(dist.utility, dense.utility, rtol=1e-5)
    np.testing.assert_allclose(dist.exposure, dense.exposure, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(dist.compliant),
                                  np.asarray(dense.compliant))
    print("distributed serving equivalence OK")

    # ---- serving engine with the distributed bucket executor --------------
    from repro.serving import ServingEngine, make_stream, Scenario
    eng_dist = ServingEngine(max_batch=8, max_wait_ms=1.0, executor="dist",
                             mesh=mesh, donate=False)
    eng_loc = ServingEngine(max_batch=8, max_wait_ms=1.0, donate=False)
    mix = (Scenario("feed", m1=200, m2=16, K=3, weight=2.0),
           Scenario("strip", m1=400, m2=8, K=5, weight=1.0))
    reqs = make_stream(mix, n_requests=32, seed=4)
    res_d = {r.rid: r for r in eng_dist.serve_stream(reqs)}
    res_l = {r.rid: r for r in eng_loc.serve_stream(reqs)}
    assert eng_dist.metrics.summary()["compiles_post_warmup"] == 0
    for rid in res_l:
        np.testing.assert_array_equal(res_d[rid].perm, res_l[rid].perm)
        np.testing.assert_allclose(res_d[rid].exposure, res_l[rid].exposure,
                                   rtol=1e-5, atol=1e-6)
        assert res_d[rid].compliant == res_l[rid].compliant
    print("engine dist executor OK")

    # ---- shard_map EP MoE == dense MoE (§Perf variant B), fwd + grads -----
    from dataclasses import replace
    from repro.models.transformer import LMConfig, TransformerLM
    from repro.distributed.sharding import LM_RULES
    cfgm = LMConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
                    d_ff=64, vocab=64, moe=True, n_experts=8, top_k=2,
                    d_ff_moe=32, dtype=jnp.float32, param_dtype=jnp.float32,
                    remat="none", dense_attn_threshold=4096,
                    capacity_factor=8.0)
    cfgs = replace(cfgm, moe_dispatch="shmap")
    md, ms = TransformerLM(cfgm), TransformerLM(cfgs)
    pm = md.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
    def l1(p): return md.loss(p, {"tokens": toks, "labels": toks})[0]
    def l2(p): return ms.loss(p, {"tokens": toks, "labels": toks})[0]
    g1 = jax.jit(jax.grad(l1))(pm)
    with use_mesh_rules(mesh, LM_RULES):
        g2 = jax.jit(jax.grad(l2))(pm)
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a_, b_: float(jnp.max(jnp.abs(a_ - b_))), g1, g2)))
    assert worst < 3e-4, worst
    print("shmap MoE grad equivalence OK", worst)

    # ---- elastic checkpoint restore onto a DIFFERENT mesh -----------------
    import tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P2
    from repro.checkpoint import CheckpointStore
    mesh_a = jax.make_mesh((2, 4), ("data", "model"))
    mesh_b = jax.make_mesh((4, 2), ("data", "model"))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh_a, P2("data", "model")))
    with tempfile.TemporaryDirectory() as d:
        store = CheckpointStore(d)
        store.save(1, {"w": w})
        like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        shardings = {"w": NamedSharding(mesh_b, P2("data", "model"))}
        restored, _ = store.restore(like, shardings=shardings)
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.arange(64.0).reshape(8, 8))
        assert restored["w"].sharding.mesh.shape["data"] == 4
    print("elastic reshard OK")
""")


@pytest.mark.slow
def test_multidevice_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # forced host devices: the child must never try to take an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _PROG], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=420)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    for marker in ("sharded_knn_topk OK", "sharded_score_topk OK",
                   "compressed_psum OK", "dryrun cell OK",
                   "paper serve SPMD OK",
                   "distributed serving equivalence OK",
                   "slab-sweep shard body equivalence OK",
                   "engine dist executor OK",
                   "shmap MoE grad equivalence OK",
                   "elastic reshard OK"):
        assert marker in r.stdout
