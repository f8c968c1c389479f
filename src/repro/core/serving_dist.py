"""Distributed (fleet-scale) variants of the online serving stage.

Beyond-paper optimizations, recorded separately in EXPERIMENTS.md §Perf:
the paper's online stage is per-user CPU code; at fleet scale the
GSPMD-global formulation (core/predictors.knn_predict + a global top_k)
makes XLA all-gather the (batch x n_db) distance matrix over the model
axis before selecting. These shard_map versions move only k candidates
per shard across the interconnect:

  knn_predict_distributed   per-shard distances + local top-k -> merge
                            (collective: B*k*shards*12 bytes, down from
                            B*n_db*4)
  rank_distributed          adjusted scores + top-m2 with the item axis
                            sharded (serve_retrieval's 2^20 candidates)

Numerically identical to the dense versions (exact KNN, exact top-k) —
asserted in tests/test_multidevice.py.

These are the rank bodies the streaming engine bakes into its
per-bucket executables when constructed with ``executor='dist'`` and a
mesh (repro.serving.engine._rank_fn): the engine's submission side
dispatches them asynchronously like any other bucket executable, and
nothing in this module blocks — the only host-side wait lives in the
engine pipeline's materialization step (and in ``warmup``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.predictors import _idw_lambda, knn_topk_scan
from repro.distributed.topk import distributed_top_k, gather_merge_top_k

Array = jax.Array


def knn_predict_distributed(
    mesh: Mesh,
    X_db: Array,     # (n_db, d) row-sharded over `db_axis`
    lam_db: Array,   # (n_db, K) REPLICATED (tiny: n_db*K floats)
    X: Array,        # (B, d) sharded over batch axes
    *,
    k: int = 10,
    db_axis: str = "model",
    batch_axes=("pod", "data"),
    chunk: int = 8192,
) -> Array:
    """Inverse-distance-weighted KNN regression, database sharded by rows.

    Matches core.predictors.knn_predict exactly (same weighting and
    relative exact-match override). The per-shard selection is the
    knn_topk_scan slab sweep — the db shard streams through in
    (B_l, chunk) slabs with only the running top-k as carry, so the
    (B_l, n_l) per-shard distance matrix of the old body never
    materializes (at 10^6 rows over 8 shards that matrix was
    B_l * 125k * 4 bytes per shard). The |x_n|^2 norms needed for the
    exact-match override are gathered per selected neighbour and ride
    the cross-shard merge — nothing database-sized crosses the
    interconnect OR sits in shard-local HBM beyond one slab.
    """
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def body(xq, xdb_local, lam_all):
        n_l = xdb_local.shape[0]
        kk = min(k, n_l)
        x2 = jnp.sum(xq * xq, axis=-1, keepdims=True)        # (B_l, 1)
        neg_v, idx_l = knn_topk_scan(xdb_local, xq, k=kk,
                                     chunk=min(chunk, n_l))
        y2l = jnp.sum(xdb_local * xdb_local, axis=-1)        # (n_l,)
        y2_sel_l = y2l[idx_l]                                # (B_l, kk)
        gidx = idx_l + jax.lax.axis_index(db_axis) * n_l
        neg_d2, idx, y2_sel = gather_merge_top_k(
            neg_v, gidx, k, db_axis, payload=y2_sel_l)
        return _idw_lambda(-neg_d2, x2, y2_sel, lam_all[idx])

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None), P(db_axis, None), P()),
        out_specs=P(batch_axes, None),
        check_vma=False,
    )(X, X_db, lam_db)


def knn_predict_quant_distributed(
    mesh: Mesh,
    X_q: Array,      # (n_db, d) packed db rows, row-sharded over `db_axis`
    q_scale: Array,  # (n_slabs, 1) per-slab scales, row-sharded likewise
    y2_q: Array,     # (n_db, 1) exact |x̃|^2, row-sharded likewise
    lam_db: Array,   # (n_db, K) REPLICATED (tiny: n_db*K floats)
    X: Array,        # (B, d) sharded over batch axes
    *,
    k: int = 10,
    mode: str = "int8",
    db_axis: str = "model",
    batch_axes=("pod", "data"),
) -> Array:
    """knn_predict_distributed over a QUANTIZED row-sharded db: each
    shard runs the quantized slab sweep + exact f32 survivor re-score
    (core.predictors.knn_quant_scan) on its rows, so the values that
    cross the interconnect are already EXACT-on-x̃ — the k·shards
    merge (gather_merge_top_k) and the shared IDW tail are untouched
    from the f32 path, and the result matches the dense
    knn_predict_quant selection bitwise (each shard's exact local
    top-k is a superset of its contribution to the global top-k; ties
    resolve to the lowest global index on both paths).

    Contract: pack with pack_knn_db at a slab that divides the
    per-shard row count so the global pack row-shards cleanly with no
    pad rows (X_q.shape[0] == lam_db.shape[0]) and each shard holds
    whole slabs with their scales.
    """
    if X_q.shape[0] != lam_db.shape[0]:
        raise ValueError(
            f"sharded quantized db must carry no pad rows: X_q has "
            f"{X_q.shape[0]} rows but lam_db {lam_db.shape[0]} — pack "
            f"with a slab dividing the per-shard row count")
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def body(xq, dbq_l, scale_l, y2q_l, lam_all):
        from repro.core.predictors import knn_quant_scan  # deferred

        n_l = dbq_l.shape[0]
        kk = min(k, n_l)
        x2 = jnp.sum(xq * xq, axis=-1, keepdims=True)        # (B_l, 1)
        # exact-on-x̃ local top-k: quantized sweep, exact re-score
        d2_l, idx_l, _ = knn_quant_scan(dbq_l, scale_l, y2q_l, xq,
                                        k=kk, mode=mode)
        y2_sel_l = y2q_l[idx_l, 0]                           # (B_l, kk)
        gidx = idx_l + jax.lax.axis_index(db_axis) * n_l
        neg_d2, idx, y2_sel = gather_merge_top_k(
            -d2_l, gidx, k, db_axis, payload=y2_sel_l)
        return _idw_lambda(-neg_d2, x2, y2_sel, lam_all[idx])

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None), P(db_axis, None),
                  P(db_axis, None), P(db_axis, None), P()),
        out_specs=P(batch_axes, None),
        check_vma=False,
    )(X, X_q, q_scale, y2_q, lam_db)


def rank_input_shardings(mesh: Mesh, *, item_axis: str = "model",
                         batch_axes=("pod", "data")) -> dict:
    """Where rank_distributed's per-request inputs live on `mesh`:
    candidates (u (B, m1), a (B, K, m1)) split over `item_axis` and
    requests over the batch axes; ``rows`` is any other (B, ...) input
    (b, gamma, lam, covariates); ``replicated`` for shared state. A
    caller that places host inputs with these puts each device's shard
    straight on that device, with no copy to one chip first."""
    batch_axes = tuple(ax for ax in batch_axes if ax in mesh.axis_names)
    return {"u": NamedSharding(mesh, P(batch_axes, item_axis)),
            "a": NamedSharding(mesh, P(batch_axes, None, item_axis)),
            "rows": NamedSharding(mesh, P(batch_axes, None)),
            "replicated": NamedSharding(mesh, P())}


def rank_distributed(
    mesh: Mesh,
    u: Array,        # (B, m1) items sharded over `item_axis`
    a: Array,        # (K, m1) shared or (B, K, m1) per-request, items sharded
    b: Array,        # (K,) shared or (B, K) per-request
    lam: Array,      # (B, K) sharded over batch axes
    gamma: Array,    # (m2,) shared or (B, m2) per-request
    *,
    m2: int,
    eps: float = 1e-4,
    item_axis: str = "model",
    batch_axes=("pod", "data"),
):
    """Online ranking with the item/candidate axis sharded: adjusted
    scores computed per item shard, local top-m2 per shard, merge of
    m2*shards candidates. Raw utilities AND the K constraint-attribute
    rows ride the merge as payloads, so utility / exposure / compliance
    need no second gather: the merged payloads feed the shared audit
    epilogue (core.ranking.audit_selected — the same math the Pallas
    rank+audit kernel runs in VMEM) and the outputs match
    rank_given_lambda exactly.

    Accepts the same shared-vs-per-request broadcast forms as
    rank_given_lambda (per-request a/b/gamma is what the shape-bucketed
    serving engine feeds when a mesh is present).

    Returns a RankingOutput.
    """
    from repro.core.ranking import RankingOutput, audit_selected

    batch_axes = tuple(ax for ax in batch_axes if ax in mesh.axis_names)
    a_spec = (P(batch_axes, None, item_axis) if a.ndim == 3
              else P(None, item_axis))
    b_spec = P(batch_axes, None) if b.ndim == 2 else P()
    gamma_spec = P(batch_axes, None) if gamma.ndim == 2 else P()

    def body(u_l, a_l, b_r, lam_l, gamma_r):
        B_l = u_l.shape[0]
        if a_l.ndim == 2:
            a_l = jnp.broadcast_to(a_l[None], (B_l,) + a_l.shape)
        if gamma_r.ndim == 1:
            gamma_r = jnp.broadcast_to(gamma_r[None], (B_l,) + gamma_r.shape)
        s = u_l + (1.0 + eps) * jnp.einsum("bk,bkm->bm", lam_l, a_l)
        payload = {"u": u_l,
                   "a": jnp.moveaxis(a_l, 1, 0)}              # (K, B_l, m1_l)
        vals, idx, sel = distributed_top_k(s, m2, item_axis, payload=payload)
        utility, exposure, compliant = audit_selected(
            sel["u"], jnp.moveaxis(sel["a"], 0, 1), gamma_r, b_r)
        return RankingOutput(perm=idx, utility=utility, exposure=exposure,
                             compliant=compliant, lam=lam_l)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, item_axis), a_spec, b_spec,
                  P(batch_axes, None), gamma_spec),
        out_specs=RankingOutput(
            perm=P(batch_axes, None), utility=P(batch_axes),
            exposure=P(batch_axes, None), compliant=P(batch_axes),
            lam=P(batch_axes, None)),
        check_vma=False,
    )(u, a, b, lam, gamma)
