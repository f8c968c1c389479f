"""Distributed top-k merge primitives (DESIGN.md §4, serving fleet).

When the KNN train-user database (or the item catalog) is sharded over the
'model' mesh axis, each shard computes a local top-k and the results are
merged: lax.top_k per shard -> all_gather(k * n_shards) -> re-top-k. The
all-gather moves only k·n_shards candidates instead of the full database —
this is the collective pattern that keeps 10^6-candidate retrieval
(retrieval_cand) and million-user KNN serving inside the latency budget.

Written with shard_map so the collective is explicit in the lowered HLO
(the dry-run collective-bytes parser counts it).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Array = jax.Array


def _take_last(p: Array, idx: Array) -> Array:
    """take_along_axis on the LAST axis; idx broadcasts over any leading
    payload dims (payloads may be (..., b, n))."""
    while idx.ndim < p.ndim:
        idx = idx[None]
    return jnp.take_along_axis(p, jnp.broadcast_to(idx, p.shape[:-1] + idx.shape[-1:]), axis=-1)


def _merge_topk(values: Array, indices: Array, k: int, payload=None):
    """Merge (b, n_cand) candidate (value, global-index) pairs -> top-k."""
    top_v, pos = jax.lax.top_k(values, k)
    top_i = jnp.take_along_axis(indices, pos, axis=-1)
    if payload is None:
        return top_v, top_i
    sel = jax.tree.map(lambda p: _take_last(p, pos), payload)
    return top_v, top_i, sel


def gather_merge_top_k(
    local_v: Array,             # (b, kk) per-shard pre-selected values
    local_i: Array,             # (b, kk) their GLOBAL indices
    k: int,
    axis_name: str,
    payload=None,               # pytree of (..., b, kk) selected slots
):
    """The collective half of distributed_top_k: all_gather each
    shard's already-selected (value, global index) candidates — and
    their payload slots — then re-top-k the union. Exposed on its own
    so bodies that produce their local top-k without a dense score
    matrix (e.g. the slab-streaming sharded KNN body in
    core.serving_dist) can join the same merge. Only k·shards
    candidates cross the interconnect."""

    def gather_flat(x):
        """(..., b?, kk) -> all_gather -> (..., shards*kk): the shard axis
        lands in front; fold it into the last axis."""
        g = jax.lax.all_gather(x, axis_name)       # (shards, ..., kk)
        g = jnp.moveaxis(g, 0, -2)                 # (..., shards, kk)
        return g.reshape(g.shape[:-2] + (-1,))

    all_v = gather_flat(local_v)
    all_i = gather_flat(local_i)
    all_p = None
    if payload is not None:
        all_p = jax.tree.map(gather_flat, payload)
    return _merge_topk(all_v, all_i, k, all_p)


def distributed_top_k(
    scores: Array,              # (b, n_local) per-shard scores
    k: int,
    axis_name: str,
    global_offset: Array | None = None,
    payload=None,
):
    """Inside shard_map: local top-k -> all_gather -> re-top-k.

    Returns (values (b, k) descending, global indices (b, k)) — plus the
    selected `payload` entries when a pytree of (b, n_local) payloads
    rides along (e.g. raw utilities / constraint attributes when
    selecting by adjusted score). `global_offset` is this shard's
    starting index in the global catalog (defaults to
    axis_index * n_local). Only k*shards candidates (and their payload
    slots) cross the interconnect.
    """
    b, n_local = scores.shape
    kk = min(k, n_local)
    local_v, local_i = jax.lax.top_k(scores, kk)
    local_p = None
    if payload is not None:
        local_p = jax.tree.map(lambda p: _take_last(p, local_i), payload)
    if global_offset is None:
        global_offset = jax.lax.axis_index(axis_name) * n_local
    local_i = local_i + global_offset
    return gather_merge_top_k(local_v, local_i, k, axis_name,
                              payload=local_p)


def sharded_knn_topk(
    mesh: Mesh,
    xq: Array,       # (b, d) queries, replicated over the model axis
    xdb: Array,      # (n, d) database, row-sharded over `shard_axis`
    k: int,
    *,
    shard_axis: str = "model",
    batch_axes=("pod", "data"),
):
    """k nearest database rows under squared L2, database sharded by rows.

    The distance matmul runs per shard (MXU); only k candidates per shard
    cross the interconnect. Returns (d2 (b,k) ascending, idx (b,k) global).
    """
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    in_specs = (P(batch_axes, None), P(shard_axis, None))
    out_specs = (P(batch_axes, None), P(batch_axes, None))

    def body(xq_l, xdb_l):
        d2 = (
            jnp.sum(xq_l * xq_l, axis=-1, keepdims=True)
            - 2.0 * (xq_l @ xdb_l.T)
            + jnp.sum(xdb_l * xdb_l, axis=-1)[None, :]
        )
        d2 = jnp.maximum(d2, 0.0)
        neg_v, idx = distributed_top_k(-d2, k, shard_axis)
        return -neg_v, idx

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(xq, xdb)


def sharded_score_topk(
    mesh: Mesh,
    scores: Array,   # (b, n_candidates) sharded over candidates
    k: int,
    *,
    shard_axis: str = "model",
    batch_axes=("pod", "data"),
):
    """Top-k over a candidate axis that is sharded over `shard_axis`
    (retrieval_cand serving: 10^6 candidates, k winners)."""
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    in_specs = (P(batch_axes, shard_axis),)
    out_specs = (P(batch_axes, None), P(batch_axes, None))

    def body(s_l):
        return distributed_top_k(s_l, k, shard_axis)

    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(scores)
