"""knn_topk — tiled squared-L2 distances + streaming top-k (Pallas TPU).

The paper's lambda predictor is an exact KNN regressor; its serving cost
is one (batch x train-users) distance computation + top-k. This kernel
streams the train-user database HBM->VMEM exactly once per query tile:

  d2[b, n] = |q_b|^2 - 2 q_b . x_n + |x_n|^2

The cross term is an MXU matmul per (query tile, db tile); |x_n|^2 is
recomputed per tile (D multiplies — negligible vs the matmul); the
running top-k (negated distances) lives in VMEM scratch across the db
sweep. VMEM working set per step:
  q (Bq, D) + db (Tn, D) + d2 (Bq, Tn) + 2 (Bq, k) buffers.

Grid: (query_tiles, db_tiles), db minor so scratch persists. Alignment:
D and Tn multiples of 128 for the MXU; Bq multiple of 8.

`knn_lambda_pallas` extends the same sweep into the paper's full
predictor: the merge carries each neighbour's λ row (K values) and its
|x_n|^2 as VMEM payload columns (common.topk_merge ride-along), and the
flush step computes the inverse-distance weights — exact-match override
included — and emits λ̂ (B, K) directly. The (B, k) d2/idx pairs that
XLA would otherwise write out, re-read, and re-gather against the λ
database never exist in HBM; neither does the (B, n_train) distance
matrix the brute-force XLA path materializes. Since the single-grid
kernel below landed, this is the predict half of the RETAINED
two-kernel chain (ops.predict_rank_audited(knn_chain=True)) — the
parity oracle and A/B baseline for the fused grid.

`knn_rank_audited_pallas` is the whole KNN online stage as ONE grid:
per batch tile the minor axis first streams the S db slabs (the
knn_lambda sweep, double-buffered by the Pallas pipeline: slab t+1's
HBM->VMEM copy overlaps slab t's distance dot + merge), computes λ̂ at
the slab-sweep flush into a VMEM scratch, and then — still inside the
same program — continues through the M candidate tiles of the
rank+audit sweep (fused_rank's shared merge body reading λ̂ from that
scratch) and emits the complete RankingOutput at the final flush. λ̂
never exists in HBM at all (the (B, K) lam output written at the end is
observability, not a handoff), and the per-micro-batch kernel-launch
count drops from two to one. The db-sweep and rank-sweep bodies are the
SAME functions the two-kernel chain runs (_db_slab_merge /
_idw_lambda_flush here, _merge_scored_tile / _audit_flush in
fused_rank), so the fused program is bitwise-identical to the chain at
matched tile geometry (tests/test_knn_fused.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.predictors import _idw_lambda
from repro.kernels.common import (
    DB_SLAB,
    NEG_INF,
    PAD_Y2,
    QUANT_EXTRA,
    TILE_B,
    TILE_M,
    bottomk_rerank,
    dequant_rows,
    exact_rescore,
    quant_d2_err,
    quant_d2_tile,
    topk_merge,
)
from repro.kernels.fused_rank import (
    MAX_KERNEL_M2,
    _audit_flush,
    _merge_scored_tile,
)


def _knn_kernel(
    q_ref, db_ref,                 # inputs
    d2_ref, idx_ref,               # outputs
    run_v, run_i,                  # scratch
    *, k: int, tile_n: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG_INF)
        run_i[...] = jnp.zeros_like(run_i)

    q = q_ref[...].astype(jnp.float32)                       # (Bq, D)
    db = db_ref[...].astype(jnp.float32)                     # (Tn, D)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)              # (Bq, 1)
    db2 = jnp.sum(db * db, axis=-1)                          # (Tn,)
    # full f32 passes: the default TPU matmul rounds operands to bf16,
    # which would move neighbours against the f32 reference
    cross = jnp.dot(q, db.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(q2 - 2.0 * cross + db2[None, :], 0.0)   # (Bq, Tn)

    base = t * tile_n
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, d2.shape, dimension=1)
    new_v, new_i = topk_merge(run_v[...], run_i[...], -d2, gidx, k)
    run_v[...] = new_v
    run_i[...] = new_i

    @pl.when(t == pl.num_programs(1) - 1)
    def _flush():
        d2_ref[...] = -run_v[...]
        idx_ref[...] = run_i[...]


@functools.partial(
    jax.jit, static_argnames=("k", "tile_q", "tile_n", "interpret"))
def knn_topk_pallas(
    xq: jax.Array,    # (B, D) queries
    xdb: jax.Array,   # (N, D) database
    *,
    k: int = 10,
    tile_q: int = TILE_B,
    tile_n: int = DB_SLAB,
    interpret: bool = False,
):
    """Returns (d2 (B, k) ascending, idx (B, k) — ties to lower index)."""
    B, D = xq.shape
    N = xdb.shape[0]
    if B % tile_q or N % tile_n:
        raise ValueError(f"(B={B}, N={N}) must tile by ({tile_q}, {tile_n})")

    grid = (B // tile_q, N // tile_n)
    kernel = functools.partial(_knn_kernel, k=k, tile_n=tile_n)
    d2, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, D), lambda b, t: (b, 0)),
            pl.BlockSpec((tile_n, D), lambda b, t: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, k), lambda b, t: (b, 0)),
            pl.BlockSpec((tile_q, k), lambda b, t: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, k), jnp.float32),
            pltpu.VMEM((tile_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(xq, xdb)
    return d2, idx


# ---------------------------------------------------------------------------
# knn_lambda: distances + top-k + inverse-distance weighting in one sweep
# ---------------------------------------------------------------------------

def _db_slab_merge(
    slab, q_ref, db_ref, lamdb_ref, run_v, run_i, run_lam, run_y2,
    *, k: int, tile_n: int, num_k: int,
):
    """One db-slab step of the KNN λ sweep: squared-L2 distances for
    this slab, merged into the running top-k with each neighbour's λ row
    and |x_n|^2 riding along as payload. Shared verbatim by
    knn_lambda_pallas and the single-grid knn_rank_audited_pallas so
    their neighbour selections (and therefore λ̂) can never drift."""
    q = q_ref[...].astype(jnp.float32)                       # (Bq, D)
    db = db_ref[...].astype(jnp.float32)                     # (Tn, D)
    lamdb = lamdb_ref[...].astype(jnp.float32)               # (Tn, K)
    bq = q.shape[0]
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)              # (Bq, 1)
    db2 = jnp.sum(db * db, axis=-1)                          # (Tn,)
    cross = jnp.dot(q, db.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(q2 - 2.0 * cross + db2[None, :], 0.0)   # (Bq, Tn)

    base = slab * tile_n
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, d2.shape, dimension=1)
    # each candidate's payload: its λ row (constraint-major) and |x_n|^2
    tile_lam = jnp.broadcast_to(lamdb.T[None], (bq, num_k, tile_n))
    tile_y2 = jnp.broadcast_to(db2[None, :], (bq, tile_n))
    new_v, new_i, new_p = topk_merge(
        run_v[...], run_i[...], -d2, gidx, k,
        run_payload={"lam": run_lam[...], "y2": run_y2[...]},
        tile_payload={"lam": tile_lam, "y2": tile_y2})
    run_v[...] = new_v
    run_i[...] = new_i
    run_lam[...] = new_p["lam"]
    run_y2[...] = new_p["y2"]


def _idw_lambda_flush(q_ref, run_v, run_lam, run_y2):
    """Inverse-distance weighting on the VMEM-resident neighbours: the
    predictor's own _idw_lambda (one source of truth for the weights,
    exact-match override, and normalization), applied to payload columns
    instead of HBM gathers — the payload is constraint-major (Bq, K, k),
    so transpose to its (b, k, C) neighbour-major convention."""
    q = q_ref[...].astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)              # (Bq, 1)
    return _idw_lambda(
        -run_v[...], q2, run_y2[...], run_lam[...].transpose(0, 2, 1))


def _knn_lambda_kernel(
    q_ref, db_ref, lamdb_ref,      # inputs
    lam_ref,                       # output: lam_hat (Bq, K)
    run_v, run_i, run_lam, run_y2,  # scratch
    *, k: int, tile_n: int, num_k: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG_INF)
        run_i[...] = jnp.zeros_like(run_i)
        run_lam[...] = jnp.zeros_like(run_lam)
        run_y2[...] = jnp.zeros_like(run_y2)

    _db_slab_merge(t, q_ref, db_ref, lamdb_ref,
                   run_v, run_i, run_lam, run_y2,
                   k=k, tile_n=tile_n, num_k=num_k)

    @pl.when(t == pl.num_programs(1) - 1)
    def _flush():
        lam_ref[...] = _idw_lambda_flush(q_ref, run_v, run_lam, run_y2)


@functools.partial(
    jax.jit, static_argnames=("k", "tile_q", "tile_n", "interpret"))
def knn_lambda_pallas(
    xq: jax.Array,      # (B, D) queries
    xdb: jax.Array,     # (N, D) train database
    lam_db: jax.Array,  # (N, K) train shadow prices
    *,
    k: int = 10,
    tile_q: int = TILE_B,
    tile_n: int = DB_SLAB,
    interpret: bool = False,
):
    """Returns lam_hat (B, K): the inverse-distance-weighted KNN λ
    prediction, with the d2/idx intermediates and the (B, N) distance
    matrix never leaving VMEM. Requires N >= k real database rows (the
    KNN contract) so far-away padding rows can never enter a top-k."""
    B, D = xq.shape
    N, K = lam_db.shape
    if xdb.shape != (N, D):
        raise ValueError(f"xdb {xdb.shape} vs lam_db {lam_db.shape}: "
                         f"row counts must match")
    if B % tile_q or N % tile_n:
        raise ValueError(f"(B={B}, N={N}) must tile by ({tile_q}, {tile_n})")

    grid = (B // tile_q, N // tile_n)
    kernel = functools.partial(_knn_lambda_kernel, k=k, tile_n=tile_n,
                               num_k=K)
    lam = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, D), lambda b, t: (b, 0)),
            pl.BlockSpec((tile_n, D), lambda b, t: (t, 0)),
            pl.BlockSpec((tile_n, K), lambda b, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((tile_q, K), lambda b, t: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tile_q, k), jnp.float32),
            pltpu.VMEM((tile_q, k), jnp.int32),
            pltpu.VMEM((tile_q, K, k), jnp.float32),
            pltpu.VMEM((tile_q, k), jnp.float32),
        ],
        interpret=interpret,
    )(xq, xdb, lam_db)
    return lam


# ---------------------------------------------------------------------------
# Quantized db sweep: low-precision slab distances + exact survivor re-score
# ---------------------------------------------------------------------------

# The per-slab dequant scales sit whole in SMEM as a flat (n_slabs,)
# vector and each db step reads its own slab's scalar: a (1, 1) VMEM
# block over the (n_slabs, 1) array breaks the TPU's (8, 128) block
# tiling rule.
_SCALES_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _db_slab_merge_quant(
    slab, q_ref, dbq_ref, scale_ref, y2q_ref, lamdb_ref,
    run_v, run_i, run_lam, run_y2, run_xr,
    *, k_keep: int, tile_n: int, num_k: int, mode: str,
):
    """One QUANTIZED db-slab step: slab distances via the low-precision
    cross term (common.quant_d2_tile — int8 integer dot or bf16 dequant
    dot), merged into a running top-k_keep with each survivor's λ row,
    exact |x̃|^2, and DEQUANTIZED f32 row riding along as payload, so the
    flush can re-score survivors exactly without any HBM gather. The
    survivor buffer over-retains (k_keep = k + QUANT_EXTRA) so
    quantization-induced rank inversions near the k-th place are
    repaired by the exact re-score instead of lost."""
    q = q_ref[...].astype(jnp.float32)                       # (Bq, D)
    dbq = dbq_ref[...]                                       # (Tn, D) stored
    scale = scale_ref[slab]                                  # slab scale (SMEM)
    lamdb = lamdb_ref[...].astype(jnp.float32)               # (Tn, K)
    y2 = y2q_ref[...].astype(jnp.float32)[:, 0]              # (Tn,) exact |x̃|²
    bq, d_dim = q.shape
    y2_row = jnp.broadcast_to(y2[None, :], (bq, tile_n))
    d2q = quant_d2_tile(q, dbq, scale, y2_row, mode=mode)    # (Bq, Tn)

    base = slab * tile_n
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, d2q.shape, dimension=1)
    xt = dequant_rows(dbq, scale)                            # (Tn, D) f32
    tile_lam = jnp.broadcast_to(lamdb.T[None], (bq, num_k, tile_n))
    tile_y2 = y2_row
    tile_xr = jnp.broadcast_to(xt.T[None], (bq, d_dim, tile_n))
    new_v, new_i, new_p = topk_merge(
        run_v[...], run_i[...], -d2q, gidx, k_keep,
        run_payload={"lam": run_lam[...], "y2": run_y2[...],
                     "xr": run_xr[...]},
        tile_payload={"lam": tile_lam, "y2": tile_y2, "xr": tile_xr})
    run_v[...] = new_v
    run_i[...] = new_i
    run_lam[...] = new_p["lam"]
    run_y2[...] = new_p["y2"]
    run_xr[...] = new_p["xr"]


def _quant_init(run_v, run_i, run_lam, run_y2, run_xr):
    """Quantized-sweep scratch init. run_y2 starts at PAD_Y2 (not 0) so
    never-filled survivor slots re-score to ~1e30 and cannot shadow a
    real neighbour in the exact re-rank."""
    run_v[...] = jnp.full_like(run_v, NEG_INF)
    run_i[...] = jnp.zeros_like(run_i)
    run_lam[...] = jnp.zeros_like(run_lam)
    run_y2[...] = jnp.full_like(run_y2, PAD_Y2)
    run_xr[...] = jnp.zeros_like(run_xr)


def _quant_lambda_flush(
    q_ref, run_v, run_i, run_lam, run_y2, run_xr,
    *, k: int, mode: str,
):
    """Flush of the quantized sweep: exact f32 re-score of the survivor
    set, exact re-rank to the final k (ties to lower global index — the
    f32 oracle's rule), then the shared inverse-distance weighting on
    the re-ranked neighbours. Returns (lam_hat (Bq, K), guard (Bq, 1)
    i32). guard flags rows whose quantized k/(k+1) distance gap is
    within the two boundary candidates' EXACT quantization errors
    (common.quant_d2_err on the VMEM-resident survivor rows): for those
    rows the quantized ORDER was ambiguous and only the exact re-score
    (always applied, branchless) pins the selection; the flag is
    observability for the fallback rate, not a branch."""
    q = q_ref[...].astype(jnp.float32)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)              # (Bq, 1)
    d2q = -run_v[...]                                        # (Bq, k_keep) asc
    gap = d2q[:, k:k + 1] - d2q[:, k - 1:k]                  # (Bq, 1)
    errs = quant_d2_err(q, run_xr[...], mode=mode)           # (Bq, k_keep)
    bound = errs[:, k - 1:k] + errs[:, k:k + 1]              # (Bq, 1)
    guard = (gap <= bound).astype(jnp.int32)                 # (Bq, 1)

    d2x = exact_rescore(q, run_xr[...], run_y2[...])         # (Bq, k_keep)
    d2_top, _, p = bottomk_rerank(
        d2x, run_i[...], k,
        payload={"lam": run_lam[...], "y2": run_y2[...]})
    lam_hat = _idw_lambda(d2_top, q2, p["y2"], p["lam"].transpose(0, 2, 1))
    return lam_hat, guard


def _knn_lambda_quant_kernel(
    q_ref, dbq_ref, scale_ref, y2q_ref, lamdb_ref,             # inputs
    lam_ref, guard_ref,                                        # outputs
    run_v, run_i, run_lam, run_y2, run_xr,                     # scratch
    *, k: int, k_keep: int, tile_n: int, num_k: int, mode: str,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        _quant_init(run_v, run_i, run_lam, run_y2, run_xr)

    _db_slab_merge_quant(t, q_ref, dbq_ref, scale_ref, y2q_ref, lamdb_ref,
                         run_v, run_i, run_lam, run_y2, run_xr,
                         k_keep=k_keep, tile_n=tile_n, num_k=num_k,
                         mode=mode)

    @pl.when(t == pl.num_programs(1) - 1)
    def _flush():
        lam, guard = _quant_lambda_flush(
            q_ref, run_v, run_i, run_lam, run_y2, run_xr,
            k=k, mode=mode)
        lam_ref[...] = lam
        guard_ref[...] = guard


@functools.partial(
    jax.jit,
    static_argnames=("k", "k_extra", "mode", "tile_q", "tile_n", "interpret"))
def knn_lambda_quant_pallas(
    xq: jax.Array,       # (B, D) queries, f32
    xdb_q: jax.Array,    # (N, D) quantized db (int8 or bf16 storage)
    q_scale: jax.Array,  # (n_slabs, 1) per-slab dequant scales
    y2_q: jax.Array,     # (N, 1) exact |x̃|^2 (PAD_Y2 on padding rows)
    lam_db: jax.Array,   # (N, K) train shadow prices
    *,
    k: int = 10,
    k_extra: int = QUANT_EXTRA,
    mode: str = "int8",
    tile_q: int = TILE_B,
    tile_n: int = DB_SLAB,
    interpret: bool = False,
):
    """Quantized-sweep twin of knn_lambda_pallas. Returns (lam_hat
    (B, K) f32, guard (B, 1) i32). The slab distance sweep runs at low
    precision on the packed db; the top-(k + k_extra) survivor set is
    re-scored exactly in f32 at the flush and re-ranked to the final k,
    so lam_hat is exact-on-x̃ (x̃ = dequantized rows — see
    kernels/common.py). The pack (predictors.pack_knn_db) must use the
    serving tile_n as its slab size: q_scale rows ARE the slab blocks."""
    B, D = xq.shape
    N, K = lam_db.shape
    if xdb_q.shape != (N, D):
        raise ValueError(f"xdb_q {xdb_q.shape} vs lam_db {lam_db.shape}: "
                         f"row counts must match")
    if B % tile_q or N % tile_n:
        raise ValueError(f"(B={B}, N={N}) must tile by ({tile_q}, {tile_n})")
    n_slabs = N // tile_n
    if q_scale.shape != (n_slabs, 1):
        raise ValueError(f"q_scale {q_scale.shape} must be ({n_slabs}, 1): "
                         f"pack slab size must equal serving tile_n={tile_n}")
    k_keep = k + k_extra

    grid = (B // tile_q, n_slabs)
    kernel = functools.partial(
        _knn_lambda_quant_kernel, k=k, k_keep=k_keep, tile_n=tile_n,
        num_k=K, mode=mode)
    lam, guard = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, D), lambda b, t: (b, 0)),
            pl.BlockSpec((tile_n, D), lambda b, t: (t, 0)),
            _SCALES_SMEM,
            pl.BlockSpec((tile_n, 1), lambda b, t: (t, 0)),
            pl.BlockSpec((tile_n, K), lambda b, t: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, K), lambda b, t: (b, 0)),
            pl.BlockSpec((tile_q, 1), lambda b, t: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, k_keep), jnp.float32),
            pltpu.VMEM((tile_q, k_keep), jnp.int32),
            pltpu.VMEM((tile_q, K, k_keep), jnp.float32),
            pltpu.VMEM((tile_q, k_keep), jnp.float32),
            pltpu.VMEM((tile_q, D, k_keep), jnp.float32),
        ],
        interpret=interpret,
    )(xq, xdb_q, q_scale.reshape(n_slabs), y2_q, lam_db)
    return lam, guard


# ---------------------------------------------------------------------------
# knn_rank_audited: predict + rank + audit as ONE grid (the KNN online stage)
# ---------------------------------------------------------------------------

def _knn_rank_audited_kernel(
    q_ref, db_ref, lamdb_ref, b_ref, gamma_ref, u_ref, a_ref,   # inputs
    vals_ref, idx_ref, util_ref, expo_ref, comp_ref, lam_ref,   # outputs
    kv, ki, klam, ky2, lam_scr, rv, ri, ru, ra,                 # scratch
    *, k: int, tile_n: int, n_slabs: int,
    eps: float, m2: int, tile_m: int, num_k: int, tol: float,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        kv[...] = jnp.full_like(kv, NEG_INF)
        ki[...] = jnp.zeros_like(ki)
        klam[...] = jnp.zeros_like(klam)
        ky2[...] = jnp.zeros_like(ky2)
        rv[...] = jnp.full_like(rv, NEG_INF)
        ri[...] = jnp.zeros_like(ri)
        ru[...] = jnp.zeros_like(ru)
        ra[...] = jnp.zeros_like(ra)

    # Phase 1 — db slab sweep (steps 0..n_slabs-1): knn_lambda's merge,
    # verbatim. The Pallas pipeline double-buffers the slab blocks, so
    # slab t+1's HBM->VMEM copy overlaps slab t's distance dot + merge.
    @pl.when(t < n_slabs)
    def _db_step():
        _db_slab_merge(t, q_ref, db_ref, lamdb_ref, kv, ki, klam, ky2,
                       k=k, tile_n=tile_n, num_k=num_k)

    # λ̂ flush: the slab sweep ends and the rank sweep begins inside the
    # same program step — λ̂ goes VMEM scratch -> VMEM scratch, no HBM.
    @pl.when(t == n_slabs - 1)
    def _lam_flush():
        lam_scr[...] = _idw_lambda_flush(q_ref, kv, klam, ky2)

    # Phase 2 — candidate tile sweep (steps n_slabs..n_slabs+M-1):
    # rank_audited's merge, verbatim, reading λ̂ from scratch.
    @pl.when(t >= n_slabs)
    def _rank_step():
        _merge_scored_tile(t - n_slabs, lam_scr[...], u_ref, a_ref,
                           rv, ri, ru, ra,
                           eps=eps, m2=m2, tile_m=tile_m, num_k=num_k)

    @pl.when(t == pl.num_programs(1) - 1)
    def _final_flush():
        _audit_flush(gamma_ref, b_ref, vals_ref, idx_ref, util_ref,
                     expo_ref, comp_ref, rv, ri, ru, ra, tol=tol)
        lam_ref[...] = lam_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "m2", "eps", "tol", "tile_b", "tile_n", "tile_m",
                     "interpret"))
def knn_rank_audited_pallas(
    xq: jax.Array,       # (B, D) query covariates
    xdb: jax.Array,      # (N, D) train database
    lam_db: jax.Array,   # (N, K) train shadow prices (K = constraint tier)
    u: jax.Array,        # (B, m1)
    a: jax.Array,        # (B, K, m1)
    b: jax.Array,        # (B, K)
    gamma: jax.Array,    # (B, m2)
    *,
    k: int = 10,
    m2: int,
    eps: float = 1e-4,
    tol: float = 1e-6,
    tile_b: int = TILE_B,
    tile_n: int = DB_SLAB,
    tile_m: int = TILE_M,
    interpret: bool = False,
):
    """The paper's whole KNN online stage — λ̂ prediction, adjusted-score
    ranking, and the audit — as ONE pallas_call with grid
    (B/tile_b, n_slabs + m1_tiles). Returns (vals (B, m2) f32 desc,
    idx (B, m2) i32, utility (B, 1) f32, exposure (B, K) f32,
    compliant (B, 1) i32, lam (B, K) f32).

    Per batch tile the minor axis streams the db slabs first (running
    top-k + λ-row/|x_n|^2 payload in VMEM scratch), flushes λ̂ into a
    VMEM scratch at the last slab, then keeps going straight into the
    candidate tiles of the rank+audit sweep. The block index maps clamp:
    during the db phase the u/a maps sit on candidate tile 0 and during
    the rank phase the db maps sit on the last slab, so no block is
    refetched and the only HBM traffic is the compulsory stream of each
    input plus the tiny outputs — λ̂ (B, K) included purely as
    observability, never read back. Requires N >= k real database rows
    (the KNN contract) so far-away padding rows can never enter a top-k.
    """
    B, D = xq.shape
    N, K = lam_db.shape
    m1 = u.shape[1]
    if xdb.shape != (N, D):
        raise ValueError(f"xdb {xdb.shape} vs lam_db {lam_db.shape}: "
                         f"row counts must match")
    if a.shape != (B, K, m1):
        raise ValueError(f"a {a.shape} must be ({B}, {K}, {m1})")
    if m2 > MAX_KERNEL_M2:
        raise ValueError(f"kernel path supports m2 <= {MAX_KERNEL_M2}; "
                         f"use repro.kernels.ops.predict_rank_audited "
                         f"(XLA fallback)")
    if B % tile_b or N % tile_n or m1 % tile_m:
        raise ValueError(f"(B={B}, N={N}, m1={m1}) must tile by "
                         f"({tile_b}, {tile_n}, {tile_m})")

    n_slabs = N // tile_n
    grid = (B // tile_b, n_slabs + m1 // tile_m)
    kernel = functools.partial(
        _knn_rank_audited_kernel, k=k, tile_n=tile_n, n_slabs=n_slabs,
        eps=eps, m2=m2, tile_m=tile_m, num_k=K, tol=tol)
    # db blocks advance then park on the last slab; u/a blocks park on
    # candidate tile 0 until the rank phase starts. Pallas skips the
    # copy whenever a block index repeats, so parking is free.
    db_map = lambda bi, t: (jnp.minimum(t, n_slabs - 1), 0)
    cand = lambda t: jnp.maximum(t - n_slabs, 0)
    vals, idx, util, expo, comp, lam = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, D), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_n, D), db_map),
            pl.BlockSpec((tile_n, K), db_map),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, tile_m), lambda bi, t: (bi, cand(t))),
            pl.BlockSpec((tile_b, K, tile_m),
                         lambda bi, t: (bi, 0, cand(t))),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, 1), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, 1), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, m2), jnp.float32),
            jax.ShapeDtypeStruct((B, m2), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, K), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, K), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_b, k), jnp.float32),      # kv: running -d2
            pltpu.VMEM((tile_b, k), jnp.int32),        # ki: neighbour idx
            pltpu.VMEM((tile_b, K, k), jnp.float32),   # klam: λ payload
            pltpu.VMEM((tile_b, k), jnp.float32),      # ky2: |x_n|^2 payload
            pltpu.VMEM((tile_b, K), jnp.float32),      # lam_scr: λ̂
            pltpu.VMEM((tile_b, m2), jnp.float32),     # rv: running scores
            pltpu.VMEM((tile_b, m2), jnp.int32),       # ri: running items
            pltpu.VMEM((tile_b, m2), jnp.float32),     # ru: u payload
            pltpu.VMEM((tile_b, K, m2), jnp.float32),  # ra: a payload
        ],
        interpret=interpret,
    )(xq, xdb, lam_db, b, gamma, u, a)
    return vals, idx, util, expo, comp, lam


def _knn_rank_audited_quant_kernel(
    q_ref, dbq_ref, scale_ref, y2q_ref, lamdb_ref,              # inputs
    b_ref, gamma_ref, u_ref, a_ref,
    vals_ref, idx_ref, util_ref, expo_ref, comp_ref, lam_ref,   # outputs
    guard_ref,
    kv, ki, klam, ky2, kxr, lam_scr, rv, ri, ru, ra,            # scratch
    *, k: int, k_keep: int, tile_n: int, n_slabs: int,
    eps: float, m2: int, tile_m: int, num_k: int, tol: float, mode: str,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        _quant_init(kv, ki, klam, ky2, kxr)
        rv[...] = jnp.full_like(rv, NEG_INF)
        ri[...] = jnp.zeros_like(ri)
        ru[...] = jnp.zeros_like(ru)
        ra[...] = jnp.zeros_like(ra)

    # Phase 1 — QUANTIZED db slab sweep (steps 0..n_slabs-1).
    @pl.when(t < n_slabs)
    def _db_step():
        _db_slab_merge_quant(t, q_ref, dbq_ref, scale_ref, y2q_ref,
                             lamdb_ref, kv, ki, klam, ky2, kxr,
                             k_keep=k_keep, tile_n=tile_n, num_k=num_k,
                             mode=mode)

    # λ̂ flush: exact survivor re-score + re-rank + IDW, VMEM -> VMEM.
    @pl.when(t == n_slabs - 1)
    def _lam_flush():
        lam, guard = _quant_lambda_flush(
            q_ref, kv, ki, klam, ky2, kxr, k=k, mode=mode)
        lam_scr[...] = lam
        guard_ref[...] = guard

    # Phase 2 — candidate tile sweep: the f32 kernel's bodies, verbatim.
    @pl.when(t >= n_slabs)
    def _rank_step():
        _merge_scored_tile(t - n_slabs, lam_scr[...], u_ref, a_ref,
                           rv, ri, ru, ra,
                           eps=eps, m2=m2, tile_m=tile_m, num_k=num_k)

    @pl.when(t == pl.num_programs(1) - 1)
    def _final_flush():
        _audit_flush(gamma_ref, b_ref, vals_ref, idx_ref, util_ref,
                     expo_ref, comp_ref, rv, ri, ru, ra, tol=tol)
        lam_ref[...] = lam_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "k_extra", "mode", "m2", "eps", "tol",
                     "tile_b", "tile_n", "tile_m", "interpret"))
def knn_rank_audited_quant_pallas(
    xq: jax.Array,       # (B, D) query covariates, f32
    xdb_q: jax.Array,    # (N, D) quantized db (int8 or bf16 storage)
    q_scale: jax.Array,  # (n_slabs, 1) per-slab dequant scales
    y2_q: jax.Array,     # (N, 1) exact |x̃|^2 (PAD_Y2 on padding rows)
    lam_db: jax.Array,   # (N, K) train shadow prices
    u: jax.Array,        # (B, m1)
    a: jax.Array,        # (B, K, m1)
    b: jax.Array,        # (B, K)
    gamma: jax.Array,    # (B, m2)
    *,
    k: int = 10,
    k_extra: int = QUANT_EXTRA,
    mode: str = "int8",
    m2: int,
    eps: float = 1e-4,
    tol: float = 1e-6,
    tile_b: int = TILE_B,
    tile_n: int = DB_SLAB,
    tile_m: int = TILE_M,
    interpret: bool = False,
):
    """Quantized-sweep twin of knn_rank_audited_pallas: still ONE
    pallas_call for the whole KNN online stage, but the db slab sweep
    streams the int8/bf16 packed db (4x / 2x fewer HBM bytes than f32)
    and runs the distance dot at low precision; the survivor set is
    re-scored exactly in f32 at the λ̂ flush. Returns the f32 kernel's
    six outputs plus guard (B, 1) i32 — the margin-guard fallback flag
    per row (see _quant_lambda_flush). The rank+audit phase is the f32
    kernel's code verbatim, so with a lossless pack (dequant(pack(X))
    == X) the full RankingOutput is bitwise-identical to the f32 path."""
    B, D = xq.shape
    N, K = lam_db.shape
    m1 = u.shape[1]
    if xdb_q.shape != (N, D):
        raise ValueError(f"xdb_q {xdb_q.shape} vs lam_db {lam_db.shape}: "
                         f"row counts must match")
    if a.shape != (B, K, m1):
        raise ValueError(f"a {a.shape} must be ({B}, {K}, {m1})")
    if m2 > MAX_KERNEL_M2:
        raise ValueError(f"kernel path supports m2 <= {MAX_KERNEL_M2}; "
                         f"use repro.kernels.ops.predict_rank_audited "
                         f"(XLA fallback)")
    if B % tile_b or N % tile_n or m1 % tile_m:
        raise ValueError(f"(B={B}, N={N}, m1={m1}) must tile by "
                         f"({tile_b}, {tile_n}, {tile_m})")
    n_slabs = N // tile_n
    if q_scale.shape != (n_slabs, 1):
        raise ValueError(f"q_scale {q_scale.shape} must be ({n_slabs}, 1): "
                         f"pack slab size must equal serving tile_n={tile_n}")
    k_keep = k + k_extra

    grid = (B // tile_b, n_slabs + m1 // tile_m)
    kernel = functools.partial(
        _knn_rank_audited_quant_kernel, k=k, k_keep=k_keep, tile_n=tile_n,
        n_slabs=n_slabs, eps=eps, m2=m2, tile_m=tile_m, num_k=K, tol=tol,
        mode=mode)
    db_map = lambda bi, t: (jnp.minimum(t, n_slabs - 1), 0)
    cand = lambda t: jnp.maximum(t - n_slabs, 0)
    vals, idx, util, expo, comp, lam, guard = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, D), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_n, D), db_map),
            _SCALES_SMEM,
            pl.BlockSpec((tile_n, 1), db_map),
            pl.BlockSpec((tile_n, K), db_map),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, tile_m), lambda bi, t: (bi, cand(t))),
            pl.BlockSpec((tile_b, K, tile_m),
                         lambda bi, t: (bi, 0, cand(t))),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, 1), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, 1), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, 1), lambda bi, t: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, m2), jnp.float32),
            jax.ShapeDtypeStruct((B, m2), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, K), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, K), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_b, k_keep), jnp.float32),     # kv: running -d2q
            pltpu.VMEM((tile_b, k_keep), jnp.int32),       # ki: neighbour idx
            pltpu.VMEM((tile_b, K, k_keep), jnp.float32),  # klam: λ payload
            pltpu.VMEM((tile_b, k_keep), jnp.float32),     # ky2: |x̃|² payload
            pltpu.VMEM((tile_b, D, k_keep), jnp.float32),  # kxr: dequant rows
            pltpu.VMEM((tile_b, K), jnp.float32),          # lam_scr: λ̂
            pltpu.VMEM((tile_b, m2), jnp.float32),         # rv: running scores
            pltpu.VMEM((tile_b, m2), jnp.int32),           # ri: running items
            pltpu.VMEM((tile_b, m2), jnp.float32),         # ru: u payload
            pltpu.VMEM((tile_b, K, m2), jnp.float32),      # ra: a payload
        ],
        interpret=interpret,
    )(xq, xdb_q, q_scale.reshape(n_slabs), y2_q, lam_db, b, gamma, u, a)
    return vals, idx, util, expo, comp, lam, guard
