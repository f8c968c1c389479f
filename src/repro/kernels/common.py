"""Shared in-kernel primitives for the Pallas TPU kernels.

`topk_merge` is the streaming-selection building block used by knn_topk
and fused_rank: merge a fresh (B, T) score tile into a running (B, k)
top-k buffer held in VMEM scratch, flash-attention-style. k passes of
(max + first-argmax + mask) over the concatenated (B, k+T) tile; every
op is a lane reduction or elementwise — no sorts, no gathers, TPU-lowerable.

Ties break toward the candidate that comes FIRST in the concatenated
order. Because the running buffer (earlier tiles) precedes the fresh tile
and within a tile iota order is ascending, global tie-breaking is 'lowest
index wins' — matching the jnp stable-argsort oracle in ref.py.

The merge optionally carries PAYLOAD columns: a pytree of arrays whose
last axis is the candidate axis (running (B, ..., k), tile (B, ..., T)).
Each selected winner drags its payload slots along, so a kernel can keep
per-candidate side data resident in VMEM across the whole streaming
sweep and never re-gather it from HBM afterwards. Three kernels build
on it:
  * fused_rank.rank_audited_pallas — raw utilities + K attribute
    columns ride along so the audit runs at the flush step;
  * fused_rank.linear_rank_audited_pallas — same sweep, with the
    affine λ-predictor folded into the prologue (λ̂ itself lives in a
    VMEM scratch, not a payload — it is per-row, not per-candidate);
  * knn_topk.knn_lambda_pallas — each neighbour's λ row + |x_n|^2 ride
    along so the inverse-distance weighting runs at the flush step and
    the kernel emits λ̂ directly, no d2/idx pairs in HBM;
  * knn_topk.knn_rank_audited_pallas — BOTH of the above in one grid:
    the db-sweep merge feeds a λ̂ flush into VMEM scratch, then the
    rank-sweep merge audits at the final flush — the whole KNN online
    stage in one kernel launch.
It is also the in-VMEM twin of the payload ride-along in
repro.distributed.topk.distributed_top_k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = float(-1e30)

# ---------------------------------------------------------------------------
# Shared tiling knobs. Every kernel wrapper in ops.py defaults to these,
# so a TPU-generation retune is a one-file edit and the benchmarks'
# traffic models can import the exact geometry the kernels run with.
# ---------------------------------------------------------------------------

LANE = 128      # TPU lane width: minor-dim alignment boundary
SUBLANE = 8     # f32 sublane count: batch-row alignment boundary

TILE_B = SUBLANE   # batch rows resident per grid step (rank + KNN sweeps)
TILE_M = 512       # candidate (m1) columns per rank-sweep tile
DB_SLAB = 512      # train-db rows per VMEM slab in the KNN db sweeps


def first_argmax(x: jnp.ndarray) -> jnp.ndarray:
    """(B, N) -> (B,) index of the first maximum along the last axis,
    via the iota-min trick (jnp.argmax's tie semantics, TPU-friendly)."""
    m = jnp.max(x, axis=-1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, dimension=x.ndim - 1)
    masked = jnp.where(x >= m, iota, jnp.iinfo(jnp.int32).max)
    return jnp.min(masked, axis=-1)


def _select_one(p: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """Extract the single onehot-marked candidate column of a payload.

    p (B, ..., C), onehot (B, C) with exactly one True per row ->
    (B, ...). Sum-of-masked is exact (x + 0.0 == x in IEEE), works for
    any signed payload, and is a pure lane reduction."""
    oh = onehot.reshape(onehot.shape[:1] + (1,) * (p.ndim - 2)
                        + onehot.shape[-1:])
    return jnp.sum(jnp.where(oh, p, jnp.zeros_like(p)), axis=-1)


def _write_col(out: jnp.ndarray, val: jnp.ndarray, col: jnp.ndarray):
    """Write val (B, ...) into the col-marked last-axis slot of
    out (B, ..., k); col is a (B, k) onehot column mask."""
    cb = col.reshape(col.shape[:1] + (1,) * (out.ndim - 2) + col.shape[-1:])
    return jnp.where(cb, val[..., None], out)


def topk_merge(
    run_vals: jnp.ndarray,   # (B, k) running top values (descending-ish)
    run_idx: jnp.ndarray,    # (B, k) their global indices
    tile_vals: jnp.ndarray,  # (B, T) fresh candidate values
    tile_idx: jnp.ndarray,   # (B, T) their global indices
    k: int,
    run_payload=None,        # pytree of (B, ..., k) per-slot side data
    tile_payload=None,       # matching pytree of (B, ..., T)
):
    """Return new (run_vals, run_idx[, run_payload]): top-k of the union,
    descending, ties to lower concat position (running buffer first).
    When payloads ride along, each winner's payload slots are selected by
    the same onehot that selects its value — (vals, idx, payload)."""
    B = run_vals.shape[0]
    has_payload = run_payload is not None
    cand_v = jnp.concatenate([run_vals, tile_vals], axis=-1)   # (B, k+T)
    cand_i = jnp.concatenate([run_idx, tile_idx], axis=-1)
    cand_p = None
    if has_payload:
        cand_p = jax.tree.map(
            lambda rp, tp: jnp.concatenate([rp, tp], axis=-1),
            run_payload, tile_payload)
    iota = jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, dimension=1)

    out_v = jnp.full((B, k), NEG_INF, cand_v.dtype)
    out_i = jnp.zeros((B, k), jnp.int32)
    out_p = (jax.tree.map(
        lambda p: jnp.zeros(p.shape[:-1] + (k,), p.dtype), cand_p)
        if has_payload else None)

    def body(j, carry):
        cand_v, out_v, out_i, out_p = carry
        sel = first_argmax(cand_v)                             # (B,)
        onehot = iota == sel[:, None]                          # (B, k+T)
        v = jnp.max(jnp.where(onehot, cand_v, NEG_INF), axis=-1)
        gi = jnp.max(jnp.where(onehot, cand_i, -1), axis=-1)
        # write column j of the output buffers
        col = jax.lax.broadcasted_iota(jnp.int32, (B, k), dimension=1) == j
        out_v = jnp.where(col, v[:, None], out_v)
        out_i = jnp.where(col, gi[:, None], out_i)
        if has_payload:
            out_p = jax.tree.map(
                lambda op, cp: _write_col(op, _select_one(cp, onehot), col),
                out_p, cand_p)
        cand_v = jnp.where(onehot, NEG_INF, cand_v)
        return cand_v, out_v, out_i, out_p

    _, out_v, out_i, out_p = jax.lax.fori_loop(
        0, k, body, (cand_v, out_v, out_i, out_p))
    if has_payload:
        return out_v, out_i, out_p
    return out_v, out_i


# ---------------------------------------------------------------------------
# Quantized db-sweep primitives.
#
# The KNN kernels' runtime is dominated by the (B, d) x (d, n_train)
# distance dot of the db-slab sweep. Storing the db as int8 (or bf16)
# cuts the HBM bytes streamed per sweep 4x (2x) and moves the dot onto
# the low-precision MXU path; a small survivor set (k + QUANT_EXTRA per
# row) is then re-scored EXACTLY in f32 at the flush step, so the final
# selection — and everything derived from it (λ̂, permutation, utility,
# exposure, compliance) — is computed at full precision.
#
# Semantics: the quantized path's ground truth is the DEQUANTIZED db
# x̃ = int8_row * slab_scale. Quantization of the stored rows is a
# representation choice (lossy vs the original f32 db unless the db was
# int8-representable to begin with); everything downstream of the pack is
# exact-on-x̃, and ref.knn_quant_select_ref reproduces the selection
# bitwise from the same packed arrays. The query stays f32 in the bf16
# mode and is symmetrically int8-quantized (per-row scale) in the int8
# mode; quant_d2_err computes, per survivor, the EXACT d2 error
# introduced by the QUERY quantization, which is what the margin guard
# tests.
#
# Every helper below is shared verbatim by the Pallas kernels
# (knn_topk.py), the XLA scan path (predictors.knn_quant_scan), and the
# oracle (ref.py) — single-source math leaves only the reduction order
# to differ between kernel and oracle (core.ranking's parity contract).
# ---------------------------------------------------------------------------

QUANT_MODES = ("off", "bf16", "int8")

# Survivor over-retention: the quantized sweep keeps k + QUANT_EXTRA
# candidates so that quantization-induced rank inversions near the k-th
# place are repaired by the exact re-score instead of lost.
QUANT_EXTRA = 8

# Exact |x̃|^2 streamed alongside the quantized slabs; padding rows get
# this sentinel so they can never survive the sweep (int8 cannot encode
# a far-away row the way the f32 path's 1e15 padding does).
PAD_Y2 = float(1e30)


def quantize_query(q: jnp.ndarray):
    """Symmetric per-row int8 quantization of the query block.

    q (B, d) f32 -> (qi (B, d) f32 holding integer values in [-127, 127],
    sq (B, 1) f32 scale). qi stays f32: the MXU consumes it directly and
    f32 dots of integer-valued operands are exact for d * 127^2 < 2^24,
    so interpret-mode (CPU f32) and compiled int8-MXU (int32 accumulate)
    agree bitwise."""
    sq = jnp.max(jnp.abs(q), axis=-1, keepdims=True) / 127.0
    sq = jnp.where(sq > 0, sq, jnp.ones_like(sq))
    qi = jnp.clip(jnp.round(q / sq), -127.0, 127.0)
    return qi, sq


def dequant_rows(rows_q: jnp.ndarray, scale) -> jnp.ndarray:
    """x̃ = stored rows * slab scale. rows_q (n, d) int8-or-f32,
    scale scalar or broadcastable; returns f32."""
    return rows_q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)


def quant_d2_tile(q: jnp.ndarray, db_q: jnp.ndarray, scale,
                  y2_row: jnp.ndarray, *, mode: str) -> jnp.ndarray:
    """Quantized squared distances of a query block to one db slab.

    q (B, d) f32, db_q (T, d) stored slab, scale scalar slab scale,
    y2_row (B, T) exact |x̃|^2 broadcast across the batch -> (B, T) f32.

    int8: the query is quantized per-row and the cross term is a single
    integer-valued dot scaled back by (2 * sq * scale); d2 is exact in
    the db term (y2 streamed at f32) and approximate only through the
    query rounding. bf16: the slab is dequantized and the dot runs at
    f32 on the already-rounded values — no query error (bound 0)."""
    if mode == "int8":
        qi, sq = quantize_query(q)
        # integer operands below 2^8 are exact in one bf16 MXU pass
        cross = jax.lax.dot_general(
            qi, db_q.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (B, T)
        q2 = jnp.sum(q * q, axis=-1, keepdims=True)
        d2 = q2 - (2.0 * sq * jnp.asarray(scale, jnp.float32)) * cross \
            + y2_row
    elif mode == "bf16":
        xt = dequant_rows(db_q, scale)                   # (T, d) f32
        cross = jax.lax.dot_general(
            q, xt, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        q2 = jnp.sum(q * q, axis=-1, keepdims=True)
        d2 = q2 - 2.0 * cross + y2_row
    else:  # pragma: no cover - callers gate on mode
        raise ValueError(f"quant_d2_tile: bad mode {mode!r}")
    return jnp.maximum(d2, 0.0)


def exact_rescore(q: jnp.ndarray, x_sel: jnp.ndarray,
                  y2_sel: jnp.ndarray) -> jnp.ndarray:
    """Exact f32 squared distances of each row's survivor set.

    q (B, d), x_sel (B, d, k') dequantized survivor rows,
    y2_sel (B, k') their exact |x̃|^2 -> (B, k') f32."""
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)          # (B, 1)
    cross = jnp.sum(q[:, :, None] * x_sel, axis=1)       # (B, k')
    return jnp.maximum(q2 - 2.0 * cross + y2_sel, 0.0)


def quant_d2_err(q: jnp.ndarray, x_sel: jnp.ndarray, *,
                 mode: str) -> jnp.ndarray:
    """EXACT per-survivor quantization error of the sweep distances.

    The int8 cross term uses q̃ = sq * round(q / sq), so for a survivor
    with dequantized row x̃:  d2_quant - d2_exact = 2 (q - q̃) · x̃ —
    and at the flush the survivors' x̃ columns are VMEM-resident
    (x_sel (B, d, k')), so the error needs no bound at all: one small
    multiply-and-sum computes it exactly. The margin guard compares the quantized
    k/(k+1) gap against the two boundary candidates' |err| sum — the
    precise condition under which query rounding could have swapped
    their order. bf16 mode rounds the db only (no query error) -> 0.
    Returns (B, k') f32 = |d2q - d2x| per survivor."""
    if mode != "int8":
        return jnp.zeros(x_sel.shape[:1] + x_sel.shape[-1:], jnp.float32)
    qi, sq = quantize_query(q)
    e = q - sq * qi                                          # (B, d)
    return jnp.abs(2.0 * jnp.sum(e[:, :, None] * x_sel, axis=1))


def bottomk_rerank(d2: jnp.ndarray, gidx: jnp.ndarray, k: int,
                   payload=None):
    """Exact ascending top-k over a small candidate set, ties to the
    LOWEST GLOBAL INDEX — the stable-argsort tie rule of the f32 oracle.

    d2 (B, k') exact distances, gidx (B, k') global indices -> (d2_top
    (B, k), idx_top (B, k)[, payload_top]). k passes of (min-d2, then
    min-gidx among the tied, onehot select, mask +inf); every op is a
    lane reduction, so it runs identically in-kernel and under XLA."""
    B, kp = d2.shape
    INF = jnp.float32(jnp.inf)
    has_payload = payload is not None
    out_v = jnp.zeros((B, k), jnp.float32)
    out_i = jnp.zeros((B, k), jnp.int32)
    out_p = (jax.tree.map(
        lambda p: jnp.zeros(p.shape[:-1] + (k,), p.dtype), payload)
        if has_payload else None)
    big = jnp.iinfo(jnp.int32).max

    def body(j, carry):
        d2c, out_v, out_i, out_p = carry
        m = jnp.min(d2c, axis=-1, keepdims=True)                 # (B, 1)
        tied = d2c <= m                                          # (B, k')
        gi_sel = jnp.min(jnp.where(tied, gidx, big), axis=-1)    # (B,)
        onehot = jnp.logical_and(tied, gidx == gi_sel[:, None])  # (B, k')
        v = jnp.min(jnp.where(onehot, d2c, INF), axis=-1)
        col = jax.lax.broadcasted_iota(jnp.int32, (B, k), dimension=1) == j
        out_v = jnp.where(col, v[:, None], out_v)
        out_i = jnp.where(col, gi_sel[:, None], out_i)
        if has_payload:
            out_p = jax.tree.map(
                lambda op, cp: _write_col(op, _select_one(cp, onehot), col),
                out_p, payload)
        d2c = jnp.where(onehot, INF, d2c)
        return d2c, out_v, out_i, out_p

    _, out_v, out_i, out_p = jax.lax.fori_loop(
        0, k, body, (d2, out_v, out_i, out_p))
    if has_payload:
        return out_v, out_i, out_p
    return out_v, out_i
