"""Shadow-price predictors f(X) -> lambda (Algorithm 1, online stage).

The paper's estimator is a ball-tree KNN regressor with inverse-distance
weights (k = 10, Euclidean). On TPU a ball tree is pointer-chasing; we use
the *exact same estimator* computed by brute force: a (batch x train_users)
distance matmul that maps perfectly onto the MXU, followed by top-k. For
train databases sharded over the `model` mesh axis the top-k is merged
across shards (lax.top_k per shard -> gather k*shards -> re-top-k), see
`repro.distributed.topk.sharded_knn_topk`.

Beyond-paper predictors (recorded separately in EXPERIMENTS.md):
  * ridge-regression linear predictor (closed form, one (d x d) solve),
  * MLP predictor trained with the repo Adam — both strictly cheaper to
    serve than KNN (no train-database residency) and often as compliant.

All predictors share the interface:
  fit(X_train, lam_train) -> fitted predictor (pytree)
  predict(X) -> lam_hat   (jit-able, vmap-able, shard_map-able)

Hot-swap state seam (serving/refresh.py): `predictor_state` extracts
exactly the ARRAY fields of a predictor (STATE_FIELDS), `with_state`
grafts a compatible state dict back on. The split matters for jit: the
serving engine threads the state dict through its bucket executables as
an ARGUMENT (same pytree structure + shapes/dtypes -> same compile-cache
entry, so refreshing state never recompiles), while non-array statics —
KNN's `k` — stay closed over in the predictor template and keep shaping
the trace (lax.top_k needs a Python int, not a tracer).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.optim import adam_init, adam_update

Array = jax.Array


# ---------------------------------------------------------------------------
# Mean predictor (paper's 'Mean lambda' baseline)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class MeanLambdaPredictor:
    """Intercept-only, covariate-free predictor: lam_hat = mean(lam_train)."""

    mean_lam: Array  # (K,)

    @staticmethod
    def fit(X_train: Array, lam_train: Array) -> "MeanLambdaPredictor":
        del X_train
        return MeanLambdaPredictor(mean_lam=jnp.mean(lam_train, axis=0))

    def predict(self, X: Array) -> Array:
        batch = X.shape[:-1]
        return jnp.broadcast_to(self.mean_lam, batch + self.mean_lam.shape)


# ---------------------------------------------------------------------------
# KNN predictor (paper's proposed 'KNeighbors lambda')
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class KNNLambdaPredictor:
    """Exact k-nearest-neighbour regressor, inverse-distance weighted.

    Identical estimator to the paper's sklearn ball-tree KNN (k=10,
    weights='distance', Euclidean metric); computed by brute force:
      d2(x, xi) = |x|^2 - 2 x.xi + |xi|^2  -> top-k -> 1/d weights.
    The train database (X_db, lam_db) rides along in the pytree so the
    predictor can be donated/sharded like any other model state.

    Optionally the predictor also owns a QUANTIZED copy of the db
    (`quantized()` / pack_knn_db): per-slab int8 or bf16 rows (X_q),
    the per-slab dequant scales (q_scale), and the exact |x̃|^2 of the
    dequantized rows (y2_q, PAD_Y2 on slab-padding rows). The quantized
    sweep is exact ON x̃ — the dequantized rows are the ground truth of
    the packed representation — and the final selection is always
    re-scored in f32 (kernels/common.py). `quant` names the storage
    mode and is a STATIC field: it shapes the trace (kernel routing),
    never travels as a jit argument.
    """

    X_db: Array    # (n_train, d)
    lam_db: Array  # (n_train, K)
    k: int
    X_q: Optional[Array] = None      # (n_pad, d) packed db rows
    q_scale: Optional[Array] = None  # (n_slabs, 1) per-slab scales
    y2_q: Optional[Array] = None     # (n_pad, 1) exact |x̃|^2
    quant: str = field(default="off", metadata=dict(static=True))

    @staticmethod
    def fit(X_train: Array, lam_train: Array, k: int = 10) -> "KNNLambdaPredictor":
        return KNNLambdaPredictor(
            X_db=jnp.asarray(X_train), lam_db=jnp.asarray(lam_train), k=int(k)
        )

    def quantized(self, mode: str = "int8",
                  slab: int = None) -> "KNNLambdaPredictor":
        """A copy of this predictor carrying the packed db for the
        quantized sweep. `slab` MUST equal the serving tile_n (the
        per-slab scales are indexed by serving slab) — defaults to the
        kernel-wide DB_SLAB."""
        from repro.kernels.common import DB_SLAB, QUANT_MODES
        if mode not in QUANT_MODES or mode == "off":
            raise ValueError(f"quantized(): mode must be one of "
                             f"{[m for m in QUANT_MODES if m != 'off']}, "
                             f"got {mode!r}")
        slab = DB_SLAB if slab is None else int(slab)
        X_q, q_scale, y2_q = pack_knn_db(self.X_db, mode=mode, slab=slab)
        return dataclasses.replace(
            self, X_q=X_q, q_scale=q_scale, y2_q=y2_q, quant=mode)

    def predict(self, X: Array) -> Array:
        # Quantized predictors predict through the same quantized-sweep
        # + exact-survivor-rescore selection the serving kernels run, so
        # every consumer of this predictor sees one estimator (exact on
        # the dequantized db x̃), kernel path or not.
        if self.X_q is not None:
            return knn_predict_quant(
                self.X_q, self.q_scale, self.y2_q, self.lam_db, X,
                k=self.k, mode=self.quant)
        # Above the threshold the (b, n_train) distance matrix of the
        # one-matmul path stops fitting comfortably in cache/HBM
        # headroom; the chunked variant streams the train database in
        # (b, chunk) slabs instead, keeping only the running top-k.
        if self.X_db.shape[0] > KNN_CHUNK_THRESHOLD:
            return knn_predict_chunked(self.X_db, self.lam_db, X, k=self.k)
        return knn_predict(self.X_db, self.lam_db, X, k=self.k)


def _idw_lambda(d2_top: Array, x2: Array, y2_sel: Array,
                lam_neighbors: Array) -> Array:
    """Inverse-distance weighting with exact-match override on already
    top-k'd neighbours — the shared tail of the full-matrix and chunked
    KNN paths (identical ops, so the two paths can never drift).

    The expanded-form d2 carries O(eps_f32 * |x|^2) error, so 'exact'
    (query coincides with a database point -> return that point's value,
    sklearn 'distance' weights semantics) is a relative test.
    """
    dist = jnp.sqrt(d2_top)
    scale2 = x2 + y2_sel + 1e-12                            # (b, k)
    exact = d2_top <= 1e-6 * scale2
    any_exact = jnp.any(exact, axis=-1, keepdims=True)
    w_inv = 1.0 / jnp.maximum(dist, 1e-12)
    w = jnp.where(any_exact, exact.astype(d2_top.dtype), w_inv)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # multiply + reduce, not a batched einsum: Mosaic refuses batched
    # dot_general, and the KNN kernels call this at their λ̂ flush
    return jnp.sum(w[:, :, None] * lam_neighbors, axis=1)


@partial(jax.jit, static_argnames=("k",))
def knn_predict(X_db: Array, lam_db: Array, X: Array, *, k: int = 10) -> Array:
    """Inverse-distance-weighted KNN regression, batched over X rows.

    X: (..., d) -> (..., K). Exact: brute-force distances then top-k.
    When a query coincides with a database point (d == 0) the estimator
    returns that point's value (sklearn 'distance' weights semantics).
    """
    squeeze = X.ndim == 1
    Xq = jnp.atleast_2d(X)
    # (b, n) squared distances via the expanded form — one MXU matmul.
    x2 = jnp.sum(Xq * Xq, axis=-1, keepdims=True)          # (b, 1)
    y2 = jnp.sum(X_db * X_db, axis=-1)                      # (n,)
    d2 = x2 - 2.0 * (Xq @ X_db.T) + y2[None, :]             # (b, n)
    d2 = jnp.maximum(d2, 0.0)
    neg_top, idx = jax.lax.top_k(-d2, k)                    # (b, k)
    out = _idw_lambda(-neg_top, x2, y2[idx], lam_db[idx])
    return out[0] if squeeze else out


# Above this many train rows KNNLambdaPredictor.predict switches to the
# chunked path: the one-matmul form's (b, n_train) distance matrix is
# n_train * 4 bytes PER QUERY ROW — at 10^6 train users and batch 32
# that is a 128 MB materialization for 10 neighbours.
KNN_CHUNK_THRESHOLD = 32_768


def knn_topk_scan(
    X_db: Array, Xq: Array, *, k: int = 10, chunk: int = 8192
) -> tuple[Array, Array]:
    """Streaming top-k of -d2: the database scans through in
    `chunk`-row slabs and the carry is only the running top-k
    (neg-d2, global index) per query — O(b * chunk) peak distance
    storage, no (b, n_train) matrix ever.

    Ties break exactly like the one-matmul path (lower global index:
    the running buffer precedes the fresh slab in the merge). Returns
    (neg_top (b, k) descending, idx (b, k)). This is the slab sweep
    shared by knn_predict_chunked and the sharded serving body
    (core.serving_dist.knn_predict_distributed), where it serves as the
    per-shard local selection ahead of the cross-shard merge.
    """
    b = Xq.shape[0]
    n, d = X_db.shape
    if n < k:
        raise ValueError(f"n_train={n} < k={k}")
    x2 = jnp.sum(Xq * Xq, axis=-1, keepdims=True)           # (b, 1)
    # pad with far-away rows (never top-k when n >= k real rows exist)
    pad = (-n) % chunk
    Xdb_p = jnp.pad(X_db, ((0, pad), (0, 0)), constant_values=1e15)
    db_slabs = Xdb_p.reshape(-1, chunk, d)
    bases = jnp.arange(db_slabs.shape[0], dtype=jnp.int32) * chunk

    def body(carry, xs):
        run_v, run_i = carry                                # (b, k) each
        db, base = xs                                       # (chunk, d), ()
        y2c = jnp.sum(db * db, axis=-1)                     # (chunk,)
        d2 = jnp.maximum(x2 - 2.0 * (Xq @ db.T) + y2c[None, :], 0.0)
        cand_v = jnp.concatenate([run_v, -d2], axis=-1)     # (b, k+chunk)
        gidx = base + jnp.broadcast_to(
            jnp.arange(chunk, dtype=jnp.int32), (b, chunk))
        cand_i = jnp.concatenate([run_i, gidx], axis=-1)
        new_v, sel = jax.lax.top_k(cand_v, k)
        new_i = jnp.take_along_axis(cand_i, sel, axis=-1)
        return (new_v, new_i), None

    init = (jnp.full((b, k), -jnp.inf, Xq.dtype),
            jnp.zeros((b, k), jnp.int32))
    (neg_top, idx), _ = jax.lax.scan(body, init, (db_slabs, bases))
    return neg_top, idx


@partial(jax.jit, static_argnames=("k", "chunk"))
def knn_predict_chunked(
    X_db: Array, lam_db: Array, X: Array, *, k: int = 10, chunk: int = 8192
) -> Array:
    """knn_predict for large train databases: identical estimator,
    built on the knn_topk_scan slab sweep. The final weighting is the
    shared _idw_lambda on k gathered neighbours.
    """
    squeeze = X.ndim == 1
    Xq = jnp.atleast_2d(X)
    neg_top, idx = knn_topk_scan(X_db, Xq, k=k, chunk=chunk)
    x2 = jnp.sum(Xq * Xq, axis=-1, keepdims=True)           # (b, 1)
    y2 = jnp.sum(X_db * X_db, axis=-1)                      # (n,) — cheap
    out = _idw_lambda(-neg_top, x2, y2[idx], lam_db[idx])
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# Quantized db pack + the XLA quantized-sweep selection
# ---------------------------------------------------------------------------
# The Pallas quantized kernels (kernels/knn_topk.py) and the XLA scan
# below consume the SAME packed arrays and the SAME shared math
# (kernels/common.py), so their selections agree bitwise. The pack is a
# Python loop of per-slab jnp programs — repack_knn_slabs re-runs the
# identical per-slab program on touched slabs only, making an
# incremental repack bitwise-equal to a full repack BY CONSTRUCTION
# (no numpy-vs-jnp reduction-order drift possible).

def _pack_one_slab(x_slab: Array, *, mode: str):
    """Pack one db slab. x_slab (s, d) f32 (already padded to the slab
    size; padding rows must be all-zero) -> (rows_q (s, d) stored,
    scale (1, 1) f32, y2 (s, 1) f32 exact |x̃|^2 of the DEQUANTIZED
    rows)."""
    x = jnp.asarray(x_slab, jnp.float32)
    if mode == "int8":
        scale = jnp.max(jnp.abs(x)) / 127.0
        scale = jnp.where(scale > 0, scale, jnp.float32(1.0))
        rows_q = jnp.clip(jnp.round(x / scale), -127.0, 127.0
                          ).astype(jnp.int8)
        xt = rows_q.astype(jnp.float32) * scale
    elif mode == "bf16":
        rows_q = x.astype(jnp.bfloat16)
        scale = jnp.float32(1.0)
        xt = rows_q.astype(jnp.float32)
    else:
        raise ValueError(f"_pack_one_slab: bad mode {mode!r}")
    y2 = jnp.sum(xt * xt, axis=-1, keepdims=True)           # (s, 1)
    return rows_q, scale.reshape(1, 1), y2


def pack_knn_db(X_db: Array, *, mode: str = "int8", slab: int = 512):
    """Quantize the KNN train db into per-slab low-precision storage.

    Returns (X_q (n_pad, d), q_scale (n_slabs, 1) f32, y2_q (n_pad, 1)
    f32) with n_pad = n rounded up to a slab multiple. Padding rows
    store zero and get y2 = PAD_Y2 so they can never survive a sweep
    (int8 cannot encode the f32 path's 1e15 far-away padding). The slab
    size MUST equal the serving tile_n — q_scale rows are the kernel's
    slab blocks."""
    from repro.kernels.common import PAD_Y2
    X = jnp.asarray(X_db, jnp.float32)
    n, d = X.shape
    pad = (-n) % slab
    rows, scales, y2s = [], [], []
    for s in range(0, n + pad, slab):
        x = X[s:s + slab]
        short = slab - x.shape[0]
        if short:
            x = jnp.pad(x, ((0, short), (0, 0)))
        rows_q, scale, y2 = _pack_one_slab(x, mode=mode)
        if short:
            y2 = y2.at[slab - short:].set(PAD_Y2)
        rows.append(rows_q)
        scales.append(scale)
        y2s.append(y2)
    return (jnp.concatenate(rows, axis=0),
            jnp.concatenate(scales, axis=0),
            jnp.concatenate(y2s, axis=0))


def repack_knn_slabs(X_db: Array, X_q: Array, q_scale: Array, y2_q: Array,
                     rows, *, mode: str, slab: int):
    """Incremental repack after a ring write: re-quantize ONLY the
    slabs containing the touched `rows` (host ints / array of row
    indices into X_db), writing fresh rows AND the slab's fresh scale —
    a stale scale is never served. Each touched slab runs the exact
    per-slab program of pack_knn_db, so the result is bitwise equal to
    a full repack of the updated db."""
    from repro.kernels.common import PAD_Y2
    import numpy as np
    X = jnp.asarray(X_db, jnp.float32)
    n = X.shape[0]
    touched = sorted({int(r) // slab for r in np.asarray(rows).ravel()})
    for s_idx in touched:
        s = s_idx * slab
        x = X[s:s + slab]
        short = slab - x.shape[0]
        if short:
            x = jnp.pad(x, ((0, short), (0, 0)))
        rows_q, scale, y2 = _pack_one_slab(x, mode=mode)
        if short:
            y2 = y2.at[slab - short:].set(PAD_Y2)
        X_q = X_q.at[s:s + slab].set(rows_q)
        q_scale = q_scale.at[s_idx:s_idx + 1].set(scale)
        y2_q = y2_q.at[s:s + slab].set(y2)
    del n
    return X_q, q_scale, y2_q


def knn_quant_scan(X_q: Array, q_scale: Array, y2_q: Array, Xq: Array,
                   *, k: int = 10, k_extra: int = None, mode: str = "int8"):
    """Quantized-sweep selection under XLA: scan the packed db in slab
    blocks at low precision carrying a top-(k + k_extra) survivor set,
    then gather the survivors' dequantized rows, re-score them EXACTLY
    in f32, and re-rank to the final k with ties to the lowest global
    index (the f32 oracle's rule). Returns (d2_top (b, k) ascending
    exact-on-x̃, idx (b, k), guard (b, 1) i32 margin-guard flags).

    This is knn_topk_scan's quantized twin and the per-shard sweep of
    the distributed quantized path: same shared math as the Pallas
    kernels (kernels/common.py), so the selections agree bitwise."""
    from repro.kernels.common import (
        QUANT_EXTRA, bottomk_rerank, exact_rescore, quant_d2_err,
        quant_d2_tile)
    if k_extra is None:
        k_extra = QUANT_EXTRA
    k_keep = k + k_extra
    b = Xq.shape[0]
    n_pad, d = X_q.shape
    n_slabs = q_scale.shape[0]
    slab = n_pad // n_slabs
    db_slabs = X_q.reshape(n_slabs, slab, d)
    y2_slabs = y2_q.reshape(n_slabs, slab)
    bases = jnp.arange(n_slabs, dtype=jnp.int32) * slab

    def body(carry, xs):
        run_v, run_i = carry                                # (b, k_keep)
        db, y2_row, scale, base = xs
        d2q = quant_d2_tile(
            Xq, db, scale[0], jnp.broadcast_to(y2_row[None, :], (b, slab)),
            mode=mode)
        cand_v = jnp.concatenate([run_v, -d2q], axis=-1)
        gidx = base + jnp.broadcast_to(
            jnp.arange(slab, dtype=jnp.int32), (b, slab))
        cand_i = jnp.concatenate([run_i, gidx], axis=-1)
        new_v, sel = jax.lax.top_k(cand_v, k_keep)
        new_i = jnp.take_along_axis(cand_i, sel, axis=-1)
        return (new_v, new_i), None

    init = (jnp.full((b, k_keep), -jnp.inf, jnp.float32),
            jnp.zeros((b, k_keep), jnp.int32))
    (neg_v, idx), _ = jax.lax.scan(
        body, init, (db_slabs, y2_slabs, q_scale, bases))

    # exact f32 re-score of the survivors (gathers are fine under XLA)
    scale_rows = q_scale[idx // slab, 0]                    # (b, k_keep)
    x_sel = X_q[idx].astype(jnp.float32) * scale_rows[..., None]
    y2_sel = y2_q[idx, 0]                                   # (b, k_keep)
    x_cols = x_sel.transpose(0, 2, 1)                       # (b, d, k_keep)
    d2x = exact_rescore(Xq, x_cols, y2_sel)

    # margin guard on the QUANTIZED order (observability — the exact
    # re-score is always applied): gap vs the boundary pair's EXACT
    # quantization errors, the kernels' rule verbatim
    d2q_sorted = -neg_v                                     # (b, k_keep) asc
    gap = d2q_sorted[:, k:k + 1] - d2q_sorted[:, k - 1:k]
    errs = quant_d2_err(Xq, x_cols, mode=mode)              # (b, k_keep)
    guard = (gap <= errs[:, k - 1:k] + errs[:, k:k + 1]).astype(jnp.int32)
    d2_top, idx_top = bottomk_rerank(d2x, idx, k)
    return d2_top, idx_top, guard


@partial(jax.jit, static_argnames=("k", "mode"))
def knn_predict_quant(X_q: Array, q_scale: Array, y2_q: Array,
                      lam_db: Array, X: Array, *, k: int = 10,
                      mode: str = "int8") -> Array:
    """knn_predict through the quantized sweep + exact survivor
    re-score: the estimator every quantized consumer (XLA predict,
    Pallas kernels, distributed shards) agrees on, exact on the
    dequantized db x̃."""
    squeeze = X.ndim == 1
    Xq = jnp.atleast_2d(jnp.asarray(X, jnp.float32))
    d2_top, idx, _guard = knn_quant_scan(
        X_q, q_scale, y2_q, Xq, k=k, mode=mode)
    x2 = jnp.sum(Xq * Xq, axis=-1, keepdims=True)           # (b, 1)
    out = _idw_lambda(d2_top, x2, y2_q[idx, 0], lam_db[idx])
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# Ridge-regression predictor (beyond paper)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class LinearLambdaPredictor:
    """Ridge regression lam ~ W x + c, closed form; lam_hat clipped >= 0."""

    W: Array  # (K, d)
    c: Array  # (K,)

    @staticmethod
    def fit(
        X_train: Array, lam_train: Array, l2: float = 1e-3
    ) -> "LinearLambdaPredictor":
        X = jnp.asarray(X_train, jnp.float32)
        Y = jnp.asarray(lam_train, jnp.float32)
        mu_x = jnp.mean(X, axis=0)
        mu_y = jnp.mean(Y, axis=0)
        Xc, Yc = X - mu_x, Y - mu_y
        d = X.shape[1]
        G = Xc.T @ Xc + l2 * jnp.eye(d, dtype=X.dtype)
        W = jnp.linalg.solve(G, Xc.T @ Yc).T               # (K, d)
        c = mu_y - W @ mu_x
        return LinearLambdaPredictor(W=W, c=c)

    def predict(self, X: Array) -> Array:
        return jnp.maximum(X @ self.W.T + self.c, 0.0)


# ---------------------------------------------------------------------------
# MLP predictor (beyond paper)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class MLPLambdaPredictor:
    """Two-layer MLP lam ~ softplus-headed f(x); trained with repo Adam."""

    params: Any

    @staticmethod
    def init_params(key: Array, d_in: int, d_hidden: int, K: int):
        k1, k2 = jax.random.split(key)
        s1 = 1.0 / jnp.sqrt(d_in)
        s2 = 1.0 / jnp.sqrt(d_hidden)
        return {
            "w1": jax.random.normal(k1, (d_in, d_hidden), jnp.float32) * s1,
            "b1": jnp.zeros((d_hidden,), jnp.float32),
            "w2": jax.random.normal(k2, (d_hidden, K), jnp.float32) * s2,
            "b2": jnp.zeros((K,), jnp.float32),
        }

    @staticmethod
    def apply(params, X: Array) -> Array:
        h = jax.nn.relu(X @ params["w1"] + params["b1"])
        # softplus keeps lam_hat >= 0 (dual feasibility) with smooth grads.
        return jax.nn.softplus(h @ params["w2"] + params["b2"])

    @staticmethod
    def fit(
        X_train: Array,
        lam_train: Array,
        *,
        d_hidden: int = 64,
        num_steps: int = 500,
        lr: float = 1e-2,
        seed: int = 0,
        return_trace: bool = False,
        init_params: Any = None,
    ):
        """Full-batch Adam fit as ONE jit dispatch: the training loop is
        a lax.scan inside the compiled program, not `num_steps` Python
        round-trips through the jit cache (the old form paid per-step
        dispatch + host sync ~500 times). The per-step loss trace is
        stacked by the scan for free — pass ``return_trace=True`` to get
        ``(predictor, losses (num_steps,))`` instead of the predictor.

        ``init_params`` warm-starts from an existing parameter pytree
        (``d_hidden``/``seed`` are then ignored) — the refresh lane's
        re-fit path: a few Adam steps from the serving parameters
        instead of a from-scratch train.
        """
        X = jnp.asarray(X_train, jnp.float32)
        Y = jnp.asarray(lam_train, jnp.float32)
        params = init_params if init_params is not None else (
            MLPLambdaPredictor.init_params(
                jax.random.key(seed), X.shape[1], d_hidden, Y.shape[1]))
        opt = adam_init(params)

        def loss_fn(p):
            pred = MLPLambdaPredictor.apply(p, X)
            return jnp.mean((pred - Y) ** 2)

        @partial(jax.jit, static_argnames=("steps",))
        def train(p, o, *, steps):
            def step(carry, _):
                p, o = carry
                loss, g = jax.value_and_grad(loss_fn)(p)
                p, o = adam_update(g, o, p, lr=lr)
                return (p, o), loss

            (p, o), losses = jax.lax.scan(step, (p, o), None, length=steps)
            return p, losses

        params, losses = train(params, opt, steps=num_steps)
        predictor = MLPLambdaPredictor(params=params)
        return (predictor, losses) if return_trace else predictor

    def predict(self, X: Array) -> Array:
        return MLPLambdaPredictor.apply(self.params, X)


PREDICTOR_REGISTRY = {
    "mean": MeanLambdaPredictor,
    "knn": KNNLambdaPredictor,
    "linear": LinearLambdaPredictor,
    "mlp": MLPLambdaPredictor,
}


# ---------------------------------------------------------------------------
# Hot-swap state seam (serving/refresh.py)
# ---------------------------------------------------------------------------

# The ARRAY fields of each family — the refreshable state the serving
# engine threads through its bucket executables as a jit argument.
# Deliberately NOT tree_flatten: KNN's `k` is registered as pytree data
# but must stay a static Python int in the trace. Optional fields (KNN's
# packed-db triple) participate only when PRESENT on the instance —
# state_fields() filters out None-valued entries, so an unquantized
# predictor's state stays exactly {X_db, lam_db} (and its swap
# validation errors unchanged) while a quantized one threads all five
# arrays through the executables and the refresh lane.
STATE_FIELDS = {
    MeanLambdaPredictor: ("mean_lam",),
    KNNLambdaPredictor: ("X_db", "lam_db", "X_q", "q_scale", "y2_q"),
    LinearLambdaPredictor: ("W", "c"),
    MLPLambdaPredictor: ("params",),
}


def state_fields(predictor) -> tuple:
    """The refreshable array fields PRESENT on this instance: the
    family's STATE_FIELDS minus any optional field currently None."""
    return tuple(f for f in STATE_FIELDS.get(type(predictor), ())
                 if getattr(predictor, f, None) is not None)


def predictor_state(predictor) -> dict:
    """The predictor's refreshable array state as a flat dict. Unknown
    (duck-typed) predictor families have no registered state and return
    {} — the engine then closes over them whole, exactly the
    pre-refresh behavior: they serve fine but cannot be hot-swapped."""
    return {f: getattr(predictor, f) for f in state_fields(predictor)}


def with_state(predictor, state: dict):
    """The predictor with its array state replaced by `state` (same
    keys as predictor_state). Non-array statics (KNN's k, quant mode)
    carry over from the template, so a jit trace through the result
    keeps them as Python constants while the state arrays may be
    tracers. An empty state (unknown family) returns the predictor
    unchanged."""
    fields = state_fields(predictor)
    if set(state) != set(fields):
        raise ValueError(f"state keys {sorted(state)} != "
                         f"{sorted(fields)} for "
                         f"{type(predictor).__name__}")
    if not fields:
        return predictor
    return dataclasses.replace(predictor, **state)
