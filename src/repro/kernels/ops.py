"""jit'd public wrappers for the Pallas kernels, with XLA fallbacks.

Call these, not the kernels directly: they pad awkward shapes to tile
boundaries, dispatch to the XLA reference when the kernel's static
constraints don't hold (huge m2, CPU runtime without interpret), and
return results in the oracle's exact format so callers can swap paths
without code changes.

interpret=None chooses from the default backend: Mosaic-compiled kernels
on a TPU, Pallas interpret mode anywhere else (the CPU test suite).
tests/test_tpu_compile.py compiles the main-path kernels for a described
v5e, and chip_smoke.py serves through them on a real one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.common import (
    DB_SLAB,
    LANE,
    NEG_INF,
    QUANT_EXTRA,
    QUANT_MODES,
    TILE_B,
    TILE_M,
)
from repro.kernels.fused_rank import (
    MAX_KERNEL_M2,
    fused_rank_pallas,
    linear_rank_audited_pallas,
    rank_audited_pallas,
)
from repro.kernels.knn_topk import (
    knn_lambda_pallas,
    knn_lambda_quant_pallas,
    knn_rank_audited_pallas,
    knn_rank_audited_quant_pallas,
    knn_topk_pallas,
)

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: Array, axis: int, mult: int, value):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# fused_rank
# ---------------------------------------------------------------------------

def fused_rank(
    u: Array, a: Array, lam: Array, *, m2: int, eps: float = 1e-4,
    use_kernel: bool | None = None, interpret: bool | None = None,
    tile_b: int = TILE_B, tile_m: int = TILE_M,
):
    """(top scores (n, m2) desc f32, item idx (n, m2)). See ref.fused_rank_ref."""
    if use_kernel is None:
        use_kernel = m2 <= MAX_KERNEL_M2
    if not use_kernel:
        return ref.fused_rank_ref(u, a, lam, m2, eps)
    if interpret is None:
        interpret = not _on_tpu()
    n, m1 = u.shape
    u_p = _pad_to(_pad_to(u, 0, tile_b, 0.0), 1, tile_m, -jnp.inf)
    a_p = _pad_to(_pad_to(a, 0, tile_b, 0.0), 2, tile_m, 0.0)
    lam_p = _pad_to(lam, 0, tile_b, 0.0)
    vals, idx = fused_rank_pallas(
        u_p, a_p, lam_p, m2=m2, eps=eps, tile_b=tile_b, tile_m=tile_m,
        interpret=interpret)
    return vals[:n], idx[:n]


# ---------------------------------------------------------------------------
# rank_audited
# ---------------------------------------------------------------------------

def rank_audited(
    u: Array,            # (n, m1)
    a: Array,            # (n, K, m1) or (K, m1)
    b: Array,            # (n, K) or (K,)
    lam: Array,          # (n, K)
    gamma: Array,        # (m2,) or (n, m2)
    *,
    m2: int,
    eps: float = 1e-4,
    tol: float | None = None,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    tile_b: int = TILE_B,
    tile_m: int = TILE_M,
):
    """Fused rank+audit dispatcher: one kernel emits the complete
    RankingOutput (perm, utility, exposure, compliant, lam) with zero
    post-kernel reads of ``u``/``a`` — the audit runs on the (K+1)·m2
    payload values the streaming top-m2 merge kept in VMEM.

    Accepts the same shared-vs-per-request broadcast forms as
    core.ranking.rank_given_lambda. ``tol`` defaults to the shared
    core.ranking.AUDIT_TOL so the compliance slack can never drift
    between the jnp and kernel paths. Falls back to the XLA oracle
    (ref.rank_audited_ref — broadcast gathers, no materialized index
    tensor) when m2 > MAX_KERNEL_M2 or ``use_kernel=False``; runs
    interpret=True off-TPU by default.
    """
    from repro.core.ranking import AUDIT_TOL, RankingOutput  # deferred: no cycle

    if tol is None:
        tol = AUDIT_TOL
    n = u.shape[0]
    if a.ndim == 2:
        a = jnp.broadcast_to(a, (n,) + a.shape)
    if b.ndim == 1:
        b = jnp.broadcast_to(b, (n,) + b.shape)
    if gamma.ndim == 1:
        gamma = jnp.broadcast_to(gamma, (n,) + gamma.shape)
    if use_kernel is None:
        use_kernel = m2 <= MAX_KERNEL_M2
    if not use_kernel:
        _, idx, utility, exposure, compliant = ref.rank_audited_ref(
            u, a, b, lam, gamma, m2, eps, tol)
        return RankingOutput(perm=idx, utility=utility, exposure=exposure,
                             compliant=compliant, lam=lam)
    if interpret is None:
        interpret = not _on_tpu()
    # NEG_INF (finite -1e30) keeps candidate padding out of every top-m2
    # while 0-discount slots still contribute exactly 0.0 to utility.
    u_p = _pad_to(_pad_to(u, 0, tile_b, 0.0), 1, tile_m, NEG_INF)
    a_p = _pad_to(_pad_to(a, 0, tile_b, 0.0), 2, tile_m, 0.0)
    b_p = _pad_to(b, 0, tile_b, 0.0)
    lam_p = _pad_to(lam, 0, tile_b, 0.0)
    gamma_p = _pad_to(gamma, 0, tile_b, 0.0)
    _, idx, util, expo, comp = rank_audited_pallas(
        u_p, a_p, b_p, lam_p, gamma_p, m2=m2, eps=eps, tol=tol,
        tile_b=tile_b, tile_m=tile_m, interpret=interpret)
    return RankingOutput(
        perm=idx[:n], utility=util[:n, 0], exposure=expo[:n],
        compliant=comp[:n, 0].astype(bool), lam=lam)


# ---------------------------------------------------------------------------
# predict_rank_audited: λ-predictor + rank + audit, one device program
# ---------------------------------------------------------------------------

def predict_rank_audited(
    X,                   # (n, d) covariates
    predictor,           # fitted λ predictor pytree (core.predictors)
    u: Array,            # (n, m1)
    a: Array,            # (n, K, m1) or (K, m1)
    b: Array,            # (n, K) or (K,)
    gamma: Array,        # (m2,) or (n, m2)
    *,
    m2: int,
    eps: float = 1e-4,
    tol: float | None = None,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    tile_b: int | None = None,
    tile_m: int = TILE_M,
    tile_n: int = DB_SLAB,
    knn_chain: bool = False,
    pad_lanes: bool | None = None,
):
    """The paper's ENTIRE online stage — predict λ̂ = f(X), rank, audit
    — as one dispatcher that lowers to a single device program, routed
    by predictor family:

      linear / mean   λ̂ = max(W x + c, 0) folds into the prologue of
                      the rank+audit kernel (linear_rank_audited_pallas)
                      — λ̂ is computed per batch tile into VMEM scratch
                      and never exists in HBM between predict and rank;
                      the mean predictor is the W = 0, no-clamp case.
                      Bitwise-identical to predict-then-rank.
      knn             knn_rank_audited_pallas: ONE pallas_call whose
                      grid streams the train db in tile_n-row slabs,
                      flushes λ̂ into VMEM scratch, and continues
                      straight into the rank+audit sweep — one kernel
                      launch per micro-batch, λ̂ never in HBM.
                      ``knn_chain=True`` keeps the pre-fusion two-kernel
                      chain (knn_lambda_pallas -> rank_audited_pallas
                      inside one jit executable, λ̂ handed off through
                      an HBM buffer) as the parity oracle the fused
                      grid is tested bitwise against at matched tiles.
      mlp / other     λ̂ = predictor.predict(X) stays XLA (matmuls are
                      already MXU-shaped) and joins the same jit
                      executable ahead of the rank+audit kernel.

    ``pad_lanes`` widens the affine prologue's covariate dim d to the
    128-lane boundary with zero W/X columns (exact: trailing zeros add
    nothing to the dot) — default on for compiled TPU kernels, OFF on
    the interpret path, whose bitwise-parity contract pins the dot's
    reduction length.

    Extra constraint rows in ``a`` beyond the predictor's output width
    (bucket-padded K) get zero shadow prices — exactly the serving
    engine's padding scheme. Falls back to the two-stage XLA oracle
    (ref.predict_rank_audited_ref) when m2 > MAX_KERNEL_M2 or
    ``use_kernel=False``; interpret=True off-TPU by default. Returns a
    complete RankingOutput whose ``lam`` is the λ̂ actually used.
    """
    from repro.core.predictors import (  # deferred: keep import DAG flat
        KNNLambdaPredictor,
        LinearLambdaPredictor,
        MeanLambdaPredictor,
    )
    from repro.core.ranking import AUDIT_TOL, RankingOutput

    if tol is None:
        tol = AUDIT_TOL
    n = u.shape[0]
    if X.shape[0] != n:
        # the kernel path pads X rows for tiling; a row-count mismatch
        # must be a loud caller error, never silently intercept-served
        raise ValueError(f"X carries {X.shape[0]} covariate rows but the "
                         f"problem has {n} users")
    if a.ndim == 2:
        a = jnp.broadcast_to(a, (n,) + a.shape)
    if b.ndim == 1:
        b = jnp.broadcast_to(b, (n,) + b.shape)
    if gamma.ndim == 1:
        gamma = jnp.broadcast_to(gamma, (n,) + gamma.shape)
    Kp = a.shape[1]
    if use_kernel is None:
        use_kernel = m2 <= MAX_KERNEL_M2
    if not use_kernel:
        _, idx, utility, exposure, compliant, lam = (
            ref.predict_rank_audited_ref(X, predictor, u, a, b, gamma,
                                         m2, eps, tol))
        return RankingOutput(perm=idx, utility=utility, exposure=exposure,
                             compliant=compliant, lam=lam)
    if interpret is None:
        interpret = not _on_tpu()

    if isinstance(predictor, KNNLambdaPredictor):
        # the KNN route picks its own batch tile: a wide resident query
        # tile divides the db-streaming cost (one sweep per tile), so it
        # defaults to knn_lambda_tile_q — 32 when the batch fills it —
        # exactly the geometry the PR 4 chain ran.
        if tile_b is None:
            tile_b = knn_lambda_tile_q(n)
    elif tile_b is None:
        tile_b = TILE_B

    if isinstance(predictor, (LinearLambdaPredictor, MeanLambdaPredictor)):
        if isinstance(predictor, LinearLambdaPredictor):
            W, c, relu = predictor.W, predictor.c, True
        else:
            # mean λ is affine with zero weights; no clamp (predict()
            # broadcasts mean_lam verbatim, clamped or not)
            W = jnp.zeros((predictor.mean_lam.shape[0], X.shape[1]),
                          jnp.float32)
            c, relu = predictor.mean_lam, False
        k_pred = W.shape[0]
        ref.check_pred_width(k_pred, Kp)
        # zero rows/intercepts for bucket-padded constraints: the
        # prologue emits exactly the 0.0 λ̂ the padding scheme wants.
        W_p = jnp.pad(W.astype(jnp.float32), ((0, Kp - k_pred), (0, 0)))
        c_p = jnp.pad(c.astype(jnp.float32), (0, Kp - k_pred))[None, :]
        u_p = _pad_to(_pad_to(u, 0, tile_b, 0.0), 1, tile_m, NEG_INF)
        a_p = _pad_to(_pad_to(a, 0, tile_b, 0.0), 2, tile_m, 0.0)
        b_p = _pad_to(b, 0, tile_b, 0.0)
        gamma_p = _pad_to(gamma, 0, tile_b, 0.0)
        X_p = _pad_to(jnp.asarray(X, jnp.float32), 0, tile_b, 0.0)
        # MXU lane alignment for the prologue dot: widen d to the
        # 128-lane boundary with zero columns of X AND zero columns of
        # W. Trailing zeros append exactly-0.0 terms at the END of the
        # reduction, so the math is exact — but the reduction LENGTH
        # changes, which on the interpret path would void the
        # bitwise-vs-predict() contract; hence the gate (compiled TPU
        # kernels only, unless a caller forces it).
        if pad_lanes is None:
            pad_lanes = not interpret
        if pad_lanes:
            X_p = _pad_to(X_p, 1, LANE, 0.0)
            W_p = _pad_to(W_p, 1, LANE, 0.0)
        _, idx, util, expo, comp, lam = linear_rank_audited_pallas(
            u_p, a_p, b_p, X_p, W_p, c_p, gamma_p, m2=m2, eps=eps, tol=tol,
            relu=relu, tile_b=tile_b, tile_m=tile_m, interpret=interpret)
        return RankingOutput(
            perm=idx[:n], utility=util[:n, 0], exposure=expo[:n],
            compliant=comp[:n, 0].astype(bool), lam=lam[:n])

    if isinstance(predictor, KNNLambdaPredictor):
        # a packed predictor (KNNLambdaPredictor.quantized) routes to
        # the quantized sweep; quant is a STATIC predictor field, so
        # the jit trace through the stateful seam branches on it as a
        # Python constant — no recompiles on state swaps.
        quant = predictor.quant if predictor.X_q is not None else "off"
        if quant != "off":
            # the per-slab scales ARE the kernel's slab blocks: the
            # pack geometry dictates the serving slab, so the sweep
            # tile follows the predictor rather than the default
            tile_n = predictor.X_q.shape[0] // predictor.q_scale.shape[0]
        if not knn_chain:
            return knn_rank_audited(
                X, predictor.X_db, predictor.lam_db, u, a, b, gamma,
                k=predictor.k, m2=m2, eps=eps, tol=tol,
                interpret=interpret, tile_b=tile_b, tile_n=tile_n,
                tile_m=tile_m, quant=quant, X_q=predictor.X_q,
                q_scale=predictor.q_scale, y2_q=predictor.y2_q)
        # the pre-fusion two-kernel chain: knn_lambda_pallas emits λ̂
        # through an HBM buffer, rank_audited_pallas reads it back —
        # kept as the single-grid kernel's bitwise parity oracle (and
        # for A/B measurement); tile_q matches the fused grid's batch
        # tile so the slab sweeps see identical tile geometry.
        lam = knn_lambda(X, predictor.X_db, predictor.lam_db,
                         k=predictor.k, interpret=interpret,
                         tile_q=tile_b, tile_n=tile_n, quant=quant,
                         X_q=predictor.X_q, q_scale=predictor.q_scale,
                         y2_q=predictor.y2_q)
        ref.check_pred_width(lam.shape[-1], Kp)
        lam = jnp.pad(lam, ((0, 0), (0, Kp - lam.shape[-1])))
    else:
        lam = predictor.predict(X).astype(jnp.float32)
        ref.check_pred_width(lam.shape[-1], Kp)
        lam = jnp.pad(lam, ((0, 0), (0, Kp - lam.shape[-1])))
    return rank_audited(u, a, b, lam, gamma, m2=m2, eps=eps, tol=tol,
                        interpret=interpret, tile_b=tile_b, tile_m=tile_m)


def predict_rank_audited_stateful(
    state: dict,         # predictor_state(predictor): the array leaves
    predictor,           # the STATIC template (family + non-array fields)
    X,
    u: Array,
    a: Array,
    b: Array,
    gamma: Array,
    **kwargs,
):
    """predict_rank_audited with the predictor's ARRAY state threaded
    as a leading argument — the hot-swap seam the serving engine jits.

    Closing a predictor over a jit body bakes its arrays in as
    executable constants, so refreshing them would force a retrace.
    Here `state` (core.predictors.predictor_state) enters the trace as
    a pytree ARGUMENT: swapping in new arrays of identical structure /
    shape / dtype hits the same compile-cache entry — zero recompiles —
    while `predictor` stays the static template whose family routes the
    dispatch and whose non-array fields (KNN's k) shape the trace.
    """
    from repro.core.predictors import with_state  # deferred: no cycle

    return predict_rank_audited(X, with_state(predictor, state),
                                u, a, b, gamma, **kwargs)


def _quant_db(X_db, X_q, q_scale, y2_q, *, quant: str, tile_n: int):
    """Resolve the packed-db triple for the quantized sweep: validate
    the pack-slab == serving-tile_n contract (the per-slab scales ARE
    the kernel's slab blocks) or auto-pack at tile_n when the caller
    hands only the f32 db. Returns (X_q, q_scale, y2_q)."""
    from repro.core.predictors import pack_knn_db  # deferred: no cycle

    if quant not in QUANT_MODES:
        raise ValueError(f"quant must be one of {QUANT_MODES}, got {quant!r}")
    if X_q is None:
        return pack_knn_db(X_db, mode=quant, slab=tile_n)
    n_pad = X_q.shape[0]
    if n_pad % tile_n or q_scale.shape[0] * tile_n != n_pad:
        raise ValueError(
            f"quantized db packed at slab={n_pad // max(q_scale.shape[0], 1)}"
            f" but serving tile_n={tile_n}: repack with slab=tile_n "
            f"(KNNLambdaPredictor.quantized(slab=tile_n))")
    return X_q, q_scale, y2_q


def knn_rank_audited(
    X: Array,            # (n, d) query covariates
    X_db: Array,         # (n_train, d) train database
    lam_db: Array,       # (n_train, K_pred) train shadow prices
    u: Array,            # (n, m1)
    a: Array,            # (n, K, m1)
    b: Array,            # (n, K)
    gamma: Array,        # (n, m2)
    *,
    k: int = 10,
    m2: int,
    eps: float = 1e-4,
    tol: float | None = None,
    interpret: bool | None = None,
    tile_b: int | None = None,
    tile_n: int = DB_SLAB,
    tile_m: int = TILE_M,
    quant: str = "off",
    X_q: Array | None = None,       # packed db (predictors.pack_knn_db)
    q_scale: Array | None = None,   # (n_slabs, 1) per-slab scales
    y2_q: Array | None = None,      # (n_pad, 1) exact |x̃|^2
    k_extra: int = QUANT_EXTRA,
    return_guard: bool = False,
):
    """The single-grid KNN online stage (knn_rank_audited_pallas) with
    the padding contract of the other dispatchers: rows to tile_b
    (default knn_lambda_tile_q — wide resident query tiles divide the
    db-streaming cost; zero covariates — phantom rows score 0
    everywhere and are sliced off),
    db rows to tile_n with far-away 1e15 rows (never top-k while the
    KNN contract n_train >= k holds; their λ rows zeroed for hygiene),
    candidates to tile_m with NEG_INF utilities, and bucket-padded
    constraint rows beyond the predictor's width priced at exactly 0.0
    (zero lam_db columns make the flush-step einsum emit 0.0). Returns
    a complete RankingOutput.

    quant='int8'|'bf16' routes to the quantized-sweep twin
    (knn_rank_audited_quant_pallas): the db streams in low precision
    (4x / 2x fewer HBM bytes) and the top-(k + k_extra) survivors are
    re-scored exactly in f32 at the flush. The packed triple comes from
    the caller (pack slab MUST equal tile_n) or is packed here at
    tile_n. ``return_guard=True`` appends the per-row margin-guard
    fallback flags ((n, 1) i32) to the return."""
    from repro.core.ranking import AUDIT_TOL, RankingOutput  # deferred: no cycle

    if tol is None:
        tol = AUDIT_TOL
    if X_db.shape[0] < k:
        raise ValueError(f"n_train={X_db.shape[0]} < k={k}")
    if interpret is None:
        interpret = not _on_tpu()
    n = u.shape[0]
    if X.shape[0] != n:
        # same loud contract as predict_rank_audited: row padding is
        # the kernel's job, a row-count mismatch is a caller bug that
        # must never be silently intercept-served or sliced away
        raise ValueError(f"X carries {X.shape[0]} covariate rows but the "
                         f"problem has {n} users")
    if tile_b is None:
        tile_b = knn_lambda_tile_q(n)
    Kp = a.shape[1]
    k_pred = lam_db.shape[1]
    ref.check_pred_width(k_pred, Kp)
    Xq_p = _pad_to(jnp.asarray(X, jnp.float32), 0, tile_b, 0.0)
    u_p = _pad_to(_pad_to(u, 0, tile_b, 0.0), 1, tile_m, NEG_INF)
    a_p = _pad_to(_pad_to(a, 0, tile_b, 0.0), 2, tile_m, 0.0)
    b_p = _pad_to(b, 0, tile_b, 0.0)
    gamma_p = _pad_to(gamma, 0, tile_b, 0.0)

    if quant != "off":
        X_q, q_scale, y2_q = _quant_db(
            X_db, X_q, q_scale, y2_q, quant=quant, tile_n=tile_n)
        # lam rows pad to the PACKED row count (pack pads with zero
        # rows + PAD_Y2, which the sweep can never select)
        lamdb_p = jnp.pad(
            lam_db, ((0, X_q.shape[0] - lam_db.shape[0]), (0, Kp - k_pred)))
        _, idx, util, expo, comp, lam, guard = knn_rank_audited_quant_pallas(
            Xq_p, X_q, q_scale, y2_q, lamdb_p, u_p, a_p, b_p, gamma_p,
            k=k, k_extra=k_extra, mode=quant, m2=m2, eps=eps, tol=tol,
            tile_b=tile_b, tile_n=tile_n, tile_m=tile_m,
            interpret=interpret)
        out = RankingOutput(
            perm=idx[:n], utility=util[:n, 0], exposure=expo[:n],
            compliant=comp[:n, 0].astype(bool), lam=lam[:n])
        return (out, guard[:n]) if return_guard else out

    xdb_p = _pad_to(X_db, 0, tile_n, 1e15)
    lamdb_p = _pad_to(
        jnp.pad(lam_db, ((0, 0), (0, Kp - k_pred))), 0, tile_n, 0.0)
    _, idx, util, expo, comp, lam = knn_rank_audited_pallas(
        Xq_p, xdb_p, lamdb_p, u_p, a_p, b_p, gamma_p, k=k, m2=m2,
        eps=eps, tol=tol, tile_b=tile_b, tile_n=tile_n, tile_m=tile_m,
        interpret=interpret)
    out = RankingOutput(
        perm=idx[:n], utility=util[:n, 0], exposure=expo[:n],
        compliant=comp[:n, 0].astype(bool), lam=lam[:n])
    if return_guard:
        return out, jnp.zeros((n, 1), jnp.int32)
    return out


def kernel_launch_count(predictor, m2: int, *,
                        use_kernel: bool | None = None,
                        knn_chain: bool = False) -> int:
    """Pallas kernel launches per dispatcher call, by route — the
    number EngineMetrics charges each flushed micro-batch with.
    ``predictor=None`` is the λ-carrying rank_audited path. Zero means
    the XLA fallback owns the batch (m2 > MAX_KERNEL_M2 or
    use_kernel=False)."""
    from repro.core.predictors import KNNLambdaPredictor  # deferred

    if use_kernel is None:
        use_kernel = m2 <= MAX_KERNEL_M2
    if not use_kernel or m2 > MAX_KERNEL_M2:
        return 0
    if isinstance(predictor, KNNLambdaPredictor) and knn_chain:
        return 2      # the pre-fusion chain: knn_lambda + rank_audited
    return 1          # affine prologue / single-grid KNN / mlp + rank


# ---------------------------------------------------------------------------
# knn_topk
# ---------------------------------------------------------------------------

def knn_topk(
    xq: Array, xdb: Array, *, k: int = 10,
    use_kernel: bool = True, interpret: bool | None = None,
    tile_q: int = TILE_B, tile_n: int = DB_SLAB,
):
    """(d2 (B, k) ascending, idx (B, k)). See ref.knn_topk_ref."""
    if not use_kernel:
        return ref.knn_topk_ref(xq, xdb, k)
    if interpret is None:
        interpret = not _on_tpu()
    B, D = xq.shape
    N = xdb.shape[0]
    # pad the db with far-away rows so padded entries never enter top-k
    xq_p = _pad_to(xq, 0, tile_q, 0.0)
    xdb_p = _pad_to(xdb, 0, tile_n, 1e15)
    d2, idx = knn_topk_pallas(
        xq_p, xdb_p, k=k, tile_q=tile_q, tile_n=tile_n, interpret=interpret)
    return d2[:B], idx[:B]


def knn_lambda_tile_q(batch: int) -> int:
    """Default resident-query-tile width for the fused KNN λ kernel: a
    wider tile divides the per-request db-streaming cost (one sweep per
    tile) — 32 when the batch fills it, the top-k kernel's 8 otherwise.
    Shared with benchmarks/kernel_bench's traffic model so the modeled
    sweep count always matches the kernel configuration that runs."""
    return 32 if batch >= 32 else 8


def knn_lambda(
    X: Array, X_db: Array, lam_db: Array, *, k: int = 10,
    use_kernel: bool = True, interpret: bool | None = None,
    tile_q: int | None = None, tile_n: int = DB_SLAB,
    quant: str = "off",
    X_q: Array | None = None, q_scale: Array | None = None,
    y2_q: Array | None = None, k_extra: int = QUANT_EXTRA,
) -> Array:
    """λ̂ (B, K) from the fused KNN kernel (knn_lambda_pallas): one db
    sweep per query tile, weighting at the flush step, no d2/idx or
    distance-matrix HBM traffic. tile_q defaults to 32 when the batch
    allows it — a bigger resident query tile divides the db-streaming
    cost by 4 vs the top-k kernel's default of 8. quant='int8'|'bf16'
    streams the packed db instead (knn_lambda_quant_pallas — exact f32
    survivor re-score at the flush, see kernels/common.py)."""
    if X_db.shape[0] < k:
        # same contract every other KNN path enforces — without it the
        # far-away db padding rows would silently enter the top-k
        raise ValueError(f"n_train={X_db.shape[0]} < k={k}")
    if not use_kernel:
        return ref.knn_lambda_ref(X, X_db, lam_db, k)
    if interpret is None:
        interpret = not _on_tpu()
    if tile_q is None:
        tile_q = knn_lambda_tile_q(X.shape[0])
    B = X.shape[0]
    Xq_p = _pad_to(jnp.asarray(X, jnp.float32), 0, tile_q, 0.0)
    if quant != "off":
        X_q, q_scale, y2_q = _quant_db(
            X_db, X_q, q_scale, y2_q, quant=quant, tile_n=tile_n)
        lamdb_p = jnp.pad(lam_db, ((0, X_q.shape[0] - lam_db.shape[0]),
                                   (0, 0)))
        lam, _guard = knn_lambda_quant_pallas(
            Xq_p, X_q, q_scale, y2_q, lamdb_p, k=k, k_extra=k_extra,
            mode=quant, tile_q=tile_q, tile_n=tile_n, interpret=interpret)
        return lam[:B]
    # far-away padding rows can never enter a top-k (requires the KNN
    # contract N >= k real rows); their λ rows are zeroed for hygiene
    xdb_p = _pad_to(X_db, 0, tile_n, 1e15)
    lamdb_p = _pad_to(lam_db, 0, tile_n, 0.0)
    lam = knn_lambda_pallas(Xq_p, xdb_p, lamdb_p, k=k, tile_q=tile_q,
                            tile_n=tile_n, interpret=interpret)
    return lam[:B]


def knn_predict_kernel(
    X_db: Array, lam_db: Array, X: Array, *, k: int = 10,
    interpret: bool | None = None,
) -> Array:
    """Kernel-backed twin of repro.core.predictors.knn_predict (same
    inverse-distance weighting and exact-match semantics)."""
    from repro.core.predictors import _idw_lambda  # deferred: no cycle

    squeeze = X.ndim == 1
    Xq = jnp.atleast_2d(X)
    d2, idx = knn_topk(Xq, X_db, k=k, interpret=interpret)
    x2 = jnp.sum(Xq * Xq, axis=-1, keepdims=True)
    y2 = jnp.sum(X_db * X_db, axis=-1)[idx]
    out = _idw_lambda(d2, x2, y2, lam_db[idx])
    return out[0] if squeeze else out
