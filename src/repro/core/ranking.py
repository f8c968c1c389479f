"""RankingPipeline — Algorithm 1 of the paper as a deployable module.

Offline stage (speed non-critical, runs as one batched accelerator program):
  1. For each train user l: solve the dual LP for optimal shadow prices
     lambda^(l)  (repro.core.dual_solver, batched subgradient).
  2. Fit a predictor f(X) -> lambda on (covariates, shadow prices).
  3. Tune the epsilon tie-break on the train subset (paper footnote 3:
     grid {0} U {i * 10^-j}).

Online stage (the < 50 ms hot path):
  4. Predict lam_hat = f(X) for the incoming user.
  5. Rank by s = u + (1 + eps) * lam_hat @ a — a sort (rearrangement
     inequality) or the fused Pallas kernel repro.kernels.fused_rank.

The pipeline also exposes the paper's four benchmark strategies
('none' / 'optimal' / 'mean' / 'knn', plus beyond-paper 'linear'/'mlp')
behind one `rank_with_strategy` entry point so benchmarks/fig2 can sweep
them uniformly.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.assignment import rank_by_sort
from repro.core.constraints import ConstraintSet
from repro.core.dual_solver import DualSolution, solve_dual_batch
from repro.core.predictors import (
    KNNLambdaPredictor,
    LinearLambdaPredictor,
    MLPLambdaPredictor,
    MeanLambdaPredictor,
)

Array = jax.Array

# Paper footnote 3: eps candidate grid {0} U {i*10^-j | i in 1:9, j in 1:4}.
# Kept in ascending order for readability; tune_eps sorts whatever grid it
# is given, so the "ties -> smaller eps" rule never depends on grid order.
EPS_GRID = tuple([0.0] + [i * 10.0 ** (-j) for j in range(4, 0, -1) for i in range(1, 10)])

# Compliance slack: exposure >= b - AUDIT_TOL counts as satisfied. Shared by
# every audit path (jnp, kernel flush, distributed merge).
AUDIT_TOL = 1e-6

# Parity contract between a Pallas kernel path and the XLA reference in
# this module. perm and compliant match exactly. utility, exposure and
# lam are float sums whose reduction order differs between a kernel's
# VMEM flush and the jnp reductions here (and, compiled on a TPU,
# between Mosaic and XLA), so they match to a few ulp, not bitwise:
# |got - want| <= PARITY_ATOL + PARITY_RTOL * |want|, about 8 f32 ulp
# at magnitude 1.
PARITY_RTOL = 1e-6
PARITY_ATOL = 1e-6


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class RankingOutput:
    """Batched serving result."""

    perm: Array        # (n, m2) item index per rank
    utility: Array     # (n,) tr(U^T P) under the *primary* utility
    exposure: Array    # (n, K)
    compliant: Array   # (n,) bool
    lam: Array         # (n, K) shadow prices used


@dataclass(frozen=True)
class RankingPipeline:
    """Fitted pipeline state. Frozen dataclass (not a pytree: holds ints &
    heterogeneous predictors); its arrays live inside the predictor pytrees."""

    m2: int
    gamma: Array
    eps: float
    predictors: dict[str, Any]
    lam_train: Array      # (n_train, K) optimal shadow prices (offline)
    train_solution: DualSolution


# ---------------------------------------------------------------------------
# Offline stage
# ---------------------------------------------------------------------------

def offline_solve(
    u_train: Array,
    a_train: Array,
    b: Array,
    gamma: Array,
    *,
    m2: int,
    num_iters: int = 400,
) -> DualSolution:
    """Batched dual solve over the train users (Algorithm 1, offline loop)."""
    return solve_dual_batch(u_train, a_train, b, gamma, m2=m2, num_iters=num_iters)


def tune_eps(
    u: Array, a: Array, b: Array, lam: Array, gamma: Array, *, m2: int,
    grid=EPS_GRID,
) -> float:
    """Pick eps minimizing train-set constraint-violation probability
    (ties -> smaller eps), per paper footnote 3.

    The strict-improvement comparison keeps the FIRST grid point reaching
    the minimum, so the grid is iterated in ascending order regardless of
    how the caller's `grid` is arranged — a descending (or interleaved,
    like the i*10^-j enumeration) sweep would keep a larger eps on ties.
    """
    best_eps, best_viol = 0.0, np.inf
    for eps in sorted(float(e) for e in grid):
        out = rank_given_lambda(u, a, b, lam, gamma, m2=m2, eps=float(eps))
        viol = float(jnp.mean(1.0 - out.compliant.astype(jnp.float32)))
        if viol < best_viol - 1e-12:
            best_viol, best_eps = viol, float(eps)
    return best_eps


def fit_pipeline(
    X_train: Array,
    u_train: Array,
    a_train: Array,
    b: Array,
    gamma: Array,
    *,
    m2: int,
    num_iters: int = 400,
    knn_k: int = 10,
    with_mlp: bool = False,
    mlp_steps: int = 300,
) -> RankingPipeline:
    """Full offline stage: dual solve -> fit all predictors -> tune eps."""
    sol = offline_solve(u_train, a_train, b, gamma, m2=m2, num_iters=num_iters)
    lam_train = sol.lam
    predictors: dict[str, Any] = {
        "mean": MeanLambdaPredictor.fit(X_train, lam_train),
        "knn": KNNLambdaPredictor.fit(X_train, lam_train, k=knn_k),
        "linear": LinearLambdaPredictor.fit(X_train, lam_train),
    }
    if with_mlp:
        predictors["mlp"] = MLPLambdaPredictor.fit(
            X_train, lam_train, num_steps=mlp_steps
        )
    eps = tune_eps(u_train, a_train, b, lam_train, gamma, m2=m2)
    return RankingPipeline(
        m2=m2, gamma=gamma, eps=eps, predictors=predictors,
        lam_train=lam_train, train_solution=sol,
    )


# ---------------------------------------------------------------------------
# Online stage
# ---------------------------------------------------------------------------

def audit_selected(
    u_sel: Array,       # (..., m2) selected items' raw utilities
    a_sel: Array,       # (..., K, m2) selected items' attribute values
    gamma: Array,       # (..., m2) slot discounts
    b: Array,           # (..., K) exposure thresholds
    *,
    tol: float = AUDIT_TOL,
):
    """The audit epilogue on already-SELECTED per-slot values: utility,
    per-constraint exposure, and compliance. This is the single source of
    truth for the audit math — used by the jnp path (rank_given_lambda),
    the distributed merge (core.serving_dist), the XLA fallback oracle
    (kernels.ref.rank_audited_ref); the Pallas rank+audit kernel's flush
    step mirrors it op-for-op in VMEM so outputs stay bitwise identical.

    Written as multiply + last-axis reductions (not einsum) so the jnp
    and in-kernel lowerings accumulate in the same order.
    """
    utility = jnp.sum(u_sel * gamma, axis=-1)                    # (...,)
    exposure = jnp.sum(a_sel * gamma[..., None, :], axis=-1)     # (..., K)
    compliant = jnp.all(exposure >= b - tol, axis=-1)            # (...,)
    return utility, exposure, compliant


@partial(jax.jit, static_argnames=("m2", "eps", "backend"))
def rank_given_lambda(
    u: Array,           # (n, m1)
    a: Array,           # (n, K, m1) or (K, m1)
    b: Array,           # (n, K) or (K,)
    lam: Array,         # (n, K)
    gamma: Array,       # (m2,) or (n, m2)
    *,
    m2: int,
    eps: float = 1e-4,
    backend: str = "xla",
) -> RankingOutput:
    """The hot path, batched: s = u + (1+eps) lam @ a; top-m2 by s.

    ``backend='xla'`` is the pure-jnp reference. ``backend='kernel'``
    routes through the fused Pallas rank+audit kernel
    (repro.kernels.ops.rank_audited): selection AND the audit epilogue
    happen inside one VMEM sweep — no post-kernel reads of ``u``/``a``
    (it degrades to this XLA path itself when the kernel's static
    constraints don't hold, e.g. m2 > MAX_KERNEL_M2).

    ``gamma`` may be per-request (n, m2): shape-bucketed serving pads
    requests with fewer real slots by zeroing their trailing discounts,
    which leaves utility/exposure/compliance identical to the unpadded
    problem (repro.serving.buckets).
    """
    if backend == "kernel":
        from repro.kernels.ops import rank_audited  # deferred: no cycle

        return rank_audited(u, a, b, lam, gamma, m2=m2, eps=eps)
    if backend != "xla":
        raise ValueError(f"unknown backend {backend!r}")
    if a.ndim == 2:
        a = jnp.broadcast_to(a, (u.shape[0],) + a.shape)
    if b.ndim == 1:
        b = jnp.broadcast_to(b, (u.shape[0],) + b.shape)
    if gamma.ndim == 1:
        gamma = jnp.broadcast_to(gamma, (u.shape[0],) + gamma.shape)
    s = u + (1.0 + eps) * jnp.einsum("nk,nkm->nm", lam, a)
    perm = rank_by_sort(s, m2)                                   # (n, m2)
    u_sel = jnp.take_along_axis(u, perm, axis=-1)                # (n, m2)
    # broadcast gather: perm (n, 1, m2) indexes every constraint row
    # without materializing an (n, K, m2) index tensor
    a_sel = jnp.take_along_axis(a, perm[:, None, :], axis=-1)    # (n, K, m2)
    utility, exposure, compliant = audit_selected(u_sel, a_sel, gamma, b)
    return RankingOutput(
        perm=perm, utility=utility, exposure=exposure,
        compliant=compliant, lam=lam,
    )


def serve(
    pipe: RankingPipeline,
    X: Array,            # (n, d) user covariates
    u: Array,            # (n, m1) utilities from the recommender backbone
    a: Array,            # (n, K, m1) or (K, m1)
    b: Array,            # (n, K) or (K,)
    *,
    predictor: str = "knn",
    backend: str = "xla",
) -> RankingOutput:
    """Online serving: predict lam_hat from covariates, then rank.

    ``backend='kernel'`` collapses the whole online stage into ONE
    device program via kernels.ops.predict_rank_audited — the affine
    predictor families fold λ̂ into the rank kernel's VMEM prologue,
    KNN fuses its inverse-distance weighting into the database sweep,
    and the MLP joins the same executable — instead of a predict
    program whose λ̂ round-trips HBM ahead of a rank program.
    """
    if backend == "kernel":
        from repro.kernels.ops import predict_rank_audited  # no cycle

        return predict_rank_audited(
            X, pipe.predictors[predictor], u, a, b, pipe.gamma,
            m2=pipe.m2, eps=pipe.eps)
    if backend != "xla":
        raise ValueError(f"unknown backend {backend!r}")
    lam_hat = pipe.predictors[predictor].predict(X)
    return rank_given_lambda(
        u, a, b, lam_hat, pipe.gamma, m2=pipe.m2, eps=pipe.eps
    )


def rank_with_strategy(
    pipe: RankingPipeline,
    strategy: str,
    X: Array,
    u: Array,
    a: Array,
    b: Array,
    *,
    dual_iters: int = 400,
) -> RankingOutput:
    """The paper's Fig-2 strategy sweep entry point.

    'none'     lam = 0 (no constraint accounting)
    'optimal'  solve the dual per holdout user (time-intensive benchmark)
    'mean' / 'knn' / 'linear' / 'mlp'  -> fitted predictors
    """
    n, K = u.shape[0], pipe.lam_train.shape[1]
    if strategy == "none":
        lam = jnp.zeros((n, K), u.dtype)
        return rank_given_lambda(u, a, b, lam, pipe.gamma, m2=pipe.m2, eps=0.0)
    if strategy == "optimal":
        sol = solve_dual_batch(u, a, b, pipe.gamma, m2=pipe.m2, num_iters=dual_iters)
        return rank_given_lambda(
            u, a, b, sol.lam, pipe.gamma, m2=pipe.m2, eps=pipe.eps
        )
    return serve(pipe, X, u, a, b, predictor=strategy)


def with_predictor(pipe: RankingPipeline, name: str, predictor: Any) -> RankingPipeline:
    preds = dict(pipe.predictors)
    preds[name] = predictor
    return replace(pipe, predictors=preds)
