"""fused_rank — the paper's online hot path as one Pallas TPU kernel.

Computes, per user n:   s = u + (1 + eps) * sum_k lam_k a_k
and streams the top-m2 (score, item) pairs out — the adjusted scores
NEVER materialize in HBM. For the retrieval_cand regime (m1 = 10^6
candidates, m2 = 50 slots) this turns

  XLA path:  read u (4 MB) + a (K*4 MB), write s (4 MB), read s (4 MB),
             top_k -> ~ (2K + 10) MB of HBM traffic per user
  kernel:    read u + a once, write m2 values  -> (K + 1) * 4 MB

i.e. strictly the compulsory traffic. The memory-bound roofline term
drops by ~(K+3)/(K+1) (measured in EXPERIMENTS.md §Perf).

Grid: (batch_tiles, m1_tiles); m1 is the minor (fastest) axis so the
running top-k scratch lives in VMEM across the whole m1 sweep of one
batch tile. BlockSpec tiles:
  u    (Bn, Tm)      VMEM
  a    (Bn, K, Tm)   VMEM  (K is small: 5-8 constraints)
  lam  (Bn, K)       VMEM, same block every m1 step
  out  (Bn, m2) x2   written on the last m1 step

Alignment: Tm is a multiple of 128 (lanes); Bn a multiple of 8
(sublanes, f32). m2 <= MAX_KERNEL_M2 keeps the merge cheap; bigger m2
falls back to the XLA path in ops.py (a full sort is the right tool
once m2 ~ m1).

rank+audit (`rank_audited_pallas`) extends the same sweep into the full
serving contract: the streaming merge carries each winner's raw utility
and K constraint-attribute values as VMEM payload columns
(common.topk_merge's payload ride-along), and the flush step computes
utility = sum(u_sel * gamma), exposure_k = sum(a_sel_k * gamma), and
compliant = all(exposure >= b - tol) before anything leaves the kernel.
The post-rank XLA epilogue (gather u/a by the emitted indices, einsum
against gamma) is gone: its HBM cost — an O((K+1)·m2) random gather
back into the (n, K, m1) attribute tensor plus a materialized
(n, K, m2) int32 index tensor — collapses to the (K+1)·m2 payload
values already resident in VMEM scratch. Audit math mirrors
core.ranking.audit_selected op-for-op so the outputs are bitwise
identical to the rank_given_lambda oracle (tests/test_rank_audited.py).

predict+rank+audit (`linear_rank_audited_pallas`) folds the λ-predictor
itself into the kernel prologue for the affine predictor families
(linear ridge and the covariate-free mean): at the first m1 step of
each batch tile it computes lam = X_blk @ W.T + c (optionally clamped
at 0, the ridge predictor's head) into a VMEM scratch buffer, and the
rest of the sweep reads λ̂ from that scratch. λ̂ never exists in HBM
between a predict program and a rank program — the only λ̂ bytes that
move are the tiny (n, K) output written at the flush step so callers
still get RankingOutput.lam. The prologue mirrors
core.predictors.LinearLambdaPredictor.predict op-for-op (one jnp.dot
plus the same max), so predict_rank_audited is bitwise-identical to
predict-then-rank for these families (tests/test_predict_rank.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import NEG_INF, topk_merge

MAX_KERNEL_M2 = 128


def _fused_rank_kernel(
    lam_ref, u_ref, a_ref,                 # inputs
    vals_ref, idx_ref,                     # outputs
    run_v, run_i,                          # VMEM scratch
    *, eps: float, m2: int, tile_m: int, num_k: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG_INF)
        run_i[...] = jnp.zeros_like(run_i)

    u = u_ref[...].astype(jnp.float32)                   # (Bn, Tm)
    lam = lam_ref[...].astype(jnp.float32)               # (Bn, K)
    # K static and small: unrolled axpy chain (no dot_general needed)
    s = u
    for k in range(num_k):
        s = s + (1.0 + eps) * lam[:, k][:, None] * a_ref[:, k, :].astype(jnp.float32)

    base = t * tile_m
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, dimension=1)
    new_v, new_i = topk_merge(run_v[...], run_i[...], s, gidx, m2)
    run_v[...] = new_v
    run_i[...] = new_i

    @pl.when(t == pl.num_programs(1) - 1)
    def _flush():
        vals_ref[...] = run_v[...]
        idx_ref[...] = run_i[...]


@functools.partial(
    jax.jit,
    static_argnames=("m2", "eps", "tile_b", "tile_m", "interpret"))
def fused_rank_pallas(
    u: jax.Array,        # (n, m1)
    a: jax.Array,        # (n, K, m1)
    lam: jax.Array,      # (n, K)
    *,
    m2: int,
    eps: float = 1e-4,
    tile_b: int = 8,
    tile_m: int = 512,
    interpret: bool = False,
):
    """Returns (top scores (n, m2) descending f32, item indices (n, m2))."""
    n, m1 = u.shape
    K = a.shape[1]
    if m2 > MAX_KERNEL_M2:
        raise ValueError(f"kernel path supports m2 <= {MAX_KERNEL_M2}; "
                         f"use repro.kernels.ops.fused_rank (XLA fallback)")
    if n % tile_b or m1 % tile_m:
        raise ValueError(f"(n={n}, m1={m1}) must tile by ({tile_b}, {tile_m})")

    grid = (n // tile_b, m1 // tile_m)
    kernel = functools.partial(
        _fused_rank_kernel, eps=eps, m2=m2, tile_m=tile_m, num_k=K)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, K), lambda b, t: (b, 0)),
            pl.BlockSpec((tile_b, tile_m), lambda b, t: (b, t)),
            pl.BlockSpec((tile_b, K, tile_m), lambda b, t: (b, 0, t)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, m2), lambda b, t: (b, 0)),
            pl.BlockSpec((tile_b, m2), lambda b, t: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m2), jnp.float32),
            jax.ShapeDtypeStruct((n, m2), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_b, m2), jnp.float32),
            pltpu.VMEM((tile_b, m2), jnp.int32),
        ],
        interpret=interpret,
    )(lam, u, a)
    return vals, idx


# ---------------------------------------------------------------------------
# rank + audit: selection AND utility/exposure/compliance in one sweep
# ---------------------------------------------------------------------------

def _merge_scored_tile(
    t, lam, u_ref, a_ref, run_v, run_i, run_u, run_a,
    *, eps: float, m2: int, tile_m: int, num_k: int,
):
    """One m1 step of the rank+audit sweep: adjusted scores for this
    tile, merged into the running top-m2 with u/a payload ride-along.
    Shared verbatim by the lam-input and predictor-prologue kernels so
    their selections can never drift apart."""
    u = u_ref[...].astype(jnp.float32)                   # (Bn, Tm)
    a = a_ref[...].astype(jnp.float32)                   # (Bn, K, Tm)
    s = u
    for k in range(num_k):
        s = s + (1.0 + eps) * lam[:, k][:, None] * a[:, k, :]

    base = t * tile_m
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, dimension=1)
    new_v, new_i, new_p = topk_merge(
        run_v[...], run_i[...], s, gidx, m2,
        run_payload={"u": run_u[...], "a": run_a[...]},
        tile_payload={"u": u, "a": a})
    run_v[...] = new_v
    run_i[...] = new_i
    run_u[...] = new_p["u"]
    run_a[...] = new_p["a"]


def _audit_flush(
    gamma_ref, b_ref, vals_ref, idx_ref, util_ref, expo_ref, comp_ref,
    run_v, run_i, run_u, run_a, *, tol: float,
):
    """The audit epilogue, entirely on VMEM residents: mirrors
    core.ranking.audit_selected op-for-op (bitwise parity)."""
    gamma = gamma_ref[...].astype(jnp.float32)           # (Bn, m2)
    b = b_ref[...].astype(jnp.float32)                   # (Bn, K)
    u_sel = run_u[...]                                   # (Bn, m2)
    a_sel = run_a[...]                                   # (Bn, K, m2)
    expo = jnp.sum(a_sel * gamma[:, None, :], axis=-1)   # (Bn, K)
    vals_ref[...] = run_v[...]
    idx_ref[...] = run_i[...]
    util_ref[...] = jnp.sum(u_sel * gamma, axis=-1, keepdims=True)
    expo_ref[...] = expo
    comp_ref[...] = jnp.all(
        expo >= b - tol, axis=-1, keepdims=True).astype(jnp.int32)


def _rank_audited_kernel(
    lam_ref, b_ref, gamma_ref, u_ref, a_ref,        # inputs
    vals_ref, idx_ref, util_ref, expo_ref, comp_ref,  # outputs
    run_v, run_i, run_u, run_a,                     # VMEM scratch
    *, eps: float, m2: int, tile_m: int, num_k: int, tol: float,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG_INF)
        run_i[...] = jnp.zeros_like(run_i)
        run_u[...] = jnp.zeros_like(run_u)
        run_a[...] = jnp.zeros_like(run_a)

    lam = lam_ref[...].astype(jnp.float32)               # (Bn, K)
    _merge_scored_tile(t, lam, u_ref, a_ref, run_v, run_i, run_u, run_a,
                       eps=eps, m2=m2, tile_m=tile_m, num_k=num_k)

    @pl.when(t == pl.num_programs(1) - 1)
    def _flush():
        _audit_flush(gamma_ref, b_ref, vals_ref, idx_ref, util_ref,
                     expo_ref, comp_ref, run_v, run_i, run_u, run_a,
                     tol=tol)


@functools.partial(
    jax.jit,
    static_argnames=("m2", "eps", "tol", "tile_b", "tile_m", "interpret"))
def rank_audited_pallas(
    u: jax.Array,        # (n, m1)
    a: jax.Array,        # (n, K, m1)
    b: jax.Array,        # (n, K)
    lam: jax.Array,      # (n, K)
    gamma: jax.Array,    # (n, m2)
    *,
    m2: int,
    eps: float = 1e-4,
    tol: float = 1e-6,
    tile_b: int = 8,
    tile_m: int = 512,
    interpret: bool = False,
):
    """Fused rank+audit: returns (vals (n, m2) f32 desc, idx (n, m2) i32,
    utility (n, 1) f32, exposure (n, K) f32, compliant (n, 1) i32).

    The (K+1) payload columns per winner live in VMEM scratch for the
    whole m1 sweep; u/a are read exactly once and never re-gathered."""
    n, m1 = u.shape
    K = a.shape[1]
    if m2 > MAX_KERNEL_M2:
        raise ValueError(f"kernel path supports m2 <= {MAX_KERNEL_M2}; "
                         f"use repro.kernels.ops.rank_audited (XLA fallback)")
    if n % tile_b or m1 % tile_m:
        raise ValueError(f"(n={n}, m1={m1}) must tile by ({tile_b}, {tile_m})")

    grid = (n // tile_b, m1 // tile_m)
    kernel = functools.partial(
        _rank_audited_kernel, eps=eps, m2=m2, tile_m=tile_m, num_k=K, tol=tol)
    vals, idx, util, expo, comp = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, tile_m), lambda bi, t: (bi, t)),
            pl.BlockSpec((tile_b, K, tile_m), lambda bi, t: (bi, 0, t)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, 1), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, 1), lambda bi, t: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m2), jnp.float32),
            jax.ShapeDtypeStruct((n, m2), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, K), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_b, m2), jnp.float32),
            pltpu.VMEM((tile_b, m2), jnp.int32),
            pltpu.VMEM((tile_b, m2), jnp.float32),
            pltpu.VMEM((tile_b, K, m2), jnp.float32),
        ],
        interpret=interpret,
    )(lam, b, gamma, u, a)
    return vals, idx, util, expo, comp


# ---------------------------------------------------------------------------
# predict + rank + audit: the affine λ-predictor folded into the prologue
# ---------------------------------------------------------------------------

def _linear_rank_audited_kernel(
    w_ref, c_ref, x_ref, b_ref, gamma_ref, u_ref, a_ref,     # inputs
    vals_ref, idx_ref, util_ref, expo_ref, comp_ref, lam_ref,  # outputs
    run_v, run_i, run_u, run_a, lam_scr,                     # VMEM scratch
    *, eps: float, m2: int, tile_m: int, num_k: int, tol: float, relu: bool,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG_INF)
        run_i[...] = jnp.zeros_like(run_i)
        run_u[...] = jnp.zeros_like(run_u)
        run_a[...] = jnp.zeros_like(run_a)
        # The predictor prologue: λ̂ = X W^T + c for this batch tile,
        # computed once per tile into VMEM scratch — the m1 sweep reads
        # it from there; λ̂ never round-trips HBM between predict and
        # rank. Ops mirror LinearLambdaPredictor.predict exactly
        # (jnp.maximum clamp when relu; the mean predictor is the
        # W = 0 degenerate case with the clamp off).
        x = x_ref[...].astype(jnp.float32)               # (Bn, d)
        w = w_ref[...].astype(jnp.float32)               # (K, d)
        lam = jnp.dot(x, w.T, precision=jax.lax.Precision.HIGHEST
                      ) + c_ref[...].astype(jnp.float32)
        if relu:
            lam = jnp.maximum(lam, 0.0)
        lam_scr[...] = lam

    _merge_scored_tile(t, lam_scr[...], u_ref, a_ref,
                       run_v, run_i, run_u, run_a,
                       eps=eps, m2=m2, tile_m=tile_m, num_k=num_k)

    @pl.when(t == pl.num_programs(1) - 1)
    def _flush():
        _audit_flush(gamma_ref, b_ref, vals_ref, idx_ref, util_ref,
                     expo_ref, comp_ref, run_v, run_i, run_u, run_a,
                     tol=tol)
        lam_ref[...] = lam_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("m2", "eps", "tol", "relu", "tile_b", "tile_m",
                     "interpret"))
def linear_rank_audited_pallas(
    u: jax.Array,        # (n, m1)
    a: jax.Array,        # (n, K, m1)
    b: jax.Array,        # (n, K)
    X: jax.Array,        # (n, d) covariates
    W: jax.Array,        # (K, d) predictor weights (0 for the mean family)
    c: jax.Array,        # (1, K) predictor intercept (row vector)
    gamma: jax.Array,    # (n, m2)
    *,
    m2: int,
    eps: float = 1e-4,
    tol: float = 1e-6,
    relu: bool = True,
    tile_b: int = 8,
    tile_m: int = 512,
    interpret: bool = False,
):
    """Predict+rank+audit in one sweep for affine λ predictors: returns
    (vals (n, m2) f32 desc, idx (n, m2) i32, utility (n, 1) f32,
    exposure (n, K) f32, compliant (n, 1) i32, lam (n, K) f32).

    λ̂ lives in VMEM scratch for the whole m1 sweep; the (n, K) lam
    output written at the flush step is the only λ̂ HBM traffic — there
    is no predict-program → rank-program handoff at all."""
    n, m1 = u.shape
    K = a.shape[1]
    d = X.shape[1]
    if m2 > MAX_KERNEL_M2:
        raise ValueError(f"kernel path supports m2 <= {MAX_KERNEL_M2}; "
                         f"use repro.kernels.ops.predict_rank_audited "
                         f"(XLA fallback)")
    if n % tile_b or m1 % tile_m:
        raise ValueError(f"(n={n}, m1={m1}) must tile by ({tile_b}, {tile_m})")
    if W.shape != (K, d) or c.shape != (1, K):
        raise ValueError(f"predictor shapes W{W.shape}/c{c.shape} do not "
                         f"match (K={K}, d={d})")

    grid = (n // tile_b, m1 // tile_m)
    kernel = functools.partial(
        _linear_rank_audited_kernel, eps=eps, m2=m2, tile_m=tile_m,
        num_k=K, tol=tol, relu=relu)
    vals, idx, util, expo, comp, lam = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, d), lambda bi, t: (0, 0)),
            pl.BlockSpec((1, K), lambda bi, t: (0, 0)),
            pl.BlockSpec((tile_b, d), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, K), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, m2), lambda bi, t: (bi, 0)),
            pl.BlockSpec((tile_b, tile_m), lambda bi, t: (bi, t)),
            pl.BlockSpec((tile_b, K, tile_m), lambda bi, t: (bi, 0, t)),
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
            jax.ShapeDtypeStruct((n, m2), jnp.float32),
            jax.ShapeDtypeStruct((n, m2), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, K), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, K), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_b, m2), jnp.float32),
            pltpu.VMEM((tile_b, m2), jnp.int32),
            pltpu.VMEM((tile_b, m2), jnp.float32),
            pltpu.VMEM((tile_b, K, m2), jnp.float32),
            pltpu.VMEM((tile_b, K), jnp.float32),
        ],
        interpret=interpret,
    )(W, c, X, b, gamma, u, a)
    return vals, idx, util, expo, comp, lam
