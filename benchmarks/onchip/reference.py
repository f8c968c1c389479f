"""The plain reference of the online stage, and the comparison that
decides ``correct``.

Independent of the program: numpy in float64, nothing imported from
``repro``. It takes the served requests' plain inputs (utilities, topic
attributes, floors, slot discounts, covariates, as the configuration's
requests/ module gives them), the deployment's λ-database, and a sample
of the database's offline users' LP inputs rebuilt from their fixed
keys, and recomputes, layer by layer:

  λ-database each sampled row holds its own offline user: its
            covariate is that user's, and its λ, ranking that user's
            offline LP by u + (1 + eps) λ·a, gives a page that meets
            every floor (the offline stage's guarantee; the stored λ is
            the solver's feasible-rounding target, not the dual optimum,
            so nearness to an optimum is not what is held);
  λ̂        the KNN predictor: inverse-distance-weighted KNN over the
            λ-database (k nearest by Euclidean distance, a query that
            coincides with a database row takes that row's value);
  perm      the top-m2 of the adjusted scores s = u + (1 + eps) λ̂·a
            under the reference λ̂;
  audit     utility Σ_j γ_j u[perm_j], exposure Σ_j γ_j a[:, perm_j] and
            compliance (every exposure >= its floor - tol) of the page
            the program served;
  unpadding the served page has the request's own geometry: m2 distinct
            candidates of its own m1, K exposures, K prices, all finite.

The numbers it returns, each held to a limit of the configuration:

  lam_err            max over rows of |Δλ̂|∞ / (|λ̂|∞ + λ̄), λ̄ the
                     database's mean |λ|∞; rows whose k-th and (k+1)-th
                     neighbours tie within ``knn_tie_rtol`` of the
                     squared norms are not compared (either is a correct
                     f32 answer)
  page_err           max over rows of the page's gap and its audit error:
                     the gap is max over slots of |s[perm] - s[perm_ref]|
                     / max|s|, with s the adjusted scores under the
                     reference λ̂ (under the served λ̂ on a neighbour
                     tie) and perm_ref their top-m2, so 0 when the pages
                     agree and rounding-sized at a near-tie; the audit
                     error is |Δutility| / Σγ|u_sel| or |Δexposure|∞ / Σγ
                     of the served page
  compliant_mismatch rows whose served compliance differs from the
                     reference's, away from a floor (exact: limit 0)
  shape_errors       rows whose page breaks the request's geometry, or
                     that another rung than the KNN predictor served
                     (exact: limit 0)
  unanswered         window requests that never got an answer (exact)
  db_rows_misplaced  sampled database rows whose covariate is not their
                     offline user's (|ΔX|∞ > 1e-6 |X|∞, a float32 copy
                     is exact) or whose λ is not finite (exact)
  db_noncompliant    sampled database rows whose λ's page of their own
                     offline LP misses a floor by more than ``tol``,
                     away from a floor and from a score tie at the
                     page's edge (exact)
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("lam_err", "page_err", "compliant_mismatch", "shape_errors",
           "unanswered", "db_rows_misplaced", "db_noncompliant")


class LamDB:
    """The λ-database in float64, with the row norms the KNN needs."""

    def __init__(self, X_db, lam_db):
        self.X = np.asarray(X_db, np.float64)
        self.lam = np.asarray(lam_db, np.float64)
        self.y2 = np.einsum("nd,nd->n", self.X, self.X)
        self.scale = float(np.abs(self.lam).max(axis=1).mean())


def knn_lambda(db: LamDB, X, *, k: int, tie_rtol: float,
               block: int = 64):
    """(λ̂ (n, K), tie (n,) bool) for query rows X (n, d), float64."""
    X = np.asarray(X, np.float64)
    out = np.empty((X.shape[0], db.lam.shape[1]))
    tie = np.zeros(X.shape[0], bool)
    for s in range(0, X.shape[0], block):
        xb = X[s:s + block]
        x2 = np.einsum("bd,bd->b", xb, xb)[:, None]
        d2 = np.maximum(x2 - 2.0 * (xb @ db.X.T) + db.y2[None, :], 0.0)
        near = np.argpartition(d2, k, axis=1)[:, :k + 1]
        for r in range(xb.shape[0]):
            idx = near[r][np.lexsort((near[r], d2[r, near[r]]))]
            dk = d2[r, idx]
            scale = x2[r, 0] + db.y2[idx]
            tie[s + r] = dk[k] - dk[k - 1] <= tie_rtol * scale[:k + 1].max()
            idx, dk, scale = idx[:k], dk[:k], scale[:k]
            exact = dk <= 1e-6 * scale
            w = exact.astype(float) if exact.any() else 1.0 / np.sqrt(dk)
            out[s + r] = w @ db.lam[idx] / w.sum()
    return out, tie


def top_m2(s: np.ndarray, m2: int) -> np.ndarray:
    """Indices of the m2 largest scores, ties to the lower index."""
    part = np.argpartition(-s, m2 - 1)[:m2] if m2 < s.size else np.arange(
        s.size)
    return part[np.lexsort((part, -s[part]))]


def shape_ok(m1: int, m2: int, K: int, ans) -> bool:
    """The answer has its request's geometry (m1 candidates, m2 slots, K
    floors) and came from the KNN predictor (rung 0)."""
    perm = np.asarray(ans.perm)
    return (ans.rung == 0 and perm.shape == (m2,)
            and perm.min(initial=0) >= 0 and perm.max(initial=0) < m1
            and np.unique(perm).size == m2
            and np.shape(ans.exposure) == (K,)
            and np.shape(ans.lam) == (K,)
            and np.isfinite(ans.utility)
            and bool(np.isfinite(ans.exposure).all())
            and bool(np.isfinite(ans.lam).all()))


def compare(inputs, answers, db: LamDB, *, k: int, eps: float, tol: float,
            tie_rtol: float, unanswered: int = 0) -> dict:
    """The numbers of the module docstring but the database's, for
    served ``answers`` (each with perm, utility, exposure, compliant,
    lam, rung) of requests with plain ``inputs`` (each a dict of u (m1,),
    a (K, m1), b (K,), gamma (m2,), X (d,), m2, m1). Rung 0 is the KNN
    predictor."""
    nums = dict.fromkeys(NUMBERS, 0.0)
    nums["unanswered"] = float(unanswered)
    counts = {"compared": 0, "knn_ties": 0, "perm_differs": 0,
              "compliant_borderline": 0}
    good = []
    for inp, ans in zip(inputs, answers):
        if shape_ok(inp["m1"], inp["m2"], len(inp["b"]), ans):
            good.append((inp, ans))
        else:
            nums["shape_errors"] += 1
    if not good:
        return {"numbers": nums, "counts": counts}
    X = np.stack([np.asarray(inp["X"]) for inp, _ in good])
    lam_ref, tie = knn_lambda(db, X, k=k, tie_rtol=tie_rtol)
    counts["knn_ties"] = int(tie.sum())
    for i, (inp, ans) in enumerate(good):
        counts["compared"] += 1
        K = len(inp["b"])
        lam_p = np.asarray(ans.lam, np.float64)
        if not tie[i]:
            ref = lam_ref[i, :K]
            err = (np.abs(lam_p - ref).max()
                   / max(np.abs(ref).max() + db.scale, 1e-30))
            nums["lam_err"] = max(nums["lam_err"], float(err))
        u = np.asarray(inp["u"], np.float64)
        a = np.asarray(inp["a"], np.float64)
        gamma = np.asarray(inp["gamma"], np.float64)
        perm = np.asarray(ans.perm)
        s = u + (1.0 + eps) * ((lam_p if tie[i] else lam_ref[i, :K]) @ a)
        perm_ref = top_m2(s, inp["m2"])
        gap = 0.0
        if not np.array_equal(perm, perm_ref):
            counts["perm_differs"] += 1
            gap = np.abs(s[perm] - s[perm_ref]).max() / np.abs(s).max()
        util = gamma @ u[perm]
        expo = a[:, perm] @ gamma
        err_u = abs(ans.utility - util) / max(gamma @ np.abs(u[perm]), 1e-30)
        err_e = np.abs(np.asarray(ans.exposure) - expo).max() / gamma.sum()
        nums["page_err"] = max(nums["page_err"], float(gap), float(err_u),
                               float(err_e))
        b = np.asarray(inp["b"], np.float64)
        margin = np.abs(expo - (b - tol)).min()
        if margin <= 1e-5 * gamma.sum():
            counts["compliant_borderline"] += 1
        elif bool(ans.compliant) != bool(np.all(expo >= b - tol)):
            nums["compliant_mismatch"] += 1
    return {"numbers": nums, "counts": counts}


def check_db(db: LamDB, rows, U, X, *, gamma, topics, b, eps: float,
             tol: float) -> dict:
    """The database's numbers of the module docstring for sampled rows
    ``rows`` of ``db``, whose offline users have utilities ``U`` (n, m1)
    and covariates ``X`` (n, d) over the offline candidate set
    (``gamma`` (m2,), ``topics`` (K, m1), floors ``b`` (K,))."""
    nums = {"db_rows_misplaced": 0.0, "db_noncompliant": 0.0}
    counts = {"db_rows": len(rows), "db_borderline": 0, "db_cov_dev": 0.0}
    gamma = np.asarray(gamma, np.float64)
    a = np.asarray(topics, np.float64)
    b = np.asarray(b, np.float64)
    m2 = gamma.size
    for r, u, x in zip(rows, U, X):
        x = np.asarray(x, np.float64)
        dev = np.abs(db.X[r] - x).max() / max(np.abs(x).max(), 1e-30)
        counts["db_cov_dev"] = max(counts["db_cov_dev"], float(dev))
        lam = db.lam[r]
        if dev > 1e-6 or not np.isfinite(lam).all():
            nums["db_rows_misplaced"] += 1
            continue
        s = np.asarray(u, np.float64) + (1.0 + eps) * (lam @ a)
        perm = top_m2(s, m2)
        expo = a[:, perm] @ gamma
        edge = (s[perm[-1]] - np.delete(s, perm).max() if m2 < s.size
                else np.inf)
        if (edge <= 1e-6 * np.abs(s).max()
                or np.abs(expo - (b - tol)).min() <= 1e-5 * gamma.sum()):
            counts["db_borderline"] += 1
        elif not np.all(expo >= b - tol):
            nums["db_noncompliant"] += 1
    return {"numbers": nums, "counts": counts}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number given within
    its limit, in the order of ``NUMBERS``."""
    checks = {n: {"value": numbers[n], "limit": limits[n]}
              for n in NUMBERS if n in numbers}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
