"""Requests whose utilities and covariates the backbone scored in
set-up: each request carries its user's pooled scores over its first m1
candidates, and the reference compares against those same arrays.

A configuration that names no ``"requests"`` module is served this way.
A module of another name has the same two functions: ``make`` builds
the window's and the warm-up's ``RankRequest``s, and ``inputs`` gives
each served request's plain inputs for the reference (a backbone on the
served path recomputes ``u`` and ``X`` there from the weights and the
request's ids).
"""

from __future__ import annotations


def make(setup, pool, mix: dict, users, m1s, *, rid0: int = 0) -> list:
    """One RankRequest per (user, m1): the user's first m1 candidates."""
    from benchmarks.onchip.deployment import predictor_tag
    from repro.serving import RankRequest

    tag = predictor_tag(setup, mix)
    m2 = setup.dep.m2
    reqs = []
    for i, (user, m1) in enumerate(zip(users, m1s)):
        m1 = int(m1)
        m2r = min(m2, m1)
        reqs.append(RankRequest(
            rid=rid0 + i, u=pool.U[user, :m1], a=pool.topics[:, :m1],
            b=pool.b, m2=m2r, X=pool.X[user], tag=tag,
            gamma=pool.gamma[:m2r]))
    return reqs


def inputs(setup, pool, reqs) -> list[dict]:
    """The reference's inputs of each request: its own arrays."""
    return [{"u": r.u, "a": r.a, "b": r.b, "gamma": r.gamma, "X": r.X,
             "m2": r.m2, "m1": r.u.shape[0]} for r in reqs]
