"""The one general traffic generator and the open-loop client.

A traffic mix (``traffic/<mix>.json``) is data: its arrival process,
rate, candidate-count range, predictor tag and pool size. This module
turns a mix and a seed into a request plan, and drives a
``ServingEngine`` open-loop through ``submit / poll / drain``.

Steadiness: a run offers a FIXED number of requests, rate x seconds,
placed by the arrival process conditioned on that count (for a Poisson
process: sorted uniform times), and its requests are a fixed set of
(user, m1) pairs, users and m1 values each spread evenly and paired by
a fixed key, in a seed-drawn order. Two seeds therefore offer the same
work; the seed moves only its order and its instants.

Every request is timed from its SCHEDULED arrival, not from when the
client got round to submitting it, so a stalled client delays the
requests behind it in the measurement too.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import numpy as np

ARRIVALS = ("poisson",)
# pairs users with candidate counts: the same pairs for every --seed
PAIR_SEED = 50_000


def arrival_times(mix: dict, n: int, t_lo: float, t_hi: float,
                  rng: np.random.Generator) -> np.ndarray:
    """n arrival instants in [t_lo, t_hi), sorted: the mix's process
    conditioned on n arrivals (for a Poisson process, n sorted uniform
    draws)."""
    if mix["arrivals"] not in ARRIVALS:
        raise ValueError(f"unknown arrival process {mix['arrivals']!r} "
                         f"(have {ARRIVALS})")
    return np.sort(rng.uniform(t_lo, t_hi, n))


@dataclass
class Plan:
    """One run's offered load: arrival instants (seconds from the
    stream start), each request's pool user and candidate count, and
    which of them fall in the measured window."""

    due: np.ndarray          # (n,) seconds from stream start, sorted
    user: np.ndarray         # (n,) index into the pre-scored pool
    m1: np.ndarray           # (n,) candidate count
    n_lead: int              # requests before the window
    lead_s: float
    window_s: float

    @property
    def window(self) -> slice:
        return slice(self.n_lead, len(self.due))


def _strata(n: int, lo: int, hi: int, rng: np.random.Generator):
    """n values spread evenly over [lo, hi] in a seed-drawn order."""
    v = lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1))
    return rng.permutation(np.minimum(v.astype(np.int64), hi))


def make_plan(mix: dict, seed: int, seconds: float, *,
              rate: float | None = None) -> Plan:
    """The request plan of one run: an unmeasured lead-in of
    ``lead_in_s`` then ``seconds`` of measured window, both at the
    mix's rate (or ``rate``, for the knee sweep). Each part spreads its
    candidate counts evenly over the mix's m1 range and its users evenly
    over the pool, pairs them by ``PAIR_SEED`` and sends the pairs in a
    seed-drawn order."""
    if rate is not None:
        mix = {**mix, "rate_rps": float(rate)}
    rng = np.random.default_rng(seed)
    lead = float(mix["lead_in_s"])
    r = float(mix["rate_rps"])
    lo, hi = (int(v) for v in mix["m1"])
    pool = int(mix["pool"])
    parts = []
    for part, (t0, t1) in enumerate(((0.0, lead), (lead, lead + seconds))):
        n = max(1, int(round(r * (t1 - t0))))
        fixed = np.random.default_rng([PAIR_SEED, part])
        user = _strata(n, 0, pool - 1, fixed)
        m1 = _strata(n, lo, hi, fixed)
        order = rng.permutation(n)
        parts.append((arrival_times(mix, n, t0, t1, rng), user[order],
                      m1[order]))
    due, user, m1 = (np.concatenate(x) for x in zip(*parts))
    return Plan(due=due, user=user, m1=m1, n_lead=len(parts[0][0]),
                lead_s=lead, window_s=float(seconds))


@dataclass
class Drive:
    """What the client saw, per request of the plan (perf_counter
    seconds): when it was due, when it was submitted, when its answer
    came back (nan if never), and the answer (RankResult or Shed)."""

    t0: float
    t_due: np.ndarray
    t_sub: np.ndarray
    t_recv: np.ndarray
    queue_lag: np.ndarray    # lateness already there when its pacing began
    answers: list
    errors: list             # (where, repr) of exceptions the engine raised


def _nospan(_name):
    return contextlib.nullcontext()


def drive_open_loop(engine, requests, plan: Plan, *, budget_s: float,
                    trace: bool = False, clock=time.perf_counter,
                    sleep=time.sleep, poll_interval_s: float = 5e-4,
                    on_window=None) -> Drive:
    """Submit ``requests[i]`` when the stream clock reaches
    ``plan.due[i]``, polling the engine while waiting, never waiting on
    completions; drain at the end. Each request's deadline is its
    scheduled arrival plus ``budget_s``. With ``trace`` the client's
    calls are host spans in the profiler's trace (bench.submit,
    bench.poll, bench.wait, bench.drain, and bench.window around the
    measured window). ``on_window(opening)`` is called as the window
    opens (True) and closes (False)."""
    if trace:
        from jax.profiler import TraceAnnotation as span
    else:
        span = _nospan
    n = len(requests)
    t_sub = np.full(n, np.nan)
    t_recv = np.full(n, np.nan)
    q_lag = np.zeros(n)
    answers: list = [None] * n
    errors: list = []
    index = {r.rid: i for i, r in enumerate(requests)}
    feed_lag = engine.observe_submission_lag

    def take(results):
        now = clock()
        for r in results:
            i = index[r.rid]
            if answers[i] is None:
                answers[i] = r
                t_recv[i] = now

    def call(where, fn, *args):
        try:
            with span(f"bench.{where}"):
                return fn(*args)
        except Exception as e:          # noqa: BLE001 - counted as failed
            errors.append((where, repr(e)))
            return []

    window_span = None
    t0 = clock()
    t_due = t0 + plan.due
    def pace(until):
        while clock() < until:
            take(call("poll", engine.poll))
            remaining = until - clock()
            if remaining > 0:
                with span("bench.wait"):
                    sleep(min(remaining, poll_interval_s))

    for i, req in enumerate(requests):
        if i == plan.n_lead:
            pace(t0 + plan.lead_s)
            window_span = span("bench.window")
            window_span.__enter__()
            if on_window is not None:
                on_window(True)
        q_lag[i] = max(0.0, clock() - t_due[i])
        pace(t_due[i])
        feed_lag(q_lag[i] * 1e3)
        req.deadline = t_due[i] + budget_s
        t_sub[i] = clock()
        take(call("submit", engine.submit, req))
        take(call("poll", engine.poll))
    # the window closes at its scheduled end, not at the last arrival
    pace(t0 + plan.lead_s + plan.window_s)
    if window_span is not None:
        window_span.__exit__(None, None, None)
        if on_window is not None:
            on_window(False)
    take(call("drain", engine.drain))
    return Drive(t0=t0, t_due=t_due, t_sub=t_sub, t_recv=t_recv,
                 queue_lag=q_lag, answers=answers, errors=errors)
