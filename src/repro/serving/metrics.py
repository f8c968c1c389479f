"""Serving-engine observability: latency percentiles, compliance,
bucket hit / compile counters, and pipeline stage timelines.

The compile counters are the contract the engine is built around: after
`warmup()`, `compiles_post_warmup` must stay 0 across any request stream
whose geometries fall inside the warmed bucket lattice (asserted in
tests/test_serving.py via these counters AND the underlying jit cache
sizes).

With the async pipeline, recording is split the same way the engine is:
`on_dispatch` fires on the submission thread when a micro-batch is
assembled and launched; `on_retire` / `on_result` fire on the
completion side when its outputs materialize. Everything here is
either a scalar add or a list append under the GIL, so the two sides
can record concurrently without a lock.
"""

from __future__ import annotations

import threading
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np


@dataclass
class EngineMetrics:
    requests: int = 0
    results: int = 0
    batches: int = 0
    # accounting for the single-dispatch contract: bucket-executable
    # invocations outside warmup, i.e. device programs the ENGINE
    # launches. One per flushed micro-batch, covariate streams included
    # — λ̂ prediction happens inside the bucket executable
    # (kernels.ops.predict_rank_audited), never as a second program.
    # NOTE this counter is incremented at the engine's one dispatch
    # site, so it reports the contract rather than proving it; the
    # proving assertions live in tests/test_serving.py (per-bucket jit
    # cache sizes stay 1, and a predictor's Python predict() is never
    # re-entered after warmup — a second per-batch program would
    # re-enter it or retrace).
    executable_calls: int = 0
    # Pallas kernel launches those executables contained (statically
    # known per bucket route: 1 for every fused-executor kernel bucket
    # — the single-grid KNN program included, which is the point: the
    # pre-fusion KNN chain charged 2 — and 0 for XLA-bodied buckets).
    kernel_launches: int = 0
    # shape-lattice behaviour
    bucket_hits: dict = field(default_factory=lambda: defaultdict(int))
    compiles: int = 0                 # executables built, ever
    compiles_post_warmup: int = 0     # executables built after warmup()
    warmed: bool = False
    oversize_requests: int = 0        # fell outside the warmed lattice
    # flush triggers
    capacity_flushes: int = 0
    deadline_flushes: int = 0
    drain_flushes: int = 0
    # padding overhead: cells are batch x m1 rows (host-transfer view),
    # flops are the rank+audit sweep work (m1*m2 + K*m1 per request vs
    # the bucket corner's) — padding_waste_ratio in summary() is the
    # padded/real quotient of each.
    real_cells: int = 0
    padded_cells: int = 0
    real_flops: int = 0
    padded_flops: int = 0
    # pipeline stage timelines (per micro-batch, ms)
    assembly_ms: list = field(default_factory=list)   # host packing
    dispatch_ms: list = field(default_factory=list)   # jit call -> futures
    exec_ms: list = field(default_factory=list)       # launch -> outputs home
    queue_depth: list = field(default_factory=list)   # in-flight at dispatch
    # quality / latency
    compliant_sum: float = 0.0
    latencies_ms: list = field(default_factory=list)
    queue_wait_ms: list = field(default_factory=list)
    # deadline accounting: every result is checked against its
    # request's (absolute) deadline at build time; sheds/degrades are
    # the admission controller's submit-time decisions. rung_stats
    # accumulates, per degradation rung actually served, the
    # compliance cost of serving from that rung — the audit outputs
    # (exposure/compliance) come free out of the fused kernel, so the
    # per-rung exposure shortfall sum(max(b - exposure, 0)) costs one
    # tiny numpy op per result.
    deadline_hits: int = 0
    deadline_misses: int = 0
    sheds: int = 0
    degrades: int = 0
    # refresh lane / hot swap accounting: swaps = predictor generations
    # published + flipped (engine.swap_predictor successes);
    # refresh_failures = refresh attempts that produced state the
    # engine refused (poisoned / wrong structure) or that crashed in
    # the lane — serving stayed on last-good each time;
    # states_retired = superseded generations whose device buffers were
    # released after their last in-flight batch materialized.
    swaps: int = 0
    refresh_failures: int = 0
    states_retired: int = 0
    # adaptive-lattice lane accounting: lattice_swaps = lattice
    # generations flipped live (engine.swap_lattice successes, == the
    # live lattice epoch); lattice_rollbacks = re-warm attempts that
    # failed (compile/validation error, crash in the lane) — serving
    # stayed on the last-good lattice each time; shadow_compiles =
    # executables built OFF the dispatch path by shadow_warm_lattice
    # (cache growth is legal only here and in warmup — the refined
    # no-recompile contract keeps compiles_post_warmup a pure
    # dispatch-path counter); shadow_warm_ms = wall time of each
    # shadow-warm window.
    lattice_swaps: int = 0
    lattice_rollbacks: int = 0
    shadow_compiles: int = 0
    shadow_warm_ms: list = field(default_factory=list)
    rung_stats: dict = field(default_factory=lambda: defaultdict(
        lambda: {"served": 0, "compliant": 0.0, "shortfall": 0.0}))
    # per-surface budget classes (RankRequest.surface): every deadline
    # outcome — hit/miss at build time, shed/degrade at submit time —
    # is also attributed to the request's surface, so a feed-vs-search
    # SLA split is readable straight off deadline_summary()['surfaces'].
    surface_stats: dict = field(default_factory=lambda: defaultdict(
        lambda: {"hits": 0, "misses": 0, "sheds": 0, "degrades": 0}))
    # on_result runs on whichever consumer thread builds a result
    # (future.result() is a public API), so unlike the submission/
    # completion pair its read-modify-writes need a real lock.
    _result_lock: threading.Lock = field(default_factory=threading.Lock,
                                         repr=False)

    # -- recording ----------------------------------------------------------

    def on_submit(self, bucket, known: bool) -> None:
        self.requests += 1
        self.bucket_hits[bucket.name] += 1
        if self.warmed and not known:
            self.oversize_requests += 1

    def on_compile(self, in_warmup: bool = False) -> None:
        """A bucket executable was built. Compiles inside `warmup` —
        including a later re-warm extending the lattice — never count
        against the post-warmup contract; only compiles forced by
        traffic (an unwarmed bucket hit by a live request) do."""
        self.compiles += 1
        if self.warmed and not in_warmup:
            self.compiles_post_warmup += 1

    def on_executable_call(self, kernel_launches: int = 0) -> None:
        """Submission side: one bucket executable was invoked (the
        whole predict+rank+audit program for its micro-batch).
        ``kernel_launches`` is how many Pallas kernel launches that
        executable contains (kernels.ops.kernel_launch_count for the
        bucket's route)."""
        self.executable_calls += 1
        self.kernel_launches += kernel_launches

    def on_dispatch(self, bucket, n_real: int, trigger: str, fill: dict,
                    *, assembly_ms: float, dispatch_ms: float,
                    depth: int, t_now: float) -> None:
        """Submission side: a micro-batch was assembled and launched at
        ``t_now`` (engine clock). This class keeps no clock; a subclass
        that places batches in time reads ``t_now`` (the on-chip
        benchmark's RecordingMetrics)."""
        self.batches += 1
        self.assembly_ms.append(assembly_ms)
        self.dispatch_ms.append(dispatch_ms)
        self.queue_depth.append(depth)
        if trigger == "capacity":
            self.capacity_flushes += 1
        elif trigger == "deadline":
            self.deadline_flushes += 1
        else:
            self.drain_flushes += 1
        self.real_cells += fill["real_cells"]
        self.padded_cells += fill["padded_cells"]
        self.real_flops += fill.get("real_flops", 0)
        self.padded_flops += fill.get("padded_flops", 0)

    def on_retire(self, exec_ms: float, t_now: float) -> None:
        """Completion side: a micro-batch's outputs reached the host at
        ``t_now`` (engine clock; for subclasses, as in ``on_dispatch``)."""
        self.exec_ms.append(exec_ms)

    def on_result(self, latency_ms: float, wait_ms: float,
                  compliant: bool, *, deadline_hit: bool | None = None,
                  rung: int = 0, shortfall: float = 0.0,
                  surface: str = "default") -> None:
        with self._result_lock:
            self.results += 1
            self.latencies_ms.append(latency_ms)
            self.queue_wait_ms.append(wait_ms)
            self.compliant_sum += float(compliant)
            if deadline_hit is not None:
                if deadline_hit:
                    self.deadline_hits += 1
                    self.surface_stats[surface]["hits"] += 1
                else:
                    self.deadline_misses += 1
                    self.surface_stats[surface]["misses"] += 1
            rs = self.rung_stats[int(rung)]
            rs["served"] += 1
            rs["compliant"] += float(compliant)
            rs["shortfall"] += float(shortfall)

    def on_shed(self, bucket, *, surface: str = "default") -> None:
        """Submission side: a request was shed at admission (its
        RankFuture resolved with a typed Shed result — it never
        entered a queue, so it appears in no other counter)."""
        with self._result_lock:
            self.sheds += 1
            self.surface_stats[surface]["sheds"] += 1

    def on_degrade(self, rung: int, *, surface: str = "default") -> None:
        """Submission side: a request was admitted on a cheaper
        degradation-ladder rung instead of its own bucket."""
        with self._result_lock:
            self.degrades += 1
            self.surface_stats[surface]["degrades"] += 1

    def on_swap(self, tag: str) -> None:
        """Refresh lane: a new predictor generation was published and
        flipped live (engine.swap_predictor succeeded)."""
        with self._result_lock:
            self.swaps += 1

    def on_refresh_failure(self, tag: str) -> None:
        """Refresh lane: a refresh attempt failed (state the engine
        refused, or a crash inside the lane) — serving kept the
        last-good generation."""
        with self._result_lock:
            self.refresh_failures += 1

    def on_state_retired(self, tag: str) -> None:
        """A superseded predictor generation's buffers were released
        (its last in-flight batch materialized)."""
        with self._result_lock:
            self.states_retired += 1

    def on_shadow_compile(self) -> None:
        """Lattice lane: one executable was built OFF the dispatch path
        inside a shadow-warm window (legal cache growth under the
        refined contract — never counted in compiles_post_warmup)."""
        with self._result_lock:
            self.shadow_compiles += 1

    def on_lattice_swap(self, epoch: int, *, warm_ms: float = 0.0) -> None:
        """Lattice lane: a new bucket lattice was shadow-warmed and
        flipped live (engine.swap_lattice succeeded)."""
        with self._result_lock:
            self.lattice_swaps += 1
            if warm_ms:
                self.shadow_warm_ms.append(float(warm_ms))

    def on_lattice_rollback(self) -> None:
        """Lattice lane: a re-warm attempt failed (compile/validation
        error or a crash in the lane) — serving kept the last-good
        lattice and its warmed executables."""
        with self._result_lock:
            self.lattice_rollbacks += 1

    # -- reporting ----------------------------------------------------------

    @staticmethod
    def _pct(xs, qs=(50, 95, 99)):
        if not xs:
            return {f"p{q}": float("nan") for q in qs}
        arr = np.asarray(xs)
        return {f"p{q}": round(float(np.percentile(arr, q)), 3) for q in qs}

    def summary(self) -> dict:
        lat = self._pct(self.latencies_ms)
        return {
            "requests": self.requests,
            "results": self.results,
            "batches": self.batches,
            "executable_calls": self.executable_calls,
            "dispatches_per_batch": round(
                self.executable_calls / self.batches, 3)
                if self.batches else float("nan"),
            "kernel_launches": self.kernel_launches,
            "kernel_launches_per_batch": round(
                self.kernel_launches / self.batches, 3)
                if self.batches else float("nan"),
            "buckets_used": len(self.bucket_hits),
            "compiles": self.compiles,
            "compiles_post_warmup": self.compiles_post_warmup,
            "oversize_requests": self.oversize_requests,
            "flushes": {"capacity": self.capacity_flushes,
                        "deadline": self.deadline_flushes,
                        "drain": self.drain_flushes},
            "fill_rate": round(self.real_cells / self.padded_cells, 3)
                         if self.padded_cells else float("nan"),
            "padding": self.padding_summary(),
            "latency_ms": lat,
            "queue_wait_ms": self._pct(self.queue_wait_ms),
            "pipeline": {
                "assembly_ms_per_batch": self._pct(self.assembly_ms),
                "dispatch_ms_per_batch": self._pct(self.dispatch_ms),
                "exec_ms_per_batch": self._pct(self.exec_ms),
                "queue_depth_max": max(self.queue_depth, default=0),
                "queue_depth_mean": round(float(np.mean(self.queue_depth)), 3)
                                    if self.queue_depth else 0.0,
            },
            "compliance": round(self.compliant_sum / self.results, 3)
                          if self.results else float("nan"),
            "deadline": self.deadline_summary(),
            "refresh": self.refresh_summary(),
            "lattice": self.lattice_summary(),
        }

    def padding_summary(self) -> dict:
        """Padded/real work ratios (>= 1.0; lower is better): rows is
        the batch x m1 host-transfer view, flops the rank+audit sweep
        view — the number the adaptive lattice exists to shrink."""
        return {
            "waste_rows": round(self.padded_cells / self.real_cells, 4)
                          if self.real_cells else float("nan"),
            "waste_flops": round(self.padded_flops / self.real_flops, 4)
                           if self.real_flops else float("nan"),
            "real_flops": self.real_flops,
            "padded_flops": self.padded_flops,
        }

    def lattice_summary(self) -> dict:
        """Adaptive-lattice lane view: generations flipped (== live
        epoch), failed re-warms (serving stayed last-good), off-path
        shadow compiles, and shadow-warm window wall times."""
        return {
            "lattice_swaps": self.lattice_swaps,
            "lattice_rollbacks": self.lattice_rollbacks,
            "shadow_compiles": self.shadow_compiles,
            "shadow_warm_ms": self._pct(self.shadow_warm_ms),
        }

    def refresh_summary(self) -> dict:
        """Hot-swap view: generations published, refreshes refused or
        crashed (serving stayed last-good), superseded generations
        whose buffers were released."""
        return {
            "swaps": self.swaps,
            "refresh_failures": self.refresh_failures,
            "states_retired": self.states_retired,
        }

    def deadline_summary(self) -> dict:
        """Deadline/admission view: hit rate over SERVED requests
        (sheds are the admission controller doing its job, not
        misses), shed/degrade decision counts, and the per-rung
        compliance-cost accumulator."""
        tracked = self.deadline_hits + self.deadline_misses
        return {
            "hits": self.deadline_hits,
            "misses": self.deadline_misses,
            "hit_rate": round(self.deadline_hits / tracked, 4)
                        if tracked else float("nan"),
            "sheds": self.sheds,
            "degrades": self.degrades,
            "surfaces": {
                surface: {
                    **ss,
                    "hit_rate": round(
                        ss["hits"] / (ss["hits"] + ss["misses"]), 4)
                        if ss["hits"] + ss["misses"] else float("nan"),
                }
                for surface, ss in sorted(self.surface_stats.items())
            },
            "rungs": {
                str(rung): {
                    "served": rs["served"],
                    "compliance": round(rs["compliant"] / rs["served"], 3)
                                  if rs["served"] else float("nan"),
                    "mean_shortfall": round(rs["shortfall"] / rs["served"], 4)
                                      if rs["served"] else float("nan"),
                }
                for rung, rs in sorted(self.rung_stats.items())
            },
        }
