"""Streaming serving engine: shape-bucketed micro-batching over the
constrained-ranking online path, with async double-buffered execution.

The unit of work is one RankRequest — one user's candidate utilities,
constraint attributes/thresholds, slot count, and either precomputed
shadow prices (lam) or the covariate vector X for an attached lambda
predictor. Requests stream in with heterogeneous geometry (m1, m2, K)
from heterogeneous upstream recommenders; the engine:

  1. maps each request to a shape Bucket (repro.serving.buckets),
     mints its RankFuture, and appends it to that bucket's queue;
  2. flushes a queue when it reaches the bucket's micro-batch capacity
     (capacity flush) or when its oldest request has waited max_wait_ms
     (deadline flush, checked by `poll`), or on `drain`;
  3. SUBMISSION SIDE (the caller's thread): assembles the flushed batch
     into a recycled StagingRing host buffer and dispatches it through
     ONE cached, pre-warmed jit executable per bucket — the existing
     online path (core.ranking.rank_given_lambda /
     kernels.ops.fused_rank / core.serving_dist.rank_distributed when
     a mesh is present) — with the big staging buffers donated to the
     runtime. Dispatch is asynchronous: the jit call returns device
     futures immediately and the submission side moves on to the next
     batch;
  4. COMPLETION SIDE (the pipeline worker thread): while the device
     executes batch N+1, the worker blocks on batch N's device→host
     transfer (GIL released), stamps completion, recycles N's staging
     buffers, and marks each of N's RankFutures done. Per-row
     unpadding to the request's real geometry is Python work, so it
     runs lazily on the consuming thread — future.result() or the
     collect path behind submit/poll/drain — never on the worker.

Steady state therefore never recompiles (the jit cache is the bucket
lattice, populated by `warmup`), never pays per-request dispatch
(amortized over the micro-batch), and never serializes host assembly
against device execution (the sole job of the old blocking `rank()`
call, retired in favor of futures). `pipeline_depth` bounds the
in-flight window — depth 1 (the default) is classic double buffering:
one batch materializing while the next is assembled and dispatched;
depth 0 recovers the synchronous single-threaded engine (same results,
no overlap), which is what the sync column of
benchmarks/latency_serve.py measures and what the equivalence tests
in tests/test_serving_pipeline.py compare against.

Every layer boundary is a `jax.profiler.TraceAnnotation` span
(`engine.*`, `pipeline.*`; docs/serving.md lists them), so a profiler
trace puts the engine's host work on the device's clock. Each span of
one micro-batch carries the flush's sequence number as its `batch`
stat (PendingBatch.seq), each request's spans its `rid`. With no trace
running a span records nothing and the completion worker skips its
explicit device wait, so the profiler itself is the switch.

See docs/serving.md for timelines, backpressure semantics and the
spans, and docs/api.md for the public API.
"""

from __future__ import annotations

import itertools
import re
import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation as span

from repro.core.predictors import predictor_state, with_state
from repro.core.ranking import RankingOutput, rank_given_lambda
from repro.serving.admission import SHED_RUNG, AdmissionController
from repro.serving.buckets import (
    AUTOTUNE_KEYS,
    Bucket,
    assemble_batch,
    fill_staging,
    fill_stats,
    load_autotune_table,
    resolve_autotune,
    unpad_result,
)
from repro.serving.lattice import Lattice, ShapeHistogram
from repro.serving.metrics import EngineMetrics
from repro.serving.pipeline import (
    ExecutionPipeline,
    PendingBatch,
    RankFuture,
    StagingRing,
)

LAM_TAG = "_lam"   # requests that carry shadow prices directly

# Default per-request latency budget: the paper's 50 ms claim.
DEFAULT_BUDGET_S = 0.050


def executable_name(bucket: Bucket) -> str:
    """The bucket executable's name in compiled text and device traces,
    e.g. ``serve_knn_m1_65536_m2_64_K_8_B_32``."""
    tag = re.sub(r"\W", "_", bucket.tag).strip("_")
    return (f"serve_{tag}_m1_{bucket.m1}_m2_{bucket.m2}_K_{bucket.K}"
            f"_B_{bucket.batch}")


def _jit(bucket: Bucket, fn: Callable, **kw) -> Callable:
    """``jax.jit`` of a bucket's body under the bucket's own name, so
    each executable is told apart in a trace (not ``jit_fn(<hash>)``)."""
    fn.__name__ = fn.__qualname__ = executable_name(bucket)
    return jax.jit(fn, **kw)


@dataclass
class RankRequest:
    """One user's ranking problem. Arrays are host (numpy) payloads —
    the engine owns staging/padding and device transfer.

    Deadline semantics: `deadline` is ABSOLUTE (in the engine's clock
    domain — time.perf_counter by default); `budget_s` is RELATIVE to
    enqueue time. When both are None the engine's default_budget_s
    (50 ms, the paper's budget) applies; when both are set, `deadline`
    wins."""

    rid: int
    u: np.ndarray                     # (m1,) candidate utilities
    a: np.ndarray                     # (K, m1) constraint attributes
    b: np.ndarray                     # (K,) exposure thresholds
    m2: int                           # slots to fill (m2 <= m1)
    lam: np.ndarray | None = None     # (K,) shadow prices, if precomputed
    X: np.ndarray | None = None      # (d,) covariates for the predictor
    tag: str = LAM_TAG                # predictor/arch affinity
    gamma: np.ndarray | None = None  # (m2,) slot discounts; default DCG
    deadline: float | None = None    # absolute deadline (engine clock)
    budget_s: float | None = None    # relative budget (enqueue + budget_s)
    surface: str = "default"         # budget class (engine.surface_budgets)

    def __post_init__(self):
        if self.lam is None and self.X is None:
            raise ValueError(f"request {self.rid}: need lam or X")
        if self.m2 > self.u.shape[0]:
            raise ValueError(f"request {self.rid}: m2 > m1")


@dataclass
class RankResult:
    rid: int
    perm: np.ndarray                  # (m2,) item indices by slot
    utility: float
    exposure: np.ndarray              # (K,)
    compliant: bool
    bucket: str
    latency_ms: float                 # enqueue -> result materialized
    wait_ms: float                    # enqueue -> batch launch
    deadline_hit: bool | None = None  # materialized before the deadline?
    rung: int = 0                     # degradation rung served (0 = own)
    epoch: int = 0                    # predictor generation that served it
    lattice_epoch: int = 0            # bucket-lattice generation at dispatch
    lam: np.ndarray | None = None     # (K,) shadow prices the ranking used


@dataclass
class Shed:
    """Typed admission-shed outcome: the request's RankFuture resolves
    with THIS (not an exception) when every degradation rung was
    predicted to miss the deadline. `predicted_ms` is the cheapest
    rung's predicted completion — the best the engine could have done
    against `budget_ms` of headroom."""

    rid: int
    bucket: str                       # the request's home bucket
    predicted_ms: float
    budget_ms: float
    reason: str = "predicted-miss-at-every-rung"
    rung: int = SHED_RUNG


@dataclass
class _QueueEntry:
    """One admitted request waiting in (or flushed from) a bucket
    queue: the request plus its admission-time bookkeeping."""

    req: RankRequest
    t_enq: float
    fut: Any                          # RankFuture
    deadline: float                   # absolute, engine clock
    rung: int                         # degradation rung being served


@dataclass
class _PredictorEntry:
    predictor: Any                    # pytree with .predict(X) -> (n, K)
    d_cov: int
    K: int


class ServingEngine:
    """Shape-bucketed micro-batching executor for ranking requests.

    executor: 'xla'   — rank_given_lambda (default; the jnp hot path)
              'fused' — kernels.ops.fused_rank (Pallas on TPU,
                        interpret-mode on CPU)
              'dist'  — core.serving_dist.rank_distributed on `mesh`
                        (candidate axis sharded; requires mesh)

    pipeline_depth: how many micro-batches the submission side may run
    ahead of the one currently materializing. 1 (default) is classic
    double buffering — batch N+1 is assembled and dispatched while
    batch N's outputs transfer back — and measures best on CPU, where
    deeper windows make XLA execute batches concurrently and thrash
    the cores; on an accelerator backend a deeper window can hide
    longer transfer tails. The submission side blocks (backpressure)
    once the window is full. 0 disables the pipeline: every flush
    dispatches, materializes, and resolves inline on the calling
    thread — bitwise the same results, strictly serial timing.

    admission: deadline-aware admission control (serving/admission.py).
    None (default) admits everything — results still carry
    `deadline_hit` against the 50 ms default budget, so an
    admission-disabled engine reports its misses. With a controller
    attached, every submit is checked against the request's deadline:
    admit on rung 0, degrade down the tag's registered ladder
    (`set_degradation_ladder`) to a cheaper pre-warmed predictor
    bucket, or shed (the RankFuture resolves with a typed `Shed`).
    At zero load admission is non-interfering: served results are
    bitwise identical to the admission-disabled engine
    (tests/test_serving_pipeline.py asserts this).
    """

    def __init__(
        self,
        *,
        max_batch: int = 32,
        max_wait_ms: float = 2.0,
        eps: float = 1e-4,
        executor: str = "xla",
        mesh=None,
        donate: bool | None = None,
        pipeline_depth: int = 1,
        admission: AdmissionController | bool | None = None,
        default_budget_s: float = DEFAULT_BUDGET_S,
        surface_budgets: dict[str, float] | None = None,
        autotune_table: dict | str | None = None,
        lattice: Lattice | None = None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if executor not in ("xla", "fused", "dist"):
            raise ValueError(f"unknown executor {executor!r}")
        if executor == "dist" and mesh is None:
            raise ValueError("executor='dist' needs a mesh")
        if pipeline_depth < 0:
            raise ValueError(f"pipeline_depth must be >= 0, got "
                             f"{pipeline_depth}")
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.eps = float(eps)
        self.executor = executor
        self.mesh = mesh
        if donate is None:  # CPU ignores donation (and warns); skip there
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        self.pipeline_depth = int(pipeline_depth)
        # admission control: None/False = every request is admitted on
        # rung 0 (pre-admission behavior, deadline tracking still on);
        # True = a default AdmissionController; or pass a configured one.
        if admission is True:
            admission = AdmissionController()
        elif admission is False:
            admission = None
        self.admission: AdmissionController | None = admission
        self.default_budget_s = float(default_budget_s)
        # per-surface budget classes: a request that carries neither a
        # deadline nor a budget_s gets its SURFACE's default budget
        # (e.g. {"feed": 0.05, "search": 0.1}); surfaces not listed
        # fall back to default_budget_s. Deadline hit/miss/shed/degrade
        # are reported per class in metrics.deadline_summary().
        self.surface_budgets = {str(k): float(v)
                                for k, v in (surface_budgets or {}).items()}
        # per-geometry kernel autotune table (benchmarks/autotune.py):
        # a dict {geometry_key: {tile_b/tile_m/tile_n/quant}}, or a
        # path to a saved JSON table (loaded here — absent file = empty
        # table = defaults). Applied per bucket in _build_executor.
        if isinstance(autotune_table, str):
            autotune_table = load_autotune_table(autotune_table)
        self.autotune_table: dict = dict(autotune_table or {})
        self.autotuned_buckets: int = 0
        # bucket lattice (repro.serving.lattice): None/default = the
        # static power-of-two lattice. The live lattice routes every
        # bucket_of; swap_lattice flips it epoch-fenced under the flush
        # lock, exactly like swap_predictor flips predictor state.
        # shape_histogram accumulates the exact per-(tag, surface,
        # m1, m2, K, d_cov) arrival counts at enqueue — what the
        # LatticeLane's optimizer proposes new corners from.
        lattice = Lattice() if lattice is None else lattice
        lattice.validate()
        self._lattice: Lattice = lattice
        self._lattice_epoch: int = 0
        self.shape_histogram = ShapeHistogram()
        self._lattice_lane = None
        self.clock = clock
        self.metrics = EngineMetrics()
        self._predictors: dict[str, _PredictorEntry] = {}
        # hot-swap generations: per tag, the LIVE state dict the bucket
        # executables are fed (threaded as jit argument 0 — never baked
        # into the trace, see _build_executor) plus a monotone epoch.
        # Superseded generations park in _old_states until every batch
        # dispatched against them has materialized (_inflight_gens
        # pins), then retire — on accelerator backends that release
        # their device buffers.
        self._pred_state: dict[str, dict] = {}
        self._pred_epoch: dict[str, int] = {}
        self._old_states: dict[str, dict[int, dict]] = {}
        self._inflight_gens: dict[tuple[str, int], int] = {}
        self._swap_lock = threading.Lock()
        self._refresh = None              # attached RefreshLane, if any
        self._ladders: dict[str, tuple[str, ...]] = {}
        self._uncollected_sheds: list[Shed] = []
        self._exec: dict[Bucket, Callable] = {}
        # Pallas kernel launches per bucket-executable invocation
        # (kernels.ops.kernel_launch_count of the bucket's route) —
        # what metrics.kernel_launches charges each flushed batch.
        self._kernel_launches: dict[Bucket, int] = {}
        self._queues: dict[Bucket, list] = {}
        self._rings: dict[Bucket, StagingRing] = {}
        self._d2h_nbytes: dict[Bucket, int] = {}   # pipeline.d2h's bytes
        self._warmed: set[Bucket] = set()
        self._in_warmup = False           # re-warm compiles aren't violations
        self._retired_sync: list = []     # sync-mode batches awaiting collect
        self._batch_seq = itertools.count()   # PendingBatch.seq, per flush
        self._pipeline = (ExecutionPipeline(depth=self.pipeline_depth)
                          if self.pipeline_depth > 0 else None)

    # -- predictors ---------------------------------------------------------

    def register_predictor(self, tag: str, predictor: Any, *, d_cov: int) -> None:
        """Attach a fitted lambda predictor under `tag`; requests with
        X and this tag get lam predicted inside the bucket executable
        (one dispatch for predict + rank)."""
        if tag == LAM_TAG:
            raise ValueError(f"{LAM_TAG!r} is reserved for raw-lam requests")
        probe = predictor.predict(jnp.zeros((1, d_cov), jnp.float32))
        self._predictors[tag] = _PredictorEntry(
            predictor=predictor, d_cov=int(d_cov), K=int(probe.shape[-1]))
        with self._swap_lock:
            self._pred_state[tag] = self._place_state(
                predictor_state(predictor))
            self._pred_epoch[tag] = 0

    def _place_state(self, state: dict) -> dict:
        """Predictor state onto the device(s) its executables read it
        from: the default device, or every device of the dist mesh."""
        if self.executor != "dist":
            return jax.device_put(state)
        from repro.core.serving_dist import rank_input_shardings
        return jax.device_put(state,
                              rank_input_shardings(self.mesh)["replicated"])

    def set_degradation_ladder(self, tag: str, fallbacks) -> None:
        """Register `tag`'s degradation ladder: when admission predicts
        rung 0 (the tag's own predictor, e.g. the KNN single-grid
        executable) would miss a deadline, requests route to
        fallbacks[0], then fallbacks[1], ... (cheaper, already-warmed
        predictor buckets — e.g. affine, then mean) before shedding.
        Every fallback must already be registered, accept the same
        covariates, and price at least as many constraints as `tag`
        (a rung that silently ignored constraints would fake its
        compliance numbers)."""
        if tag not in self._predictors:
            raise KeyError(f"no predictor registered for tag {tag!r}")
        fallbacks = tuple(fallbacks)
        primary = self._predictors[tag]
        for fb in fallbacks:
            if fb not in self._predictors:
                raise KeyError(f"ladder fallback {fb!r} is not a "
                               f"registered predictor")
            entry = self._predictors[fb]
            if entry.d_cov != primary.d_cov:
                raise ValueError(
                    f"ladder fallback {fb!r}: d_cov {entry.d_cov} != "
                    f"{primary.d_cov} of {tag!r}")
            if entry.K < primary.K:
                raise ValueError(
                    f"ladder fallback {fb!r} emits {entry.K} shadow "
                    f"prices < the {primary.K} that {tag!r} serves")
        self._ladders[tag] = fallbacks

    # -- predictor hot swap (serving/refresh.py's engine seam) ---------------

    def attach_refresh(self, lane) -> None:
        """Attach a refresh lane: every predictor-served result's
        (X, λ̂, exposure, b) row is fed to `lane.observe` at build time
        — the audit outputs are already on the host, so telemetry costs
        zero extra device reads."""
        self._refresh = lane

    def predictor_epoch(self, tag: str) -> int:
        """The tag's current predictor generation (0 = as registered)."""
        return self._pred_epoch[tag]

    def predictor_tags(self) -> tuple[str, ...]:
        """Registered predictor tags — what a fleet supervisor iterates
        when restoring a restarted replica from epoch checkpoints."""
        return tuple(self._predictors)

    def predictor_state_of(self, tag: str) -> dict:
        """The tag's LIVE state dict (device arrays) — what the next
        flush will dispatch against. The refresh lane builds its
        incremental updates on this."""
        with self._swap_lock:
            return self._pred_state[tag]

    def predictor_template(self, tag: str):
        """The registered predictor instance (the static template whose
        family routes kernel dispatch; its arrays are the generation-0
        state, NOT necessarily the live one)."""
        return self._predictors[tag].predictor

    def swap_predictor(self, tag: str, new, *, epoch: int | None = None
                       ) -> int:
        """Epoch-fenced two-phase hot swap of `tag`'s predictor state.

        `new` is a state dict (core.predictors.predictor_state) or a
        predictor instance to take the state from. Phase 1 (publish)
        validates structure/shape/dtype against the live generation —
        a mismatch would silently retrace the bucket executables, so it
        raises ValueError and the engine keeps serving last-good — and
        checks every leaf finite (a poisoned refresh must never reach
        the executables), then transfers the new buffers to the device.
        Phase 2 (flip) swaps the (state, epoch) pair under the same
        lock every flush reads it under, so the flip lands exactly at a
        micro-batch boundary: a batch is dispatched entirely against
        one generation, never a torn mix. The superseded generation is
        retired once its last in-flight batch materializes.

        Returns the new epoch. Never recompiles: the state enters the
        warmed executables as an argument with unchanged treedef.

        `epoch` pins the published generation's number instead of
        current+1 — the checkpoint-restore path: a restarted replica
        swapping in its last-good state must RESUME that state's epoch,
        so results it serves are labeled with the same generation the
        pre-crash replica's were. Epochs stay monotone: a pinned epoch
        at or below the live one raises.
        """
        if tag not in self._predictors:
            raise KeyError(f"no predictor registered for tag {tag!r}")
        state = dict(new) if isinstance(new, dict) else predictor_state(new)
        cur = self._pred_state[tag]
        if not cur:
            raise ValueError(
                f"swap {tag!r}: predictor family has no registered "
                f"refreshable state (core.predictors.STATE_FIELDS)")
        if set(state) != set(cur):
            raise ValueError(
                f"swap {tag!r}: state keys {sorted(state)} != "
                f"{sorted(cur)} of the live generation")
        cur_leaves = jax.tree_util.tree_leaves_with_path(cur)
        new_leaves = jax.tree_util.tree_leaves_with_path(state)
        if [p for p, _ in new_leaves] != [p for p, _ in cur_leaves]:
            raise ValueError(
                f"swap {tag!r}: state tree structure differs from the "
                f"live generation (would retrace the warmed executables)")
        for (path, new_leaf), (_, cur_leaf) in zip(new_leaves, cur_leaves):
            new_leaf = jnp.asarray(new_leaf)
            if (new_leaf.shape != cur_leaf.shape
                    or new_leaf.dtype != cur_leaf.dtype):
                raise ValueError(
                    f"swap {tag!r}: leaf {jax.tree_util.keystr(path)} is "
                    f"{new_leaf.shape}/{new_leaf.dtype}, live generation "
                    f"has {cur_leaf.shape}/{cur_leaf.dtype} — shapes are "
                    f"frozen (the no-recompile contract)")
            if not bool(np.all(np.isfinite(np.asarray(new_leaf)))):
                raise ValueError(
                    f"swap {tag!r}: non-finite values in leaf "
                    f"{jax.tree_util.keystr(path)} — poisoned state "
                    f"refused, serving stays on the live generation")
        state = self._place_state(state)  # phase 1: publish new buffers
        with self._swap_lock:             # phase 2: flip at batch boundary
            old_epoch = self._pred_epoch[tag]
            new_epoch = old_epoch + 1 if epoch is None else int(epoch)
            if new_epoch <= old_epoch:
                raise ValueError(
                    f"swap {tag!r}: pinned epoch {new_epoch} <= live epoch "
                    f"{old_epoch} — epochs are monotone (restore resumes, "
                    f"never rewinds)")
            self._old_states.setdefault(tag, {})[old_epoch] = cur
            self._pred_state[tag] = state
            self._pred_epoch[tag] = new_epoch
            self._retire_unpinned(tag)
        self.metrics.on_swap(tag)
        return new_epoch

    def _current_gen(self, tag: str) -> tuple[dict, int]:
        """The (state, epoch) pair a flush dispatches against, read
        atomically — the other half of the swap fence."""
        with self._swap_lock:
            epoch = self._pred_epoch[tag]
            self._inflight_gens[(tag, epoch)] = (
                self._inflight_gens.get((tag, epoch), 0) + 1)
            return self._pred_state[tag], epoch

    def _release_gen(self, tag: str, epoch: int) -> None:
        """A batch dispatched against (tag, epoch) has materialized:
        unpin the generation and retire it if it is superseded and no
        other batch still holds it."""
        key = (tag, epoch)
        with self._swap_lock:
            n = self._inflight_gens.get(key, 1) - 1
            if n <= 0:
                self._inflight_gens.pop(key, None)
            else:
                self._inflight_gens[key] = n
            self._retire_unpinned(tag)

    def _retire_unpinned(self, tag: str) -> None:
        # caller holds _swap_lock
        old = self._old_states.get(tag)
        if not old:
            return
        cur = self._pred_epoch[tag]
        for epoch in [e for e in old
                      if e < cur and (tag, e) not in self._inflight_gens]:
            del old[epoch]
            self.metrics.on_state_retired(tag)

    # -- bucketing ----------------------------------------------------------

    def bucket_of(self, req: RankRequest,
                  lattice: Lattice | None = None) -> Bucket:
        """Route a request to its bucket on the LIVE lattice (or an
        explicit one — what shadow warm uses to pre-route against a
        proposal before it flips)."""
        lattice = self._lattice if lattice is None else lattice
        tag = LAM_TAG if req.lam is not None else req.tag
        K = req.a.shape[0]
        if tag != LAM_TAG:
            if tag not in self._predictors:
                raise KeyError(f"no predictor registered for tag {tag!r}")
            K_pred = self._predictors[tag].K
            if K > K_pred:
                # the predictor cannot price constraints it was not fit
                # for; serving them with lam=0 would silently ignore them.
                raise ValueError(
                    f"request {req.rid}: {K} constraints but predictor "
                    f"{tag!r} emits only {K_pred} shadow prices")
            # the bucket tier must hold every predicted entry; extra
            # predicted entries beyond the request's K hit zero a-rows.
            K = K_pred
        return lattice.bucket_for(m1=req.u.shape[0], m2=req.m2, K=K,
                                  tag=tag, batch=self.max_batch)

    def _rung_buckets(self, req: RankRequest, home: Bucket,
                      lattice: Lattice | None = None
                      ) -> list[tuple[int, Bucket]]:
        """The request's degradation ladder as (rung, bucket) pairs,
        rung 0 (its own bucket) first. Raw-lam requests have no ladder
        — the rank itself is already the cheapest program."""
        lattice = self._lattice if lattice is None else lattice
        rungs = [(0, home)]
        if req.X is None or home.tag == LAM_TAG:
            return rungs
        K_req = req.a.shape[0]
        for i, fb in enumerate(self._ladders.get(req.tag, ()), start=1):
            entry = self._predictors[fb]
            if entry.K < K_req:      # cannot price this request's system
                continue
            rungs.append((i, lattice.bucket_for(
                m1=req.u.shape[0], m2=req.m2, K=entry.K, tag=fb,
                batch=self.max_batch)))
        return rungs

    # -- adaptive lattice: telemetry, shadow warm, epoch-fenced swap --------

    def attach_lattice_lane(self, lane) -> None:
        """Attach a LatticeLane: the engine feeds its trough detector
        arrival times at enqueue and lag samples through
        observe_submission_lag — the same admission signal."""
        self._lattice_lane = lane

    def lattice(self) -> Lattice:
        """The live bucket lattice (what bucket_of routes on)."""
        return self._lattice

    def lattice_epoch(self) -> int:
        """The live lattice generation (0 = the boot lattice)."""
        return self._lattice_epoch

    def _lattice_buckets(self, lattice: Lattice,
                         sample=None) -> set[Bucket]:
        """Every bucket the OBSERVED traffic (the shape histogram, plus
        an optional sample of RankRequests/Buckets) reaches on
        `lattice`, ladder rungs included — the set a shadow warm must
        compile so the flipped lattice never forces a dispatch-path
        compile on traffic shaped like what we've seen."""
        buckets: set[Bucket] = set()
        for tag, _, m1, m2, K, _, _ in self.shape_histogram.shapes():
            if tag != LAM_TAG and tag not in self._predictors:
                continue                      # tag retired since observed
            K_route = K
            if tag != LAM_TAG:
                K_pred = self._predictors[tag].K
                if K > K_pred:
                    continue                  # bucket_of would refuse it
                K_route = K_pred
            buckets.add(lattice.bucket_for(m1=m1, m2=m2, K=K_route,
                                           tag=tag, batch=self.max_batch))
            # ladder rungs, mirroring _rung_buckets against the
            # request's REAL constraint count
            if tag != LAM_TAG:
                for fb in self._ladders.get(tag, ()):
                    entry = self._predictors[fb]
                    if entry.K < K:
                        continue
                    buckets.add(lattice.bucket_for(
                        m1=m1, m2=m2, K=entry.K, tag=fb,
                        batch=self.max_batch))
        for r in sample or ():
            if isinstance(r, Bucket):
                buckets.add(r)
                continue
            home = self.bucket_of(r, lattice)
            for _, bk in self._rung_buckets(r, home, lattice):
                buckets.add(bk)
        return buckets

    def shadow_warm_lattice(self, new_lattice: Lattice,
                            sample=None) -> dict:
        """Compile + warm `new_lattice`'s executables OFF the dispatch
        path: every bucket the observed traffic would reach on the new
        lattice that is not already warmed gets built and executed on a
        phantom batch here — on the calling thread (the LatticeLane's
        background thread in production), never on a flush. Warmed
        executables are installed into the live cache under the swap
        lock; until swap_lattice flips, routing still uses the old
        lattice, so this is pure cache growth (counted as
        metrics.shadow_compiles — the refined no-recompile contract
        allows cache growth ONLY here and in warmup()).

        Raises on any compile/validation failure — nothing was flipped,
        so the engine keeps serving the last-good lattice untouched.
        """
        new_lattice.validate()
        t0 = self.clock()
        compiled = []
        buckets = self._lattice_buckets(new_lattice, sample)
        for bucket in sorted(buckets):
            if bucket in self._warmed:
                continue
            fn = self._build_executor(bucket)
            staged = assemble_batch([], bucket, d_cov=self._dcov(bucket))
            jax.block_until_ready(self._call(fn, bucket, staged).perm)
            if self.admission is not None:
                t0b = self.clock()
                jax.block_until_ready(self._call(fn, bucket, staged).perm)
                self.admission.observe_service(
                    bucket.name, (self.clock() - t0b) * 1e3)
            with self._swap_lock:
                self._exec[bucket] = fn
                self._warmed.add(bucket)
            self.metrics.on_shadow_compile()
            compiled.append(bucket.name)
        return {"buckets": sorted(b.name for b in buckets),
                "compiled": compiled,
                "warm_ms": (self.clock() - t0) * 1e3}

    def swap_lattice(self, new_lattice: Lattice, *,
                     epoch: int | None = None,
                     warm_ms: float = 0.0) -> int:
        """Epoch-fenced flip of the live lattice, exactly like
        swap_predictor's phase 2: validate that every bucket the
        observed traffic reaches on `new_lattice` is already warmed
        (shadow_warm_lattice's job — an unwarmed corner would compile
        ON the dispatch path, the one thing the contract forbids), then
        swap (lattice, epoch) under the same lock every flush stamps
        its batch under. A batch is bucketed-and-dispatched entirely
        within one lattice generation; old-lattice buckets stay warmed
        in the cache, so queued/in-flight work routed before the flip
        drains with zero recompiles. Epochs are monotone; `epoch` pins
        the generation number for checkpoint-restore paths."""
        new_lattice.validate()
        missing = [b.name for b in sorted(self._lattice_buckets(new_lattice))
                   if b not in self._warmed]
        if missing:
            raise ValueError(
                f"swap_lattice: observed traffic reaches unwarmed buckets "
                f"{missing} — run shadow_warm_lattice first (a cold corner "
                f"would compile on the dispatch path)")
        with self._swap_lock:
            old_epoch = self._lattice_epoch
            new_epoch = old_epoch + 1 if epoch is None else int(epoch)
            if new_epoch <= old_epoch:
                raise ValueError(
                    f"swap_lattice: pinned epoch {new_epoch} <= live epoch "
                    f"{old_epoch} — epochs are monotone")
            self._lattice = new_lattice
            self._lattice_epoch = new_epoch
        self.metrics.on_lattice_swap(new_epoch, warm_ms=warm_ms)
        return new_epoch

    def rewarm_lattice(self, new_lattice: Lattice, sample=None) -> dict:
        """shadow_warm_lattice + swap_lattice in one move — what the
        LatticeLane calls in a trough. Any failure propagates BEFORE
        the flip, so the caller's rollback is a no-op: the last-good
        lattice and its warmed executables never stopped serving."""
        report = self.shadow_warm_lattice(new_lattice, sample)
        report["epoch"] = self.swap_lattice(new_lattice,
                                            warm_ms=report["warm_ms"])
        return report

    # -- executables --------------------------------------------------------

    def _rank_fn(self, bucket: Bucket):
        """The bucket's rank body over already-padded device arrays."""
        m2, eps = bucket.m2, self.eps
        if self.executor == "dist":
            mesh = self.mesh
            from repro.core.serving_dist import rank_distributed

            def rank(u, a, b, lam, gamma):
                return rank_distributed(mesh, u, a, b, lam, gamma,
                                        m2=m2, eps=eps)
        elif self.executor == "fused":
            # One fused rank+audit kernel: utility/exposure/compliance are
            # computed in VMEM at the flush step — no post-kernel gather
            # or einsum ever reads u/a again (kernels/fused_rank.py).
            from repro.kernels.ops import rank_audited

            def rank(u, a, b, lam, gamma):
                return rank_audited(u, a, b, lam, gamma, m2=m2, eps=eps)
        else:
            rank = partial(rank_given_lambda, m2=m2, eps=eps)
        return rank

    def _build_executor(self, bucket: Bucket) -> Callable:
        """One fresh jit wrapper per bucket: its compile cache holds
        exactly one entry, so `jit_cache_sizes` exposes recompiles."""
        from repro.kernels.ops import kernel_launch_count

        predictor = (None if bucket.tag == LAM_TAG
                     else self._predictors[bucket.tag].predictor)
        self._kernel_launches[bucket] = (
            kernel_launch_count(predictor, bucket.m2)
            if self.executor == "fused" else 0)
        rank = self._rank_fn(bucket)
        # dist: host staging transfers straight into the mesh layout
        # rank_distributed computes in, one shard per device
        placed = {}
        if self.executor == "dist":
            from repro.core.serving_dist import rank_input_shardings
            sh = rank_input_shardings(self.mesh)
            rows = sh["rows"]
            placed["in_shardings"] = (
                (rows, rows, sh["u"], sh["a"], rows) if bucket.tag == LAM_TAG
                else (sh["replicated"], rows, rows, sh["u"], sh["a"], rows))
        if bucket.tag == LAM_TAG:

            def fn(b, gamma, u, a, lam):
                return rank(u, a, b, lam, gamma)

            return _jit(bucket, fn,
                        donate_argnums=(2, 3) if self.donate else (),
                        **placed)

        # Predictor-tagged buckets take the predictor's ARRAY state as
        # argument 0 instead of closing over it: closed-over arrays are
        # baked into the executable as constants, so a λ-refresh would
        # force a retrace. As an argument with frozen pytree structure
        # + shapes + dtypes, a hot-swapped generation hits the same
        # compile-cache entry — the no-recompile contract holds across
        # swaps. Only the static template (family, KNN's k) is closed
        # over. u/a stay the donated staging buffers; the state is NOT
        # donated — it serves every batch until the next swap.
        entry = self._predictors[bucket.tag]
        pred = entry.predictor              # static template
        donate = (3, 4) if self.donate else ()
        if self.executor == "dist":
            # the mesh-sharded rank body keeps its own predict stage
            # (still inside this one jit executable)
            pad_k = bucket.K - entry.K

            def fn(state, b, gamma, u, a, X):
                lam = with_state(pred, state).predict(X)    # (B, K_pred)
                lam = jnp.pad(lam, ((0, 0), (0, pad_k)))
                return rank(u, a, b, lam, gamma)

            return _jit(bucket, fn, donate_argnums=donate, **placed)

        # The single-sweep dispatcher (kernels.ops.predict_rank_audited
        # behind its stateful seam): predict + rank + audit lower to
        # ONE device program per flushed batch — for the fused executor
        # the affine families fold λ̂ into the rank kernel's VMEM
        # prologue and KNN fuses its weighting into the db sweep; the
        # xla executor runs the same dispatcher's two-stage XLA body
        # (use_kernel=False), still one executable.
        # metrics.executable_calls counts the contract.
        from repro.kernels.ops import predict_rank_audited_stateful

        m2, eps = bucket.m2, self.eps
        use_kernel = None if self.executor == "fused" else False
        # autotuned tile geometry for this bucket (benchmarks/autotune):
        # tile_* feed the dispatcher's kernel tiling; a 'quant' entry is
        # advisory — the packed predictor's own static quant field (and
        # its pack slab) route the quantized sweep, so the table entry
        # documents the winning mode rather than forcing a repack here.
        # resolved against ACTUAL geometry (never lattice position), so
        # tuned tiles survive a lattice swap; an adaptive corner with no
        # exact entry inherits its nearest covering tuned geometry's
        # tiles, clamped to fit (buckets.resolve_autotune).
        tune = resolve_autotune(self.autotune_table, bucket,
                                d_cov=self._dcov(bucket))
        tiles = {kk: int(v) for kk, v in tune.items()
                 if kk in AUTOTUNE_KEYS and kk != "quant"}
        if tune:
            self.autotuned_buckets += 1

        def fn(state, b, gamma, u, a, X):
            return predict_rank_audited_stateful(state, pred, X, u, a, b,
                                                 gamma, m2=m2, eps=eps,
                                                 use_kernel=use_kernel,
                                                 **tiles)

        return _jit(bucket, fn, donate_argnums=donate)

    def _executor_for(self, bucket: Bucket) -> Callable:
        fn = self._exec.get(bucket)
        if fn is None:
            fn = self._exec[bucket] = self._build_executor(bucket)
            self.metrics.on_compile(in_warmup=self._in_warmup)
        return fn

    def warmup(self, sample) -> dict:
        """Compile every bucket reachable from `sample` (RankRequests or
        Buckets) by executing one phantom batch per bucket — including
        every degradation-ladder rung of each request's tag, so a
        degrade decision can never trip the no-recompile contract.
        After this, any stream inside the lattice runs with zero
        recompiles. This is the only place the engine blocks on the
        device directly. With admission attached, a second (compiled)
        phantom execution per bucket seeds the controller's
        service-time EWMAs, so the very first live decision already
        has a real estimate instead of the prior."""
        buckets = set()
        for r in sample:
            if isinstance(r, Bucket):
                buckets.add(r)
                continue
            home = self.bucket_of(r)
            for _, bk in self._rung_buckets(r, home):
                buckets.add(bk)
        self._in_warmup = True
        try:
            for bucket in sorted(buckets):
                fn = self._executor_for(bucket)
                staged = assemble_batch([], bucket, d_cov=self._dcov(bucket))
                jax.block_until_ready(self._call(fn, bucket, staged).perm)
                if self.admission is not None:
                    t0 = self.clock()
                    jax.block_until_ready(
                        self._call(fn, bucket, staged).perm)
                    self.admission.observe_service(
                        bucket.name, (self.clock() - t0) * 1e3)
                self._warmed.add(bucket)
        finally:
            self._in_warmup = False
        self.metrics.warmed = True
        return {"buckets": [b.name for b in sorted(buckets)],
                "compiles": self.metrics.compiles}

    def _dcov(self, bucket: Bucket) -> int | None:
        if bucket.tag == LAM_TAG:
            return None
        return self._predictors[bucket.tag].d_cov

    def _args(self, bucket: Bucket, staged: dict,
              state: dict | None = None) -> tuple:
        """A bucket executable's arguments, in its signature's order."""
        if bucket.tag == LAM_TAG:
            return (staged["b"], staged["gamma"], staged["u"], staged["a"],
                    staged["lam"])
        if state is None:                  # warmup path: no gen pinning
            state = self._pred_state[bucket.tag]
        return (state, staged["b"], staged["gamma"], staged["u"],
                staged["a"], staged["X"])

    def _call(self, fn, bucket: Bucket, staged: dict,
              state: dict | None = None) -> RankingOutput:
        return fn(*self._args(bucket, staged, state))

    def compiled_executables(self) -> dict[str, jax.stages.Compiled]:
        """The compiled program of every warmed bucket, by bucket name,
        lowered from a phantom batch of the bucket's shapes. Its
        ``as_text()`` is what the device runs (a Pallas kernel appears
        as a ``tpu_custom_call``), its ``input_shardings`` where each
        argument lives."""
        return {bk.name: self._exec[bk].lower(*self._args(
                    bk, assemble_batch([], bk, d_cov=self._dcov(bk))))
                .compile()
                for bk in sorted(self._warmed)}

    def jit_cache_sizes(self) -> dict[str, int]:
        """Per-bucket jit compile-cache sizes (1 = exactly the warmed
        executable; >1 = something retraced). The no-recompile test
        asserts every value stays 1 across a mixed-shape stream."""
        return {b.name: fn._cache_size() for b, fn in self._exec.items()}

    # -- submission side: queueing / flushing -------------------------------

    def submit(self, req: RankRequest, now: float | None = None):
        """Enqueue; returns whatever results have retired so far (the
        capacity-flushed batch itself, when the pipeline is enabled,
        retires asynchronously — collect it from later submit/poll
        calls or from `drain`)."""
        self._enqueue(req, now)
        return self._collect()

    def submit_future(self, req: RankRequest,
                      now: float | None = None) -> RankFuture:
        """Enqueue and return this request's RankFuture. The future
        resolves when the request's micro-batch retires — or
        immediately, with a typed `Shed` result, when admission sheds
        it. Completed results also keep flowing through
        submit/poll/drain, so mixing the two styles is safe (same
        underlying results objects)."""
        return self._enqueue(req, now)

    def observe_submission_lag(self, lag_ms: float) -> None:
        """Feed the open-loop driver's queueing-lag sample (pacing
        clock-drift already separated out by serve_open_loop) to the
        admission controller as its online saturation signal. No-op
        without a controller."""
        if self.admission is not None:
            self.admission.observe_lag(lag_ms)
        if self._lattice_lane is not None:
            self._lattice_lane.observe_lag(lag_ms)

    def _deadline_of(self, req: RankRequest, now: float) -> float:
        if req.deadline is not None:
            return float(req.deadline)
        if req.budget_s is not None:
            budget = req.budget_s
        else:
            budget = self.surface_budgets.get(req.surface,
                                              self.default_budget_s)
        return now + float(budget)

    def _enqueue(self, req: RankRequest, now: float | None) -> RankFuture:
        now = self.clock() if now is None else now
        bucket = self.bucket_of(req)
        name = bucket.name
        with span("engine.enqueue", rid=req.rid, bucket=name):
            self.metrics.on_submit(bucket, known=bucket in self._warmed)
            # shape telemetry: the request's REAL geometry (pre-padding,
            # pre-K-widening) — what the lattice optimizer learns corners
            # from. A dict update per request; no device reads.
            self.shape_histogram.observe(
                tag=bucket.tag, m1=req.u.shape[0], m2=req.m2,
                K=req.a.shape[0],
                d_cov=None if req.X is None else req.X.shape[-1],
                surface=req.surface)
            if self._lattice_lane is not None:
                self._lattice_lane.observe_arrival(now)
            fut = RankFuture(req.rid, name)
            deadline = self._deadline_of(req, now)
            rung = 0
            if self.admission is not None:
                rungs = self._rung_buckets(req, bucket)
                inflight = (self._pipeline.inflight()
                            if self._pipeline is not None else 0)
                preds = [(r, self.admission.predict_ms(
                              bk.name,
                              queue_len=len(self._queues.get(bk, ())),
                              batch_cap=bk.batch, inflight=inflight,
                              max_wait_ms=self.max_wait_ms))
                         for r, bk in rungs]
                decision = self.admission.decide(
                    budget_ms=(deadline - now) * 1e3, rung_predictions=preds)
                if not decision.admitted:
                    self.metrics.on_shed(bucket, surface=req.surface)
                    shed = Shed(rid=req.rid, bucket=bucket.name,
                                predicted_ms=decision.predicted_ms,
                                budget_ms=decision.budget_ms)
                    fut._resolve(shed)
                    self._uncollected_sheds.append(shed)
                    return fut
                if decision.rung > 0:
                    rung = decision.rung
                    bucket = dict(rungs)[rung]
                    self.metrics.on_degrade(rung, surface=req.surface)
            q = self._queues.setdefault(bucket, [])
            q.append(_QueueEntry(req=req, t_enq=now, fut=fut,
                                 deadline=deadline, rung=rung))
            if len(q) >= bucket.batch:
                self._flush_bucket(bucket, trigger="capacity")
            return fut

    def poll(self, now: float | None = None):
        """Deadline check: flush every queue whose oldest request has
        waited longer than max_wait_ms; returns results retired so far."""
        now = self.clock() if now is None else now
        for bucket in list(self._queues):
            q = self._queues[bucket]
            if q and (now - q[0].t_enq) * 1e3 >= self.max_wait_ms:
                self._flush_bucket(bucket, trigger="deadline")
        return self._collect()

    def drain(self):
        """Flush every queue and wait for all in-flight batches to
        retire (stream end / graceful shutdown barrier). Returns every
        result not yet collected."""
        for bucket in list(self._queues):
            if self._queues[bucket]:
                self._flush_bucket(bucket, trigger="drain")
        if self._pipeline is not None:
            results = self._take_sheds()
            for pending in self._pipeline.flush():
                results += pending.results()
            return results
        return self._collect()

    def handoff_queued(self, error: BaseException | None = None) -> list:
        """Evict every QUEUED (not yet flushed) request — the fleet's
        drain/handoff primitive, generalizing the pipeline's drain: a
        draining or crashed replica first lets its in-flight batches
        retire (they were dispatched; their futures resolve normally),
        while its queued-but-unflushed requests must MOVE to another
        replica instead of being flushed into a dying engine. Each
        evicted entry's future fails with `error` (so a fleet router's
        failure path picks it up uniformly) and the request objects are
        returned for resubmission elsewhere."""
        if error is None:
            error = RuntimeError("request evicted for handoff")
        evicted = []
        for bucket in list(self._queues):
            entries, self._queues[bucket] = self._queues[bucket], []
            for e in entries:
                evicted.append(e.req)
                e.fut._fail(error)
        return evicted

    def close(self) -> None:
        """Graceful shutdown: drain in-flight work and stop the
        pipeline worker. The engine rejects flushes afterwards."""
        if self._pipeline is not None:
            self._pipeline.flush()
            self._pipeline.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _take_sheds(self) -> list:
        sheds, self._uncollected_sheds = self._uncollected_sheds, []
        return sheds

    def _collect(self):
        """Build results for every batch retired since the last call
        (plus any Shed outcomes since the last call). Runs on the
        caller's thread — the Python-heavy unpadding deliberately
        lives here, not on the pipeline worker, so it overlaps device
        execution instead of starving it via the GIL."""
        if self._pipeline is not None:
            batches = self._pipeline.collect()
        else:
            batches, self._retired_sync = self._retired_sync, []
        results = self._take_sheds()
        for pending in batches:
            results += pending.results()
        return results

    def _ring_for(self, bucket: Bucket) -> StagingRing:
        ring = self._rings.get(bucket)
        if ring is None:
            # the in-flight window holds pipeline_depth queued batches
            # plus the one materializing; one more slot keeps assembly
            # of the next batch from ever waiting on a buffer.
            ring = self._rings[bucket] = StagingRing(
                bucket, d_cov=self._dcov(bucket),
                depth=self.pipeline_depth + 2)
        return ring

    def _flush_bucket(self, bucket: Bucket, *, trigger: str) -> None:
        entries = self._queues[bucket]
        self._queues[bucket] = []
        seq = next(self._batch_seq)
        with span("engine.flush", batch=seq, bucket=bucket.name,
                  trigger=trigger, rows=len(entries)):
            reqs = [e.req for e in entries]
            ring = self._ring_for(bucket)
            fn = self._executor_for(bucket)
            t0 = self.clock()
            staged = ring.acquire()
            with span("engine.fill_staging", batch=seq):
                fill_staging(staged, reqs, bucket)
            # epoch fence: the (state, epoch) pair is read atomically, so
            # this whole batch dispatches against exactly one predictor
            # generation — a concurrent swap lands before or after, never
            # inside. The generation stays pinned until the batch
            # materializes (_release_gen), which is what delays retirement
            # of superseded device buffers past their last in-flight use.
            state, epoch = ((None, 0) if bucket.tag == LAM_TAG
                            else self._current_gen(bucket.tag))
            # lattice fence, same discipline: the epoch is read under the
            # swap lock, so a concurrent swap_lattice lands before or
            # after this batch, never inside it.
            with self._swap_lock:
                lattice_epoch = self._lattice_epoch
            t_launch = self.clock()
            try:
                # the jit call queues the copies of the staged host
                # arrays to the device and launches: async, no block.
                # The runtime's own transfer events in a trace say when
                # each copy is done, often after this span has ended.
                with span("engine.dispatch", batch=seq, bytes=ring.nbytes):
                    out = self._call(fn, bucket, staged, state)
            except BaseException as e:                # noqa: BLE001
                # dispatch itself blew up (bad executable, device OOM, an
                # injected fault): fail this batch's futures so every one
                # still resolves exactly once, and recycle the staging set
                # — the ring is finite, and a leaked buffer would
                # eventually deadlock acquire() under continued load.
                for entry in entries:
                    entry.fut._fail(e)
                ring.release(staged)
                if bucket.tag != LAM_TAG:      # nothing dispatched: unpin
                    self._release_gen(bucket.tag, epoch)
                raise
            t1 = self.clock()
            # the single-dispatch contract: this call was the batch's ONE
            # executable invocation — predictor buckets included (λ̂ is
            # predicted inside the executable, never as a separate
            # program) — and it contained the route's static kernel-launch
            # count (ONE for every fused-executor kernel bucket, KNN
            # included since the single-grid predict+rank+audit kernel).
            self.metrics.on_executable_call(self._kernel_launches[bucket])
            pending = PendingBatch(
                bucket=bucket, entries=entries,
                futures=[e.fut for e in entries], out=out, staged=staged,
                ring=ring, t_launch=t_launch, trigger=trigger,
                materialize=self._materialize_batch,
                build=self._build_result,
                assembly_ms=(t_launch - t0) * 1e3,
                dispatch_ms=(t1 - t_launch) * 1e3, epoch=epoch,
                lattice_epoch=lattice_epoch, seq=seq)
            if self._pipeline is not None:
                self._pipeline.submit(pending)   # may block: backpressure
            else:
                pending.finish()
                self._retired_sync.append(pending)
            self.metrics.on_dispatch(
                bucket, len(reqs), trigger, fill_stats(reqs, bucket),
                assembly_ms=pending.assembly_ms,
                dispatch_ms=pending.dispatch_ms,
                depth=pending.depth_at_dispatch, t_now=t_launch)

    # -- completion side ----------------------------------------------------

    def _materialize_batch(self, pending: PendingBatch) -> None:
        """Block until one batch's outputs reach the host. Runs on the
        pipeline worker (async mode) or inline (sync mode); this is the
        ONLY blocking step on the completion side — the GIL is released
        while waiting, so the submission thread keeps assembling.

        One bulk device->host copy per output; per-request unpadding is
        then pure numpy (slicing jax arrays row-by-row would dispatch —
        and on first touch compile — one tiny program per slice)."""
        out = pending.out
        # Under a trace, wait for the device (behind earlier batches
        # too) apart from the copy home that follows. The extra wait
        # hands the interpreter lock over once more a batch, which cost
        # about 3 % of the feed cell's p50 on a TPU v5e, so it runs only
        # while a trace records; the copies below wait anyway.
        if span.is_enabled():
            with span("pipeline.device_wait", batch=pending.seq):
                jax.block_until_ready(out)
        # lam comes home with the rest: the refresh lane's telemetry
        # (λ̂ actually served) reads it row-by-row in _build_result, and
        # slicing a device array there would dispatch per row.
        nbytes = self._d2h_nbytes.get(pending.bucket)
        if nbytes is None:          # fixed by the bucket's shapes
            nbytes = self._d2h_nbytes[pending.bucket] = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(out))
        with span("pipeline.d2h", batch=pending.seq, bytes=nbytes):
            pending.out = RankingOutput(
                perm=np.asarray(out.perm), utility=np.asarray(out.utility),
                exposure=np.asarray(out.exposure),
                compliant=np.asarray(out.compliant),
                lam=np.asarray(out.lam))
        pending.t_done = self.clock()
        exec_ms = (pending.t_done - pending.t_launch) * 1e3
        self.metrics.on_retire(exec_ms, pending.t_done)
        if self.admission is not None:
            self.admission.observe_service(pending.bucket.name, exec_ms)
        if pending.ring is not None:            # inputs consumed: recycle
            pending.ring.release(pending.staged)
            pending.staged = None
        if pending.bucket.tag != LAM_TAG:       # epoch fence: unpin the gen
            self._release_gen(pending.bucket.tag, pending.epoch)

    def _build_result(self, pending: PendingBatch, i: int) -> RankResult:
        """Unpad row `i` into its RankResult. Runs lazily, exactly once
        per row (memoized by the row's RankFuture), on whichever
        consumer thread first asks — the engine's collect path or a
        direct future.result() call."""
        with span("engine.build_result", rid=pending.entries[i].req.rid,
                  batch=pending.seq):
            return self._unpad_row(pending, i)

    def _unpad_row(self, pending: PendingBatch, i: int) -> RankResult:
        entry = pending.entries[i]
        req, t_enq = entry.req, entry.t_enq
        perm, utility, exposure, compliant = unpad_result(pending.out, i, req)
        deadline_hit = pending.t_done <= entry.deadline
        # per-rung compliance cost: the exposure shortfall against the
        # request's REAL thresholds, computed from the fused kernel's
        # already-unpadded audit outputs — one tiny numpy op per row.
        shortfall = float(np.clip(req.b - exposure, 0.0, None).sum())
        self.metrics.on_result((pending.t_done - t_enq) * 1e3,
                               (pending.t_launch - t_enq) * 1e3, compliant,
                               deadline_hit=deadline_hit, rung=entry.rung,
                               shortfall=shortfall, surface=req.surface)
        if self.admission is not None:
            # measured-trend feed: the controller's windowed p99-vs-
            # budget tracker shifts the default degradation rung when
            # trailing MEASURED latency (not the submit-time
            # prediction) blows the budget for consecutive windows.
            self.admission.observe_result(
                (pending.t_done - t_enq) * 1e3,
                (entry.deadline - t_enq) * 1e3)
        if self._refresh is not None and pending.bucket.tag != LAM_TAG:
            # feed the refresh lane: covariates + the λ̂ / exposure /
            # threshold rows at the SERVED tag's predictor width (the
            # dual-subgradient triple). All host numpy already — the
            # audit outputs came home with the batch, zero extra
            # device reads.
            K_pred = self._predictors[pending.bucket.tag].K
            K_req = req.b.shape[0]
            expo_row = np.zeros(K_pred, np.float32)
            expo_row[:K_req] = exposure[:K_pred][:K_req]
            b_row = np.zeros(K_pred, np.float32)
            b_row[:K_req] = req.b[:K_pred][:K_req]
            self._refresh.observe(
                pending.bucket.tag, X=req.X,
                lam=np.asarray(pending.out.lam[i, :K_pred], np.float32),
                exposure=expo_row, b=b_row)
        return RankResult(
            rid=req.rid, perm=perm, utility=utility, exposure=exposure,
            compliant=compliant, bucket=pending.bucket.name,
            latency_ms=(pending.t_done - t_enq) * 1e3,
            wait_ms=(pending.t_launch - t_enq) * 1e3,
            deadline_hit=deadline_hit, rung=entry.rung,
            epoch=pending.epoch, lattice_epoch=pending.lattice_epoch,
            lam=np.asarray(pending.out.lam[i, :req.b.shape[0]]))

    # -- convenience driver -------------------------------------------------

    def serve_stream(self, requests, *, warmup: bool = True):
        """Synchronous driver: submit each request in arrival order,
        honoring deadlines between arrivals, and drain at stream end.
        Returns results ordered by completion (retirement order)."""
        requests = list(requests)
        if warmup and not self.metrics.warmed:
            self.warmup(requests)
        results = []
        for req in requests:
            results += self.submit(req)
            results += self.poll()
        results += self.drain()
        return results
