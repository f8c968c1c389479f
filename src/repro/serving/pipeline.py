"""Async double-buffered execution pipeline for the serving engine.

The engine's hot path is split in two (see docs/serving.md for the
timeline diagrams):

  submission side   the caller's thread: bucketing, host-side batch
                    assembly into a recycled StagingRing buffer, and
                    jit dispatch. Dispatch is asynchronous — the jit
                    call returns device futures immediately — so the
                    submission thread goes straight back to assembling
                    the next micro-batch.

  completion side   one worker thread (per ExecutionPipeline) that
                    retires dispatched batches in dispatch order: it
                    blocks on the device→host transfer of batch N's
                    outputs while the device is already executing batch
                    N+1, then marks every RankFuture of the batch done
                    and recycles the staging buffers. The worker does
                    NOTHING else — per-request unpadding and result
                    construction are Python-heavy (they would hold the
                    GIL against the submission thread), so they run
                    lazily on whichever consumer thread first asks:
                    `RankFuture.result()` or the engine's collect path
                    (submit/poll/drain return values). Each result is
                    built exactly once (futures memoize under a lock).

The bounded in-flight queue IS the double buffer: `depth` is how many
dispatched batches may queue behind the one the worker is currently
materializing, so depth=1 keeps (at most) two batches alive between
dispatch and retirement — classic double buffering — and a further
dispatch blocks the submission side (backpressure) instead of growing
an unbounded device queue. StagingRing carries one slot more than the
in-flight window (depth queued + 1 materializing) so assembly of the
next batch always has a free buffer while earlier batches are in
flight; a buffer is recycled only after its batch's outputs have fully
materialized, so reuse can never race an in-flight transfer (and, on
accelerator backends, never races a donated device buffer).

Nothing in this module knows about ranking — PendingBatch's
`materialize` and `build` callables (bound by the engine) own
device→host copies, unpadding, and metrics. This module owns only
threads, queues, futures, and lifetime.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Callable

from jax.profiler import TraceAnnotation as span

from repro.serving.buckets import Bucket, alloc_staging

__all__ = ["RankFuture", "StagingRing", "PendingBatch", "ExecutionPipeline"]


class RankFuture:
    """Handle for one submitted request's eventual RankResult.

    Marked done by the completion side when the request's micro-batch
    outputs reach the host. `result()` blocks (optionally with a
    timeout) and builds/memoizes the RankResult on the calling thread;
    `done()` and `add_done_callback()` never block. Callbacks run on
    the thread that marks the future done — the pipeline worker in
    async mode, the submitting thread in sync mode — so they must be
    cheap; call `result()` inside one only if doing the unpadding work
    on that thread is acceptable.

    Settlement is FIRST-WINS: `_finish`/`_resolve`/`_fail` each return
    True only for the call that settled the future; later calls are
    no-ops returning False. Exactly-once resolution is what the fleet
    layer's hedging leans on — a hedged request holds one fleet-level
    future that both replica attempts race to settle, and the loser's
    completion (or crash) must never overwrite the winner's result.
    """

    __slots__ = ("rid", "bucket_name", "_event", "_batch", "_index",
                 "_result", "_error", "_callbacks", "_lock")

    def __init__(self, rid: int, bucket_name: str):
        self.rid = rid
        self.bucket_name = bucket_name
        self._event = threading.Event()
        self._batch: "PendingBatch | None" = None
        self._index = -1
        self._result = None
        self._error: BaseException | None = None
        self._callbacks: list[Callable] = []
        self._lock = threading.Lock()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        """The RankResult, blocking until the batch's outputs are home."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid}: no result within "
                               f"{timeout}s (did you drain()?)")
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._result is None:
                self._result = self._batch.build(self._batch, self._index)
                # a held future must not pin the whole batch (padded
                # outputs + every row's request arrays) once its own
                # row is memoized.
                self._batch = None
            return self._result

    def add_done_callback(self, cb: Callable[["RankFuture"], None]) -> None:
        with self._lock:
            if not self._event.is_set():
                self._callbacks.append(cb)
                return
        cb(self)

    def _fire_callbacks(self) -> None:
        with self._lock:
            callbacks, self._callbacks = self._callbacks, []
        for cb in callbacks:
            cb(self)

    def _finish(self, batch: "PendingBatch", index: int) -> bool:
        with self._lock:
            if self._event.is_set():
                return False
            self._batch, self._index = batch, index
        self._event.set()
        self._fire_callbacks()
        return True

    def _resolve(self, result) -> bool:
        """Resolve immediately with a pre-built result — the shed path
        (typed Shed, not an exception) and the fleet's hedge-winner
        path. First caller wins; a settled future is never rewritten."""
        with self._lock:
            if self._event.is_set():
                return False
            self._result = result
        self._event.set()
        self._fire_callbacks()
        return True

    def _fail(self, error: BaseException) -> bool:
        with self._lock:
            if self._event.is_set():
                return False
            self._error = error
        self._event.set()
        self._fire_callbacks()
        return True


class StagingRing:
    """Fixed ring of reusable host staging-buffer sets for one bucket.

    `acquire` hands out a free buffer set and blocks when every set is
    attached to an in-flight batch — backpressure that bounds host
    memory to `depth` buffer sets per bucket regardless of offered
    load. `release` (called by the completion side once a batch's
    outputs have materialized) returns the set for reuse.

    The buffers are PINNED: allocated exactly once here, page-aligned
    (buckets._aligned_empty), and recycled for the ring's whole
    lifetime. `release` asserts the returned set is one the ring handed
    out — a foreign dict means some path re-allocated staging on the
    submission side, which is precisely the per-batch host allocation
    the ring exists to eliminate. `reuses` counts acquires beyond the
    first per buffer, so tests can assert steady state allocates
    nothing.
    """

    def __init__(self, bucket: Bucket, *, d_cov: int | None, depth: int):
        self.bucket = bucket
        self.depth = int(depth)
        self._free: queue.Queue = queue.Queue()
        self._owned: frozenset[int] = frozenset()
        self._handed_out = 0
        owned = []
        for _ in range(self.depth):
            staged = alloc_staging(bucket, d_cov=d_cov)
            owned.append(id(staged))
            self._free.put(staged)
        self._owned = frozenset(owned)
        # bytes of one buffer set: what a batch copies to the device
        self.nbytes = sum(x.nbytes for x in staged.values())

    @property
    def allocated(self) -> int:
        """Buffer sets this ring ever allocated — depth, by construction,
        for the ring's whole lifetime."""
        return len(self._owned)

    @property
    def reuses(self) -> int:
        """Acquires beyond the first use of each buffer set."""
        return max(0, self._handed_out - self.depth)

    def acquire(self) -> dict:
        self._handed_out += 1
        return self._free.get()

    def release(self, staged: dict) -> None:
        if id(staged) not in self._owned:
            raise AssertionError(
                f"StagingRing[{self.bucket.name}]: released a buffer set "
                f"it never allocated — a submission path allocated fresh "
                f"staging instead of reusing the pinned ring")
        self._free.put(staged)


@dataclass
class PendingBatch:
    """One dispatched micro-batch, from dispatch through result build.

    Created by the submission side at dispatch time. `materialize`
    (engine-bound) blocks on the device→host transfer, restamps `out`
    with host arrays, sets `t_done`, and recycles `staged`; `build`
    (engine-bound) unpads row `i` into a RankResult. The completion
    worker calls only `materialize` — `build` runs lazily on consumer
    threads via RankFuture.
    """

    bucket: Bucket
    entries: list                     # [engine._QueueEntry] (req, t_enq,
                                      # deadline, rung)
    futures: list                     # [RankFuture], aligned with entries
    out: Any                          # RankingOutput: device, then host arrays
    staged: dict | None               # staging buffers to recycle
    ring: StagingRing | None
    t_launch: float
    trigger: str
    materialize: Callable = None      # (PendingBatch) -> None
    build: Callable = None            # (PendingBatch, i) -> RankResult
    t_done: float | None = None
    assembly_ms: float = 0.0
    dispatch_ms: float = 0.0
    depth_at_dispatch: int = 0
    fill: dict = field(default_factory=dict)
    # predictor generation the batch was dispatched against (engine
    # epoch fence): every row of the batch shares it — a swap lands
    # between batches, never inside one. 0 for raw-lam buckets.
    epoch: int = 0
    # lattice generation at dispatch (same fence discipline): a lattice
    # swap lands between batches, so every row of a batch was bucketed
    # and served under one lattice. 0 = the boot power-of-two lattice.
    lattice_epoch: int = 0
    # the flush's sequence number: the `batch` stat of every trace span
    # of this batch
    seq: int = -1

    def finish(self) -> None:
        """Materialize outputs and mark every future done. Called by
        the pipeline worker (async) or inline after dispatch (sync)."""
        self.materialize(self)
        for i, fut in enumerate(self.futures):
            fut._finish(self, i)

    def results(self) -> list:
        """Build (or fetch memoized) results for all rows, in order."""
        return [fut.result(timeout=0) for fut in self.futures]


class ExecutionPipeline:
    """Completion side: a worker thread retiring batches in dispatch order.

    `submit` enqueues a PendingBatch (blocking when `depth` batches are
    already in flight), the worker calls `pending.finish()` on each —
    the blocking device→host wait — and finished batches accumulate
    until the submission side collects them with `collect`
    (non-blocking) or `flush` (barrier: waits for every in-flight
    batch). A worker error is captured, fails that batch's futures,
    and re-raises on the next `flush`/`submit` so a single-threaded
    driver still sees it.
    """

    def __init__(self, *, depth: int):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self._inflight: queue.Queue = queue.Queue(maxsize=self.depth)
        self._retired: queue.Queue = queue.Queue()
        self._error: BaseException | None = None
        self._worker: threading.Thread | None = None
        self._closed = False
        self._lock = threading.Lock()

    # -- worker -------------------------------------------------------------

    def _ensure_worker(self) -> None:
        with self._lock:
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._run, name="serving-pipeline", daemon=True)
                self._worker.start()

    def _run(self) -> None:
        while True:
            pending = self._inflight.get()
            if pending is None:                       # shutdown sentinel
                self._inflight.task_done()
                return
            try:
                pending.finish()
                self._retired.put(pending)
            except BaseException as e:                # noqa: BLE001
                self._error = self._error or e
                for fut in pending.futures:
                    fut._fail(e)
                # recycle the staging buffers even on failure — the
                # ring is finite, and leaking one set per error would
                # eventually deadlock acquire() on the submission side.
                if pending.ring is not None and pending.staged is not None:
                    pending.ring.release(pending.staged)
                    pending.staged = None
            finally:
                self._inflight.task_done()

    # -- submission-side API ------------------------------------------------

    def submit(self, pending: PendingBatch) -> None:
        """Hand a dispatched batch to the completion side. Blocks while
        `depth` batches are in flight (backpressure). A stored worker
        error re-raises here, but only AFTER this batch is enqueued —
        the batch was already dispatched, and dropping it would leak
        its staging buffers and leave its futures unresolved forever."""
        if self._closed:
            raise RuntimeError("pipeline is closed")
        self._ensure_worker()
        pending.depth_at_dispatch = self._inflight.qsize()
        with span("pipeline.backpressure", batch=pending.seq):
            self._inflight.put(pending)
        self._raise_pending_error()

    def inflight(self) -> int:
        """Batches dispatched but not yet retired (approximate: the
        batch currently being materialized no longer counts)."""
        return self._inflight.qsize()

    def collect(self) -> list:
        """All batches retired so far; never blocks."""
        out = []
        while True:
            try:
                out.append(self._retired.get_nowait())
            except queue.Empty:
                return out

    def flush(self) -> list:
        """Barrier: wait until every in-flight batch has retired, then
        return everything collected (including earlier retirees)."""
        if self._worker is not None:
            self._inflight.join()
        self._raise_pending_error()
        return self.collect()

    def close(self) -> None:
        """Graceful shutdown: retire everything in flight, then stop
        the worker. Idempotent; the pipeline rejects submits after."""
        if self._closed:
            return
        self._closed = True
        if self._worker is not None:
            self._inflight.put(None)
            self._worker.join()
            self._worker = None

    def _raise_pending_error(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise err
