"""Reduce the serving engine's own spans in a profiler trace to the
numbers the staging, completion, result-build and device metrics read.

The engine marks each layer boundary with a ``jax.profiler``
``TraceAnnotation`` named ``engine.*`` or ``pipeline.*``
(listed in docs/serving.md); they land on the host planes of the
same ``.xplane.pb`` that ``trace_reduce`` reads, on the device's clock.
Within the client's ``bench.window`` span:

  spans            per span name: ``n``, the spans overlapping the
                   window; ``dur_s``, their durations clipped to it;
                   ``bytes``, the sum of their ``bytes`` stat
  h2d_s            per ``engine.dispatch`` span in the window whose
                   copies all finished, the time from the span's start
                   to the TPU runtime's done event of the last transfer
                   of a staged host array its jit call issued: when the
                   batch's inputs were on the device (the call returns
                   once the copies are queued, so the span's own length
                   is not the copy's)
  inflight_idle_s  device 0's idle time while a batch was between launch
                   and home: the window less device 0's op intervals,
                   intersected with the union of the IN_FLIGHT spans of
                   every thread; None when the trace has no such span
  window_s         the window's length

A program without these spans (an older engine) reduces to no spans,
no ``h2d_s`` and ``inflight_idle_s`` None, so every metric that reads
them is left out.
"""

from __future__ import annotations

import bisect
import functools
from collections import defaultdict

from benchmarks.onchip import trace_reduce
from benchmarks.onchip.run import TRACE_DIR

PROGRAM_SPANS = ("engine.", "pipeline.")
IN_FLIGHT = ("engine.dispatch", "pipeline.device_wait", "pipeline.d2h")
# The TPU runtime's host events of one host array's copy to the device,
# chained by flow ids: the copy into a transfer buffer on the calling
# thread (stat ``_p``) is consumed by a dispatch on a runtime thread
# (``_c``), which holds the transfer (``_p``), whose done event fires
# when the transfer has finished (``_c``).
LINEARIZE = "TpuClient::LinearizeIntoImpl"
H2D_DISPATCH = "H2D Dispatch"
TRANSFER = "tpu::System::TransferToDevice"
TRANSFER_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"


def _intersect(a, b) -> list:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _copies_done(dispatches, transfers, done) -> dict:
    """For each copy's flow id, the end of the done event of the last
    transfer its dispatch held, or None if one of them never finished.
    ``dispatches`` and ``transfers`` map a host line to its
    (start, end, flow id) events; ``done`` maps a transfer's flow id to
    its done event's end."""
    out = {}
    for key, ds in dispatches.items():
        ds.sort()
        starts = [d[0] for d in ds]
        for s, e, p in transfers.get(key, ()):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > ds[i][1]:
                continue
            c, end = ds[i][2], done.get(p)
            if end is None or (c in out and out[c] is None):
                out[c] = None
            else:
                out[c] = max(out.get(c, end), end)
    return out


def reduce_spans(pd) -> dict:
    """The reduction of a ``ProfileData`` (see the module docstring)."""
    window, events, ops = None, [], {}
    copies, done = [], {}
    dispatches, transfers = defaultdict(list), defaultdict(list)
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            ops[int(m.group(2))] = [
                (ev.start_ns, ev.start_ns + ev.duration_ns)
                for line in plane.lines
                if line.name in trace_reduce.OPS_LINES
                for ev in line.events]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            key = (plane.name, li)
            for ev in line.events:
                name, s = ev.name, ev.start_ns
                if name == trace_reduce.WINDOW_SPAN and window is None:
                    window = (s, s + ev.duration_ns)
                elif name.startswith(PROGRAM_SPANS):
                    events.append(ev)
                elif name == LINEARIZE:
                    copies.append((s, dict(ev.stats).get("_p")))
                elif name == H2D_DISPATCH:
                    dispatches[key].append(
                        (s, s + ev.duration_ns, dict(ev.stats).get("_c")))
                elif name == TRANSFER:
                    transfers[key].append(
                        (s, s + ev.duration_ns, dict(ev.stats).get("_p")))
                elif name == TRANSFER_DONE:
                    done[dict(ev.stats).get("_c")] = s + ev.duration_ns
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN!r} host span in "
                         f"the trace")
    lo, hi = window
    copies.sort()
    copy_starts = [c[0] for c in copies]
    copy_done = _copies_done(dispatches, transfers, done)
    spans = defaultdict(lambda: {"n": 0, "dur_s": [], "bytes": 0})
    in_flight, h2d = [], []
    for ev in events:
        s, e = trace_reduce._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  lo, hi)
        if e <= s:
            continue
        sp = spans[ev.name]
        sp["n"] += 1
        sp["dur_s"].append((e - s) / 1e9)
        sp["bytes"] += int(dict(ev.stats).get("bytes", 0))
        if ev.name in IN_FLIGHT:
            in_flight.append((s, e))
        if ev.name == "engine.dispatch":
            # the copies this jit call started, on whichever thread
            i = bisect.bisect_left(copy_starts, ev.start_ns)
            j = bisect.bisect_right(copy_starts, ev.start_ns + ev.duration_ns)
            ends = [copy_done.get(p) for _, p in copies[i:j]]
            if ends and None not in ends:
                h2d.append((max(ends) - ev.start_ns) / 1e9)
    inflight_idle = None
    if in_flight and ops:
        busy = trace_reduce._union(
            trace_reduce._clip(s, e, lo, hi) for s, e in ops[min(ops)]
            if min(e, hi) > max(s, lo))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        inflight_idle = sum(e - s for s, e in _intersect(
            idle, trace_reduce._union(in_flight))) / 1e9
    return {"spans": dict(spans), "h2d_s": h2d,
            "inflight_idle_s": inflight_idle, "window_s": (hi - lo) / 1e9}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    from jax.profiler import ProfileData

    return reduce_spans(ProfileData.from_file(path))


def of_record(rec) -> dict | None:
    """The span reduction of the run's traced window: the newest trace
    under ``TRACE_DIR``, read once and shared by every reader; None for
    a run that was not traced."""
    if not rec.get("trace"):
        return None
    path = trace_reduce.find_xplane(TRACE_DIR)
    return _reduce_file(str(path), path.stat().st_mtime_ns)


def span_of(rec, name: str) -> dict | None:
    """Span ``name``'s entry in the run's reduction, or None when the
    run was not traced or the program recorded no such span."""
    r = of_record(rec)
    sp = r["spans"].get(name) if r else None
    return sp if sp and sp["n"] else None
