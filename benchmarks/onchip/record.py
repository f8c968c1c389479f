"""The run's record: what the client saw per request and what the
engine's metrics saw per batch, cut to the measured window. Every
metric reader (``metrics/<name>.py``) reads one of these dicts.

Keys:
  seconds, window_ms, budget_ms, chips, max_batch, setup_s
  attempted, served, shed, failed, degraded, compliant, ok_in_budget
  lat_ms       (attempted,) scheduled arrival -> answer received; a shed
               or failed request enters at the window's length
  gen_lag_ms   (attempted,) scheduled arrival -> submit
  wait_ms      (served,) RankResult.wait_ms (enqueue -> batch launch)
  batches      per batch launched in the window: bucket, predictor,
               real rows, assembly_ms, launch_to_done_ms
  padding      real / padded rank+audit flops of those batches
  work         per batch, the work/ counts of its kernel and of its
               whole step, with their least times at the chip's peaks
  trace        trace_reduce's reduction of the traced window, or None
  peaks        the chip's peaks (peaks.json)
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.serving import RankResult, Shed
from repro.serving.metrics import EngineMetrics


@dataclass
class RecordingMetrics(EngineMetrics):
    """The engine's metrics, also keeping the bucket, size and clock of
    every launched batch, in launch order (batches retire in launch
    order, so ``exec_ms[i]`` belongs to ``launched[i]``)."""

    launched: list = field(default_factory=list)

    def on_dispatch(self, bucket, n_real, trigger, fill, **kw):
        super().on_dispatch(bucket, n_real, trigger, fill, **kw)
        self.launched.append((kw["t_now"], bucket, int(n_real),
                              float(kw["assembly_ms"])))


def least_time(work: dict, peaks: dict) -> float:
    """Seconds the chip needs at least: operations at peak FLOP/s or
    bytes at peak bandwidth, whichever is longer."""
    return max(work["flops"] / peaks["bf16_flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])


def batch_shape(bucket, *, predictor: str, d: int, n_db: int, k: int,
                k_pred: int, widths: dict) -> dict:
    """A batch's shape as the work/ counts take it: the bucket's sizes,
    the λ-database's, and the configuration's published ``widths``."""
    return {"B": bucket.batch, "m1": bucket.m1, "m2": bucket.m2,
            "K": bucket.K, "d": d, "n_db": n_db, "k": k, "k_pred": k_pred,
            "predictor": predictor, "widths": widths}


def build_record(drive, plan, metrics: RecordingMetrics, *,
                 t_open: float, t_close: float, budget_ms: float,
                 setup_s: float, chips: int, max_batch: int,
                 predictor_of, shape_of, routes: dict, counters: dict,
                 peaks: dict, padding: dict, trace=None) -> dict:
    """``predictor_of(bucket)`` names a batch's predictor family,
    ``shape_of(bucket, predictor)`` its work shape, ``routes`` maps a
    family to its kernel's work name and ``counters`` work names to
    their count functions. ``padding`` is the engine's own real /
    padded flops accounting over the window (EngineMetrics deltas)."""
    win = plan.window
    window_ms = plan.window_s * 1e3
    t_due, t_sub, t_recv = (drive.t_due[win], drive.t_sub[win],
                            drive.t_recv[win])
    answers = drive.answers[win]
    lat = (t_recv - t_due) * 1e3
    served = np.array([isinstance(a, RankResult) for a in answers])
    shed = np.array([isinstance(a, Shed) for a in answers])
    failed = ~(served | shed)
    lat = np.where(served, lat, window_ms)
    compliant = sum(1 for a, s in zip(answers, served) if s and a.compliant)
    degraded = sum(1 for a, s in zip(answers, served) if s and a.rung > 0)
    wait = np.array([a.wait_ms for a, s in zip(answers, served) if s])
    in_win = [i for i, (t, *_rest) in enumerate(metrics.launched)
              if t_open <= t < t_close]
    batches = []
    work_totals: dict = {}
    for i in in_win:
        _, bucket, n_real, asm = metrics.launched[i]
        pred = predictor_of(bucket)
        shape = shape_of(bucket, pred)
        kname = routes[pred]
        for name in ("step", kname):
            w = counters[name](shape)
            tot = work_totals.setdefault(name, {"flops": 0, "bytes": 0,
                                                "least_s": 0.0,
                                                "batches": 0})
            tot["flops"] += w["flops"]
            tot["bytes"] += w["bytes"]
            tot["least_s"] += least_time(w, peaks)
            tot["batches"] += 1
        ex = metrics.exec_ms[i] if i < len(metrics.exec_ms) else float("nan")
        batches.append({"bucket": bucket.name, "predictor": pred,
                        "rows": n_real, "assembly_ms": asm,
                        "launch_to_done_ms": ex})
    return {
        "seconds": plan.window_s, "window_ms": window_ms,
        "budget_ms": budget_ms, "chips": chips, "max_batch": max_batch,
        "setup_s": setup_s,
        "attempted": len(answers), "served": int(served.sum()),
        "shed": int(shed.sum()), "failed": int(failed.sum()),
        "degraded": degraded, "compliant": compliant,
        "ok_in_budget": int((served & (lat <= budget_ms)).sum()),
        "lat_ms": lat, "gen_lag_ms": (t_sub - t_due) * 1e3,
        "wait_ms": wait, "batches": batches,
        "padding": padding,
        "work": work_totals, "trace": trace, "peaks": peaks,
    }

