"""inflight_idle_pct (%): share of the traced window in which device 0
ran no op while a batch was between launch and home (inside an
``engine.dispatch``, ``pipeline.device_wait`` or ``pipeline.d2h`` span;
span_reduce). The rest of ``device_idle`` had no batch in flight."""

from benchmarks.onchip import span_reduce


def read(rec):
    r = span_reduce.of_record(rec)
    if not r or r["inflight_idle_s"] is None or not r["window_s"]:
        return None
    return 100.0 * r["inflight_idle_s"] / r["window_s"]
