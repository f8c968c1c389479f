"""d2h_ms (ms): mean duration of the engine's ``pipeline.d2h`` span a
batch, the completion worker's copy of the outputs home after the device
finished, over the traced window (span_reduce)."""

from benchmarks.onchip import span_reduce


def read(rec):
    sp = span_reduce.span_of(rec, "pipeline.d2h")
    return 1e3 * sum(sp["dur_s"]) / sp["n"] if sp else None
