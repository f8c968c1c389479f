"""h2d_mb (MB): mean ``bytes`` stat of the engine's ``engine.dispatch``
span a batch, the staged host arrays its jit call copies to the device,
over the traced window (span_reduce)."""

from benchmarks.onchip import span_reduce


def read(rec):
    sp = span_reduce.span_of(rec, "engine.dispatch")
    return sp["bytes"] / sp["n"] / 1e6 if sp else None
