"""build_us (us): mean duration of the engine's ``engine.build_result``
span a request, unpadding one row into its RankResult, over the traced
window (span_reduce)."""

from benchmarks.onchip import span_reduce


def read(rec):
    sp = span_reduce.span_of(rec, "engine.build_result")
    return 1e6 * sum(sp["dur_s"]) / sp["n"] if sp else None
