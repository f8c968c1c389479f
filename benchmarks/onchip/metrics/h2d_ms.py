"""h2d_ms (ms): mean time from a batch's jit call to the moment its
staged host arrays are on the device: the TPU runtime's done event of
the call's last host-to-device transfer, a batch, over the traced window
(span_reduce)."""

from benchmarks.onchip import span_reduce


def read(rec):
    r = span_reduce.of_record(rec)
    xs = r["h2d_s"] if r else None
    return 1e3 * sum(xs) / len(xs) if xs else None
