"""launch_to_done_ms (ms): mean time from a batch's launch to its
outputs on the host (device, D2H and the wait behind earlier batches)."""

import numpy as np


def read(rec):
    xs = [b["launch_to_done_ms"] for b in rec["batches"]
          if np.isfinite(b["launch_to_done_ms"])]
    return float(np.mean(xs)) if xs else None
