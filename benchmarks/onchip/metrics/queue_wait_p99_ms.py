"""queue_wait_p99_ms (ms): 99th percentile of RankResult.wait_ms, the
engine's enqueue to batch launch, over the window's served requests."""

import numpy as np


def read(rec):
    if len(rec["wait_ms"]) == 0:
        return None
    return float(np.percentile(rec["wait_ms"], 99))
