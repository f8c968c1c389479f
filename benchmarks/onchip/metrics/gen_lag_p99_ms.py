"""gen_lag_p99_ms (ms): 99th percentile of how late the client
submitted each window request after its scheduled arrival."""

import numpy as np


def read(rec):
    return float(np.percentile(rec["gen_lag_ms"], 99))
