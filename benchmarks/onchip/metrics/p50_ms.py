"""p50_ms (ms): median of the same sample as p99_ms."""

import numpy as np


def read(rec):
    return float(np.percentile(rec["lat_ms"], 50))
