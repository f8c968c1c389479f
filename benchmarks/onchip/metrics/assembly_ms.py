"""assembly_ms (ms): mean host time to stage a batch (fill_staging
through launch) over the window's batches."""

import numpy as np


def read(rec):
    xs = [b["assembly_ms"] for b in rec["batches"]]
    return float(np.mean(xs)) if xs else None
