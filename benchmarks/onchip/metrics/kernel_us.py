"""kernel_us (us): device time of the Pallas kernels per executed step,
from the trace."""


def read(rec):
    t = rec["trace"]
    if not t or not t["modules"] or not t["kernel_s"]:
        return None
    return 1e6 * sum(t["kernel_s"].values()) / t["modules"]
