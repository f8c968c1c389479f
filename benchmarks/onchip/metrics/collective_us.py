"""collective_us (us): device time of the collective ops (the cross-chip
merge's all-gathers and any other all-reduce, collective-permute,
all-to-all or reduce-scatter, with the start and done halves of their
asynchronous forms) per executed step, as the mean over the chips."""


def read(rec):
    t = rec["trace"]
    if not t or not t["modules"] or not t.get("collective_s"):
        return None
    return 1e6 * t["collective_s"] / t["modules"]
