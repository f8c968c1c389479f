"""knn_rank_audited_roofline (%): the least time of the single-grid KNN
kernel's work (work/knn_rank_audited.py at the window's bucket shapes,
at the chip's peaks) over its measured device time, per execution."""


def read(rec):
    t, w = rec["trace"], rec["work"].get("knn_rank_audited")
    if not t or not w or not w["batches"]:
        return None
    kern = t["kernel_s"].get("knn_rank_audited")
    n = t["kernel_n"].get("knn_rank_audited")
    if not kern or not n:
        return None
    return 100.0 * (w["least_s"] / w["batches"]) / (kern / n)
