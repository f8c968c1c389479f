"""Work of one whole served step (one bucket executable per batch):
the predictor family's kernel work, whichever executor ran it, so a
change that removes or replaces a kernel is still held to the same
count."""

from benchmarks.onchip.layout import work_counter

ROUTES = {"knn": "knn_rank_audited"}


def count(shape: dict) -> dict:
    return work_counter(ROUTES[shape["predictor"]])(shape)
