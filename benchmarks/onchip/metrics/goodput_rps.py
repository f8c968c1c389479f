"""goodput_rps (req/s): requests answered within the budget (50 ms) of
their scheduled arrival, over the window's seconds."""


def read(rec):
    return rec["ok_in_budget"] / rec["seconds"]
