"""padding_ratio (ratio): padded over real rank+audit flops of the
window's batches (the engine's padding_summary()['waste_flops'])."""


def read(rec):
    p = rec["padding"]
    if not p["real_flops"]:
        return None
    return p["padded_flops"] / p["real_flops"]
