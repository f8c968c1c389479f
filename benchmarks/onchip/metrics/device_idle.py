"""device_idle (%): share of the traced window in which no op ran on the
device, averaged over the chips used."""


def read(rec):
    t = rec["trace"]
    if not t or not t["window_s"] or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
