"""compliant_share (%): requests whose served page meets every exposure
floor, over all attempted; shed and failed requests are not compliant."""


def read(rec):
    return 100.0 * rec["compliant"] / rec["attempted"]
