"""step_mfu (%): the whole served step's least time (work/step.py at the
window's bucket shapes, at the peaks of all the chips used) over the
device time of its executable, per step."""


def read(rec):
    t, w = rec["trace"], rec["work"].get("step")
    if not t or not w or not w["batches"] or not t["modules"]:
        return None
    least = w["least_s"] / w["batches"] / rec["chips"]
    return 100.0 * least / (t["module_s"] / t["modules"])
