"""Share of the Google apply's roofline, in %: the least time of one
apply by the CSR work model (workmodel.py) over the device time per
iteration, which is the device's busy time in the traced window over the
iterations the window's solves ran. Nothing without a traced device."""
from workmodel import least_apply_s


def read(run):
    tr, peak = run.get("trace"), run.get("peaks")
    iters = sum(c["iters"] for c in run["counters"])
    if not tr or tr["busy_s"] <= 0 or not peak or not iters:
        return None
    w = run["work"]
    least = least_apply_s(run["graph"]["n"], run["graph"]["nnz"], w["nv"],
                          w["itemsize"], peak)
    return 100.0 * least / (tr["busy_s"] / iters)
