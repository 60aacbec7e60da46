"""Share of the Google apply's roofline that the Pallas BSR kernel
reaches, in %: the least time of one apply by the CSR work model
(workmodel.py) over the kernel's device seconds per iteration. The
kernel's seconds are the self times of the traced operations named
`bsr_spmv` or `bsr_spmv.<n>` (one per chunk of block rows) per chip,
over the iterations the window's solves ran. Nothing without a traced
device, or where no kernel ran."""
import re

from workmodel import least_apply_s

KERNEL_OP = re.compile(r"^bsr_spmv(\.\d+)?$")


def read(run):
    tr, peak = run.get("trace"), run.get("peaks")
    iters = sum(c["iters"] for c in run["counters"])
    if not tr or tr["busy_s"] <= 0 or not peak or not iters:
        return None
    kernel_s = sum(s for name, s in tr["breakdown"]["device_ops"]
                   if KERNEL_OP.match(name)) / max(tr["devices"], 1)
    if kernel_s <= 0:
        return None
    w = run["work"]
    least = least_apply_s(run["graph"]["n"], run["graph"]["nnz"], w["nv"],
                          w["itemsize"], peak)
    return 100.0 * least / (kernel_s / iters)
