"""Share of the batches' roofline that the Pallas BSR kernel reaches, in
%: the least bytes of the window's batches over the peak HBM bandwidth,
over the kernel's device seconds (the self times of the traced operations
named `bsr_spmv` or `bsr_spmv.<n>`, per chip). A batch's least bytes are
the CSR work model's (workmodel.py) summed over its iterations t, each at
the a_t lanes that ran at least t iterations (`lane_iters`): frozen lanes
cost nothing. Nothing without a traced device, or where no kernel ran."""
import re

from workmodel import csr_apply_bytes

KERNEL_OP = re.compile(r"^bsr_spmv(\.\d+)?$")


def least_bytes(n: int, nnz: int, lane_iters, itemsize: int) -> int:
    li = list(lane_iters)
    return sum(csr_apply_bytes(n, nnz, sum(i >= t for i in li), itemsize)
               for t in range(1, max(li, default=0) + 1))


def read(run):
    tr, peak = run.get("trace"), run.get("peaks")
    if not tr or tr["busy_s"] <= 0 or not peak:
        return None
    kernel_s = sum(s for name, s in tr["breakdown"]["device_ops"]
                   if KERNEL_OP.match(name)) / max(tr["devices"], 1)
    if kernel_s <= 0:
        return None
    n, nnz = run["graph"]["n"], run["graph"]["nnz"]
    total = sum(least_bytes(n, nnz, c["lane_iters"], run["work"]["itemsize"])
                for c in run["counters"])
    return 100.0 * total / peak["hbm_bytes_per_s"] / kernel_s
