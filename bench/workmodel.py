"""The work of one application of the Google matrix, whatever implements it.

One apply of G = alpha (P^T + w d^T) + (1 - alpha) v e^T to nv vectors, as
CSR PageRank does it, reads the link structure once and the vectors once:

    bytes = 4 nnz              column index of every link (int32), no
                               stored values: a link's weight is the
                               inverse out-degree of its source
          + 4 (n + 1)          row pointers (int32)
          + w n                inverse out-degrees
          + 3 w n nv           read x and v, write y

with w the bytes of one value (4 for float32). Its 2 nnz nv operations are
far below what the chip computes in the time those bytes take, so memory
bounds it: the least time is bytes over the peak HBM bandwidth. The count
does not change with the layout (blocks, padding, hub split) that a
program picks, so a layout that moves fewer bytes shows as a gain.
"""
from __future__ import annotations


def csr_apply_bytes(n: int, nnz: int, nv: int = 1, itemsize: int = 4) -> int:
    return 4 * nnz + 4 * (n + 1) + itemsize * n + 3 * itemsize * n * nv


def csr_apply_flops(nnz: int, nv: int = 1) -> int:
    return 2 * nnz * nv


def least_apply_s(n: int, nnz: int, nv: int, itemsize: int,
                  peak: dict) -> float:
    """The larger of bytes over HBM bandwidth and operations over the
    peak rate: the bandwidth term, for any graph with a link per page."""
    return max(csr_apply_bytes(n, nnz, nv, itemsize) / peak["hbm_bytes_per_s"],
               csr_apply_flops(nnz, nv) / peak["flops_per_s"])
