"""A graph as the benchmark holds it: n pages and a sorted, duplicate-free
list of links (src -> dst). Generators make it, the traffic mutates it,
the plain reference reads it, and the program is handed its CSR form."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    n: int
    src: np.ndarray       # int64, sorted by (src, dst)
    dst: np.ndarray       # int64
    # the relabelling that made this graph: page i of the generated graph
    # is page perm[i] here (None: not relabelled)
    perm: Optional[np.ndarray] = None

    @property
    def nnz(self) -> int:
        return int(self.src.size)

    @staticmethod
    def from_pairs(n: int, src, dst) -> "Graph":
        """Duplicates collapse to one link; self-links are kept."""
        key = np.unique(np.asarray(src, np.int64) * n
                        + np.asarray(dst, np.int64))
        return Graph.from_keys(n, key)

    @staticmethod
    def from_keys(n: int, key: np.ndarray) -> "Graph":
        return Graph(n=n, src=key // n, dst=key % n)

    def keys(self) -> np.ndarray:
        return self.src * self.n + self.dst

    def relabel(self, perm: np.ndarray) -> "Graph":
        """Page i becomes page perm[i]."""
        g = Graph.from_pairs(self.n, perm[self.src], perm[self.dst])
        total = perm if self.perm is None else perm[self.perm]
        return dataclasses.replace(g, perm=total)

    def indptr(self) -> np.ndarray:
        counts = np.bincount(self.src, minlength=self.n)
        out = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(counts, out=out[1:])
        return out

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)
