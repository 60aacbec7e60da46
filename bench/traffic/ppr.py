"""Personalized queries, as batches of preference sets drawn from the seed.

A query is a preference set of `seeds_min` to `seeds_max` pages (the
count uniform), drawn distinct and in proportion to in-degree + 1: users
prefer popular pages (Jeh & Widom, "Scaling Personalized Web Search",
WWW 2003; Gupta et al., "WTF: The Who to Follow Service at Twitter",
WWW 2013). A batch is `batch` queries, each asking for the L1 tolerance
`tol`; one batch is in flight at a time.

The stream is drawn from the mix's `stream_seed` on the graph in its
generator's page ids, and relabelled as the graph was: every run seed
gets the same queries, and so the same work, in its own page ids.
"""
from __future__ import annotations

import numpy as np


class Queries:
    def __init__(self, mix: dict, graph):
        n = self.n = graph.n
        self.perm = (np.arange(n, dtype=np.int64) if graph.perm is None
                     else graph.perm)
        # generated page i is page perm[i] of the run's graph
        self.cdf = np.cumsum(np.bincount(graph.dst, minlength=n)[self.perm]
                             + 1.0)
        self.batch = int(mix["batch"])
        self.lo, self.hi = int(mix["seeds_min"]), int(mix["seeds_max"])
        self.rng = np.random.default_rng(mix["stream_seed"])

    def __iter__(self):
        return self

    def __next__(self) -> list:
        """One batch: a sorted array of page ids per query."""
        return [self.query() for _ in range(self.batch)]

    def query(self) -> np.ndarray:
        rng = self.rng
        k = int(rng.integers(self.lo, self.hi + 1))
        pages = []
        while len(pages) < k:
            # a page drawn again is drawn anew: the rest stay in proportion
            i = int(np.searchsorted(self.cdf, rng.random() * self.cdf[-1],
                                    side="right"))
            if i not in pages:
                pages.append(i)
        return np.sort(self.perm[pages])


def make(mix: dict, graph, seed: int) -> Queries:
    """The run's seed acts through the graph's relabelling alone."""
    return Queries(mix, graph)
