"""A crawl's link churn, as batches of edge changes drawn from the seed.

Each batch changes `batch_frac` of the links: `insert_share` of them are
new links and the rest deletions of links that exist. A deletion picks a
link uniformly. An insertion picks its source page uniformly and its
target as the target of a uniformly chosen link (so popular pages gain
links in proportion to the links they have), and is never a link that
exists or one already in the batch. The stream tracks the graph itself,
so `keys` after each batch is the link set that batch produced.

The stream is drawn from the mix's `stream_seed` on the graph in its
generator's page ids, and relabelled as the graph was: every run seed
gets the same churn, and so the same work, in its own page ids.
"""
from __future__ import annotations

import numpy as np


class Churn:
    def __init__(self, mix: dict, graph):
        n = self.n = graph.n
        self.perm = (np.arange(n, dtype=np.int64) if graph.perm is None
                     else graph.perm)
        inv = np.empty_like(self.perm)
        inv[self.perm] = np.arange(n)
        self.base = np.sort(inv[graph.src] * n + inv[graph.dst])
        self.size = int(round(mix["batch_frac"] * graph.nnz))
        self.n_ins = int(round(mix["insert_share"] * self.size))
        self.rng = np.random.default_rng(mix["stream_seed"])

    @property
    def keys(self) -> np.ndarray:
        """The current links, sorted, in the graph's page ids."""
        n, p = self.n, self.perm
        return np.sort(p[self.base // n] * n + p[self.base % n])

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        rng, n, keys = self.rng, self.n, self.base
        n_del = self.size - self.n_ins
        gone = np.sort(rng.choice(keys.size, size=n_del, replace=False))
        del_keys = keys[gone]
        ins = np.empty(0, dtype=np.int64)
        while ins.size < self.n_ins:
            k = 2 * (self.n_ins - ins.size)
            src = rng.integers(0, n, size=k)
            dst = keys[rng.integers(0, keys.size, size=k)] % n
            cand = src * n + dst
            pos = np.minimum(np.searchsorted(keys, cand), keys.size - 1)
            cand = cand[keys[pos] != cand]
            _, first = np.unique(cand, return_index=True)
            cand = cand[np.sort(first)]
            ins = np.concatenate([ins, cand[~np.isin(cand, ins)]])
        ins = ins[:self.n_ins]
        kept = np.delete(keys, gone)
        ins_sorted = np.sort(ins)
        self.base = np.insert(kept, np.searchsorted(kept, ins_sorted),
                              ins_sorted)
        p = self.perm
        return dict(add_src=p[ins // n], add_dst=p[ins % n],
                    del_src=p[del_keys // n], del_dst=p[del_keys % n])


def make(mix: dict, graph, seed: int) -> Churn:
    """The run's seed acts through the graph's relabelling alone."""
    return Churn(mix, graph)
