"""Power-law web graph with site locality: the Stanford-Web replica.

`powerlaw_webgraph` is the repository's generator
(`repro.graph.generate.powerlaw_webgraph`) copied here so that the
benchmark's graph cannot move with the program: out-degrees from a
truncated zeta, link targets from a Zipf popularity ranking, a share of
links kept inside the source's site of consecutive pages.

`generate` makes the configuration's graph from one fixed generation seed
and then relabels it by the run's seed: whole sites trade places, the
pages inside a site keep their order, and the last partial site stays.
Every seed therefore gets the same graph up to a relabelling, with the same
block structure and the same work, and different page ids.
"""
from __future__ import annotations

import numpy as np

from edges import Graph


def powerlaw_webgraph(n: int, target_nnz: int, n_dangling: int = 0,
                      alpha_out: float = 2.2, alpha_in: float = 2.1,
                      locality: float = 0.8, site_size: int = 512,
                      seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)

    n_linked = n - n_dangling
    deg = rng.zipf(alpha_out, size=n_linked).astype(np.int64)
    deg = np.minimum(deg, 1000)
    scale = target_nnz / max(deg.sum(), 1)
    if scale > 1.0:
        extra = rng.multinomial(target_nnz - deg.sum(),
                                np.ones(n_linked) / n_linked)
        deg = deg + extra
    else:
        deg = np.maximum((deg * scale).astype(np.int64), 1)
    diff = int(target_nnz - deg.sum())
    if diff != 0:
        idx = rng.choice(n_linked, size=abs(diff), replace=True)
        np.add.at(deg, idx, 1 if diff > 0 else -1)
        deg = np.maximum(deg, 1)
    nnz = int(deg.sum())

    perm = rng.permutation(n)
    src_linked = np.repeat(np.arange(n_linked, dtype=np.int64), deg)
    node_perm = rng.permutation(n)
    src = node_perm[src_linked]

    def draw_dst(k, src_ids):
        ranks = (rng.zipf(alpha_in, size=k).astype(np.int64) - 1) % n
        global_dst = perm[ranks].astype(np.int64)
        if locality <= 0.0:
            return global_dst
        local = rng.random(k) < locality
        site_start = (src_ids // site_size) * site_size
        local_dst = site_start + rng.integers(0, site_size, size=k)
        local_dst = np.minimum(local_dst, n - 1)
        return np.where(local, local_dst, global_dst)

    dst = draw_dst(nnz, src)
    key = src * n + dst
    for _ in range(40):
        order = np.argsort(key, kind="stable")
        key_sorted = key[order]
        dup_sorted = np.zeros(nnz, dtype=bool)
        dup_sorted[1:] = key_sorted[1:] == key_sorted[:-1]
        dup = np.zeros(nnz, dtype=bool)
        dup[order] = dup_sorted
        ndup = int(dup.sum())
        if ndup == 0:
            break
        new_dst = draw_dst(ndup, src[dup])
        uni = rng.random(ndup) < 0.5
        new_dst[uni] = rng.integers(0, n, size=int(uni.sum()))
        dst[dup] = new_dst
        key[dup] = src[dup] * n + dst[dup]

    return Graph.from_pairs(n, src, dst)


def site_permutation(n: int, site_size: int, seed: int) -> np.ndarray:
    """Page relabelling that moves whole sites and keeps the last partial
    one in place."""
    sites = n // site_size
    order = np.random.default_rng(seed).permutation(sites)
    perm = np.arange(n, dtype=np.int64)
    head = np.arange(sites * site_size, dtype=np.int64)
    perm[:head.size] = order[head // site_size] * site_size \
        + head % site_size
    return perm


def generate(params: dict, seed: int) -> Graph:
    g = powerlaw_webgraph(
        n=params["n"], target_nnz=params["target_nnz"],
        n_dangling=params["n_dangling"], alpha_out=params["alpha_out"],
        alpha_in=params["alpha_in"], locality=params["locality"],
        site_size=params["site_size"], seed=params["generation_seed"])
    return g.relabel(site_permutation(g.n, params["site_size"], seed))
