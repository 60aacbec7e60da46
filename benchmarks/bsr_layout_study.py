"""BSR layout study (paper §6 future work: permutations, cf. [11]).

Quantifies block fill / K-budget / arithmetic intensity for the TPU SpMV
under orderings (natural site-local, RCM, degree-sort), block sizes, and
hub-row splitting — the data behind EXPERIMENTS.md §Kernels' design rule:

  * web SpMV is HBM-bound at any layout (AI << v5e ridge);
  * the gather/segment-sum form is the right single-vector path;
  * BSR + hub-split + 32x32 + multi-vector (personalization) is the only
    compute-dense regime.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT
from repro.graph.reorder import (rcm_permutation, degree_sort_permutation,
                                 apply_permutation)

RESULTS = Path(__file__).parent / "results"


def layout_stats(pt: TransitionT, bm: int, hub_quantile: float = 0.99):
    indeg = np.diff(pt.indptr)
    hub_cut = np.quantile(indeg, hub_quantile)
    hubs = indeg > hub_cut
    keep = ~hubs[pt.row_ids]
    nbc = pt.n // bm + 1
    br = pt.row_ids[keep] // bm
    bc = pt.src[keep] // bm
    uniq, _ = np.unique(br.astype(np.int64) * nbc + bc, return_counts=True)
    per_row = np.bincount((uniq // nbc).astype(int))
    nnz_kept = int(keep.sum())
    fill = nnz_kept / (len(uniq) * bm * bm)
    return dict(bm=bm, hub_nnz_frac=float(indeg[hubs].sum() / max(len(pt.src), 1)),
                k_max=int(per_row.max()), k_mean=float(per_row.mean()),
                fill=float(fill),
                # bytes per useful flop: dense blocks f32 vs csr (4+4+4)/nnz
                bsr_bytes_per_nnz=float(bm * bm * 4 / max(fill * bm * bm, 1e-9)),
                csr_bytes_per_nnz=12.0)


def main(n=16384, nnz=131072):
    g = powerlaw_webgraph(n=n, target_nnz=nnz, n_dangling=16, seed=4)
    rows = []
    for tag, perm_fn in [("natural", None), ("rcm", rcm_permutation),
                         ("degree", degree_sort_permutation)]:
        gg = g if perm_fn is None else apply_permutation(g, perm_fn(g))
        pt = TransitionT.from_graph(gg)
        for bm in (32, 128):
            st = dict(order=tag, **layout_stats(pt, bm))
            rows.append(st)
            print(f"  {tag:8s} bm={bm:3d} K_max={st['k_max']:4d} "
                  f"K_mean={st['k_mean']:6.1f} fill={st['fill']:.4f} "
                  f"BSR B/nnz={st['bsr_bytes_per_nnz']:.0f} (csr 12)")
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "bsr_layout.json").write_text(json.dumps(rows, indent=1))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
