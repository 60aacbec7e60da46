"""Streaming-update benchmark: us-per-delta-batch and frontier size as a
function of delta size on a 50k-node power-law graph, plus the replay
scenario's freshness-vs-throughput summary.

The interesting curve is the push/fallback crossover: tiny deltas should be
orders of magnitude cheaper than a cold solve (visiting a small fraction of
the graph), while large deltas degrade gracefully into the warm-started
backend solver.
"""
from __future__ import annotations

import time

import numpy as np

from repro.graph.generate import powerlaw_webgraph
from repro.streaming import (DeltaGraph, EdgeDelta, ReplayConfig, cold_state,
                             replay_trace, synth_edge_trace, update_ranks)

N, NNZ = 50_000, 400_000
DELTA_SIZES = (1, 8, 64, 512, 4096)


def _random_delta(dg: DeltaGraph, k: int, rng) -> EdgeDelta:
    """k-edge batch: 85% inserts (uniform src, popularity-biased dst),
    15% deletes of existing edges."""
    g = dg.graph()
    n_del = k * 15 // 100
    n_add = k - n_del
    a_src = rng.integers(0, dg.n, size=n_add)
    a_dst = g.indices[rng.integers(0, g.nnz, size=n_add)].astype(np.int64)
    if n_del:
        slots = rng.choice(g.nnz, size=n_del, replace=False)
        src_of_edge = np.repeat(np.arange(g.n, dtype=np.int64),
                                np.diff(g.indptr))
        d_src, d_dst = src_of_edge[slots], g.indices[slots].astype(np.int64)
    else:
        d_src = d_dst = np.empty(0, np.int64)
    return EdgeDelta(add_src=np.asarray(a_src, np.int64), add_dst=a_dst,
                     del_src=d_src, del_dst=d_dst)


def delta_sweep(tol: float = 1e-5, seed: int = 4, repeats: int = 3):
    """us per delta batch + push-frontier stats vs batch size."""
    g = powerlaw_webgraph(n=N, target_nnz=NNZ, n_dangling=50, seed=seed)
    dg = DeltaGraph(g)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    state = cold_state(dg, tol=tol)
    cold_s = time.perf_counter() - t0
    rows = []
    for k in DELTA_SIZES:
        times, stats_list = [], []
        for _ in range(repeats):
            d = _random_delta(dg, k, rng)
            t0 = time.perf_counter()
            state, stats = update_ranks(dg, d, state, tol=tol)
            times.append(time.perf_counter() - t0)
            stats_list.append(stats)
        med = float(np.median(times))
        s = stats_list[np.argsort(times)[len(times) // 2]]
        rec = dict(
            delta_edges=k, us_per_batch=med * 1e6,
            us_per_edge=med * 1e6 / k, path=s.path, pushes=s.pushes,
            nodes_visited=s.nodes_visited,
            visited_frac=s.nodes_visited / dg.n,
            frontier_peak=s.frontier_peak, cert=s.cert,
            speedup_vs_cold=cold_s / med,
        )
        rows.append(rec)
        print(f"  delta={k:5d} edges: {med * 1e3:8.1f} ms/batch "
              f"[{s.path:12s}] visited={s.nodes_visited:6d} "
              f"({100 * rec['visited_frac']:5.2f}%) "
              f"frontier_peak={s.frontier_peak:6d} "
              f"{rec['speedup_vs_cold']:6.1f}x vs cold")
    return dict(n=N, nnz=NNZ, tol=tol, cold_solve_s=cold_s, sweep=rows)


def replay_bench(n_batches: int = 24, batch_edges: int = 2,
                 seed: int = 5):
    """Freshness-vs-throughput under the DES replay clock (Table-2 mirror:
    fresh-serve percentages instead of completed-import percentages).
    Small batches keep the updater on the push path — the regime the
    update-while-serve design targets; the delta sweep above maps where
    that regime ends."""
    g = powerlaw_webgraph(n=N, target_nnz=NNZ, n_dangling=50, seed=seed)
    dg = DeltaGraph(g)
    state = cold_state(dg, tol=1e-5)
    trace = synth_edge_trace(dg, n_batches=n_batches,
                             batch_edges=batch_edges, seed=seed)
    cfg = ReplayConfig(query_rate=500.0, delta_interval=0.25, tol=1e-5,
                       seed=seed)
    t0 = time.perf_counter()
    res = replay_trace(dg, state, trace, cfg)
    wall = time.perf_counter() - t0
    push_batches = sum(1 for r in res.rows if r.path == "push")
    rec = dict(
        n=N, batches=n_batches, batch_edges=batch_edges,
        fresh_pct=res.fresh_pct, mean_age_s=res.mean_age_s,
        p95_age_s=res.p95_age_s, mean_lag_batches=res.mean_lag_batches,
        busy_frac=res.busy_frac, us_per_delta_edge=res.us_per_delta_edge,
        deltas_per_s=res.deltas_per_s, push_batches=push_batches,
        wall_s=wall,
    )
    print(f"  replay: fresh={res.fresh_pct:.1f}% "
          f"mean_age={res.mean_age_s * 1e3:.0f}ms "
          f"p95={res.p95_age_s * 1e3:.0f}ms busy={res.busy_frac:.2f} "
          f"{res.deltas_per_s:.1f} deltas/s "
          f"({push_batches}/{n_batches} push-path)")
    return rec


def main():
    print("  [streaming] delta sweep ...")
    sweep = delta_sweep()
    print("  [streaming] replay ...")
    replay = replay_bench()
    return dict(bench="streaming incremental updates (PR 2)",
                delta_sweep=sweep, replay=replay)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
