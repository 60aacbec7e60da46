"""Sharded-runtime benchmark (PR 3): §6 sparsified exchange vs allgather.

Two measurements on the 50k-node power-law graph:

  * SPMD schedules (p=4 forced host devices, subprocess): bytes moved per
    superstep and in total for `allgather` vs `sparsified` at tol=1e-8 —
    the acceptance gate is sparsified <= 50% of allgather's total bytes;
  * the sharded streaming updater (p=4): a 1% edge delta drained with
    boundary-residual exchange under both plans, with the Fig. 1
    all-reduced certificate and modeled exchange bytes.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

RESULTS = Path(__file__).parent / "results"

_SPMD_CODE = r"""
import json
import jax
import numpy as np
from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT
from repro.graph.google import GoogleOperator, exact_pagerank
from repro.core import SPMDConfig, solve_spmd

g = powerlaw_webgraph(n=50_000, target_nnz=400_000, n_dangling=50, seed=3)
op = GoogleOperator(pt=TransitionT.from_graph(g), alpha=0.85)
xref = exact_pagerank(op, tol=1e-13)
rows = []
for sched, kw in [("allgather", {}),
                  ("sparsified", {}),
                  ("sparsified", dict(sparsify_refresh_every=32))]:
    cfg = SPMDConfig(p=4, schedule=sched, tol=1e-8, dtype="float32",
                     max_supersteps=4000, seed=3, **kw)
    r = solve_spmd(op, cfg)
    rows.append(dict(schedule=sched, **kw, supersteps=r.supersteps,
                     platform=jax.devices()[0].platform,
                     err=float(np.abs(r.x - xref).max()),
                     bytes_per_step=r.comm_bytes_per_step,
                     total_comm_bytes=r.comm_bytes_total,
                     rows_sent=r.rows_sent))
print(json.dumps(rows))
"""


def spmd_sparsified_bench():
    env = dict(os.environ)
    # CPU forced-device experiment: the child must never reach a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", _SPMD_CODE], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    base = next(r for r in rows if r["schedule"] == "allgather")
    for r in rows:
        rel = r["total_comm_bytes"] / base["total_comm_bytes"]
        print(f"  {r['schedule']:11s} R={r.get('sparsify_refresh_every', '-'):>3} "
              f"steps={r['supersteps']:4d} err={r['err']:.1e} "
              f"bytes/step={r['bytes_per_step']:>9d} "
              f"total={r['total_comm_bytes']:>11d} ({rel:.2f}x allgather)")
        r["vs_allgather"] = rel
    return rows


def sharded_stream_bench():
    from repro.graph.generate import powerlaw_webgraph
    from repro.streaming import DeltaGraph, EdgeDelta, cold_state, \
        update_ranks_sharded

    g = powerlaw_webgraph(n=50_000, target_nnz=400_000, n_dangling=50,
                          seed=3)
    rng = np.random.default_rng(31)
    k = g.nnz // 100
    n_del = k * 15 // 100
    slots = rng.choice(g.nnz, size=n_del, replace=False)
    soe = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
    delta = EdgeDelta(
        add_src=rng.integers(0, g.n, k - n_del),
        add_dst=g.indices[rng.integers(0, g.nnz, k - n_del)].astype(np.int64),
        del_src=soe[slots], del_dst=g.indices[slots].astype(np.int64))

    rows = []
    for exchange in ("allgather", "sparsified"):
        dg = DeltaGraph(g)
        st = cold_state(dg, tol=5e-7)
        t0 = time.perf_counter()
        st, stats = update_ranks_sharded(dg, delta, st, p=4, tol=8e-7,
                                         exchange=exchange)
        dt = time.perf_counter() - t0
        rows.append(dict(exchange=exchange, path=stats.path, s=dt,
                         supersteps=stats.supersteps, pushes=stats.pushes,
                         exchanges=stats.exchanges,
                         bytes_moved=stats.bytes_moved,
                         cert=stats.cert,
                         stop_superstep=stats.stop_superstep))
        print(f"  sharded[{exchange:11s}] {dt:6.1f}s "
              f"steps={stats.supersteps:3d} pushes={stats.pushes} "
              f"bytes={stats.bytes_moved} cert={stats.cert:.2e}")
    return rows


def main():
    print("  [shard] SPMD sparsified-vs-allgather (50k, 4 host devices)...")
    spmd_rows = spmd_sparsified_bench()
    print("  [shard] sharded streaming updater (50k, 1% delta, p=4) ...")
    stream_rows = sharded_stream_bench()
    rec = dict(bench="sharded runtime: sparsified vs allgather (PR 3)",
               spmd=spmd_rows, sharded_stream=stream_rows)
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "shard_bench.json").write_text(json.dumps(rec, indent=1))
    return rec


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
