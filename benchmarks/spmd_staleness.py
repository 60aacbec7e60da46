"""Beyond-paper: TPU-native bounded-staleness schedules (core.spmd) —
supersteps-to-convergence vs per-step collective bytes, run on 8 forced
host devices in a subprocess (the bench process keeps 1 device)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

RESULTS = Path(__file__).parent / "results"

_CODE = r"""
import json
import jax
import numpy as np
from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT
from repro.graph.google import GoogleOperator, exact_pagerank
from repro.core import SPMDConfig, solve_spmd

g = powerlaw_webgraph(n=16384, target_nnz=131072, n_dangling=32, seed=2)
op = GoogleOperator(pt=TransitionT.from_graph(g), alpha=0.85)
xref = exact_pagerank(op, tol=1e-13)
rows = []
for sched, kw in [("allgather", {}),
                  ("allgather_k", dict(sync_every=2)),
                  ("allgather_k", dict(sync_every=4)),
                  ("allgather_k", dict(sync_every=8)),
                  ("ring", {}),
                  ("ring", dict(delivery_prob=0.7))]:
    cfg = SPMDConfig(p=8, schedule=sched, tol=1e-8, dtype="float32",
                     max_supersteps=5000, **kw)
    r = solve_spmd(op, cfg)
    err = float(np.abs(r.x - xref).max())
    total = r.comm_bytes_per_step * r.supersteps
    rows.append(dict(schedule=sched, **kw, supersteps=r.supersteps,
                     platform=jax.devices()[0].platform, err=err, bytes_per_step=r.comm_bytes_per_step,
                     total_comm_bytes=total))
print(json.dumps(rows))
"""


def main():
    env = dict(os.environ)
    # CPU forced-device experiment: the child must never reach a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", _CODE], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "spmd_staleness.json").write_text(json.dumps(rows, indent=1))
    base = next(r for r in rows if r["schedule"] == "allgather")
    for r in rows:
        rel = r["total_comm_bytes"] / base["total_comm_bytes"]
        print(f"  {r['schedule']:12s} {str(r.get('sync_every', '')):3s} "
              f"q={r.get('delivery_prob', 1.0):<4} steps={r['supersteps']:4d} "
              f"err={r['err']:.1e} bytes/step={r['bytes_per_step']:>9d} "
              f"total={r['total_comm_bytes']:>12d} ({rel:.2f}x baseline)")
    return rows


if __name__ == "__main__":
    main()
