"""Async shard executor benchmark (PRs 4/5): async vs superstep drains,
threads vs procpool transports.

Workload: the 50k-node power-law graph with a 1% edge delta (the
acceptance workload of PRs 2/3), drained to tol=1e-8 by
`update_ranks_sharded` at p = 1, 2, 4, 8.

Measurement regimes:

  raw
      Plain wall-clock of the numpy drains.  On small-core containers
      this measures numpy's GIL behavior as much as the executor (most of
      the drain kernel — gathers, bincount, repeat — holds the GIL), so
      it is reported for the record, not as the scaling claim.  PR 5 adds
      ``transport="procpool"`` rows (p = 1..cores and p=4): worker
      *processes* over a shared-memory ShardArena, where the same numpy
      drains no longer share a GIL.

  drain_dominated (sleep)
      The paper's regime: local computation dominates communication.
      Each shard's drain is given a *calibrated* per-push compute cost
      (``DRAIN_RATE`` pushes/s, the same modeled-clock methodology as
      `streaming/scenario.py`'s replay), implemented as a sleep after the
      real sweep — sleeps release the GIL completely, so worker threads
      overlap exactly as heavier real drains would on dedicated cores.
      p=4 async >= 1.5x p=1 async is the PR 4 acceptance gate
      (``speedup_p4_vs_p1_async``).

  drain_dominated_burn (PR 5 acceptance regime)
      The same calibrated per-push cost, but as *real CPU work* (a
      GIL-holding spin) instead of a sleep.  This is the drain-dominated
      regime measured as RAW wall-clock: threads serialize on the GIL
      (<= 1.0x at any p — the ROADMAP pathology), while procpool workers
      burn on separate cores.  The PR 5 acceptance gate is procpool
      p=4 >= 1.5x p=1 (``procpool_burn_speedup_p4_vs_p1``); on a c-core
      container the ceiling is (pushes_p1 / pushes_p4) * min(p, c), and
      the rows run one process per shard (see the inline comment).

  heterogeneous
      The paper's motivating platform: shard i runs at rate/(1+i) — a 4x
      spread at p=4.  The superstep loop serializes every shard's slow
      drain per superstep; the async executor lets fast shards run ahead
      (bounded by the §6 exchange plan).

  chaos (PR 6)
      The acceptance workload drained by p=4 procpool under seeded
      faults (mid-drain worker kill, 10% drop + 10% duplicate, both):
      recovery time and total overhead vs the no-fault baseline, with
      the certificate required to hold in every row.

  device (PR 9)
      The acceptance workload drained by ``transport="device"`` — the
      traced ShardStep as p shard programs over forced host devices
      (``XLA_FLAGS=--xla_force_host_platform_device_count=4``) — at
      p=1 and p=4.  The rows run in a subprocess because this process's
      jax is already initialized single-device; each p is run twice and
      the warm (second) wall-clock is the throughput row, so the jit
      compile is not billed to the drain.  The certificate must hold and
      the recorded bytes must reproduce from the (rows, fulls) counters
      through ``step.comm_bytes_model`` —
      benchmarks/check_device_transport.py gates both.

Emits benchmarks/results/async_shard_bench.json and feeds the
``async_shard`` section of BENCH_PR9.json via benchmarks/run.py.
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
REPO = Path(__file__).parent.parent

PS = (1, 2, 4, 8)
TOL = 1e-8
DRAIN_RATE = 1e5            # modeled pushes/s for the drain-dominated case
BURN_REPEATS = 2            # burn rows keep the best of N runs (the async
#                           # schedule is nondeterministic; min is the
#                           # standard timing estimator)


def _spin(seconds: float) -> float:
    """Burn ~`seconds` of CPU while HOLDING the GIL (python-level loop):
    the honest stand-in for a heavier drain kernel whose numpy ops don't
    release the GIL.  Sleeping would overlap perfectly on threads and
    hide exactly the contention this regime exists to measure."""
    t_end = time.perf_counter() + seconds
    x = 1.0
    while time.perf_counter() < t_end:
        x = x * 1.0000001 + 1e-9
    return x


def _workload():
    from repro.graph.generate import powerlaw_webgraph
    from repro.streaming import DeltaGraph, EdgeDelta, cold_state

    g = powerlaw_webgraph(n=50_000, target_nnz=400_000, n_dangling=50,
                          seed=3)
    rng = np.random.default_rng(31)
    k = g.nnz // 100
    n_del = k * 15 // 100
    slots = rng.choice(g.nnz, size=n_del, replace=False)
    soe = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
    delta = EdgeDelta(
        add_src=rng.integers(0, g.n, k - n_del),
        add_dst=g.indices[rng.integers(0, g.nnz, k - n_del)].astype(
            np.int64),
        del_src=soe[slots], del_dst=g.indices[slots].astype(np.int64))
    base = cold_state(DeltaGraph(g), tol=5e-9)
    return g, delta, base


def _run(g, delta, base, mode: str, p: int, rate_per_shard=None,
         transport: str = "threads", cost: str = "sleep",
         n_workers=None, faults=None, observe: bool = False,
         schedule=None):
    """One sharded update; rate_per_shard (pushes/s, per shard) switches
    on the modeled drain clock via a scoped _drain_shard wrapper —
    `cost="sleep"` yields the GIL (dedicated-core model), `cost="burn"`
    holds it (real-CPU model).  The wrapper reaches procpool workers too:
    they are forked after the module is patched."""
    import warnings

    from repro.streaming import DeltaGraph, update_ranks_sharded
    from repro.streaming.incremental import RankState
    from repro.streaming import sharded as sharded_mod

    dg = DeltaGraph(g)
    st = RankState(x=base.x.copy(), r=base.r.copy(), version=0,
                   alpha=base.alpha)
    real_drain = sharded_mod._drain_shard
    part_size = -(-g.n // p)

    if rate_per_shard is not None:
        pay = _spin if cost == "burn" else time.sleep

        def modeled_drain(arrays, x, r, outbox, s, e, *args, **kwargs):
            got = real_drain(arrays, x, r, outbox, s, e, *args, **kwargs)
            if got:
                pay(got / rate_per_shard[min(s // part_size, p - 1)])
            return got
        sharded_mod._drain_shard = modeled_drain
    try:
        t0 = time.perf_counter()
        with warnings.catch_warnings():
            # the burn rows intentionally oversubscribe (one process per
            # shard): the guard's warning is the expected behavior
            warnings.filterwarnings("ignore", message=".*oversubscribes.*",
                                    category=RuntimeWarning)
            st, stats = update_ranks_sharded(dg, delta, st, p=p, tol=TOL,
                                             mode=mode, transport=transport,
                                             n_workers=n_workers,
                                             faults=faults, observe=observe,
                                             schedule=schedule)
        dt = time.perf_counter() - t0
    finally:
        sharded_mod._drain_shard = real_drain
    row = dict(mode=mode, p=p, transport=transport,
               s=round(dt, 3), path=stats.path,
               pushes=int(stats.pushes), supersteps=int(stats.supersteps),
               exchanges=int(stats.exchanges),
               bytes_moved=int(stats.bytes_moved),
               cert=float(stats.cert), idle_s=round(float(stats.idle_s), 3),
               attempts=int(stats.attempts),
               recoveries=int(stats.recoveries),
               recovery_s=round(float(stats.recovery_s), 4))
    if observe:
        # PR 7: attribution roll-up plus the full observed payload (the
        # event stream) — the caller (observe_bench) pops `_observed`
        # before serializing the row
        row.update(pushes_first=int(stats.pushes_first),
                   pushes_local=int(stats.pushes_local),
                   pushes_boundary=int(stats.pushes_boundary))
        row["_observed"] = stats.observed
    return row


_DEVICE_CODE = """
import json, time
import jax
import numpy as np
from benchmarks.async_shard_bench import TOL, _workload
from repro.streaming import DeltaGraph, update_ranks_sharded
from repro.streaming.incremental import RankState

g, delta, base = _workload()
rows = []
for p in (1, 4):
    best = None
    for run in range(2):          # second run is warm (jit cached per p)
        dg = DeltaGraph(g)
        st = RankState(x=base.x.copy(), r=base.r.copy(), version=0,
                       alpha=base.alpha)
        t0 = time.perf_counter()
        st, stats = update_ranks_sharded(dg, delta, st, p=p, tol=TOL,
                                         mode="async", transport="device")
        dt = time.perf_counter() - t0
        row = dict(mode="async", p=p, transport="device",
                   platform=jax.devices()[0].platform,
                   s=round(dt, 3), path=stats.path,
                   supersteps=int(stats.supersteps),
                   exchanges=int(stats.exchanges),
                   rows_sent=int(stats.rows_sent), fulls=int(stats.fulls),
                   bytes_moved=int(stats.bytes_moved),
                   cert=float(stats.cert), attempts=int(stats.attempts),
                   device_resid=float(stats.device_resid))
        if run == 0:
            cold_s = row["s"]
        best = row
    best["cold_s"] = cold_s
    rows.append(best)
print("DEVICE_ROWS " + json.dumps(rows))
"""


def _device_rows(timeout: int = 1800):
    """PR 9: the device-transport throughput rows, in a forced-host-device
    subprocess (see the `device` regime note in the module docstring)."""
    env = dict(os.environ)
    # a CPU forced-device experiment by design: the parent has touched
    # jax, so on an accelerator host the child must never reach the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), str(REPO)])
    out = subprocess.run([sys.executable, "-c", _DEVICE_CODE], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"device bench subprocess failed:\n"
                           f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}")
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("DEVICE_ROWS "))
    return json.loads(line[len("DEVICE_ROWS "):])


def main():
    print("  [async] building 50k 1%-delta workload (cold solve) ...")
    g, delta, base = _workload()
    cores = os.cpu_count() or 1

    raw = []
    print("  [async] raw wall-clock, p=1..8, async vs superstep ...")
    _run(g, delta, base, "async", 1)            # warm caches
    for mode in ("async", "superstep"):
        for p in PS:
            row = _run(g, delta, base, mode, p)
            raw.append(row)
            print(f"    raw       {mode:9s} p={p} {row['s']:7.2f}s "
                  f"pushes={row['pushes']} path={row['path']}")
    # PR 5: procpool raw rows, p = 1..cores plus the p=4 acceptance point
    pp_ps = sorted({pp for pp in PS if pp <= cores} | {4})
    for p in pp_ps:
        row = _run(g, delta, base, "async", p, transport="procpool")
        raw.append(row)
        print(f"    raw       procpool  p={p} {row['s']:7.2f}s "
              f"pushes={row['pushes']} path={row['path']}")

    print(f"  [async] drain-dominated (modeled {DRAIN_RATE:.0f} pushes/s "
          "per shard, sleep = dedicated cores) ...")
    dom = []
    for mode in ("async", "superstep"):
        for p in PS:
            row = _run(g, delta, base, mode, p,
                       rate_per_shard=[DRAIN_RATE] * p)
            dom.append(row)
            print(f"    dominated {mode:9s} p={p} {row['s']:7.2f}s "
                  f"pushes={row['pushes']} idle={row['idle_s']}s")

    print("  [async] drain-dominated BURN (real CPU per push): threads "
          f"vs procpool, raw wall-clock, best of {BURN_REPEATS} ...")
    # procpool burn rows run one process per shard (n_workers=p): parked
    # shards spend the drain-dominated regime sleeping, and a sleeping
    # shard co-resident with a busy one taxes the busy shard's GIL — one
    # process per shard lets the kernel overlap them (measured ~25% faster
    # than the min(p, cores) pool on the 2-core reference container)
    burn = []
    pp_burn = sorted({pp for pp in (1, 2) if pp <= cores} | {1, 4})
    for transport, ps in (("threads", (1, 4)), ("procpool", pp_burn)):
        for p in ps:
            nw = p if transport == "procpool" else None
            row = min((_run(g, delta, base, "async", p,
                            rate_per_shard=[DRAIN_RATE] * p,
                            transport=transport, cost="burn", n_workers=nw)
                       for _ in range(BURN_REPEATS)), key=lambda r: r["s"])
            burn.append(row)
            print(f"    burn      {transport:9s} p={p} {row['s']:7.2f}s "
                  f"pushes={row['pushes']}")

    print("  [async] chaos (PR 6): p=4 procpool under seeded faults ...")
    # Recovery cost of the self-healing runtime: the acceptance workload
    # drained under (a) a mid-drain worker kill, (b) a 10% drop + 10%
    # duplicate lossy wire, (c) both at once — against a no-fault
    # baseline measured the same way.  `recovery_s` is the supervisor's
    # death-detection -> respawned time; `overhead_vs_no_faults` is total
    # wall-clock (re-drain attempts included) over the clean run.
    from repro.runtime import FaultPlan
    chaos = []
    chaos_plans = [
        ("no_faults", None),
        ("kill", FaultPlan(seed=7, kill={1: 40})),
        ("drop_dup", FaultPlan(seed=7, drop_rate=0.10, dup_rate=0.10)),
        ("kill_drop_dup", FaultPlan(seed=7, kill={1: 40},
                                    drop_rate=0.10, dup_rate=0.10)),
    ]
    base_s = None
    for name, fplan in chaos_plans:
        row = _run(g, delta, base, "async", 4, transport="procpool",
                   faults=fplan)
        row["faults"] = name
        if name == "no_faults":
            base_s = row["s"]
        row["overhead_vs_no_faults"] = (round(row["s"] / base_s, 3)
                                        if base_s else None)
        chaos.append(row)
        print(f"    chaos     {name:14s} p=4 {row['s']:7.2f}s "
              f"recoveries={row['recoveries']} "
              f"recovery_s={row['recovery_s']:.3f} "
              f"overhead={row['overhead_vs_no_faults']}x "
              f"cert={row['cert']:.1e}")

    print("  [async] device transport (PR 9): p=1 vs p=4, forced host "
          "devices, warm wall-clock ...")
    dev = _device_rows()
    for row in dev:
        print(f"    device    {'async':9s} p={row['p']} {row['s']:7.2f}s "
              f"(cold {row['cold_s']:.2f}s) steps={row['supersteps']} "
              f"cert={row['cert']:.1e} path={row['path']}")

    print("  [async] heterogeneous shards (rate/(1+i), p=4) ...")
    het = []
    rates = [DRAIN_RATE / (1 + i) for i in range(4)]
    for mode in ("async", "superstep"):
        row = _run(g, delta, base, mode, 4, rate_per_shard=rates)
        het.append(row)
        print(f"    hetero    {mode:9s} p=4 {row['s']:7.2f}s")

    def t(rows, mode, p, transport="threads"):
        return next(r["s"] for r in rows if r["mode"] == mode
                    and r["p"] == p and r["transport"] == transport)

    rec = dict(
        bench="async shard executor: threads vs procpool (PR 5)",
        workload="50k power-law, 1% delta, tol=1e-8",
        drain_rate_pushes_per_s=DRAIN_RATE,
        cores=cores,
        raw=raw, drain_dominated=dom, drain_dominated_burn=burn,
        heterogeneous=het, chaos=chaos, device=dev,
        device_tol=TOL,
        device_speedup_p4_vs_p1=round(
            t(dev, "async", 1, "device") / t(dev, "async", 4, "device"), 3),
        chaos_recovery_s=next(r["recovery_s"] for r in chaos
                              if r["faults"] == "kill_drop_dup"),
        chaos_overhead_vs_no_faults=next(
            r["overhead_vs_no_faults"] for r in chaos
            if r["faults"] == "kill_drop_dup"),
        speedup_p4_vs_p1_async=round(t(dom, "async", 1)
                                     / t(dom, "async", 4), 3),
        raw_speedup_p4_vs_p1_async=round(t(raw, "async", 1)
                                         / t(raw, "async", 4), 3),
        procpool_raw_speedup_p4_vs_p1=round(
            t(raw, "async", 1, "procpool")
            / t(raw, "async", 4, "procpool"), 3),
        threads_burn_speedup_p4_vs_p1=round(
            t(burn, "async", 1) / t(burn, "async", 4), 3),
        procpool_burn_speedup_p4_vs_p1=round(
            t(burn, "async", 1, "procpool")
            / t(burn, "async", 4, "procpool"), 3),
        procpool_burn_speedup_p2_vs_p1=(round(
            t(burn, "async", 1, "procpool")
            / t(burn, "async", 2, "procpool"), 3)
            if any(r["p"] == 2 and r["transport"] == "procpool"
                   for r in burn) else None),
        speedup_async_vs_superstep_hetero_p4=round(
            t(het, "superstep", 4) / t(het, "async", 4), 3),
    )
    print(f"  [async] device p4-vs-p1 (warm wall-clock, forced host "
          f"devices): {rec['device_speedup_p4_vs_p1']:.2f}x")
    print(f"  [async] drain-dominated p4-vs-p1 async: "
          f"{rec['speedup_p4_vs_p1_async']:.2f}x (sleep) | burn raw: "
          f"threads {rec['threads_burn_speedup_p4_vs_p1']:.2f}x vs "
          f"procpool {rec['procpool_burn_speedup_p4_vs_p1']:.2f}x "
          f"({cores} cores) | hetero p=4 async-vs-superstep: "
          f"{rec['speedup_async_vs_superstep_hetero_p4']:.2f}x")
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "async_shard_bench.json").write_text(
        json.dumps(rec, indent=1))
    return rec


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
