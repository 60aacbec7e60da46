"""Observability bench (PR 7): push-inflation attribution, the chaos
trace demo, and the zero-cost-when-off gate.

Three studies over the PR 4/5 acceptance workload (50k power-law graph,
1% edge delta, tol=1e-8):

  attribution
      `update_ranks_sharded(observe=True)` at p = 1 and p = 4 on both
      transports, decomposing the push-inflation ratio pushes_p4 /
      pushes_p1 that every prior BENCH file reports as a single opaque
      number.  The async schedule is wall-clock nondeterministic, so
      since PR 8 every row is the median-of-``ATTR_REPEATS`` by total
      pushes (single-shot rows drifted 15%+ between runs, enough to flip
      the decomposition's headline shares).  Each push is classified at drain time (runtime/observe.py)
      as *first* (the row's first push this update), *boundary* (re-push
      whose residual was re-seeded by a cross-shard exchange fold since
      its last push) or *local* (re-push from same-shard mass movement /
      drain cadence).  first + local + boundary == pushes exactly on a
      fault-free run.  At p = 1 boundary is structurally 0 (there is no
      exchange), so `boundary_p4` is the pure cross-shard re-activation
      cost of sharding and `local` growth is the asynchrony/cadence cost.

  trace_demo
      The Fig. 1 / eq. (5) cycle made visible: a p=4 procpool solve
      under a seeded mid-drain worker SIGKILL (the PR 6 chaos "kill"
      plan), exported as Chrome trace_event JSON --
      benchmarks/results/observe_trace_p4_procpool.json -- loadable in
      Perfetto / chrome://tracing (one track per shard: INTAKE / DRAIN /
      EXCHANGE spans, CONVERGE / STOP / KILL / RECOVERY instants).  The
      KILL instant is written by the dying incarnation (the ring lives
      in the parent-owned arena) and the RECOVERY by the supervisor.
      Also runnable alone: ``python -m benchmarks.observe_bench
      --trace-demo``.

  overhead
      The acceptance gate: observability must be pay-for-use.  The
      drain-dominated burn row (threads p=1, the most deterministic
      regime: wall-clock is dominated by the calibrated per-push spin,
      so it is machine-independent) is re-measured with observe=False
      and compared against the same row of the pre-PR BENCH file --
      within 3% or benchmarks/check_observe_overhead.py fails.  The
      observe=True re-measurement is informational (attribution adds a
      per-frontier classification to every drain).

Emits benchmarks/results/observe_bench.json and feeds the ``observe``
section of BENCH_PR7.json via benchmarks/run.py.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.async_shard_bench import (BURN_REPEATS, DRAIN_RATE, _run,
                                          _workload)

REPO_ROOT = Path(__file__).parent.parent
RESULTS = Path(__file__).parent / "results"
TRACE_PATH = RESULTS / "observe_trace_p4_procpool.json"
BASELINE_BENCH = "BENCH_PR6.json"   # pre-PR perf trajectory (overhead ref)
OVERHEAD_LIMIT = 1.03               # observe=off within 3% of pre-PR burn
ATTR_REPEATS = 3                    # median-of-k by pushes per attribution
#                                   # row (PR 8: the async schedule is
#                                   # nondeterministic; k=1 was too noisy)


def _attr_row(row):
    """Serialize an observe=True row: drop the event stream, keep the
    roll-up (counters + attribution) the JSON record needs."""
    obs = row.pop("_observed", None)
    if obs is not None:
        row["events_written"] = [int(v) for v in obs["events_written"]]
        row["events_dropped"] = [int(v) for v in obs["events_dropped"]]
        row["counters"] = {k: [int(v) for v in vals]
                           for k, vals in obs["counters"].items()}
    return row


def attribution_study(g, delta, base):
    rows = []
    for transport in ("threads", "procpool"):
        for p in (1, 4):
            nw = p if transport == "procpool" else None
            reps = sorted((_run(g, delta, base, "async", p,
                                transport=transport, n_workers=nw,
                                observe=True)
                           for _ in range(ATTR_REPEATS)),
                          key=lambda r: r["pushes"])
            row = reps[len(reps) // 2]
            rows.append(_attr_row(row))
            print(f"    attr      {transport:9s} p={p} {row['s']:7.2f}s "
                  f"pushes={row['pushes']} first={row['pushes_first']} "
                  f"local={row['pushes_local']} "
                  f"boundary={row['pushes_boundary']}")

    def pick(transport, p):
        return next(r for r in rows if r["transport"] == transport
                    and r["p"] == p)

    decomp = {}
    for transport in ("threads", "procpool"):
        r1, r4 = pick(transport, 1), pick(transport, 4)
        inflation = r4["pushes"] - r1["pushes"]
        decomp[transport] = dict(
            pushes_p1=r1["pushes"], pushes_p4=r4["pushes"],
            inflation=inflation,
            inflation_ratio=round(r4["pushes"] / r1["pushes"], 4),
            # cross-shard re-activation: pushes whose residual arrived
            # over the wire (structurally impossible at p=1)
            boundary_p4=r4["pushes_boundary"],
            # asynchrony/cadence: extra same-shard re-pushes vs p=1
            local_excess=r4["pushes_local"] - r1["pushes_local"],
            first_p4=r4["pushes_first"], first_p1=r1["pushes_first"],
            boundary_share_of_inflation=(
                round(r4["pushes_boundary"] / inflation, 4)
                if inflation > 0 else None),
        )
        d = decomp[transport]
        print(f"    decomp    {transport:9s} inflation="
              f"{d['inflation_ratio']:.2f}x boundary={d['boundary_p4']} "
              f"({d['boundary_share_of_inflation']}) "
              f"local_excess={d['local_excess']}")
    return rows, decomp


def trace_demo(g=None, delta=None, base=None):
    """p=4 procpool kill/recovery solve -> Perfetto-loadable trace."""
    from repro.runtime import FaultPlan, write_chrome_trace

    if g is None:
        print("  [observe] building 50k 1%-delta workload (cold solve) ...")
        g, delta, base = _workload()
    row = _run(g, delta, base, "async", 4, transport="procpool",
               n_workers=4, faults=FaultPlan(seed=7, kill={1: 40}),
               observe=True)
    obs = row.pop("_observed")
    events = obs["events"]
    RESULTS.mkdir(exist_ok=True, parents=True)
    write_chrome_trace(TRACE_PATH, events, p=4)
    kinds = {}
    for ev in events:
        kinds[ev["kind"]] = kinds.get(ev["kind"], 0) + 1
    kills = int(sum(obs["counters"]["kills"]))
    recs = int(sum(obs["counters"]["recoveries"]))
    print(f"    trace     p=4 procpool kill: {len(events)} events "
          f"({row['s']:.2f}s, kills={kills}, recoveries={recs}) -> "
          f"{TRACE_PATH.relative_to(REPO_ROOT)}")
    return dict(path=str(TRACE_PATH.relative_to(REPO_ROOT)),
                events=len(events),
                events_dropped=[int(v) for v in obs["events_dropped"]],
                kills=kills, recoveries=recs,
                wall_s=row["s"], cert=row["cert"],
                counters={k: [int(v) for v in vals]
                          for k, vals in obs["counters"].items()})


def overhead_study(g, delta, base):
    """observe=off vs the pre-PR burn baseline, observe=on vs off."""
    def burn(observe):
        return min((_run(g, delta, base, "async", 1,
                         rate_per_shard=[DRAIN_RATE], cost="burn",
                         observe=observe)
                    for _ in range(BURN_REPEATS)), key=lambda r: r["s"])

    off = burn(False)
    on = _attr_row(burn(True))
    baseline_s = None
    note = None
    bpath = REPO_ROOT / BASELINE_BENCH
    if bpath.exists():
        try:
            pre = json.loads(bpath.read_text())
            baseline_s = next(
                r["s"] for r in pre["async_shard"]["drain_dominated_burn"]
                if r["transport"] == "threads" and r["p"] == 1)
        except (KeyError, StopIteration, json.JSONDecodeError) as e:
            note = f"baseline row unreadable in {BASELINE_BENCH}: {e}"
    else:
        note = f"{BASELINE_BENCH} not found; overhead gate will skip"
    rec = dict(
        regime="drain_dominated_burn threads p=1 (best of "
               f"{BURN_REPEATS})",
        off_s=off["s"], on_s=on["s"],
        baseline=BASELINE_BENCH, baseline_s=baseline_s,
        limit=OVERHEAD_LIMIT,
        off_vs_baseline=(round(off["s"] / baseline_s, 4)
                         if baseline_s else None),
        on_vs_off=round(on["s"] / off["s"], 4),
        within_limit=(baseline_s is not None
                      and off["s"] / baseline_s <= OVERHEAD_LIMIT),
        note=note,
    )
    print(f"    overhead  off={off['s']:.2f}s on={on['s']:.2f}s "
          f"baseline={baseline_s} off_vs_baseline={rec['off_vs_baseline']} "
          f"on_vs_off={rec['on_vs_off']}x")
    if note:
        print(f"    overhead  NOTE: {note}")
    return rec


def main():
    print("  [observe] building 50k 1%-delta workload (cold solve) ...")
    g, delta, base = _workload()

    print("  [observe] push-inflation attribution "
          "(threads/procpool, p=1 vs p=4, observe=True) ...")
    rows, decomp = attribution_study(g, delta, base)

    print("  [observe] chaos trace demo (p=4 procpool, seeded kill) ...")
    trace = trace_demo(g, delta, base)

    print("  [observe] zero-cost-when-off gate (burn p=1, "
          f"observe off/on vs {BASELINE_BENCH}) ...")
    overhead = overhead_study(g, delta, base)

    rec = dict(
        bench="runtime observability: attribution, trace, overhead (PR 7)",
        workload="50k power-law, 1% delta, tol=1e-8",
        attribution=rows, inflation=decomp,
        trace_demo=trace, overhead=overhead,
    )
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "observe_bench.json").write_text(json.dumps(rec, indent=1))
    return rec


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-demo", action="store_true",
                    help="only regenerate the Perfetto kill/recovery "
                         "trace (make trace-demo)")
    if ap.parse_args().trace_demo:
        trace_demo()
    else:
        main()
