"""Drain-schedule bench (PR 8): inflation-aware drain scheduling.

PR 7's attribution decomposed the async scaling tax — p=4 inflates pushes
over p=1, threads losing half-or-more to *local* drain cadence, procpool
~90% to *boundary* re-activation.  This bench measures how far each
`runtime.schedule.ScheduleSpec` rendering closes that gap on the PR 4/5
acceptance workload (50k power-law graph, 1% edge delta, tol=1e-8):

  arms
      For each transport (threads, procpool): the p=1 default-schedule
      baseline, then p=4 under default / priority / boundary / randomized
      / priority+boundary, every arm with attribution on.  The async
      schedule is wall-clock nondeterministic, so every arm is the
      median-of-``REPEATS`` by total pushes (the same stabilization the
      PR 8 observe_bench adopts) and the p=1 / p=4 arms share one
      workload build.  The tuned knobs per transport live in ``TUNED`` —
      priority's boost-2 bar plus a coarser drain stride for the threads
      local-cadence regime; boundary batching (batch_updates=8) on top
      for procpool's boundary regime.

  summary
      Per (transport, schedule): ``inflation_ratio`` = pushes_p4 /
      pushes_p1(default) — the honest denominator: the single-shard
      default drain, so a schedule cannot improve its ratio by inflating
      its own p=1 arm — plus the PR 7 attribution split (local excess vs
      p=1, boundary re-activation) that shows *which* half of the tax the
      schedule removed.  ``best`` picks the lowest-inflation non-default
      schedule per transport; `benchmarks/check_schedule_inflation.py`
      gates threads <= 1.2x and procpool <= 1.1x on it.

  burn projection
      The PR 5 burn regime (real CPU per push) needs >= 4 cores to show
      wall-clock scaling; on smaller containers the machine-independent
      projection ``min(p, cores_assumed=4) * pushes_p1 / pushes_p4`` is
      recorded instead (the burn regime's wall-clock is push-count *
      per-push cost, so fewer pushes convert 1:1).  When the host really
      has >= 4 cores the measured burn rows are emitted too and the gate
      checks both.

Emits benchmarks/results/schedule_bench.json and feeds the ``schedule``
section of BENCH_PR8.json via benchmarks/run.py.
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings
from pathlib import Path

from benchmarks.async_shard_bench import DRAIN_RATE, _run, _workload
from repro.runtime.schedule import ScheduleSpec

RESULTS = Path(__file__).parent / "results"

REPEATS = 3          # median-of-k by pushes per arm (async nondeterminism)
TOL = 1e-8
PROJECT_CORES = 4    # the burn projection's dedicated-core assumption

#: tuned knobs per transport (measured on the acceptance workload; the
#: spec is recorded verbatim in the JSON so any row is reproducible)
TUNED = {
    "threads": {
        "priority": ScheduleSpec(name="priority", retain_boost=2.0,
                                 drain_frac=0.45),
        "boundary": ScheduleSpec(name="boundary"),
        "randomized": ScheduleSpec(name="randomized", select_frac=0.25),
        "priority+boundary": ScheduleSpec(name="priority+boundary",
                                          retain_boost=2.0,
                                          drain_frac=0.45),
    },
    "procpool": {
        "priority": ScheduleSpec(name="priority", retain_boost=2.0,
                                 drain_frac=0.38),
        "boundary": ScheduleSpec(name="boundary", batch_updates=8),
        "randomized": ScheduleSpec(name="randomized", select_frac=0.25),
        "priority+boundary": ScheduleSpec(name="priority+boundary",
                                          retain_boost=2.0,
                                          batch_updates=8,
                                          drain_frac=0.38),
    },
}


def _median_run(g, delta, base, p, transport, schedule=None, **kw):
    """median-of-REPEATS by total pushes (the gated metric)."""
    nw = p if transport == "procpool" else None
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*oversubscribes.*",
                                category=RuntimeWarning)
        rows = sorted((_run(g, delta, base, "async", p, transport=transport,
                            n_workers=nw, observe=True, schedule=schedule,
                            **kw)
                       for _ in range(REPEATS)),
                      key=lambda r: r["pushes"])
    row = rows[len(rows) // 2]
    row.pop("_observed", None)
    return row


def main():
    print("  [schedule] building 50k 1%-delta workload (cold solve) ...")
    g, delta, base = _workload()
    cores = os.cpu_count() or 1

    arms = []
    summary = {}
    for transport in ("threads", "procpool"):
        r1 = _median_run(g, delta, base, 1, transport)
        r1["schedule"] = "default"
        arms.append(r1)
        print(f"    baseline  {transport:9s} p=1 default "
              f"pushes={r1['pushes']}")
        summary[transport] = {}
        scheds = [("default", None)] + sorted(TUNED[transport].items())
        for name, spec in scheds:
            r4 = _median_run(g, delta, base, 4, transport, schedule=spec)
            r4["schedule"] = name
            if spec is not None:
                r4["spec"] = dataclasses.asdict(spec)
            arms.append(r4)
            summary[transport][name] = dict(
                pushes_p1=r1["pushes"], pushes_p4=r4["pushes"],
                inflation_ratio=round(r4["pushes"] / r1["pushes"], 4),
                boundary_p4=r4["pushes_boundary"],
                local_excess=r4["pushes_local"] - r1["pushes_local"],
                cert=r4["cert"],
            )
            d = summary[transport][name]
            print(f"    arm       {transport:9s} p=4 {name:18s} "
                  f"pushes={r4['pushes']} "
                  f"inflation={d['inflation_ratio']:.3f}x "
                  f"local_excess={d['local_excess']} "
                  f"boundary={d['boundary_p4']} cert={r4['cert']:.1e}")

    best = {}
    for transport in ("threads", "procpool"):
        cands = {k: v for k, v in summary[transport].items()
                 if k != "default"}
        name = min(cands, key=lambda k: cands[k]["inflation_ratio"])
        best[transport] = dict(
            schedule=name, spec=dataclasses.asdict(TUNED[transport][name]),
            **cands[name])

    # burn projection (and measurement, when the host can show it)
    pp = best["procpool"]
    projected = round(min(4, PROJECT_CORES)
                      * pp["pushes_p1"] / pp["pushes_p4"], 3)
    burn = dict(cores=cores, project_cores=PROJECT_CORES,
                projected_speedup_p4_vs_p1=projected, measured=None)
    if cores >= 4:
        spec = TUNED["procpool"][best["procpool"]["schedule"]]
        b1 = _median_run(g, delta, base, 1, "procpool",
                         rate_per_shard=[DRAIN_RATE], cost="burn")
        b4 = _median_run(g, delta, base, 4, "procpool", schedule=spec,
                         rate_per_shard=[DRAIN_RATE] * 4, cost="burn")
        burn["measured"] = dict(
            p1_s=b1["s"], p4_s=b4["s"],
            speedup_p4_vs_p1=round(b1["s"] / b4["s"], 3))
        print(f"    burn      procpool  measured "
              f"{burn['measured']['speedup_p4_vs_p1']:.2f}x "
              f"(projected {projected:.2f}x)")
    else:
        print(f"    burn      procpool  projected {projected:.2f}x at "
              f"{PROJECT_CORES} cores ({cores}-core host: wall-clock "
              "scaling cannot manifest; gate uses the push-ratio "
              "projection)")

    for transport in ("threads", "procpool"):
        b = best[transport]
        d0 = summary[transport]["default"]
        print(f"  [schedule] best {transport}: {b['schedule']} "
              f"{b['inflation_ratio']:.3f}x (default "
              f"{d0['inflation_ratio']:.3f}x)")

    rec = dict(
        bench="drain-schedule inflation (PR 8)",
        workload="50k power-law, 1% delta, tol=1e-8",
        tol=TOL, repeats=REPEATS, cores=cores,
        arms=arms, summary=summary, best=best, burn=burn,
    )
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "schedule_bench.json").write_text(json.dumps(rec, indent=1))
    return rec


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
