"""One benchmark run of one cell: set up, warm up, measure, check, report.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name, as files under `bench/`:

    BENCHMARK.json            the cell's configuration, traffic and chips,
                              and the metrics it reports
    configs/<config>.json     the deployment: graph generator and its sizes
    graphs/<generator>.py     makes the graph's edges from the seed
    traffic/<traffic>.json    the mix's parameters, naming its `kind`
    traffic/<kind>.py         makes the mix's work items from the seed
                              (read only for an entry that takes items:
                              a batch entry repeats one call, and its
                              traffic is a name alone)
    workloads/<cell>.json     the entry the window drives, its arguments
                              and the limits that decide `correct`
    entries/<entry>.py        drives the program's entry and checks what
                              it returned against the plain reference
    metrics/<metric>.py       reads one metric from the run's record

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`: every number compared, beside its limit).
The checks are also the last lines on standard error. With no TPU, or
fewer chips than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


class Refused(RuntimeError):
    """The run cannot be made here (no TPU, too few chips, unknown
    device kind, a cell or file that does not exist)."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def load_json(root: Path, kind: str, name: str) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_module(root: Path, kind: str, name: str):
    path = root / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} module {path}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(benchmark: dict, cell: str) -> dict:
    for w in benchmark["workloads"]:
        if w["name"] == cell:
            return w
    raise Refused(f"no cell {cell!r} in BENCHMARK.json")


def cell_metrics(benchmark: dict, cell: str, group: str) -> list:
    """The metrics of `group` ("end_to_end" or "per_layer") that this
    cell reports: those with no `workloads` key, and those that list it."""
    return [m for m in benchmark[group]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------
def find_devices(chips: int, peaks: dict, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise Refused(f"no TPU: jax found {devs[0].platform!r} devices")
        if len(devs) < chips:
            raise Refused(f"the cell asks for {chips} chips, jax found "
                          f"{len(devs)}")
        if devs[0].device_kind not in peaks:
            raise Refused(f"device kind {devs[0].device_kind!r} is not in "
                          "peaks.json")
    return devs[:chips]


def use_compile_cache(checkout: Path) -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR`
    where it is set, else the fixed `<checkout>/.jax_cache` (a path that
    moves never hits). Every program is written to it, however short its
    compile, so a second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(devs) -> int:
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs))


class Phases:
    """Wall and CPU seconds, and page faults, of each step of set-up: where
    set-up time goes, and whether a slow one waited or worked."""

    def __init__(self, t_start: float):
        self.rows = {}
        self._last = (t_start,) + self._usage()[1:]

    @staticmethod
    def _usage():
        import resource
        r = resource.getrusage(resource.RUSAGE_SELF)
        return time.perf_counter(), r.ru_utime, r.ru_stime, r.ru_minflt

    def mark(self, name: str) -> None:
        now = self._usage()
        self.rows[name] = dict(
            wall_s=now[0] - self._last[0], user_s=now[1] - self._last[1],
            sys_s=now[2] - self._last[2], minflt=now[3] - self._last[3])
        self._last = now


# ---------------------------------------------------------------------------
# what an entry is handed
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    seed: int
    config: dict          # configs/<config>.json
    mix: dict             # traffic/<traffic>.json
    workload: dict        # workloads/<cell>.json
    graph: object         # graphs/<generator>.py's generate(): an edges.Graph
    traffic: object       # traffic/<kind>.py (None for an entry without)
    # workloads/<cell>.json's `control`, when the control is put in the
    # program's place (bench/control.py); None in the benchmark's runs
    control: Optional[dict] = None
    phases: Optional[Phases] = None


def main(argv=None, *, bench_root: Path = BENCH,
         benchmark_path: Optional[Path] = None, require_tpu: bool = True,
         t_start: Optional[float] = None, control: bool = False) -> int:
    """One run. `control` puts the cell's control (the reference at a
    lower precision) in the program's place: bench/control.py's runs,
    which have to come out as not correct; the benchmark's never do."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return _run(args, bench_root, benchmark_path
                    or bench_root.parent / "BENCHMARK.json",
                    require_tpu, t_start, control)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


def _run(args, root: Path, benchmark_path: Path, require_tpu: bool,
         t_start: float, control: bool) -> int:
    phases = Phases(t_start)
    if not benchmark_path.is_file():
        raise Refused(f"no {benchmark_path}")
    benchmark = json.loads(benchmark_path.read_text())
    spec = cell_spec(benchmark, args.workload)
    peaks = json.loads((root / "peaks.json").read_text())["devices"]
    devs = find_devices(int(spec["chips"]), peaks, require_tpu)
    phases.mark("start_to_devices")
    cache_dir = use_compile_cache(root.parent)
    # the system under test
    src = root.parent / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program under {src}")
    sys.path.insert(0, str(src))
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from compileclock import CompileClock
    import devtrace

    workload = load_json(root, "workloads", args.workload)
    config = load_json(root, "configs", spec["config"])
    entry = load_module(root, "entries", workload["entry"])
    uses_traffic = getattr(entry, "USES_TRAFFIC", True)
    mix = load_json(root, "traffic", spec["traffic"]) if uses_traffic \
        else None
    e2e = cell_metrics(benchmark, args.workload, "end_to_end")
    layer = cell_metrics(benchmark, args.workload, "per_layer")
    readers = {m["name"]: load_module(root, "metrics", m["name"])
               for m in (layer if args.trace else e2e)}
    clock = CompileClock()

    generator = load_module(root, "graphs", config["generator"])
    phases.mark("imports")
    graph = generator.generate(config["params"], args.seed)
    phases.mark("generate")
    ctx = Context(seed=args.seed, config=config, mix=mix, workload=workload,
                  graph=graph,
                  traffic=load_module(root, "traffic", mix["kind"])
                  if uses_traffic else None,
                  control=workload["control"] if control else None,
                  phases=phases)
    session = entry.setup(ctx)
    phases.mark("entry_setup")
    setup_s = time.perf_counter() - t_start

    # --- the measured window: calls into the program's entry only ---------
    tracer = devtrace.Tracer() if args.trace else None
    counters, c0 = [], clock.snapshot()
    if tracer:
        tracer.start()
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    t = t0
    while t < deadline:
        c = session.step()
        c["s"] = time.perf_counter() - t
        t += c["s"]
        counters.append(c)
    window_s = t - t0
    trace = tracer.stop(window_s, len(devs)) if tracer else None
    in_window = clock.since(c0)
    memory_peak = peak_bytes(devs)

    # --- the answers, against the plain reference, once the window closed --
    session.release()
    gc.collect()
    checks, failed = session.check()

    units = int(sum(c["units"] for c in counters))
    run = dict(cell=args.workload, seed=args.seed, setup_s=setup_s,
               window_s=window_s, units=units, calls=len(counters),
               counters=counters, compiles_in_window=in_window["compiles"],
               compile_s_in_window=in_window["secs"],
               graph=dict(n=int(graph.n), nnz=int(graph.nnz)),
               work=session.work(), peaks=peaks[devs[0].device_kind]
               if devs[0].device_kind in peaks else None, trace=trace)
    metrics = {}
    for m in (layer if args.trace else e2e):
        value = readers[m["name"]].read(run)
        if value is None:
            if not args.trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    dev0 = devs[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(failed == 0 and all(
               c["value"] <= c["limit"] for c in checks.values())),
           "attempted": units,
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        out["breakdown"] = trace["breakdown"]
    out["checks"] = checks
    print(json.dumps(dict(diagnostics=dict(
        cell=args.workload, seed=args.seed, compile_cache=cache_dir,
        compiles_in_window=in_window, calls=len(counters), units=units,
        window_s=window_s, setup_s=setup_s, setup_phases=phases.rows,
        control=control, counters=counters[:8]))),
        file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def mean(values) -> Optional[float]:
    values = [float(v) for v in values]
    return float(np.mean(values)) if values else None
