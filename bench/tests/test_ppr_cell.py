"""The batched personalized PageRank cell, `stanford-web.ppr`, on the CPU:
the harness finds it by name, a tiny copy runs correct through the whole
harness, and a broken lane, the control or a program without the
counters its metrics read does not pass; its metrics read traced records."""
import dataclasses
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import control  # noqa: E402
import harness  # noqa: E402
from test_bench_runs import run, tiny_checkout  # noqa: E402
from workmodel import csr_apply_bytes  # noqa: E402

CELL = "stanford-web.ppr"
TINY = "tiny-web.ppr"
METRICS = ["ppr_kernel_roofline", "idle_share.ppr", "ppr_idle_s_per_batch",
           "solver_idle_s_per_batch", "lane_iters_per_batch"]
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


@pytest.fixture
def checkout(tmp_path):
    """tiny_checkout with a tiny copy of the cell's configuration, on the
    tiny web graph's sizes, and a tiny copy of the cell on it."""
    root = tiny_checkout(tmp_path)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    spec = harness.cell_spec(bm, CELL)
    cfg = harness.load_json(root / "bench", "configs", spec["config"])
    cfg["params"] = harness.load_json(root / "bench", "configs",
                                      "tiny-web")["params"]
    (root / "bench/configs/tiny-web-ppr.json").write_text(json.dumps(cfg))
    bm["configs"].append(dict(
        next(c for c in bm["configs"] if c["name"] == spec["config"]),
        name="tiny-web-ppr", file="bench/configs/tiny-web-ppr.json"))
    bm["workloads"].append(dict(spec, name=TINY, config="tiny-web-ppr"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    shutil.copy(root / "bench/workloads" / f"{CELL}.json",
                root / "bench/workloads" / f"{TINY}.json")
    return root


def test_harness_finds_the_cell():
    spec = harness.cell_spec(BENCHMARK, CELL)
    assert (spec["config"], spec["traffic"], spec["chips"]) == (
        "stanford-web-ppr", "ppr-batch16", 1)
    # the deployment's graph is the paper's crawl at its full size
    web = harness.load_json(BENCH, "configs", "stanford-web")
    cfg = harness.load_json(BENCH, "configs", spec["config"])
    assert (cfg["generator"], cfg["params"], cfg["alpha"], cfg["reduced"]) \
        == (web["generator"], web["params"], web["alpha"], [])
    wl = harness.load_json(BENCH, "workloads", CELL)
    entry = harness.load_module(BENCH, "entries", wl["entry"])
    mix = harness.load_json(BENCH, "traffic", spec["traffic"])
    assert getattr(entry, "USES_TRAFFIC", True) and mix["batch"] == 16
    assert harness.load_module(BENCH, "traffic", mix["kind"]).make
    e2e = {m["name"] for m in harness.cell_metrics(BENCHMARK, CELL,
                                                   "end_to_end")}
    layer = {m["name"] for m in harness.cell_metrics(BENCHMARK, CELL,
                                                     "per_layer")}
    assert e2e == {"setup_s", "solve_s"} and layer == set(METRICS)


def test_tiny_cell_runs_correct(checkout, capsys):
    rc, out, err = run(checkout, TINY, capsys)
    assert rc == 0, err
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"l1_err", "cert"}
    assert out["checks"]["cert"]["limit"] == 1e-4
    assert 0 < out["checks"]["l1_err"]["value"] <= 1e-4
    assert {"setup_s", "solve_s"} <= set(out["metrics"])
    diag = json.loads([ln for ln in err.splitlines()
                       if ln.startswith("{")][-1])["diagnostics"]
    assert diag["compiles_in_window"]["compiles"] == 0
    c = diag["counters"][0]
    assert len(c["lane_iters"]) == 16 and c["chunks"] == len(c["widths"])
    assert sum(i for _, i in c["widths"]) == c["iters"]


def broken_lane(monkeypatch):
    """One lane's answer altered where it is produced."""
    import repro.streaming
    real = repro.streaming.ppr_push_batched

    def solve(*a, **kw):
        x, certs, stats = real(*a, **kw)
        x = x.copy()
        x[0, -1] += 1e-3
        return x, certs, stats
    monkeypatch.setattr(repro.streaming, "ppr_push_batched", solve)


def false_certificate(monkeypatch):
    """The answers as they were, one certificate over its tolerance."""
    import repro.streaming
    real = repro.streaming.ppr_push_batched

    def solve(*a, **kw):
        x, certs, stats = real(*a, **kw)
        certs = certs.copy()
        certs[-1] = 2e-4
        return x, certs, stats
    monkeypatch.setattr(repro.streaming, "ppr_push_batched", solve)


@pytest.mark.parametrize("fault", [broken_lane, false_certificate],
                         ids=lambda f: f.__name__)
def test_broken_batch_is_not_correct(checkout, capsys, monkeypatch, fault):
    fault(monkeypatch)
    rc, out, err = run(checkout, TINY, capsys)
    assert rc == 0, err
    assert out["correct"] is False and out["failed"] == out["attempted"]


def test_control_fails_the_cells_limit(checkout, capsys):
    (row,) = control.readings(TINY, [2**31 + 11], 0.5,
                              bench_root=checkout / "bench",
                              require_tpu=False)
    capsys.readouterr()
    assert row["rc"] == 0 and row["correct"] is False and row["failed"] > 0
    assert row["checks"]["l1_err"]["value"] > row["checks"]["l1_err"]["limit"]


def test_program_without_the_counters_fails_before_packing(
        checkout, capsys, monkeypatch):
    """A program whose BatchedPPRStats lacks the counters (the parent of
    the cell) stops in set-up, before a layout is packed."""
    import repro.kernels.bsr_spmv as bsr
    import repro.streaming.incremental as inc

    @dataclasses.dataclass
    class OldStats:
        path: str
        nv: int
        iters: int
    packs = []
    monkeypatch.setattr(inc, "BatchedPPRStats", OldStats)
    monkeypatch.setattr(bsr, "hybrid_from_transition",
                        lambda *a, **kw: packs.append(1))
    with pytest.raises(RuntimeError, match="cannot run the cell"):
        run(checkout, TINY, capsys)
    assert not packs


# ---------------------------------------------------------------------------
# the new metrics on recorded run records
# ---------------------------------------------------------------------------
def reader(name):
    return harness.load_module(BENCH, "metrics", name).read


PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
LANES = [[70, 70, 68, 65], [71, 71, 71, 71]]


def ppr_run(device_ops=(("bsr_spmv.13", 0.6), ("bsr_spmv.12", 0.5),
                        ("fusion.28", 0.2)), busy_s=1.4, trace=True):
    return dict(
        units=2, graph=dict(n=281903, nnz=2312497), peaks=PEAK,
        work=dict(itemsize=4),
        counters=[dict(units=1, lane_iters=li) for li in LANES],
        trace=dict(busy_s=busy_s, window_s=1.5, devices=1,
                   breakdown=dict(device_ops=[list(o) for o in device_ops],
                                  idle_gaps=[["ppr.certify", 0.03],
                                             ["solver.recheck", 0.02],
                                             ["ppr.stack", 0.01],
                                             ["bench.ppr", 0.04]]))
        if trace else None)


@pytest.mark.parametrize("metric", METRICS)
def test_new_metric_reads_a_traced_record(metric):
    value = reader(metric)(ppr_run())
    assert isinstance(value, float) and np.isfinite(value) and value > 0


def test_new_metrics_read_their_numbers():
    r = ppr_run()
    want = sum(csr_apply_bytes(281903, 2312497, sum(i >= t for i in li), 4)
               for li in LANES for t in range(1, max(li) + 1))
    assert reader("ppr_kernel_roofline")(r) == pytest.approx(
        100 * want / 819e9 / 1.1)
    # the first batch: 65 iterations at 4 lanes, then 3 at 3, 2 at 2
    assert want == (sum(csr_apply_bytes(281903, 2312497, a, 4) * k
                    for a, k in ((4, 65), (3, 3), (2, 2)))
                    + csr_apply_bytes(281903, 2312497, 4, 4) * 71)
    assert reader("idle_share.ppr")(r) == pytest.approx(100 * (1 - 1.4 / 1.5))
    assert reader("ppr_idle_s_per_batch")(r) == pytest.approx(0.02)
    assert reader("solver_idle_s_per_batch")(r) == pytest.approx(0.01)
    assert reader("lane_iters_per_batch")(r) == (273 + 284) / 2


@pytest.mark.parametrize("metric", METRICS[:4])
def test_trace_metrics_read_nothing_without_a_traced_device(metric):
    assert reader(metric)(ppr_run(trace=False)) is None
    assert reader(metric)(ppr_run(busy_s=0.0)) is None


def test_kernel_roofline_reads_nothing_where_no_kernel_ran():
    assert reader("ppr_kernel_roofline")(
        ppr_run(device_ops=[("fusion.16", 1.0)])) is None
