"""Whole runs of the harness on the CPU, at a tiny size: it finds a new
cell by name, refuses to run without a TPU, and reports `correct` false
when the timed path underneath is broken or the control is in its place."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import harness  # noqa: E402

# tiny cell -> (its configuration, the cell it copies)
TINY = {
    "tiny-web.batch": ("tiny-web", "stanford-web.batch"),
    "tiny-web.refresh": ("tiny-web", "stanford-web.refresh"),
    "tiny-kron.batch": ("tiny-kron", "graph500-22.batch"),
}
TINY_PARAMS = {
    "tiny-web": ("stanford-web", dict(n=2000, target_nnz=16000,
                                      n_dangling=5)),
    "tiny-kron": ("graph500-22", dict(scale=9)),
}


def tiny_checkout(tmp_path: Path) -> Path:
    """A checkout with tiny configurations and one tiny cell for each
    cell, added as new files and BENCHMARK.json entries."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    b = root / "bench"
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, (base, params) in TINY_PARAMS.items():
        cfg = json.loads((b / f"configs/{base}.json").read_text())
        cfg["params"].update(params)
        (b / f"configs/{name}.json").write_text(json.dumps(cfg))
        bm["configs"].append(dict(
            next(c for c in bm["configs"] if c["name"] == base),
            name=name, file=f"bench/configs/{name}.json"))
    for name, (config, cell) in TINY.items():
        spec = harness.cell_spec(bm, cell)
        bm["workloads"].append(dict(spec, name=name, config=config))
        shutil.copy(b / "workloads" / f"{cell}.json",
                    b / "workloads" / f"{name}.json")
        for m in bm["end_to_end"] + bm["per_layer"]:
            if cell in m.get("workloads", ()):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def run(root: Path, cell: str, capsys, trace: int = 0, seed: int = 2**31 + 9):
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "1", "--trace", str(trace)],
                      bench_root=root / "bench", require_tpu=False)
    out = capsys.readouterr()
    return rc, (json.loads(out.out.strip().splitlines()[-1])
                if rc == 0 else None), out.err


@pytest.fixture
def checkout(tmp_path):
    return tiny_checkout(tmp_path)


@pytest.mark.parametrize("kind", ["batch", "refresh"])
def test_tiny_cell_runs_correct(checkout, capsys, kind):
    rc, out, err = run(checkout, f"tiny-web.{kind}", capsys)
    assert rc == 0, err
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    assert err.strip().splitlines()[-1].startswith("check ")


def test_new_config_cell_and_metric_found_by_name(checkout, capsys):
    """A cell, its configuration and a per-layer metric added as files and
    BENCHMARK.json entries alone, with no other file edited."""
    b = checkout / "bench"
    cfg = json.loads((b / "configs/tiny-web.json").read_text())
    cfg["params"].update(n=1500, target_nnz=9000)
    (b / "configs/tinier-web.json").write_text(json.dumps(cfg))
    shutil.copy(b / "workloads/tiny-web.batch.json",
                b / "workloads/tinier-web.batch.json")
    (b / "metrics/calls_in_window.py").write_text(
        "def read(run):\n    return run['calls']\n")
    bm = json.loads((checkout / "BENCHMARK.json").read_text())
    bm["configs"].append(dict(bm["configs"][0], name="tinier-web",
                              file="bench/configs/tinier-web.json"))
    bm["workloads"].append(dict(harness.cell_spec(bm, "tiny-web.batch"),
                                name="tinier-web.batch",
                                config="tinier-web"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "tiny-web.batch" in m.get("workloads", ()):
            m["workloads"].append("tinier-web.batch")
    bm["per_layer"].append(dict(
        name="calls_in_window", unit="calls", better="higher",
        source="program_counter", layer="batch solver", moves="solve_s",
        workloads=["tinier-web.batch"]))
    (checkout / "BENCHMARK.json").write_text(json.dumps(bm))
    rc, out, err = run(checkout, "tinier-web.batch", capsys, trace=1)
    assert rc == 0, err
    assert out["correct"]
    assert out["metrics"]["calls_in_window"]["value"] >= 1
    assert out["metrics"]["solve_iters"]["value"] > 0
    assert "breakdown" in out and "window_s" in out["device"]


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stanford-web.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path, capsys):
    """Only BENCHMARK.json and bench/: no program to run."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    rc = harness.main(["--workload", "stanford-web.batch", "--seed", "1",
                       "--seconds", "1", "--trace", "0"],
                      bench_root=tmp_path / "bench", require_tpu=False)
    assert rc != 0 and capsys.readouterr().out == ""


# ---------------------------------------------------------------------------
# the timed path broken underneath: `correct` must come out false
# ---------------------------------------------------------------------------
def unchanged_solve(monkeypatch):
    """The solve returns its start vector: a step that leaves its state
    as it was."""
    import repro.core
    real = repro.core.solve_power

    def solve(op, *a, **kw):
        res = real(op, *a, **kw)
        res.x = np.full_like(res.x, 1.0 / res.x.size)
        return res
    monkeypatch.setattr(repro.core, "solve_power", solve)


def altered_solve(monkeypatch):
    """One rank in the answer altered where it is produced."""
    import repro.core
    real = repro.core.solve_power

    def solve(op, *a, **kw):
        res = real(op, *a, **kw)
        res.x = res.x.copy()
        res.x[res.x.size // 2] += 1e-3
        return res
    monkeypatch.setattr(repro.core, "solve_power", solve)


def unchanged_update(monkeypatch):
    """The update applies the delta to the graph and leaves the ranks as
    they were."""
    import repro.streaming.server as server
    real = server.update_ranks_sharded

    def update(dg, delta, state, **kw):
        x0 = state.x.copy()
        state, stats = real(dg, delta, state, **kw)
        state.x[:] = x0
        return state, stats
    monkeypatch.setattr(server, "update_ranks_sharded", update)


def altered_update(monkeypatch):
    import repro.streaming.server as server
    real = server.update_ranks_sharded

    def update(dg, delta, state, **kw):
        state, stats = real(dg, delta, state, **kw)
        state.x[0] += 1e-7
        return state, stats
    monkeypatch.setattr(server, "update_ranks_sharded", update)


@pytest.mark.parametrize("kind, fault", [
    ("batch", unchanged_solve), ("batch", altered_solve),
    ("refresh", unchanged_update), ("refresh", altered_update)],
    ids=lambda v: getattr(v, "__name__", v))
def test_broken_timed_path_is_not_correct(checkout, capsys, monkeypatch,
                                          kind, fault):
    fault(monkeypatch)
    rc, out, err = run(checkout, f"tiny-web.{kind}", capsys)
    assert rc == 0, err
    assert out["correct"] is False and out["failed"] > 0


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_fails_the_cells_limit(checkout, capsys, cell):
    """The reference at the control's lower precision, put in the program's
    place in a whole run of the harness, comes out as not correct, by a
    number the run compares beside its limit."""
    rows = list(control.readings(cell, [2**31 + 11], 0.5,
                                 bench_root=checkout / "bench",
                                 require_tpu=False))
    capsys.readouterr()
    (row,) = rows
    assert row["rc"] == 0 and row["correct"] is False and row["failed"] > 0
    assert row["checks"]["l1_err"]["value"] > row["checks"]["l1_err"]["limit"]
