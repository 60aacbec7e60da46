"""The benchmark's yardstick, part by part, on the CPU: generators, traffic,
the plain reference, the work model, the trace reduction
and the rules that BENCHMARK.json keeps."""
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import workmodel  # noqa: E402
from edges import Graph  # noqa: E402

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def gen(name):
    return harness.load_module(BENCH, "graphs", name)


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 7])
def test_stanford_generator_is_the_programs(seed):
    from repro.graph.generate import powerlaw_webgraph
    kw = dict(n=4000, target_nnz=32000, n_dangling=9, locality=0.93,
              site_size=256, seed=seed)
    ours = gen("powerlaw_web").powerlaw_webgraph(**kw)
    theirs = powerlaw_webgraph(**kw)
    assert ours.n == theirs.n
    np.testing.assert_array_equal(ours.indptr(), theirs.indptr)
    np.testing.assert_array_equal(ours.dst, theirs.indices)


def block_row_counts(g: Graph, bm: int) -> np.ndarray:
    blocks = np.unique((g.dst // bm) * (g.n // bm + 1) + g.src // bm)
    return np.sort(np.bincount(blocks // (g.n // bm + 1)))


def test_site_relabelling_keeps_the_block_layout():
    web = gen("powerlaw_web")
    params = dict(n=5000, target_nnz=40000, n_dangling=9, alpha_out=2.2,
                  alpha_in=2.1, locality=0.93, site_size=256,
                  generation_seed=0)
    a, b = web.generate(params, 1), web.generate(params, 2**31 + 5)
    assert a.nnz == b.nnz and not np.array_equal(a.keys(), b.keys())
    np.testing.assert_array_equal(block_row_counts(a, 128),
                                  block_row_counts(b, 128))
    np.testing.assert_array_equal(np.sort(a.out_degree()),
                                  np.sort(b.out_degree()))
    np.testing.assert_array_equal(a.keys(), web.generate(params, 1).keys())


def test_kronecker_graph_is_a_graphalytics_set():
    kron = gen("kronecker")
    params = dict(scale=10, edge_factor=16, A=0.57, B=0.19, C=0.19,
                  generation_seed=0)
    g = kron.generate(params, 3)
    assert np.all(g.src != g.dst)
    np.testing.assert_array_equal(
        np.sort(g.keys()), np.sort(g.dst * g.n + g.src))   # symmetric
    assert np.all(g.out_degree() > 0)                      # no isolated
    assert np.unique(g.keys()).size == g.nnz
    h = kron.generate(params, 4)
    assert (h.n, h.nnz) == (g.n, g.nnz)
    np.testing.assert_array_equal(np.sort(g.out_degree()),
                                  np.sort(h.out_degree()))


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def test_churn_batches_keep_their_size_and_replay():
    g = gen("powerlaw_web").powerlaw_webgraph(n=3000, target_nnz=24000,
                                              seed=1)
    churn = harness.load_module(BENCH, "traffic", "refresh")
    mix = dict(batch_frac=0.01, insert_share=0.85, stream_seed=3)
    stream = churn.make(mix, g, 2**33 + 1)
    keys = g.keys()
    for _ in range(3):
        b = next(stream)
        add = b["add_src"] * g.n + b["add_dst"]
        dele = b["del_src"] * g.n + b["del_dst"]
        assert (add.size, dele.size) == (204, 36)
        assert not np.isin(add, keys).any() and np.isin(dele, keys).all()
        assert np.unique(add).size == add.size
        keys = np.union1d(np.setdiff1d(keys, dele), add)
        np.testing.assert_array_equal(stream.keys, keys)
    again = churn.make(mix, g, 2**33 + 1)
    for _ in range(3):
        next(again)
    np.testing.assert_array_equal(again.keys, stream.keys)


def test_churn_is_the_same_for_every_seed_up_to_relabelling():
    web = gen("powerlaw_web")
    params = dict(n=3000, target_nnz=24000, n_dangling=5, alpha_out=2.2,
                  alpha_in=2.1, locality=0.93, site_size=256,
                  generation_seed=0)
    churn = harness.load_module(BENCH, "traffic", "refresh")
    mix = dict(batch_frac=0.01, insert_share=0.85, stream_seed=3)
    graphs = [web.generate(params, s) for s in (5, 2**32 + 7)]
    streams = [churn.make(mix, g, s) for g, s in zip(graphs, (5, 2**32 + 7))]
    for _ in range(2):
        batches = [next(s) for s in streams]
        back = []
        for g, b in zip(graphs, batches):
            inv = np.argsort(g.perm)
            back.append({k: np.sort(inv[b[k + "src"]] * g.n
                                    + inv[b[k + "dst"]])
                         for k in ("add_", "del_")})
        for k in back[0]:
            np.testing.assert_array_equal(back[0][k], back[1][k])
        assert not np.array_equal(batches[0]["add_src"],
                                  batches[1]["add_src"])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def cycle(n):
    return Graph.from_pairs(n, np.arange(n), (np.arange(n) + 1) % n)


def test_reference_matches_closed_form_on_a_cycle():
    n, alpha = 97, 0.85
    x = reference.pagerank(cycle(n), alpha, tol=1e-14)
    np.testing.assert_allclose(x, np.full(n, 1.0 / n), rtol=1e-12)
    # from any start: the power method forgets it
    x0 = np.arange(1, n + 1, dtype=np.float64)
    x = reference.pagerank(cycle(n), alpha, x0=x0 / x0.sum(), tol=1e-15)
    np.testing.assert_allclose(x, np.full(n, 1.0 / n), rtol=1e-12)


def test_reference_matches_the_dense_google_matrix():
    rng = np.random.default_rng(0)
    n, alpha = 60, 0.85
    g = Graph.from_pairs(n, rng.integers(0, n - 3, 300),
                         rng.integers(0, n, 300))      # last 3 dangling
    A = np.zeros((n, n))
    A[g.src, g.dst] = 1.0
    deg = A.sum(1)
    S = np.where(deg[:, None] > 0, A / np.maximum(deg, 1)[:, None],
                 1.0 / n).T
    G = alpha * S + (1 - alpha) / n
    w, V = np.linalg.eig(G)
    x = np.real(V[:, np.argmax(np.real(w))])
    x /= x.sum()
    np.testing.assert_allclose(reference.pagerank(g, alpha, tol=1e-15), x,
                               atol=1e-12)


# ---------------------------------------------------------------------------
# work model and peaks
# ---------------------------------------------------------------------------
def test_work_model_counts_csr_work():
    n, nnz = 281_903, 2_312_497
    assert workmodel.csr_apply_bytes(n, nnz, 1, 4) == \
        4 * nnz + 4 * (n + 1) + 4 * n + 12 * n == 14_888_052
    peak = json.loads((BENCH / "peaks.json").read_text())[
        "devices"]["TPU v5 lite"]
    t = workmodel.least_apply_s(n, nnz, 1, 4, peak)
    assert t == pytest.approx(14_888_052 / 819e9)     # memory bounds it
    assert workmodel.csr_apply_bytes(n, nnz, 16, 4) > \
        workmodel.csr_apply_bytes(n, nnz, 1, 4)


class FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind, chips, ok", [
    ("TPU v5 lite", 1, True), ("TPU v9 unknown", 1, False),
    ("TPU v5 lite", 4, False)])
def test_device_check(monkeypatch, kind, chips, ok):
    import jax
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    monkeypatch.setattr(jax, "devices", lambda: [FakeDevice(kind)])
    if ok:
        assert len(harness.find_devices(chips, peaks, True)) == 1
    else:
        with pytest.raises(harness.Refused):
            harness.find_devices(chips, peaks, True)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_trace_reduction_on_a_synthetic_trace():
    ms = 1e6
    device = {0: [("fusion.1", 10 * ms, 30 * ms),
                  ("bsr_kernel", 25 * ms, 50 * ms),   # overlaps fusion.1
                  ("fusion.1", 70 * ms, 80 * ms),
                  ("outside", 200 * ms, 300 * ms)],   # after the window
              1: [("other", 0, 100 * ms)]}
    host = [(devtrace.WINDOW_SPAN, 0, 100 * ms),
            ("bench.solve_power", 0, 100 * ms),
            ("host_pack", 50 * ms, 70 * ms),
            ("copy_back", 85 * ms, 99 * ms)]
    out = devtrace.reduce_trace(device, host, n_devices=1, window_s=0.1)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.05)      # 10-50 and 70-80 ms
    assert out["devices"] == 1
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 0.03, "bsr_kernel": 0.025})
    nested = devtrace.self_times([("while.3", 0, 100), ("body", 10, 40),
                                  ("body", 50, 60)], 0, 100)
    assert nested == pytest.approx({"while.3": 60e-9, "body": 40e-9})
    gaps = dict(out["breakdown"]["idle_gaps"])
    # each gap goes, whole, to the innermost span over its middle
    assert gaps == pytest.approx({"bench.solve_power": 0.01,
                                  "host_pack": 0.02, "copy_back": 0.02})
    assert devtrace.idle_share_pct(dict(trace=out)) == pytest.approx(50.0)
    two = devtrace.reduce_trace(device, host, n_devices=2, window_s=0.1)
    assert two["busy_s"] == pytest.approx((0.05 + 0.1) / 2)


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.3" } }
  event_metadata { key: 3 value { id: 3 name: "jit_solve" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
"""


def test_trace_loading_reads_device_ops_and_host_spans():
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(XSPACE)
    device, host = devtrace.load_events(pd)
    assert [e[0] for e in device[0]] == ["fusion.7", "custom-call.3"]
    assert [e[0] for e in host] == [devtrace.WINDOW_SPAN]
    out = devtrace.reduce_trace(device, host, n_devices=1, window_s=0.005)
    assert out["window_s"] == pytest.approx(0.005)
    assert out["busy_s"] == pytest.approx(0.003)


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_and_units_use_allowed_characters():
    b = BENCHMARK
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    names += [c["name"] for c in b["configs"]]
    names += [k for c in b["configs"] for k in c["reduced"]]
    names += [w[k] for w in b["workloads"]
              for k in ("name", "config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in b["end_to_end"]
               + b["per_layer"])
    for group in (b["end_to_end"], b["per_layer"], b["configs"],
                  b["workloads"]):
        assert len({x["name"] for x in group}) == len(group)
    for text in ([w["why"] for w in b["workloads"]]
                 + [m["layer"] for m in b["per_layer"]]
                 + [c["source"] for c in b["configs"]] + b["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text


def test_every_name_has_its_file():
    b = BENCHMARK
    for c in b["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in b["workloads"]:
        assert harness.load_json(BENCH, "configs", w["config"])
        wl = harness.load_json(BENCH, "workloads", w["name"])
        entry = harness.load_module(BENCH, "entries", wl["entry"])
        if getattr(entry, "USES_TRAFFIC", True):
            mix = harness.load_json(BENCH, "traffic", w["traffic"])
            assert (BENCH / "traffic" / f"{mix['kind']}.py").is_file()
        assert w["chips"] in (1, 4)
    for m in b["end_to_end"] + b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert all(m["moves"] in e2e for m in b["per_layer"])
    for w in b["workloads"]:
        assert len(harness.cell_metrics(b, w["name"], "end_to_end")) >= 2
        assert harness.cell_metrics(b, w["name"], "per_layer")
