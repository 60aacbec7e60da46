"""Program spans (`repro.runtime.observe.span`): the helper itself, the
refresh path's spans in a `jax.profiler` trace, and their seconds in
`RankServer.metrics()` / `metrics_text()`."""
import glob
import os

import numpy as np
import pytest

import repro.core  # noqa: F401  (resolves the runtime<->core import cycle)
from repro.graph.generate import powerlaw_webgraph
from repro.runtime.observe import span
from repro.streaming import DeltaGraph, EdgeDelta
from repro.streaming.server import RankServer

REFRESH_SPANS = {
    "serving.apply", "serving.publish", "update.apply_delta",
    "transport.operator", "transport.pack", "transport.dispatch",
    "transport.fetch", "certify.exact_residual",
}


def _device_server():
    g = powerlaw_webgraph(n=300, target_nnz=2000, n_dangling=3, seed=11)
    return RankServer(DeltaGraph(g), updater="sharded", shards=1,
                      shard_mode="async", shard_transport="device")


def _batch(seed):
    rng = np.random.default_rng(seed)
    return EdgeDelta.inserts(rng.integers(0, 300, 4),
                             rng.integers(0, 300, 4))


def test_span_nests_and_accumulates_into():
    into = {}
    with span("outer.a", into=into):
        with span("inner.b", into=into):
            pass
        with span("inner.b", into=into):
            pass
        with span("inner.c"):           # no dict: timed by nothing
            pass
    assert set(into) == {"outer.a", "inner.b"}
    assert 0.0 < into["inner.b"] <= into["outer.a"]
    first = into["outer.a"]
    with pytest.raises(RuntimeError):
        with span("outer.a", into=into):
            raise RuntimeError("the span still closes")
    assert into["outer.a"] > first      # added to, not replaced


def test_refresh_spans_land_in_a_profiler_trace(tmp_path):
    import jax
    srv = _device_server()
    srv.ingest(_batch(0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        stats = srv.apply_pending()
    finally:
        jax.profiler.stop_trace()
    assert stats.path == "sharded_push" and stats.transport == "device"

    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host, = [pl for pl in pd.planes if pl.name == "/host:CPU"]
    spans = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name in REFRESH_SPANS:
                spans.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert set(spans) == REFRESH_SPANS
    (a0, a1), = spans["serving.apply"]
    for s, e in spans["certify.exact_residual"] + spans["transport.pack"]:
        assert a0 <= s <= e <= a1


def test_phase_seconds_in_metrics_and_text():
    srv = _device_server()
    assert srv.metrics()["phase_s"] == {}
    srv.ingest(_batch(1))
    srv.apply_pending()
    m1 = srv.metrics()["phase_s"]
    assert set(m1) == REFRESH_SPANS
    assert all(v > 0.0 for v in m1.values())
    # serving.apply holds everything else the batch did
    assert m1["serving.apply"] >= max(v for k, v in m1.items()
                                      if k != "serving.apply")
    assert srv.apply_pending() is None          # an empty queue adds none
    assert srv.metrics()["phase_s"] == m1
    srv.ingest(_batch(2))
    stats = srv.apply_pending()
    m2 = srv.metrics()["phase_s"]
    # the updater's spans come from its stats; the server adds serving.*
    assert set(stats.phase_s) == REFRESH_SPANS - {"serving.apply",
                                                  "serving.publish"}
    for k, v in stats.phase_s.items():
        assert m2[k] == pytest.approx(m1[k] + v)
    assert all(m2[k] > m1[k] for k in REFRESH_SPANS)

    txt = srv.metrics_text()
    assert "# TYPE repro_rank_server_phase_seconds_total counter" in txt
    lines = [ln for ln in txt.splitlines()
             if ln.startswith("repro_rank_server_phase_seconds_total{")]
    assert len(lines) == len(REFRESH_SPANS)
    for k in REFRESH_SPANS:
        line, = [ln for ln in lines if f'{{phase="{k}"}}' in ln]
        assert float(line.split()[-1]) == pytest.approx(m2[k])


def test_incremental_updater_reports_serving_phases_only():
    g = powerlaw_webgraph(n=300, target_nnz=2000, n_dangling=3, seed=11)
    srv = RankServer(DeltaGraph(g))
    srv.ingest(_batch(3))
    srv.apply_pending()
    assert set(srv.metrics()["phase_s"]) == {"serving.apply",
                                             "serving.publish"}
