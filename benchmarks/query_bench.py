"""Query-tier benchmark: batched PPR throughput + closed-loop load gen.

Two sections, both on the 50k acceptance graph:

  batched   — sequential per-seed `ppr_push` loop vs `ppr_push_batched`
              at batch sizes 4/16/32 (same tol, exact certification on
              every lane).  The gated number is the throughput ratio at
              batch >= 16.
  load      — closed-loop mixed traffic (top_k / scores / personalized)
              from concurrent client threads against a live RankServer
              whose daemon updater keeps applying 1%%-delta batches.
              Queries route through the full serving tier: QueryRouter
              read-replicas with staleness-bounded reads (top_k/scores),
              QueryBatcher + PPRCache behind personalized().  Reports
              p50/p99 latency per kind, queries/s-under-update, updater
              progress, and the staleness/cert invariants the gate
              checks (no router reject, every sampled snapshot cert
              certified, every PPR answer within tol).

Run: PYTHONPATH=src python -m benchmarks.query_bench
"""
from __future__ import annotations

import json
import threading
import time
from pathlib import Path

import numpy as np

from repro.graph.generate import powerlaw_webgraph
from repro.serving import attach_query_tier
from repro.serving.router import QueryRouter
from repro.streaming import (DeltaGraph, EdgeDelta, RankServer, ppr_push,
                             ppr_push_batched)

RESULTS = Path(__file__).parent / "results"
N, NNZ = 50_000, 400_000
ALPHA, QTOL = 0.85, 1e-4


def _graph(seed: int = 3):
    return powerlaw_webgraph(n=N, target_nnz=NNZ, n_dangling=50, seed=seed)


def _seed_sets(rng, count: int, n: int = N):
    return [rng.choice(n, size=int(rng.integers(1, 4)), replace=False)
            for _ in range(count)]


def batched_ppr(dg: DeltaGraph, seq_sample: int = 8,
                batches=(4, 16, 32)) -> dict:
    """Sequential per-seed loop vs the fused lane solve, same tol."""
    view = dg.freeze()
    op = dg.operator(ALPHA)
    pt = dg.scipy_pt()
    rng = np.random.default_rng(5)
    sets = _seed_sets(rng, max(batches))

    t0 = time.perf_counter()
    for s in sets[:seq_sample]:
        _, cert, _ = ppr_push(view, s, alpha=ALPHA, tol=QTOL)
        assert np.isfinite(cert)
    seq_per_q = (time.perf_counter() - t0) / seq_sample

    rows = []
    for nv in batches:
        ppr_push_batched(dg, sets[:nv], alpha=ALPHA, tol=QTOL,
                         op=op, pt_sp=pt)          # warm the path
        t0 = time.perf_counter()
        _, certs, stats = ppr_push_batched(dg, sets[:nv], alpha=ALPHA,
                                           tol=QTOL, op=op, pt_sp=pt)
        tb = time.perf_counter() - t0
        rows.append(dict(
            batch=nv, s=tb, ms_per_query=tb / nv * 1e3,
            speedup_vs_sequential=seq_per_q * nv / tb,
            path=stats.path, iters=int(stats.iters),
            certs_ok=bool(np.all(certs <= QTOL)),
            max_cert=float(certs.max())))
        print(f"  [query] batch={nv:3d} {tb:.2f}s "
              f"({tb / nv * 1e3:.0f} ms/q) "
              f"{rows[-1]['speedup_vs_sequential']:.2f}x vs sequential "
              f"[{stats.path}]")
    return dict(tol=QTOL, sequential_ms_per_query=seq_per_q * 1e3,
                sweep=rows,
                speedup_at_16=next(r["speedup_vs_sequential"]
                                   for r in rows if r["batch"] >= 16))


def _pct(a, q):
    return float(np.percentile(np.asarray(a), q)) if len(a) else float("nan")


def load_gen(dg: DeltaGraph, duration_s: float = 8.0, clients: int = 3,
             delta_frac: float = 0.01, server_tol: float = 1e-5) -> dict:
    """Closed-loop clients vs a live updater, through the full tier."""
    srv = RankServer(dg, alpha=ALPHA, tol=server_tol)
    batcher, cache, router = attach_query_tier(
        srv, max_batch=16, max_delay_s=0.005, cache_capacity=64,
        replicas=2, max_version_lag=2, on_stale="redirect")
    rng = np.random.default_rng(11)
    # a finite seed-set pool + skewed popularity so the cache sees repeats
    pool = _seed_sets(rng, 32, dg.n)
    pop = (1.0 / np.arange(1, 33)) ** 1.1
    pop /= pop.sum()

    stop = threading.Event()
    errors: list = []
    lat = {k: [] for k in ("top_k", "scores", "ppr")}
    bad_cert = [0]
    max_snap_cert = [0.0]
    lock = threading.Lock()

    def client(cid: int):
        crng = np.random.default_rng(100 + cid)
        my = {k: [] for k in lat}
        try:
            while not stop.is_set():
                u = crng.random()
                t0 = time.perf_counter()
                if u < 0.55:
                    ids, scores = router.top_k(int(crng.integers(1, 100)))
                    assert np.all(np.diff(scores) <= 0)
                    my["top_k"].append(time.perf_counter() - t0)
                elif u < 0.85:
                    vals = router.scores(crng.integers(0, dg.n, 8))
                    assert np.isfinite(vals).all()
                    my["scores"].append(time.perf_counter() - t0)
                else:
                    s = pool[int(crng.choice(32, p=pop))]
                    x, cert, _ = srv.personalized(s, tol=1e-3)
                    my["ppr"].append(time.perf_counter() - t0)
                    if not (np.isfinite(cert) and cert <= 1e-3):
                        with lock:
                            bad_cert[0] += 1
                snap = srv.snapshot()
                with lock:
                    max_snap_cert[0] = max(max_snap_cert[0],
                                           float(snap.cert))
        except BaseException as exc:
            errors.append(exc)
            stop.set()
        finally:
            with lock:
                for k in lat:
                    lat[k].extend(my[k])

    srv.start(poll_s=0.002)
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    k_delta = max(1, int(delta_frac * dg.graph().nnz))
    g = dg.graph()
    deltas_sent = 0
    try:
        while time.perf_counter() - t_start < duration_s \
                and not stop.is_set():
            src = rng.integers(0, dg.n, k_delta)
            dst = g.indices[rng.integers(0, g.nnz, k_delta)].astype(
                np.int64)
            srv.ingest(EdgeDelta(np.asarray(src, np.int64), dst,
                                 np.empty(0, np.int64),
                                 np.empty(0, np.int64)))
            deltas_sent += 1
            time.sleep(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        elapsed = time.perf_counter() - t_start
        srv.stop()
        batcher.stop()
    if errors:
        raise errors[0]

    total = sum(len(v) for v in lat.values())
    rec = dict(
        duration_s=elapsed, clients=clients,
        delta_edges_per_batch=k_delta, delta_batches_sent=deltas_sent,
        qps_under_update=total / elapsed,
        queries=dict((k, len(v)) for k, v in lat.items()),
        latency_ms=dict(
            (k, dict(p50=_pct(v, 50) * 1e3, p99=_pct(v, 99) * 1e3))
            for k, v in lat.items()),
        updater=dict(batches_applied=int(srv.batches_applied),
                     fallbacks=int(srv.fallbacks),
                     final_version=int(dg.version)),
        served_cert_ok=bool(max_snap_cert[0] <= server_tol * 1.01),
        max_served_cert=max_snap_cert[0],
        ppr_cert_violations=int(bad_cert[0]),
        router=router.stats(),
        batcher=batcher.stats(),
        cache=cache.stats())
    print(f"  [query] {total} queries in {elapsed:.1f}s "
          f"({rec['qps_under_update']:.0f} qps) while "
          f"{rec['updater']['batches_applied']} delta batches applied; "
          f"top_k p50/p99 = {rec['latency_ms']['top_k']['p50']:.1f}/"
          f"{rec['latency_ms']['top_k']['p99']:.1f} ms, "
          f"ppr p50 = {rec['latency_ms']['ppr']['p50']:.1f} ms, "
          f"cache hits = {rec['cache']['hits']}")
    return rec


def main() -> dict:
    print("  [query] building 50k graph ...")
    dg = DeltaGraph(_graph())
    print("  [query] batched PPR vs sequential ...")
    brec = batched_ppr(dg)
    print("  [query] closed-loop load gen (update-while-serve) ...")
    lrec = load_gen(dg)
    rec = dict(batched=brec, load=lrec)
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "query_bench.json").write_text(json.dumps(rec, indent=1))
    return rec


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
