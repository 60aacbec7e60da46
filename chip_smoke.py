#!/usr/bin/env python3
"""Drive the PageRank main path once on a TPU at the paper's Stanford-Web size.

    python chip_smoke.py [--seed N]          # one chip, every phase below
    python chip_smoke.py --chips 4 [--seed N]  # the sharded path on four chips

The graph is `stanford_web_replica(seed)`: 281,903 pages and 2,312,497
links, the published Stanford-Web statistics. Everything is made from the
seed; nothing is read from outside the repository.

One chip, in one process:
  batch         solve_power(backend="bsr_pallas"), the compiled Pallas BSR
                kernel at bm=128, and solve_linear (f64 segment-sum), each
                against exact_pagerank (host scipy f64).
  served        RankServer(updater="sharded", shards=1,
                shard_transport="device") applying a seeded 1% edge delta
                and single-edge deltas inline; every batch must take the
                device path with a certificate within tol and no fallback.
  personalized  RankServer.personalized and ppr_push_batched over 16 seeded
                seed sets; every lane's certificate within tol.
With --chips 4, only:
  sharded       update_ranks_sharded(p=4, transport="device") under
                "allgather" and "sparsified", and solve_spmd(p=4, "linear"),
                each against its one-chip run and exact_pagerank, with the
                shard arrays spanning four devices.

Each phase prints one JSON line: resolved backend, compile seconds (JAX's
own compile events, persistent-cache reads included) against run seconds,
certificates, L1 distance to the reference and peak device bytes. The last
line is {"ok": true, "device": {...}}. With no TPU, or when any check
fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ALPHA = 0.85

# Device kinds this smoke was sized for: v5e ("TPU v5 lite"), 16 GB of HBM
# per chip (Google Cloud documentation, "TPU v5e"), which the 10.1 GB
# block array must fit. Any other kind is an error, not a default.
KNOWN_KINDS = ("TPU v5 lite",)

# The f32 BSR solve's residual threshold, an order above the f32 floor of
# the power method on this graph (see PERF.md, "Findings").
BSR_TOL = 1e-5
SERVE_TOL = 1e-8        # certified L1 error of every served batch
# solve_spmd (--chips 4) runs in f32 and stops on a per-shard inf-norm
# change of SPMD_LOCAL_TOL; its answer is held to the f32 solve's exact
# residual threshold, and to unit mass, before anything is normalized
SPMD_LOCAL_TOL = 1e-8
SPMD_TOL = BSR_TOL
PPR_TOL = 1e-4          # certified L1 error of every personalized lane
N_QUERIES = 16
TOP = 100


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's compile-duration events (tracing, lowering, and the
    backend compile or its persistent-cache read) and counts
    persistent-cache hits, so a phase can split compile from run time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Phase:
    """Times one phase and prints its JSON line, with "failed" set when
    one of its checks failed."""

    def __init__(self, name: str, clock: CompileClock):
        self.clock = clock
        self.rec: dict = {"phase": name}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.secs, self.clock.hits
        return self.rec

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None and exc_type is not SmokeFailure:
            return False
        import jax
        self.rec.update(
            wall_s=time.perf_counter() - self.t0,
            compile_s=self.clock.secs - self.c0,
            cache_hits=self.clock.hits - self.h0,
            peak_bytes_in_use=[(d.memory_stats() or {}).get(
                "peak_bytes_in_use") for d in jax.local_devices()])
        if exc is not None:
            # a failed check still shows what the phase measured
            self.rec["failed"] = str(exc)
        print(json.dumps(self.rec), flush=True)
        return False


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def top_overlap(x, y, k: int = TOP) -> float:
    a = np.argsort(-x, kind="stable")[:k]
    b = np.argsort(-y, kind="stable")[:k]
    return len(np.intersect1d(a, b)) / k


def certify(op, x) -> tuple:
    """(exact f64 L1 residual of the linear form, certificate
    ||x - x*||_1 <= resid / (1 - alpha)) of an iterate, on the host."""
    resid = float(np.abs(op.apply_linear_numpy(x) - x).sum())
    return resid, resid / (1.0 - op.alpha)


def reference(op) -> tuple:
    """exact_pagerank (host f64) and its own certificate: a distance to
    it bounds the distance to the true fixed point only up to that."""
    from repro.graph.google import exact_pagerank
    x_ref = exact_pagerank(op)
    return x_ref, certify(op, x_ref)[1]


def one_percent_delta(g, rng):
    """A seeded ~1% edge delta: 85% inserts, 15% deletes of existing
    edges."""
    from repro.streaming import EdgeDelta
    k = g.nnz // 100
    n_del = k * 15 // 100
    slots = rng.choice(g.nnz, size=n_del, replace=False)
    src_of_edge = np.repeat(np.arange(g.n, dtype=np.int64),
                            np.diff(g.indptr))
    return EdgeDelta(
        add_src=rng.integers(0, g.n, k - n_del),
        add_dst=g.indices[rng.integers(0, g.nnz, k - n_del)].astype(np.int64),
        del_src=src_of_edge[slots],
        del_dst=g.indices[slots].astype(np.int64))


def kernel_in_loop(op, spec, tol: float) -> bool:
    """Whether the compiled fused power loop holds the Mosaic kernel."""
    import jax.numpy as jnp
    from repro.core.backend import prepare
    from repro.core.pagerank import _solve_jit
    dev, meta, x0 = prepare(op, spec, jnp.float32)
    text = _solve_jit.lower(
        dev, x0, jnp.full((meta.nv,), tol, jnp.float32), meta=meta,
        linear=False, max_iters=1000).compile().as_text()
    return "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_batch(g, clock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import solve_linear, solve_power
    from repro.core.backend import as_spec, prepare
    from repro.graph.csr import TransitionT
    from repro.graph.google import GoogleOperator

    op = GoogleOperator(pt=TransitionT.from_graph(g), alpha=ALPHA)
    (x_ref, ref_cert), ref_s = timed(reference, op)

    with Phase("batch_bsr_pallas", clock) as rec:
        spec = as_spec("bsr_pallas")
        check(spec.impl == "pallas" and spec.bm == 128,
              f"bsr_pallas resolved to impl={spec.impl} bm={spec.bm}, "
              "not the compiled kernel at bm=128")
        # pack on the host and upload; device_put returns before the
        # transfer ends, so wait for it
        _, pack_s = timed(lambda: jax.block_until_ready(
            prepare(op, spec, jnp.float32)[::2]))
        hyb = op.hybrid_bsr(bm=spec.bm, bn=spec.bm,
                            hub_quantile=spec.hub_quantile)
        c0 = clock.secs
        res, first_s = timed(solve_power, op, tol=BSR_TOL,
                             backend="bsr_pallas")
        compile_s = clock.secs - c0
        res, run_s = timed(solve_power, op, tol=BSR_TOL,
                           backend="bsr_pallas")
        check(kernel_in_loop(op, spec, BSR_TOL),
              "no tpu_custom_call in the compiled bsr_pallas loop")
        resid, cert = certify(op, res.x)
        err = float(np.abs(res.x - x_ref).sum())
        rec.update(
            backend="bsr_pallas", impl=spec.impl, bm=spec.bm,
            nbr=hyb.bsr.nbr, K=hyb.bsr.K,
            block_bytes=int(hyb.bsr.blocks.nbytes),
            hub_nnz_frac=hyb.hub_nnz_frac, tpu_custom_call=True,
            n=op.n, nnz=int(g.nnz), ref_s=ref_s, ref_cert=ref_cert,
            pack_upload_s=pack_s, first_call_s=first_s, first_call_compile_s=compile_s,
            run_s=run_s, iters=res.iters, tol=BSR_TOL,
            solver_resid=res.resid_l1, exact_resid=resid, cert=cert,
            l1_err=err, top100=top_overlap(res.x, x_ref))
        check(res.resid_l1 <= BSR_TOL and resid <= BSR_TOL,
              f"bsr_pallas residual {resid:.3e} above tol {BSR_TOL}")
        check(err <= cert + ref_cert,
              f"bsr_pallas L1 error {err:.3e} > cert {cert:.3e}")
        check(rec["top100"] >= 0.95, f"bsr_pallas top-100 {rec['top100']}")
    del hyb
    op._cache().clear()
    gc.collect()

    with Phase("batch_segment_sum_f64", clock) as rec:
        c0 = clock.secs
        res, first_s = timed(solve_linear, op)
        compile_s = clock.secs - c0
        res, run_s = timed(solve_linear, op)
        resid, cert = certify(op, res.x)
        err = float(np.abs(res.x - x_ref).sum())
        rec.update(
            backend="segment_sum", dtype="float64",
            first_call_s=first_s, first_call_compile_s=compile_s,
            run_s=run_s, iters=res.iters, tol=1e-9,
            solver_resid=res.resid_l1, exact_resid=resid, cert=cert,
            l1_err=err, top100=top_overlap(res.x, x_ref))
        check(resid <= 1e-9, f"segment_sum residual {resid:.3e} > 1e-9")
        check(err <= cert + ref_cert,
              f"segment_sum L1 error {err:.3e} > cert {cert:.3e}")
        check(rec["top100"] >= 0.99, f"segment_sum top-100 {rec['top100']}")


def phase_served(g, clock, rng):
    from repro.streaming import DeltaGraph, EdgeDelta, RankServer

    with Phase("served_updates", clock) as rec:
        dg = DeltaGraph(g)
        srv, cold_s = timed(
            RankServer, dg, alpha=ALPHA, tol=SERVE_TOL, updater="sharded",
            shards=1, shard_mode="async", shard_transport="device")
        check(srv.snapshot().cert <= SERVE_TOL,
              f"cold certificate {srv.snapshot().cert:.3e} > {SERVE_TOL}")
        deltas = [one_percent_delta(g, rng)] + [
            EdgeDelta.inserts(rng.integers(0, g.n, 1),
                              rng.integers(0, g.n, 1)) for _ in range(3)]
        batches = []
        for delta in deltas:
            srv.ingest(delta)
            c0 = clock.secs
            st, wall = timed(srv.apply_pending)
            batches.append(dict(
                edges=int(delta.add_src.size + delta.del_src.size),
                path=st.path, transport=st.transport, devices=st.devices,
                wall_s=wall, compile_s=clock.secs - c0,
                attempts=st.attempts, supersteps=st.supersteps,
                cert=st.cert))
            check(st.path == "sharded_push" and st.transport == "device",
                  f"batch took path={st.path} transport={st.transport}")
            check(st.cert <= SERVE_TOL,
                  f"batch certificate {st.cert:.3e} > {SERVE_TOL}")
            check(srv.fallbacks == 0, f"{srv.fallbacks} fallbacks")
            health = srv.health()
            check(health["status"] == "ok", f"server health {health}")
        snap = srv.snapshot()
        x_ref, ref_cert = reference(dg.operator(ALPHA))
        err = float(np.abs(snap.x - x_ref).sum())
        rec.update(cold_state_s=cold_s, tol=SERVE_TOL, batches=batches,
                   fallbacks=srv.fallbacks, version=snap.version,
                   cert=snap.cert, l1_err=err,
                   top100=top_overlap(snap.x, x_ref))
        check(err <= snap.cert + ref_cert, f"served L1 error {err:.3e} > "
              f"cert {snap.cert:.3e}")
    return srv


def phase_personalized(srv, clock, rng) -> None:
    from repro.streaming import ppr_push_batched

    n = srv.dg.n
    seed_sets = [rng.choice(n, size=int(rng.integers(1, 9)), replace=False)
                 for _ in range(N_QUERIES)]
    with Phase("personalized", clock) as rec:
        singles, single_s = timed(
            lambda: [srv.personalized(s, tol=PPR_TOL) for s in seed_sets])
        certs = np.array([c for _, c, _ in singles])
        check(bool(np.all(certs <= PPR_TOL)),
              f"personalized certificate {certs.max():.3e} > {PPR_TOL}")
        c0 = clock.secs
        (X, bcerts, bst), first_s = timed(ppr_push_batched, srv.dg,
                                          seed_sets, tol=PPR_TOL)
        compile_s = clock.secs - c0
        (X, bcerts, bst), run_s = timed(ppr_push_batched, srv.dg,
                                        seed_sets, tol=PPR_TOL)
        check(bool(np.all(bcerts <= PPR_TOL)),
              f"batched certificate {bcerts.max():.3e} > {PPR_TOL}")
        gap = np.array([np.abs(X[:, i] - x).sum()
                        for i, (x, _, _) in enumerate(singles)])
        check(bool(np.all(gap <= bcerts + certs)),
              "batched and per-query answers disagree beyond their "
              "certificates")
        rec.update(queries=N_QUERIES, tol=PPR_TOL,
                   single_path=sorted({st.path for _, _, st in singles}),
                   single_s=single_s, single_cert_max=float(certs.max()),
                   batched_path=bst.path, batched_iters=bst.iters,
                   batched_first_call_s=first_s,
                   batched_first_call_compile_s=compile_s,
                   batched_run_s=run_s,
                   batched_cert_max=float(bcerts.max()),
                   max_gap=float(gap.max()))


def phase_sharded(g, clock, rng) -> None:
    import jax
    from repro.core import SPMDConfig, solve_spmd
    from repro.graph.csr import TransitionT
    from repro.graph.google import GoogleOperator
    from repro.streaming import (DeltaGraph, RankState, cold_state,
                                 update_ranks_sharded)

    check(len(jax.devices()) >= 4, f"--chips 4 found {len(jax.devices())} "
          "devices")
    delta = one_percent_delta(g, rng)
    with Phase("sharded_updates", clock) as rec:
        base = cold_state(DeltaGraph(g), alpha=ALPHA, tol=SERVE_TOL)
        runs = {}
        for p, exchange in ((1, "allgather"), (4, "allgather"),
                            (4, "sparsified")):
            dg = DeltaGraph(g)
            st = RankState(x=base.x.copy(), r=base.r.copy(),
                           version=dg.version, alpha=ALPHA)
            c0 = clock.secs
            (st, stats), wall = timed(
                update_ranks_sharded, dg, delta, st, p=p, tol=SERVE_TOL,
                exchange=exchange, mode="async", transport="device")
            check(stats.path == "sharded_push"
                  and stats.transport == "device",
                  f"p={p} {exchange} took path={stats.path}")
            check(stats.cert <= SERVE_TOL, f"p={p} {exchange} certificate "
                  f"{stats.cert:.3e} > {SERVE_TOL}")
            check(stats.devices == p, f"p={p} {exchange} program spans "
                  f"{stats.devices} devices")
            runs[(p, exchange)] = (st.x, stats, wall, clock.secs - c0)
        x_ref, ref_cert = reference(dg.operator(ALPHA))
        x1 = runs[(1, "allgather")][0]
        rows = []
        for (p, exchange), (x, stats, wall, comp) in runs.items():
            err = float(np.abs(x - x_ref).sum())
            gap = float(np.abs(x - x1).sum())
            rows.append(dict(p=p, exchange=exchange, devices=stats.devices,
                             wall_s=wall, compile_s=comp,
                             supersteps=stats.supersteps,
                             attempts=stats.attempts,
                             rows_sent=stats.rows_sent, fulls=stats.fulls,
                             bytes_moved=stats.bytes_moved, cert=stats.cert,
                             l1_err=err, l1_vs_one_chip=gap))
            # every cert is the host's exact recompute and already
            # checked against SERVE_TOL; the distances are held to that
            # fixed bound, not to the computed certificates
            check(err <= SERVE_TOL + ref_cert,
                  f"p={p} {exchange} L1 error {err:.3e} > {SERVE_TOL}")
            check(gap <= 2 * SERVE_TOL,
                  f"p={p} {exchange} differs from one chip by {gap:.3e}")
        rec.update(tol=SERVE_TOL, runs=rows)

    with Phase("sharded_spmd", clock) as rec:
        op = GoogleOperator(pt=TransitionT.from_graph(g), alpha=ALPHA)
        x_ref, ref_cert = reference(op)
        bound = SPMD_TOL / (1.0 - ALPHA)
        out = {}
        for p in (1, 4):
            cfg = SPMDConfig(p=p, kind="linear", tol=SPMD_LOCAL_TOL,
                             max_supersteps=2000)
            c0 = clock.secs
            res, wall = timed(solve_spmd, op, cfg)
            # the linear form keeps unit mass; a shard's lost or doubled
            # fragment shows here and in the exact residual
            mass = float(res.x.sum())
            resid, cert = certify(op, res.x)
            out[p] = res.x
            rec[f"p{p}"] = dict(devices=res.devices, wall_s=wall,
                                compile_s=clock.secs - c0,
                                supersteps=res.supersteps,
                                mass_err=abs(mass - 1.0), exact_resid=resid,
                                cert=cert,
                                l1_err=float(np.abs(res.x - x_ref).sum()))
            check(res.devices == p, f"solve_spmd p={p} spans "
                  f"{res.devices} devices")
            check(res.supersteps < cfg.max_supersteps,
                  f"solve_spmd p={p} hit max_supersteps")
            check(abs(mass - 1.0) <= SPMD_TOL,
                  f"solve_spmd p={p} mass {mass!r} is not 1")
            check(resid <= SPMD_TOL, f"solve_spmd p={p} residual "
                  f"{resid:.3e} above tol {SPMD_TOL}")
            check(rec[f"p{p}"]["l1_err"] <= bound + ref_cert,
                  f"solve_spmd p={p} L1 error "
                  f"{rec[f'p{p}']['l1_err']:.3e} > {bound:.3e}")
        gap = float(np.abs(out[4] - out[1]).sum())
        rec.update(kind="linear", dtype="float32", local_tol=SPMD_LOCAL_TOL,
                   tol=SPMD_TOL, l1_p4_vs_p1=gap)
        check(gap <= 2 * bound,
              f"solve_spmd p=4 differs from p=1 by {gap:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform!r} devices)",
              file=sys.stderr)
        return 2
    if dev.device_kind not in KNOWN_KINDS:
        print(f"chip_smoke: unknown device kind {dev.device_kind!r}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    from repro.graph.generate import stanford_web_replica

    cache_dir = use_compile_cache()
    clock = CompileClock()
    t0 = time.perf_counter()
    g = stanford_web_replica(seed=args.seed)
    print(json.dumps(dict(phase="setup", graph="stanford_web_replica",
                          seed=args.seed, n=int(g.n), nnz=int(g.nnz),
                          graph_s=time.perf_counter() - t0,
                          compile_cache=cache_dir, chips=args.chips,
                          device_kind=dev.device_kind)), flush=True)
    rng = np.random.default_rng(args.seed + 1)
    try:
        if args.chips == 4:
            phase_sharded(g, clock, rng)
        else:
            phase_batch(g, clock)
            srv = phase_served(g, clock, rng)
            phase_personalized(srv, clock, rng)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(dict(total_s=time.perf_counter() - t0,
                          compile_s=clock.secs, cache_hits=clock.hits)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
