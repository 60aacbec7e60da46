"""Transport-agnostic shard workers (PR 5): ShardArena lifecycle, the
SPSC rings, procpool cross-process determinism/soundness (50k acceptance),
worker-crash containment with the arena released, and the adaptive
sparsified payload sizing.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import repro.core  # noqa: F401  (resolves the runtime<->core import cycle)
from repro.core.partition import block_rows
from repro.graph.generate import powerlaw_webgraph
from repro.graph.google import exact_pagerank
from repro.runtime import (AllToAllPlan, ProcPoolShardExecutor, ShardArena,
                           ShmRing, TerminationDriver, default_pool_size)
from repro.streaming import (DeltaGraph, EdgeDelta, cold_state,
                             refresh_residual, update_ranks_sharded)
from repro.streaming.incremental import RankState
from repro.streaming.server import RankServer


def _shm_leftovers():
    try:
        return [f for f in os.listdir("/dev/shm") if f.startswith("repro_arena")]
    except FileNotFoundError:        # pragma: no cover - non-Linux
        return []


# ---------------------------------------------------------------------------
# ShardArena lifecycle
# ---------------------------------------------------------------------------
def test_arena_create_attach_close_unlink():
    arrays = dict(r=np.arange(7, dtype=np.float64),
                  idx=np.arange(12, dtype=np.int32).reshape(3, 4))
    arena = ShardArena.from_arrays(arrays)
    name = arena.name
    assert name in os.listdir("/dev/shm")
    np.testing.assert_array_equal(arena["r"], arrays["r"])
    # attach sees writes from the owner (and vice versa)
    other = ShardArena.attach(arena.handle())
    other["r"][2] = 99.0
    assert arena["r"][2] == 99.0
    other.close()                       # non-owner close never unlinks
    assert name in os.listdir("/dev/shm")
    arena.close()
    assert name not in os.listdir("/dev/shm")
    arena.close()                       # idempotent


def test_arena_close_with_live_views_still_unlinks():
    arena = ShardArena.from_arrays(dict(r=np.zeros(5)))
    name = arena.name
    view = arena["r"]                   # keep a reference across close
    arena.close()
    assert name not in os.listdir("/dev/shm")
    assert view.shape == (5,)           # the mapping outlives the unlink


# ---------------------------------------------------------------------------
# ShmRing (SPSC payload ring)
# ---------------------------------------------------------------------------
def _ring(depth=4, cap=8):
    arena = ShardArena.create(dict(
        head=((1,), np.int64), tail=((1,), np.int64),
        cnt=((depth,), np.int64), idx=((depth, cap), np.int32),
        val=((depth, cap), np.float64)))
    return arena, ShmRing(arena["head"], arena["tail"], arena["cnt"],
                          arena["idx"], arena["val"])


def test_shm_ring_push_pop_fifo():
    arena, ring = _ring()
    assert ring.empty()
    assert ring.push(np.array([0, 2], np.int32), np.array([1.0, -2.0]))
    assert ring.push(np.array([2], np.int32), np.array([0.5]))
    out = np.zeros(4)
    moved = ring.pop_into(out)
    assert moved == pytest.approx(3.5)
    np.testing.assert_allclose(out, [1.0, 0.0, -1.5, 0.0])
    assert ring.empty()
    arena.close()


def test_proc_context_send_chunks_large_payloads():
    """A boundary payload larger than the ring's slot cap is split across
    records (the slot cap bounds the control arena at O(p^2*depth*cap),
    not O(p*depth*n)); every row arrives and the in-flight ledger nets
    to zero after the fold."""
    from repro.runtime.transport import (ProcContext, WorkerConfig,
                                         _ctl_spec)
    p, n, cap = 2, 40, 4
    part = block_rows(n, p)
    ctl = ShardArena.create(_ctl_spec(p, n, part, ring_depth=8,
                                      payload_cap=cap))
    try:
        ctx = ProcContext(ctl, part, WorkerConfig(l1_target=1e-9),
                          pc_max_compute=1)
        box = ctx.outbox(0)
        sd, ed = part.block(1)
        box[sd:ed] = 0.5                       # 20 nonzero rows > cap=4
        shipped = ctx.send(0, 1, box[sd:ed])
        assert shipped == ed - sd
        assert np.all(box == 0.0)
        r = np.zeros(n)
        assert ctx.fold_intake(1, r, sd, ed)
        assert np.all(r[sd:ed] == 0.5)
        assert ctx.inflight_l1(0) == pytest.approx(0.0)
    finally:
        ctl.close()


def test_shm_ring_backpressure_and_reuse():
    arena, ring = _ring(depth=2)
    one = np.array([0], np.int32)
    assert ring.push(one, np.array([1.0]))
    assert ring.push(one, np.array([1.0]))
    assert not ring.push(one, np.array([1.0]))   # full: reject, not block
    out = np.zeros(1)
    assert ring.pop_into(out) == pytest.approx(2.0)
    assert ring.push(one, np.array([1.0]))       # slots freed by the pop
    arena.close()


# ---------------------------------------------------------------------------
# procpool executor primitives
# ---------------------------------------------------------------------------
class _AbsorbDrain:
    """Synthetic absorbing drain (no graph): keep 30% of own mass, ship
    20% to the successor's rows, absorb the rest (picklable factory)."""

    def __init__(self, p, n):
        self.p, self.n = p, n

    def __call__(self, views):
        part = block_rows(self.n, self.p)
        r = views["r"]

        def drain_fn(i, s, e, step_target, outbox):
            own = r[s:e]
            l1 = float(np.abs(own).sum())
            if l1 <= step_target:
                return 0, 0.0
            moved = own.copy()
            own[:] = 0.0
            ns, ne = part.block((i + 1) % self.p)
            outbox[ns:ns + moved.size] += 0.2 * moved
            r[s:e] += 0.3 * moved
            return moved.size, 0.0
        return drain_fn


def test_procpool_synthetic_drain_terminates_and_conserves_mass():
    p, n = 2, 30
    part = block_rows(n, p)
    rng = np.random.default_rng(0)
    target = 1e-6
    arena = ShardArena.from_arrays(dict(r=rng.random(n)))
    try:
        ex = ProcPoolShardExecutor(part, AllToAllPlan(p),
                                   TerminationDriver(p), l1_target=target,
                                   max_rounds=100_000)
        res = ex.run(_AbsorbDrain(p, n), arena)
        assert res.stopped and not res.capped
        assert res.exchanges > 0 and res.bytes_moved > 0
        assert (res.rounds_per_shard >= 1).all()
        assert float(np.abs(arena["r"]).sum()) <= 2.0 * target
    finally:
        arena.close()
    assert not _shm_leftovers()


class _NeverConverges:
    def __call__(self, views):
        def drain_fn(i, s, e, step_target, outbox):
            return 1, 0.0        # claims pushes, removes no mass
        return drain_fn


def test_procpool_round_cap_reports_capped_and_conserves():
    p, n = 2, 10
    part = block_rows(n, p)
    arena = ShardArena.from_arrays(dict(r=np.ones(n)))
    try:
        ex = ProcPoolShardExecutor(part, AllToAllPlan(p),
                                   TerminationDriver(p), l1_target=1e-12,
                                   max_rounds=50)
        res = ex.run(_NeverConverges(), arena)
        assert res.capped and not res.stopped
        assert float(np.abs(arena["r"]).sum()) == pytest.approx(n)
    finally:
        arena.close()


def test_procpool_oversubscription_guard_warns():
    p = 2
    part = block_rows(10, p)
    cores = os.cpu_count() or 1
    with pytest.warns(RuntimeWarning, match="oversubscribes"):
        ex = ProcPoolShardExecutor(part, AllToAllPlan(p),
                                   TerminationDriver(p), l1_target=1e-6,
                                   n_workers=cores + 7)
    assert ex.n_workers <= p          # never more workers than shards
    # the default is the guardrail: min(p, cores), no warning
    ex2 = ProcPoolShardExecutor(part, AllToAllPlan(p),
                                TerminationDriver(p), l1_target=1e-6)
    assert ex2.n_workers == min(p, cores)
    assert 1 <= default_pool_size(64) <= cores


class _Crasher:
    """Shard 0 raises after a couple of rounds; the run must raise with
    the control arena released."""

    def __call__(self, views):
        calls = [0]

        def drain_fn(i, s, e, step_target, outbox):
            if i == 0:
                calls[0] += 1
                if calls[0] > 2:
                    raise ValueError("synthetic shard failure")
            time.sleep(0.001)
            return 1, 0.0
        return drain_fn


def test_procpool_worker_exception_raises_and_releases():
    p, n = 2, 12
    part = block_rows(n, p)
    arena = ShardArena.from_arrays(dict(r=np.ones(n)))
    try:
        ex = ProcPoolShardExecutor(part, AllToAllPlan(p),
                                   TerminationDriver(p), l1_target=1e-12,
                                   max_rounds=10_000)
        with pytest.raises(RuntimeError, match="worker"):
            ex.run(_Crasher(), arena)
    finally:
        arena.close()
    assert not _shm_leftovers()


# kill-a-worker-mid-drain, exercised in a subprocess reaper so the assert
# also covers "nothing leaked in /dev/shm even though a process died".
# PR 6 flips the contract: a deterministic FaultPlan SIGKILL (the worker
# kills its own process at report time, round >= 3) must now be
# *recovered* by the supervisor — the run completes with recoveries >= 1
# instead of raising — and /dev/shm stays clean across the restart.
_REAPER_SCRIPT = r"""
import os
import numpy as np
from repro.core.partition import block_rows
from repro.runtime import (AllToAllPlan, FaultPlan, ProcPoolShardExecutor,
                           ShardArena, TerminationDriver)

class AbsorbDrain:
    def __init__(self, p, n):
        self.p, self.n = p, n
    def __call__(self, views):
        part = block_rows(self.n, self.p)
        r = views["r"]
        def drain_fn(i, s, e, step_target, outbox):
            own = r[s:e]
            l1 = float(np.abs(own).sum())
            if l1 <= step_target:
                return 0, 0.0
            moved = own.copy()
            own[:] = 0.0
            ns, ne = part.block((i + 1) % self.p)
            outbox[ns:ns + moved.size] += 0.2 * moved
            r[s:e] += 0.3 * moved
            return moved.size, 0.0
        return drain_fn

part = block_rows(40, 2)
arena = ShardArena.from_arrays({'r': np.ones(40)})
ex = ProcPoolShardExecutor(part, AllToAllPlan(2), TerminationDriver(2),
                           l1_target=1e-9, max_rounds=10**6,
                           faults=FaultPlan(kill={0: 3}))
try:
    res = ex.run(AbsorbDrain(2, 40), arena)
    resid = float(np.abs(arena['r']).sum())
    print("RECOVERED", "recoveries=%d" % res.recoveries,
          "stopped=%s" % res.stopped, "resid_ok=%s" % (resid <= 2e-9))
except RuntimeError as e:
    print("RAISED:", e)
finally:
    arena.close()
left = [f for f in os.listdir('/dev/shm') if f.startswith('repro_arena')]
print("LEFTOVERS:", left)
"""


def test_procpool_killed_worker_recovers_no_shm_leak():
    before = set(_shm_leftovers())
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"    # host-only child of a jax parent
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", _REAPER_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "RAISED:" not in out.stdout, out.stdout
    assert "RECOVERED" in out.stdout, out.stdout
    assert "stopped=True" in out.stdout and "resid_ok=True" in out.stdout, \
        out.stdout
    # the SIGKILL really happened and was really recovered
    rec = int(out.stdout.split("recoveries=")[1].split()[0])
    assert rec >= 1, out.stdout
    assert "LEFTOVERS: []" in out.stdout, out.stdout
    # the reaper's own view: nothing new survived the crash
    assert set(_shm_leftovers()) <= before


# ---------------------------------------------------------------------------
# procpool end to end (small graphs; 50k acceptance below)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("exchange", ["allgather", "sparsified"])
def test_procpool_update_sequence_tracks_exact(exchange):
    g = powerlaw_webgraph(n=2500, target_nnz=20000, n_dangling=12, seed=61)
    dg = DeltaGraph(g)
    st = cold_state(dg, tol=1e-9)
    rng = np.random.default_rng(62)
    paths = set()
    for step in range(3):
        k = int(rng.integers(1, 6))
        d = EdgeDelta.inserts(rng.integers(0, dg.n, k),
                              rng.integers(0, dg.n, k))
        st, stats = update_ranks_sharded(dg, d, st, p=4, tol=1e-7,
                                         exchange=exchange, mode="async",
                                         transport="procpool")
        assert stats.cert <= 1e-7
        assert stats.transport == "procpool" and stats.mode == "async"
        paths.add(stats.path)
        if stats.path == "sharded_push":
            # async certificates are the exact post-fold residual under
            # either transport
            assert st.cert == pytest.approx(stats.cert, rel=1e-12)
    assert "sharded_push" in paths
    x_ref = exact_pagerank(dg.operator(0.85), tol=1e-13)
    assert np.abs(st.x - x_ref).sum() < 1.5e-7
    # the maintained residual is still exact after the arena round-trip
    r_inc = st.r.copy()
    refresh_residual(dg, st)
    assert np.abs(r_inc - st.r).max() < 1e-12
    assert not _shm_leftovers()


def test_procpool_node_arrivals_and_deletions():
    g = powerlaw_webgraph(n=1500, target_nnz=11000, n_dangling=8, seed=63)
    dg = DeltaGraph(g)
    st = cold_state(dg, tol=1e-9)
    d = EdgeDelta(add_src=np.array([1500, 7]), add_dst=np.array([3, 1500]),
                  del_src=np.empty(0, np.int64),
                  del_dst=np.empty(0, np.int64), new_nodes=1)
    st, stats = update_ranks_sharded(dg, d, st, p=3, tol=1e-7, mode="async",
                                     transport="procpool")
    assert st.x.shape == (1501,)
    u = int(np.argmax(dg.out_degree))
    row = dg.out_neighbors(u)
    st, stats = update_ranks_sharded(
        dg, EdgeDelta.deletes(np.full(row.size, u), row), st, p=3,
        tol=1e-7, mode="async", transport="procpool")
    x_ref = exact_pagerank(dg.operator(0.85), tol=1e-13)
    assert np.abs(st.x - x_ref).sum() < 1.5e-7


def test_transport_validation():
    g = powerlaw_webgraph(n=300, target_nnz=2400, n_dangling=2, seed=9)
    dg = DeltaGraph(g)
    st = cold_state(dg, tol=1e-8)
    with pytest.raises(ValueError, match="transport"):
        update_ranks_sharded(dg, EdgeDelta.empty(), st, mode="async",
                             transport="rpc")
    with pytest.raises(ValueError, match="procpool"):
        update_ranks_sharded(dg, EdgeDelta.empty(), st, mode="superstep",
                             transport="procpool")
    with pytest.raises(ValueError, match="shard_transport"):
        RankServer(dg, updater="sharded", shard_transport="rpc")
    with pytest.raises(ValueError, match="procpool"):
        RankServer(dg, updater="sharded", shard_mode="superstep",
                   shard_transport="procpool")


def test_rank_server_procpool_transport():
    g = powerlaw_webgraph(n=1200, target_nnz=9000, n_dangling=6, seed=21)
    dg = DeltaGraph(g)
    srv = RankServer(dg, tol=1e-7, updater="sharded", shards=2,
                     shard_mode="async", shard_transport="procpool")
    rng = np.random.default_rng(3)
    for _ in range(4):
        srv.ingest(EdgeDelta.inserts(rng.integers(0, dg.n, 2),
                                     rng.integers(0, dg.n, 2)))
    stats = srv.apply_pending()
    assert stats is not None and stats.transport == "procpool"
    snap = srv.snapshot()
    assert snap.cert <= 1e-7
    ids, vals = srv.top_k(5)
    assert len(ids) == 5 and np.all(np.diff(vals) <= 0)
    assert not _shm_leftovers()


# ---------------------------------------------------------------------------
# ISSUE 5 acceptance: cross-process determinism/soundness on the 50k graph
# (accept_graph / accept_delta / accept_cold / accept_base are the shared
# session fixtures in conftest.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("p", [2, 4])
def test_accept_procpool_one_percent_delta_50k(accept_graph, accept_delta,
                                               accept_cold, accept_base,
                                               p):
    """Acceptance: transport="procpool" applies the 1% delta on the 50k
    graph with p shard worker *processes* and certifies at tol=1e-8
    against a cold solve — the maintained residual IS the published
    certificate (exact post-fold recompute), same contract as threads."""
    tol = 1e-8
    dg = DeltaGraph(accept_graph)
    st = RankState(x=accept_base.x.copy(), r=accept_base.r.copy(),
                   version=0, alpha=accept_base.alpha)
    st, stats = update_ranks_sharded(dg, accept_delta, st, p=p, tol=tol,
                                     mode="async", transport="procpool")
    assert stats.path == "sharded_push", (p, stats)
    assert stats.transport == "procpool" and stats.p == p
    assert stats.cert <= tol
    assert st.cert == pytest.approx(stats.cert, rel=1e-12)
    l1 = np.abs(st.x - accept_cold).sum()
    assert l1 < 2 * tol, (p, l1)
    assert not _shm_leftovers()


def test_accept_procpool_threads_agree_50k(accept_graph, accept_delta,
                                           accept_base):
    """Determinism-of-result across transports: the same delta drained by
    threads and by procpool lands within the certified band of the same
    fixed point (schedules differ; certificates must both hold)."""
    tol = 1e-8
    outs = {}
    for transport in ("threads", "procpool"):
        dg = DeltaGraph(accept_graph)
        st = RankState(x=accept_base.x.copy(), r=accept_base.r.copy(),
                       version=0, alpha=accept_base.alpha)
        st, stats = update_ranks_sharded(dg, accept_delta, st, p=2,
                                         tol=tol, mode="async",
                                         transport=transport)
        assert stats.cert <= tol, (transport, stats)
        outs[transport] = st.x
    # both are certified within tol (L1) of the same fixed point
    l1 = np.abs(outs["threads"] - outs["procpool"]).sum()
    assert l1 <= 2 * tol
