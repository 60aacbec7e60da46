"""Batched personalized PageRank through the jax lane backends
(`ppr_push_batched(backend="bsr_pallas" | "segment_sum")`): every lane
against a plain float64 scipy solve, the layout packed once per graph
version, the spans and counters of a batch, and the hub side's two-level
sum."""
import contextlib

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

import repro.core  # noqa: F401  (resolves the runtime<->core import cycle)
from repro.graph.generate import powerlaw_webgraph
from repro.streaming import DeltaGraph, EdgeDelta, ppr_push_batched

ALPHA = 0.85
BACKENDS = ["bsr_pallas", "segment_sum"]


@pytest.fixture(scope="module")
def graph():
    return powerlaw_webgraph(n=5000, target_nnz=40000, n_dangling=20,
                             seed=16)


def seed_sets(g, nv, seed):
    rng = np.random.default_rng(seed)
    return [rng.choice(g.n, size=int(rng.integers(1, 9)), replace=False)
            for _ in range(nv)]


def reference_ppr(g, sets):
    """x = alpha S x + (1 - alpha) v for each lane, from the edges alone:
    a sparse LU of I - alpha P^T, with the dangling pages' uniform
    column folded in exactly (Sherman-Morrison)."""
    n = g.n
    src = np.repeat(np.arange(n), np.diff(g.indptr))
    deg = np.diff(g.indptr)
    pt = sp.csc_matrix((1.0 / deg[src], (g.indices, src)), shape=(n, n))
    dangling = deg == 0
    lu = spla.splu(sp.identity(n, format="csc") - ALPHA * pt)
    v = np.zeros((n, len(sets)))
    for i, s in enumerate(sets):
        v[s, i] = 1.0 / len(s)
    y = lu.solve((1.0 - ALPHA) * v)
    z = lu.solve(np.full(n, 1.0 / n))
    dx = y[dangling].sum(axis=0) / (1.0 - ALPHA * z[dangling].sum())
    return y + ALPHA * dx[None, :] * z[:, None]


@pytest.mark.parametrize("nv", [1, 3, 16])
@pytest.mark.parametrize("backend", BACKENDS)
def test_lanes_within_their_certificates(graph, backend, nv):
    """Every lane is within its exact certificate of the float64 answer,
    and every certificate within the lane's own tolerance (mixed)."""
    sets = seed_sets(graph, nv, nv)
    tol = np.resize([1e-3, 1e-4, 3e-5], nv)
    x, certs, stats = ppr_push_batched(DeltaGraph(graph), sets, alpha=ALPHA,
                                       tol=tol, backend=backend)
    err = np.abs(x - reference_ppr(graph, sets)).sum(axis=0)
    assert x.shape == (graph.n, nv) and stats.path == "batched_linear"
    assert np.all(err <= certs) and np.all(certs <= tol)
    assert (stats.kernel is not None) == (backend == "bsr_pallas")


def test_layout_packed_once_per_graph_version(graph, monkeypatch):
    import repro.kernels.bsr_spmv as bsr
    packs = []
    real = bsr.hybrid_from_transition

    def counted(*a, **kw):
        packs.append(1)
        return real(*a, **kw)
    monkeypatch.setattr(bsr, "hybrid_from_transition", counted)
    dg = DeltaGraph(graph)
    for seed in (1, 2):
        _, certs, _ = ppr_push_batched(dg, seed_sets(graph, 16, seed),
                                       alpha=ALPHA, tol=1e-4,
                                       backend="bsr_pallas")
        assert np.all(certs <= 1e-4)
    assert len(packs) == 1
    dg.apply(EdgeDelta.inserts([0, 1, 2], [10, 11, 12]))
    _, certs, _ = ppr_push_batched(dg, seed_sets(graph, 16, 3), alpha=ALPHA,
                                   tol=1e-4, backend="bsr_pallas")
    assert len(packs) == 2 and np.all(certs <= 1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_batch_spans_and_counters(graph, backend, monkeypatch):
    """A 16-lane batch opens `ppr.stack`, `ppr.certify` and, after each
    chunk of the freezing driver, `solver.recheck`; its chunks' widths
    and iterations add up to the batch's iterations."""
    import jax.profiler
    opened = []
    real = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def recorded(name, **kw):
        opened.append(name)
        with real(name, **kw):
            yield
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recorded)
    # lanes with tolerances 100x apart freeze in different chunks
    tol = np.resize([1e-3, 1e-5], 16)
    _, _, stats = ppr_push_batched(DeltaGraph(graph), seed_sets(graph, 16, 4),
                                   alpha=ALPHA, tol=tol, backend=backend)
    assert {"ppr.stack", "ppr.certify", "solver.recheck"} <= set(opened)
    assert opened.count("solver.recheck") == stats.chunks
    assert stats.chunks == len(stats.widths) >= 2
    assert sum(it for _, it in stats.widths) == stats.iters
    assert stats.widths[0][0] == 16 and stats.widths[-1][0] <= 8
    assert stats.lane_iters.max() == stats.iters


@pytest.mark.parametrize("links", [1, 3, 256])
def test_hub_slots_bound_what_one_accumulator_sums(links):
    """The hub side sums each row in two levels: every slot holds links of
    one row only, at most `links` of them, and the slots of a row carry
    all its links."""
    from repro.kernels.bsr_spmv.ops import hub_slots
    rng = np.random.default_rng(links)
    rows = np.concatenate([np.full(1000, 7), rng.integers(0, 50, 500)])
    rng.shuffle(rows)
    slots, slot_rows = hub_slots(rows, links=links)
    assert np.array_equal(slot_rows[slots], rows)
    counts = np.bincount(slots, minlength=slot_rows.size)
    assert counts.min() >= 1 and counts.max() <= links
    assert np.array_equal(np.bincount(slot_rows, weights=counts,
                                      minlength=50 + 8)[:58],
                          np.bincount(rows, minlength=58))
    empty = hub_slots(np.empty(0, np.int32), links=links)
    assert empty[0].size == 0 and empty[1].size == 0


@pytest.mark.parametrize("hub_quantile", [0.9, 1.0])
def test_hybrid_apply_matches_float64(graph, hub_quantile):
    """The hybrid layout's apply (blocks, and hub rows summed in two
    levels) against a float64 product, also with no hub rows."""
    import jax.numpy as jnp
    from repro.graph.csr import TransitionT
    from repro.kernels.bsr_spmv import (hybrid_from_transition,
                                        hybrid_matvec, pad_x, unpad_y)
    pt = TransitionT.from_graph(graph)
    hyb = hybrid_from_transition(pt, bm=8, bn=8, hub_quantile=hub_quantile)
    x = np.random.default_rng(0).random((graph.n, 3))
    y = unpad_y(np.asarray(hybrid_matvec(
        hyb.device(), jnp.asarray(pad_x(x.astype(np.float32), graph.n, 8)),
        impl="ref"), np.float64), graph.n)
    want = pt.to_scipy() @ x
    assert np.abs(y - want).sum() <= 1e-6 * np.abs(want).sum()


def test_one_layout_held_across_graph_versions(graph):
    """After a graph version's apply, only the newest version's operator
    keeps a BSR layout (host pack and device arrays): one is all a chip
    holds."""
    dg = DeltaGraph(graph)

    def holders():
        return [k for k, op in dg._ops.items()
                if any(c[0] in ("hybrid", "bsr_dev") for c in op._cache())]
    for step in range(3):
        _, certs, _ = ppr_push_batched(dg, seed_sets(graph, 4, step),
                                       alpha=ALPHA, tol=1e-4,
                                       backend="bsr_pallas")
        assert np.all(certs <= 1e-4)
        assert holders() == [(dg.version, ALPHA)]
        dg.apply(EdgeDelta.inserts([step], [100 + step]))
        assert holders() == []


@pytest.mark.parametrize("teleport", ["seeds", "dense", "uniform"])
def test_residual_in_row_blocks_matches_whole_apply(graph, teleport):
    """`residual_l1_numpy` (row blocks on threads, a sparse seed stack
    kept sparse) reads what the whole apply's residual reads."""
    from repro.core.backend import seed_stack
    from repro.graph.csr import TransitionT
    from repro.graph.google import GoogleOperator
    op = GoogleOperator(pt=TransitionT.from_graph(graph), alpha=ALPHA)
    x = np.random.default_rng(5).random((graph.n, 5))
    v = None if teleport == "uniform" else seed_stack(
        graph.n, seed_sets(graph, 5, 5))
    dense = None if v is None else v.toarray()
    want = np.abs(op.apply_linear_numpy(x, v=dense) - x).sum(axis=0)
    got = op.residual_l1_numpy(x, v=dense if teleport == "dense" else v,
                               workers=3)
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_sparse_stack_pads_as_dense(graph):
    from repro.core.backend import seed_stack
    from repro.kernels.bsr_spmv import pad_x
    v = seed_stack(graph.n, seed_sets(graph, 6, 6))
    np.testing.assert_array_equal(
        pad_x(v, graph.n, 8, dtype=np.float32),
        pad_x(v.toarray().astype(np.float32), graph.n, 8))
