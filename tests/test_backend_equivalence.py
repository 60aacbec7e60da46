"""BSR-vs-segment-sum backend equivalence: the bsr_pallas path must produce
the same PageRank (values to f32 accuracy, ranking essentially exactly) as
the segment-sum reference on randomized power-law graphs, with multi-vector
lanes, under reorderings, and end to end through solve_power."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT
from repro.graph.google import GoogleOperator
from repro.core import (solve_power, solve_linear, kendall_tau_topk,
                        BackendSpec)
from repro.kernels.bsr_spmv import (build_bsr, build_hybrid_bsr,
                                    hybrid_from_transition, hybrid_matvec,
                                    pad_x, unpad_y)


def _op(n, nnz, seed, alpha=0.85):
    g = powerlaw_webgraph(n=n, target_nnz=nnz, n_dangling=max(2, n // 500),
                          seed=seed)
    return GoogleOperator(pt=TransitionT.from_graph(g), alpha=alpha)


# ---------------------------------------------------------------------------
# layer 1: the hybrid (hub-split) matvec against scipy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,bm,hub_q", [(0, 32, 0.99), (1, 16, 0.95),
                                           (2, 32, 1.0)])
def test_hybrid_matvec_vs_scipy(seed, bm, hub_q):
    g = powerlaw_webgraph(n=900, target_nnz=7000, n_dangling=5, seed=seed)
    pt = TransitionT.from_graph(g)
    hyb = hybrid_from_transition(pt, bm=bm, bn=bm, hub_quantile=hub_q)
    rng = np.random.default_rng(seed)
    x = rng.random((g.n, 2)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, g.n, bm))
    y = unpad_y(np.asarray(hybrid_matvec(hyb.device(), xp, impl="ref")), g.n)
    y_ref = pt.to_scipy() @ x.astype(np.float64)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-6)
    if hub_q < 1.0:
        assert hyb.hub_nnz_frac > 0  # the split actually routed something


def test_bincount_scatter_matches_add_at():
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 500, 4000)
    cols = rng.integers(0, 300, 4000)
    vals = rng.standard_normal(4000)
    a = build_bsr(rows, cols, vals, 500, 300, bm=32, bn=16,
                  scatter="bincount")
    b = build_bsr(rows, cols, vals, 500, 300, bm=32, bn=16,
                  scatter="add_at")
    np.testing.assert_array_equal(a.blk_cols, b.blk_cols)
    np.testing.assert_allclose(a.blocks, b.blocks, rtol=1e-6, atol=1e-6)


def test_hybrid_caps_k():
    # a graph with hub rows: without the split K explodes to ~nbc
    g = powerlaw_webgraph(n=4000, target_nnz=40000, n_dangling=4, seed=11)
    pt = TransitionT.from_graph(g)
    full = build_bsr(pt.row_ids.astype(np.int64), pt.src.astype(np.int64),
                     np.asarray(pt.weight, np.float32), pt.n, pt.n,
                     bm=32, bn=32)
    hyb = hybrid_from_transition(pt, bm=32, bn=32, hub_quantile=0.99)
    assert hyb.bsr.K < full.K
    assert hyb.bsr.fill_ratio > full.fill_ratio


# ---------------------------------------------------------------------------
# layer 2: full solves agree across backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_solve_power_backends_agree(seed):
    op = _op(1200 + 700 * seed, 9000 + 4000 * seed, seed)
    ref = solve_power(op, tol=1e-12, max_iters=2000)
    bsr = solve_power(op, tol=3e-7, max_iters=500, backend="bsr_pallas")
    assert np.abs(ref.x - bsr.x).max() < 1e-6
    assert kendall_tau_topk(ref.x, bsr.x, k=100) > 0.999


def test_solve_linear_backends_agree():
    op = _op(1500, 11000, 5)
    ref = solve_linear(op, tol=1e-12, max_iters=2000)
    bsr = solve_linear(op, tol=3e-7, max_iters=500, backend="bsr_pallas")
    assert np.abs(ref.x - bsr.x).max() < 1e-6


def test_multivector_lanes_match_individual_solves():
    op = _op(1000, 8000, 7)
    rng = np.random.default_rng(7)
    V = rng.random((op.n, 3))
    V /= V.sum(axis=0)
    multi = solve_power(op, tol=3e-7, v=V, backend="bsr_pallas")
    assert multi.x.shape == (op.n, 3)
    assert multi.resid_per_vec is not None
    assert multi.resid_per_vec.shape == (3,)
    for k in range(3):
        single = solve_power(op, tol=1e-10, v=V[:, k])
        assert np.abs(multi.x[:, k] - single.x).max() < 1e-6
        assert kendall_tau_topk(multi.x[:, k], single.x, k=50) > 0.999


@pytest.mark.parametrize("method", ["rcm", "indeg"])
def test_reordered_solve_matches(method):
    op = _op(1100, 9000, 13)
    plain = solve_power(op, tol=1e-10)
    perm = solve_power(op, tol=3e-7, backend="bsr_pallas", reorder=method)
    assert np.abs(plain.x - perm.x).max() < 1e-6


def test_interpret_mode_pallas_end_to_end():
    """The actual Pallas kernel (interpret mode on CPU) inside the fused
    solver loop — small graph, real grid."""
    op = _op(400, 2500, 17)
    spec = BackendSpec(name="bsr_pallas", impl="interpret", bm=16)
    ref = solve_power(op, tol=1e-10)
    ki = solve_power(op, tol=3e-7, backend=spec)
    assert np.abs(ref.x - ki.x).max() < 1e-6
    assert kendall_tau_topk(ref.x, ki.x, k=50) > 0.999


def test_repeated_solves_reuse_cached_state():
    op = _op(800, 6000, 19)
    solve_power(op, tol=3e-7, backend="bsr_pallas")
    cache = op._cache()
    assert any(k[0] == "hybrid" for k in cache)
    hyb_before = {k: v for k, v in cache.items() if k[0] == "hybrid"}
    solve_power(op, tol=3e-7, backend="bsr_pallas")
    for k, v in hyb_before.items():
        assert cache[k] is v  # same object — no re-pack
    # segment_sum device arrays are memoized per dtype as well
    d1 = op.device_arrays(dtype=jnp.float32)
    d2 = op.device_arrays(dtype=jnp.float32)
    assert d1["weight"] is d2["weight"]


@pytest.mark.slow
def test_rank_agreement_50k():
    """Acceptance gate: ≥50k-node power-law graph, bsr_pallas vs
    segment_sum, Kendall-tau top-100 ≥ 0.999."""
    op = _op(50_000, 400_000, 3)
    ref = solve_power(op, tol=1e-10, max_iters=1000)
    bsr = solve_power(op, tol=1e-6, max_iters=300, backend="bsr_pallas")
    tau = kendall_tau_topk(ref.x, bsr.x, k=100)
    assert tau >= 0.999, tau


def test_bsr_layout_round_trip():
    """The bsr_pallas layout contract: `prepare` puts (n, nv) stacks in the
    lane-dense (nv, nbr, bm) layout, padding exactly zero and the masks
    lane-invariant (1, nbr, bm); `from_layout`/`unpad_y` take the stack
    back, `take_lanes` selects lanes of the iterate and the teleport
    stack, and `l1_residual` reduces every axis but the lanes."""
    from repro.core.backend import (from_layout, l1_residual, prepare,
                                    take_lanes)
    op = _op(1000, 8000, 3)
    spec = BackendSpec(name="bsr_pallas", impl="ref", bm=16).resolved()
    rng = np.random.default_rng(0)
    x = rng.random((op.n, 5)).astype(np.float32)
    v = rng.random((op.n, 5))
    v /= v.sum(axis=0)
    dev, meta, x_dev = prepare(op, spec, jnp.float32, v=v, x0=x)
    nbr = -(-op.n // 16)
    assert x_dev.shape == dev["v"].shape == (5, nbr, 16)
    assert dev["valid"].shape == dev["dang"].shape == (1, nbr, 16)
    assert float(dev["valid"].sum()) == op.n
    flat = np.asarray(x_dev).reshape(5, -1)
    np.testing.assert_array_equal(flat[:, :op.n], x.T)
    assert not flat[:, op.n:].any()
    np.testing.assert_array_equal(from_layout(meta, x_dev), x)
    np.testing.assert_array_equal(unpad_y(np.asarray(x_dev), op.n), x)
    np.testing.assert_array_equal(pad_x(x, op.n, 16), np.asarray(x_dev))

    idx = np.array([3, 0, 3])
    dev2, meta2, x2 = take_lanes(meta, dev, x_dev, idx)
    assert meta2.nv == 3 and x2.shape == (3, nbr, 16)
    np.testing.assert_array_equal(from_layout(meta2, x2), x[:, idx])
    np.testing.assert_allclose(unpad_y(np.asarray(dev2["v"]), op.n),
                               v[:, idx].astype(np.float32))
    assert dev2["valid"] is dev["valid"]

    y = x_dev * 2.0
    np.testing.assert_allclose(np.asarray(l1_residual(meta, y, x_dev)),
                               np.abs(x).sum(axis=0), rtol=1e-5)
