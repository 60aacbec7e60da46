"""BSR SpMV Pallas kernel: interpret-mode sweeps vs the pure-jnp oracle."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels.bsr_spmv import (build_bsr, bsr_from_transition, pad_x,
                                    unpad_y, spmv, bsr_spmv_ref)
from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT


def random_coo(rng, n_rows, n_cols, nnz):
    rows = rng.integers(0, n_rows, nnz)
    cols = rng.integers(0, n_cols, nnz)
    vals = rng.standard_normal(nnz)
    # dedup
    key = rows * n_cols + cols
    _, idx = np.unique(key, return_index=True)
    return rows[idx], cols[idx], vals[idx]


@pytest.mark.parametrize("n_rows,n_cols,nnz,bm,bn,nv", [
    (100, 100, 500, 32, 32, 1),
    (257, 130, 800, 64, 32, 4),
    (512, 512, 4000, 128, 128, 8),
    (64, 300, 600, 16, 64, 2),
])
def test_kernel_matches_ref_shapes(n_rows, n_cols, nnz, bm, bn, nv):
    rng = np.random.default_rng(nnz)
    rows, cols, vals = random_coo(rng, n_rows, n_cols, nnz)
    bsr = build_bsr(rows, cols, vals, n_rows, n_cols, bm=bm, bn=bn)
    x = rng.standard_normal((n_cols, nv)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, n_cols, bn))
    y_k = np.asarray(spmv(bsr, xp, interpret=True))
    y_r = np.asarray(bsr_spmv_ref(*bsr.device(), xp))
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    rows, cols, vals = random_coo(rng, 128, 128, 700)
    bsr = build_bsr(rows, cols, vals, 128, 128, bm=32, bn=32)
    x = rng.standard_normal((128, 2)).astype(dtype)
    xp = jnp.asarray(pad_x(x, 128, 32))
    y_k = np.asarray(spmv(bsr, xp, interpret=True))
    y_r = np.asarray(bsr_spmv_ref(*bsr.device(), xp))
    np.testing.assert_allclose(y_k, y_r, rtol=2e-2, atol=2e-2)


def test_kernel_vs_scipy_on_webgraph():
    g = powerlaw_webgraph(n=800, target_nnz=6000, n_dangling=4, seed=5)
    pt = TransitionT.from_graph(g)
    bsr = bsr_from_transition(pt, bm=64, bn=64)
    rng = np.random.default_rng(1)
    x = rng.random((g.n, 3)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, g.n, 64))
    y_k = unpad_y(np.asarray(spmv(bsr, xp, interpret=True)), g.n)
    y_s = pt.to_scipy() @ x.astype(np.float64)
    np.testing.assert_allclose(y_k, y_s, rtol=1e-4, atol=1e-5)


def test_empty_rows_and_padding():
    # a matrix with fully-empty block rows must produce zeros there
    rows = np.array([0, 1, 300])
    cols = np.array([5, 200, 10])
    vals = np.array([1.0, 2.0, 3.0])
    bsr = build_bsr(rows, cols, vals, 400, 256, bm=64, bn=64)
    x = np.ones((256, 1), np.float32)
    xp = jnp.asarray(pad_x(x, 256, 64))
    y = unpad_y(np.asarray(spmv(bsr, xp, interpret=True)), 400)
    assert y[0, 0] == pytest.approx(1.0)
    assert y[1, 0] == pytest.approx(2.0)
    assert y[300, 0] == pytest.approx(3.0)
    assert np.abs(y).sum() == pytest.approx(6.0)


def test_row_chunks_fit_smem_budget():
    from repro.kernels.bsr_spmv.bsr_spmv import SMEM_TABLE_BYTES, row_chunks
    for nbr, K in [(1, 1), (2203, 70), (5000, 3), (7, 40000)]:
        chunks = row_chunks(nbr, K)
        assert chunks[0][0] == 0
        assert sum(rows for _, rows in chunks) == nbr
        for (r0, rows), (r1, _) in zip(chunks, chunks[1:]):
            assert r1 == r0 + rows
        # one block-row is the floor even when its table alone is larger
        assert all(rows * K * 4 <= SMEM_TABLE_BYTES or rows == 1
                   for _, rows in chunks)
    assert len(row_chunks(2203, 70)) == 3


@pytest.mark.parametrize("accum", ["f32", "kahan"])
def test_kernel_row_chunked_grid(monkeypatch, accum):
    """A column table over the SMEM budget runs as several pallas_calls
    whose concatenated output equals the oracle (shapes unique to this
    test, so no trace cached under the real budget is reused)."""
    import importlib
    from repro.kernels.bsr_spmv import bsr_spmv
    # the package re-exports the function under the module's name
    bk = importlib.import_module("repro.kernels.bsr_spmv.bsr_spmv")
    rng = np.random.default_rng(17)
    rows, cols, vals = random_coo(rng, 176, 176, 900)
    bsr = build_bsr(rows, cols, vals, 176, 176, bm=16, bn=16)
    # three block-rows of table per call: 11 rows -> 4 calls
    monkeypatch.setattr(bk, "SMEM_TABLE_BYTES", 3 * bsr.K * 4)
    assert len(bk.row_chunks(bsr.nbr, bsr.K)) == 4
    x = rng.standard_normal((176, 3)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, 176, 16))
    blocks, blk_cols = bsr.device()
    y_k = np.asarray(bsr_spmv(blocks, blk_cols, xp, interpret=True,
                              accum=accum))
    y_r = np.asarray(bsr_spmv_ref(blocks, blk_cols, xp))
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-5)


def test_fill_ratio_reported():
    g = powerlaw_webgraph(n=500, target_nnz=3000, n_dangling=2, seed=2)
    pt = TransitionT.from_graph(g)
    bsr = bsr_from_transition(pt)
    assert 0 < bsr.fill_ratio <= 1


# ---------------------------------------------------------------------------
# accumulation lanes (PR 9): compensated kernel vs the f64 reference
# ---------------------------------------------------------------------------
def _deep_bsr(rng, nbc=64, bm=8):
    """A block row with a long K chain — accumulation error grows with the
    number of partial sums, which is what the compensated lane targets."""
    n_rows, n_cols = bm, nbc * bm
    rows = np.repeat(np.arange(bm), nbc)
    cols = (np.tile(np.arange(nbc), bm) * bm
            + rng.integers(0, bm, nbc * bm))
    vals = rng.standard_normal(nbc * bm) * 10.0 ** rng.integers(
        -3, 3, nbc * bm)
    return build_bsr(rows, cols, vals, n_rows, n_cols, bm=bm, bn=bm)


def test_kahan_lane_matches_f64_reference():
    """The compensated-summation kernel lane lands (much) nearer the f64
    segment-sum-grade reference than the plain f32 lane on a deep-K
    contraction, and stays float32 end to end."""
    import jax
    from repro.kernels.bsr_spmv import bsr_spmv

    rng = np.random.default_rng(42)
    bsr = _deep_bsr(rng, nbc=128, bm=8)
    x = rng.standard_normal((bsr.n_cols, 2)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, bsr.n_cols, 8))
    blocks, blk_cols = bsr.device()

    with jax.enable_x64(True):
        ref64 = np.asarray(bsr_spmv_ref(
            np.asarray(blocks, dtype=np.float64), np.asarray(blk_cols),
            np.asarray(xp, dtype=np.float64), accum="f64"))
    y32 = np.asarray(bsr_spmv(blocks, blk_cols, xp, interpret=True))
    yk = np.asarray(bsr_spmv(blocks, blk_cols, xp, interpret=True,
                             accum="kahan"))
    assert yk.dtype == np.float32
    err32 = np.abs(y32 - ref64).max()
    errk = np.abs(yk - ref64).max()
    # compensation may tie on lucky draws but must never be worse, and
    # on a deep chain it should win clearly
    assert errk <= err32
    assert errk < 0.5 * err32, (errk, err32)


def test_ref_accum_lanes():
    rng = np.random.default_rng(5)
    bsr = _deep_bsr(rng, nbc=32, bm=8)
    x = rng.standard_normal((bsr.n_cols, 1)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, bsr.n_cols, 8))
    blocks, blk_cols = bsr.device()
    # without x64, the wide lanes silently degrade to f32 (no crash, no
    # warning spam) and still match the f32 oracle closely
    y_f32 = np.asarray(bsr_spmv_ref(blocks, blk_cols, xp, accum="f32"))
    y_k = np.asarray(bsr_spmv_ref(blocks, blk_cols, xp, accum="kahan"))
    assert y_k.dtype == np.float32
    np.testing.assert_allclose(y_f32, y_k, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="accum"):
        bsr_spmv_ref(blocks, blk_cols, xp, accum="f16")


def test_resolve_impl_dispatch():
    import jax
    from repro.kernels.bsr_spmv import resolve_impl

    # explicit names pass through untouched; auto picks by backend
    for impl in ("pallas", "interpret", "ref"):
        assert resolve_impl(impl) == impl
    auto = resolve_impl("auto")
    if jax.default_backend() == "tpu":
        assert auto == "pallas"
    else:
        assert auto == "interpret"
    with pytest.raises(ValueError):
        resolve_impl("simd")


def test_spmv_impl_auto_matches_explicit():
    """The dispatching entry point (impl=) agrees with the historic
    boolean overrides on the same operand."""
    from repro.kernels.bsr_spmv import bsr_matvec

    rng = np.random.default_rng(9)
    rows, cols, vals = random_coo(rng, 128, 128, 700)
    bsr = build_bsr(rows, cols, vals, 128, 128, bm=32, bn=32)
    x = rng.standard_normal((128, 2)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, 128, 32))
    blocks, blk_cols = bsr.device()
    y_auto = np.asarray(bsr_matvec(blocks, blk_cols, xp))
    y_interp = np.asarray(spmv(bsr, xp, interpret=True))
    y_ref = np.asarray(spmv(bsr, xp, use_ref=True))
    np.testing.assert_allclose(y_auto, y_interp, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y_auto, y_ref, rtol=1e-5, atol=1e-5)
