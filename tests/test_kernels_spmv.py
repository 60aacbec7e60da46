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


@pytest.fixture
def group_cap(monkeypatch):
    """Cap the kernel's groups at `cap` blocks of (bm, bn) through its VMEM
    budget, so small operands stream in several groups a row. The jit
    caches are cleared, so no trace made under another cap is reused."""
    import importlib
    import jax
    bk = importlib.import_module("repro.kernels.bsr_spmv.bsr_spmv")

    def set_cap(cap, bm=16, bn=16):
        monkeypatch.setattr(bk, "BLOCK_VMEM_BYTES", 2 * 4 * bm * bn * cap)
        jax.clear_caches()
    yield set_cap
    jax.clear_caches()


def stored_per_row(rows, cols, n_cols, bm, bn):
    """Distinct block columns of each block row (the unpadded K)."""
    nbc = -(-n_cols // bn)
    key = np.unique((rows // bm) * nbc + cols // bn)
    return np.bincount(key // nbc)


def _shape_case(*args, cap=None, accum="f32"):
    """One case; the ungrouped f32 cases keep their shape-only ids."""
    ident = "-".join(map(str, args))
    if cap is not None:
        ident += f"-cap{cap}-{accum}"
    return pytest.param(*args, cap, accum, id=ident)


@pytest.mark.parametrize("n_rows,n_cols,nnz,bm,bn,nv,cap,accum", [
    _shape_case(100, 100, 500, 32, 32, 1),
    _shape_case(257, 130, 800, 64, 32, 4),
    _shape_case(512, 512, 4000, 128, 128, 8),
    _shape_case(64, 300, 600, 16, 64, 2),
    # grouped grid with K not a multiple of the group: VPU and MXU forms
    _shape_case(300, 300, 3000, 16, 16, 1, cap=4),
    _shape_case(300, 300, 3000, 16, 16, 1, cap=4, accum="kahan"),
    _shape_case(300, 300, 3000, 16, 16, 16, cap=4),
    _shape_case(300, 300, 3000, 16, 16, 16, cap=4, accum="kahan"),
])
def test_kernel_matches_ref_shapes(group_cap, n_rows, n_cols, nnz, bm, bn,
                                   nv, cap, accum):
    rng = np.random.default_rng(nnz)
    rows, cols, vals = random_coo(rng, n_rows, n_cols, nnz)
    if cap is not None:
        group_cap(cap, bm, bn)
    bsr = build_bsr(rows, cols, vals, n_rows, n_cols, bm=bm, bn=bn)
    if cap is not None:
        k_raw = stored_per_row(rows, cols, n_cols, bm, bn).max()
        assert bsr.group <= cap and k_raw % bsr.group
        assert bsr.K == -(-k_raw // bsr.group) * bsr.group
    x = rng.standard_normal((n_cols, nv)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, n_cols, bn))
    y_k = np.asarray(spmv(bsr, xp, interpret=True, accum=accum))
    y_r = np.asarray(bsr_spmv_ref(*bsr.device(), xp))
    np.testing.assert_allclose(y_k, y_r, rtol=1e-5, atol=1e-5)


def ragged_bsr(counts, bm=16, seed=0):
    """A BSR whose block row r stores counts[r] blocks (zero allowed)."""
    rng = np.random.default_rng(seed)
    nbc = max(counts)
    rows, cols = [], []
    for r, c in enumerate(counts):
        for bc in rng.choice(nbc, size=c, replace=False):
            rows.append(r * bm + rng.integers(0, bm, 6))
            cols.append(bc * bm + rng.integers(0, bm, 6))
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    key = np.unique(rows * nbc * bm + cols)
    rows, cols = key // (nbc * bm), key % (nbc * bm)
    vals = rng.standard_normal(len(rows))
    return build_bsr(rows, cols, vals, len(counts) * bm, nbc * bm, bm=bm,
                     bn=bm, unique_pairs=True), rows, cols, vals


@pytest.mark.parametrize("nv", [1, 16])          # the VPU and MXU forms
@pytest.mark.parametrize("accum", ["f32", "kahan"])
def test_ragged_rows_stop_at_last_group(group_cap, nv, accum):
    """Rows of 0..11 stored blocks under a cap of 5 a group: three groups
    of 4 (K = 11 padded to 12), and every row stops at its last stored
    group. Groups past it are poisoned with NaN, so any read of them
    would show; the empty row gives exact zeros."""
    import scipy.sparse as sp
    from repro.kernels.bsr_spmv import bsr_spmv
    group_cap(5)
    counts = [0, 7, 1, 11, 3, 10, 5, 2]
    bsr, rows, cols, vals = ragged_bsr(counts)
    assert bsr.K == 12 and bsr.group == 4
    np.testing.assert_array_equal(bsr.row_groups,
                                  [-(-c // 4) for c in counts])
    blocks = bsr.blocks.copy()
    for r, ng in enumerate(bsr.row_groups):
        blocks[r, ng * 4:] = np.nan
    rng = np.random.default_rng(nv)
    x = rng.standard_normal((bsr.n_cols, nv)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, bsr.n_cols, 16))
    y = np.asarray(bsr_spmv(jnp.asarray(blocks), jnp.asarray(bsr.blk_cols),
                            xp, jnp.asarray(bsr.row_groups), interpret=True,
                            accum=accum))
    y_r = np.asarray(bsr_spmv_ref(*bsr.device(), xp))
    np.testing.assert_allclose(y, y_r, rtol=1e-5, atol=1e-5)
    a = sp.csr_matrix((vals, (rows, cols)), shape=(bsr.n_rows, bsr.n_cols))
    np.testing.assert_allclose(unpad_y(y, bsr.n_rows), a @ x, rtol=1e-4,
                               atol=1e-5)
    assert not y[:, 0].any()                    # block row 0 stores none


def test_streamed_share_counter(group_cap):
    """The pack-time engagement counters: the share of the (nbr x K) slot
    grid the kernel streams, and the bytes one apply moves."""
    group_cap(3)
    g = powerlaw_webgraph(n=2000, target_nnz=16000, n_dangling=4, seed=3)
    pt = TransitionT.from_graph(g)
    rows, cols = pt.row_ids.astype(np.int64), pt.src.astype(np.int64)
    bsr = build_bsr(rows, cols, np.asarray(pt.weight, np.float32), pt.n,
                    pt.n, bm=16, bn=16, unique_pairs=True)
    kg = bsr.group
    assert kg <= 3
    groups = -(-stored_per_row(rows, cols, pt.n, 16, 16) // kg)
    assert bsr.streamed_share == pytest.approx(
        groups.sum() * kg / (bsr.nbr * bsr.K))
    assert 0 < bsr.streamed_share < 1
    assert bsr.streamed_bytes(nv=2) == 4 * (
        groups.sum() * kg * 16 * 16 + 2 * 2 * bsr.nbr * 16)

    # exported through runtime.observe, and returned by a bsr_pallas solve
    from repro.core import BackendSpec, solve_power
    from repro.graph.google import GoogleOperator
    from repro.runtime.observe import kernel_counters, render_prometheus
    text = render_prometheus([(k, "gauge", v) for k, v
                              in kernel_counters(bsr, nv=2).items()])
    assert f"repro_bsr_streamed_block_share {bsr.streamed_share!r}" in text
    assert (f"repro_bsr_streamed_bytes_per_apply {bsr.streamed_bytes(2)}"
            in text)
    op = GoogleOperator(pt=pt)
    res = solve_power(op, tol=1e-6, backend=BackendSpec("bsr_pallas", bm=16))
    hyb = op.hybrid_bsr(bm=16, bn=16)
    assert res.kernel == kernel_counters(hyb.bsr)


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
    """A column table over the SMEM budget, and lanes over the resident
    VMEM budget, run as several pallas_calls whose concatenated output
    equals the oracle (shapes unique to this test, so no trace cached
    under the real budgets is reused)."""
    import importlib
    from repro.kernels.bsr_spmv import bsr_spmv
    # the package re-exports the function under the module's name
    bk = importlib.import_module("repro.kernels.bsr_spmv.bsr_spmv")
    rng = np.random.default_rng(17)
    rows, cols, vals = random_coo(rng, 176, 176, 900)
    bsr = build_bsr(rows, cols, vals, 176, 176, bm=16, bn=16)
    # three block-rows of tables (columns and a group count) per call:
    # 11 rows -> 4 calls
    monkeypatch.setattr(bk, "SMEM_TABLE_BYTES", 3 * (bsr.K + 1) * 4)
    assert len(bk.row_chunks(bsr.nbr, bsr.K)) == 4
    # two lanes' iterate and output per call: 3 lanes -> 2 lane chunks
    monkeypatch.setattr(bk, "RESIDENT_VMEM_BYTES", 2 * 4 * 2 * 176)
    assert bk.lane_chunks(3, 11, 16, 11, 16) == [(0, 2), (2, 1)]
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
