"""Jit'd wrappers + host-side BSR construction for the SpMV kernel.

Two host containers:

  * BSRMatrix   — the kernel's fixed-budget block-CSR layout (every block-row
                  padded to K nonzero blocks).
  * HybridBSR   — solve-grade layout for real web graphs: rows whose in-links
                  span many block columns ("hub" pages, the in-degree tail)
                  are split out into a COO side structure evaluated with
                  gather + segment-sum, and only the site-local remainder is
                  blocked. Without the split, one hub row drives K up to the
                  full number of block columns and the dense-block array
                  explodes (50k-node power-law graph: K = nbc, ~10 GB; after
                  a 99th-percentile split: K ~ 43, ~0.3 GB).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .bsr_spmv import bsr_spmv, DEFAULT_BM, DEFAULT_BN
from .ref import bsr_spmv_ref
from ...graph.csr import TransitionT

# the dense-block array is uploaded whole to one device, so refuse to
# pack one that cannot be solved on a v5e chip: 16 GB of HBM less the
# fused power loop's ~0.93 GB of temporaries at the Stanford-Web shape
# (AOT memory_analysis for v5e). Packing happens on the host, so this is
# also the host memory a pack may take on any platform
MAX_BLOCK_BYTES = 15 * 10**9


@dataclasses.dataclass(frozen=True)
class BSRMatrix:
    """Host container: block-CSR with a fixed blocks-per-row budget."""
    n_rows: int                 # logical (unpadded) rows
    n_cols: int
    bm: int
    bn: int
    blocks: np.ndarray          # (nbr, K, bm, bn) float32
    blk_cols: np.ndarray        # (nbr, K) int32
    fill_ratio: float           # nnz / dense-block capacity actually used

    @property
    def nbr(self) -> int:
        return self.blocks.shape[0]

    @property
    def nbc(self) -> int:
        return -(-self.n_cols // self.bn)

    @property
    def K(self) -> int:
        return self.blocks.shape[1]

    def device(self) -> Tuple[jax.Array, jax.Array]:
        return jnp.asarray(self.blocks), jnp.asarray(self.blk_cols)


def _ravel_index(blocks, ub_row, slot, inv, rows, cols, bm, bn):
    K = blocks.shape[1]
    # one base offset per unique block (tiny array), then a single gather
    # per edge; bit-masked intra-block coordinates for power-of-two blocks
    base = (ub_row * K + slot) * (bm * bn)
    r = rows & (bm - 1) if (bm & (bm - 1)) == 0 else rows % bm
    c = cols & (bn - 1) if (bn & (bn - 1)) == 0 else cols % bn
    return base[inv] + r * bn + c


def _scatter_blocks_bincount(blocks, ub_row, slot, inv, rows, cols, vals,
                             bm, bn, unique_pairs):
    """Scatter COO values through a raveled index into the blocks buffer.

    unique_pairs=True (every (row, col) occurs once — guaranteed for edges
    coming out of CSRGraph/TransitionT): one vectorized fancy assignment,
    no per-element loop at all. Otherwise duplicates are accumulated with
    np.bincount over the *compacted* raveled-index domain (np.unique
    compresses the index space so bincount never allocates the full dense
    raster)."""
    flat = _ravel_index(blocks, ub_row, slot, inv, rows, cols, bm, bn)
    bf = blocks.reshape(-1)
    if unique_pairs:
        bf[flat] = np.asarray(vals, dtype=np.float32)
        return
    uniq_flat, inv2 = np.unique(flat, return_inverse=True)
    sums = np.bincount(inv2, weights=vals.astype(np.float64),
                       minlength=len(uniq_flat))
    bf[uniq_flat] = sums.astype(np.float32)


def _scatter_blocks_add_at(blocks, ub_row, slot, inv, rows, cols, vals,
                           bm, bn, unique_pairs):
    """The original np.add.at scatter — kept only as the micro-benchmark
    baseline (np.add.at with a 4-tuple fancy index is notoriously slow)."""
    np.add.at(
        blocks,
        (ub_row[inv], slot[inv], rows % bm, cols % bn),
        vals.astype(np.float32),
    )


def build_bsr(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              n_rows: int, n_cols: int, bm: int = DEFAULT_BM,
              bn: int = DEFAULT_BN, k_budget: Optional[int] = None,
              scatter: str = "bincount",
              unique_pairs: bool = False) -> BSRMatrix:
    """Pack COO triplets into the fixed-budget BSR layout.

    If a block-row holds more distinct nonzero block-columns than k_budget,
    the budget is raised to the max (the kernel needs a static K).
    Set unique_pairs=True when no (row, col) repeats (graph edge lists) —
    the scatter then skips duplicate accumulation entirely.
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    nbr = -(-n_rows // bm)
    nbc = -(-n_cols // bn)
    brow = rows // bm
    bcol = cols // bn
    key = brow * nbc + bcol
    uniq, inv = np.unique(key, return_inverse=True)
    ub_row = (uniq // nbc).astype(np.int64)
    ub_col = (uniq % nbc).astype(np.int32)

    per_row = np.bincount(ub_row, minlength=nbr)
    K = int(per_row.max()) if k_budget is None else max(k_budget,
                                                        int(per_row.max()))
    K = max(K, 1)

    # slot of each unique block within its block-row
    order = np.argsort(ub_row, kind="stable")
    slot_sorted = np.arange(len(uniq)) - np.concatenate(
        [[0], np.cumsum(per_row)])[ub_row[order]]
    slot = np.empty(len(uniq), dtype=np.int64)
    slot[order] = slot_sorted

    est = nbr * K * bm * bn * 4
    if est > MAX_BLOCK_BYTES:
        raise MemoryError(
            f"BSR dense-block array would be {est/1e9:.1f} GB "
            f"(K={K}); use build_hybrid_bsr (hub split), reordering, or "
            f"larger blocks")
    blocks = np.zeros((nbr, K, bm, bn), dtype=np.float32)
    blk_cols = np.zeros((nbr, K), dtype=np.int32)
    blk_cols[ub_row, slot] = ub_col

    scatter_fn = {"bincount": _scatter_blocks_bincount,
                  "add_at": _scatter_blocks_add_at}[scatter]
    scatter_fn(blocks, ub_row, slot, inv, rows, cols, vals, bm, bn,
               unique_pairs)
    # len(uniq) == 0 is reachable (hub split can route every edge to the
    # COO side); an all-zero-block BSR with fill 0 is the right answer
    fill = len(rows) / float(len(uniq) * bm * bn) if len(uniq) else 0.0
    return BSRMatrix(n_rows=n_rows, n_cols=n_cols, bm=bm, bn=bn,
                     blocks=blocks, blk_cols=blk_cols, fill_ratio=fill)


# --------------------------------------------------------------------------
# Hub-split hybrid layout (solve-grade)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HybridBSR:
    """BSR over site-local mass + COO over hub rows (in-degree tail).

    The COO side is evaluated as gather + segment-sum over the *padded* row
    space, so a fused Google-apply can stay entirely in the kernel's
    (n_blocks, block, nv) layout.
    """
    bsr: BSRMatrix
    hub_rows: np.ndarray      # int32 (hub_nnz,) destination row of each edge
    hub_cols: np.ndarray      # int32 (hub_nnz,) source column
    hub_vals: np.ndarray      # float32 (hub_nnz,)
    hub_nnz_frac: float       # fraction of nnz routed through the COO side

    @property
    def n_rows(self) -> int:
        return self.bsr.n_rows

    @property
    def n_cols(self) -> int:
        return self.bsr.n_cols

    def device(self) -> dict:
        blocks, blk_cols = self.bsr.device()
        return dict(blocks=blocks, blk_cols=blk_cols,
                    hub_rows=jnp.asarray(self.hub_rows),
                    hub_cols=jnp.asarray(self.hub_cols),
                    hub_vals=jnp.asarray(self.hub_vals))


def build_hybrid_bsr(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                     n_rows: int, n_cols: int, bm: int = DEFAULT_BM,
                     bn: int = DEFAULT_BN, hub_quantile: float = 0.99,
                     k_budget: Optional[int] = None,
                     scatter: str = "bincount",
                     unique_pairs: bool = False) -> HybridBSR:
    """Split rows above the `hub_quantile` of row-nnz into the COO side and
    block the remainder. hub_quantile=1.0 disables the split."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals)
    row_nnz = np.bincount(rows, minlength=n_rows)
    if hub_quantile < 1.0 and len(rows):
        cut = np.quantile(row_nnz, hub_quantile)
        hub_mask_row = row_nnz > cut
    else:
        hub_mask_row = np.zeros(n_rows, dtype=bool)
    is_hub = hub_mask_row[rows]
    keep = ~is_hub
    bsr = build_bsr(rows[keep], cols[keep], vals[keep], n_rows, n_cols,
                    bm=bm, bn=bn, k_budget=k_budget, scatter=scatter,
                    unique_pairs=unique_pairs)
    return HybridBSR(
        bsr=bsr,
        hub_rows=rows[is_hub].astype(np.int32),
        hub_cols=cols[is_hub].astype(np.int32),
        hub_vals=vals[is_hub].astype(np.float32),
        hub_nnz_frac=float(is_hub.mean()) if len(rows) else 0.0,
    )


def bsr_from_transition(pt: TransitionT, bm: int = DEFAULT_BM,
                        bn: int = DEFAULT_BN) -> BSRMatrix:
    """BSR of P^T (rows = destination pages, cols = source pages)."""
    return build_bsr(rows=pt.row_ids.astype(np.int64),
                     cols=pt.src.astype(np.int64),
                     vals=np.asarray(pt.weight, dtype=np.float32),
                     n_rows=pt.n, n_cols=pt.n, bm=bm, bn=bn,
                     unique_pairs=True)


def hybrid_from_transition(pt: TransitionT, bm: int = DEFAULT_BM,
                           bn: int = DEFAULT_BN,
                           hub_quantile: float = 0.99) -> HybridBSR:
    """Solve-grade hybrid layout of P^T."""
    return build_hybrid_bsr(rows=pt.row_ids.astype(np.int64),
                            cols=pt.src.astype(np.int64),
                            vals=np.asarray(pt.weight, dtype=np.float32),
                            n_rows=pt.n, n_cols=pt.n, bm=bm, bn=bn,
                            hub_quantile=hub_quantile, unique_pairs=True)


def pad_x(x: np.ndarray, n_cols: int, bn: int) -> np.ndarray:
    """(n, nv) or (n,) -> (nbc, bn, nv) padded block layout."""
    if x.ndim == 1:
        x = x[:, None]
    n, nv = x.shape
    nbc = -(-n_cols // bn)
    xp = np.zeros((nbc * bn, nv), dtype=x.dtype)
    xp[:n] = x
    return xp.reshape(nbc, bn, nv)


def unpad_y(y: np.ndarray, n_rows: int) -> np.ndarray:
    """(nbr, bm, nv) -> (n_rows, nv)."""
    nbr, bm, nv = y.shape
    return y.reshape(nbr * bm, nv)[:n_rows]


IMPLS = ("auto", "pallas", "interpret", "ref")


def resolve_impl(impl: str = "auto") -> str:
    """Kernel-dispatch policy: "auto" picks the compiled Pallas kernel on a
    TPU (the kernel is Mosaic-only) and interpret mode elsewhere (the
    faithful kernel semantics on hosts with no Mosaic backend); an
    explicit impl is passed
    through untouched — the kernel tests' override.  (The *solver* policy,
    which prefers the fast blocked-einsum oracle on CPU, lives in
    core.backend.BackendSpec.resolved() — same math, different speed
    trade.)"""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def bsr_matvec(blocks: jax.Array, blk_cols: jax.Array, x: jax.Array,
               impl: str = "auto", accum: str = "f32") -> jax.Array:
    """Dispatch the block multiply: Pallas kernel, interpret mode, or the
    jnp blocked-einsum oracle (same math, XLA-compiled — the CPU path).
    impl="auto" resolves via `resolve_impl` (pallas on TPU, interpret
    elsewhere).  `accum` selects the accumulation lane: "f32"
    (default), "kahan" (compensated summation — on the kernel paths a
    scratch-carried Kahan sum, on the ref path the f64-accumulate limit
    cast back to f32), or "f64" (ref path only: full f64 accumulate,
    result in x's dtype; the kernel paths render it as "kahan" — the MXU
    has no f64)."""
    impl = resolve_impl(impl)
    if impl == "pallas":
        return bsr_spmv(blocks, blk_cols, x, interpret=False,
                        accum="f32" if accum == "f32" else "kahan")
    if impl == "interpret":
        return bsr_spmv(blocks, blk_cols, x, interpret=True,
                        accum="f32" if accum == "f32" else "kahan")
    return bsr_spmv_ref(blocks, blk_cols, x, accum=accum)


def hybrid_matvec(dev: dict, x: jax.Array, impl: str = "ref",
                  accum: str = "f32") -> jax.Array:
    """y = PT @ x in the padded block layout for a HybridBSR device dict.

    x: (nbc, bn, nv) -> y: (nbr, bm, nv). The hub COO side is a gather +
    segment-sum over the padded row space, fused into the same jit scope
    (accumulated in the same lane as the block side: f64 when accum
    requests it and x64 is live).
    """
    y = bsr_matvec(dev["blocks"], dev["blk_cols"], x, impl=impl,
                   accum=accum)
    nbr, bm, nv = y.shape
    xf = x.reshape(-1, nv)
    if accum == "f32":
        contrib = dev["hub_vals"][:, None] * xf[dev["hub_cols"]]
    else:
        wide = jax.dtypes.canonicalize_dtype(jnp.float64)
        contrib = (dev["hub_vals"].astype(wide)[:, None]
                   * xf.astype(wide)[dev["hub_cols"]])
    hub = jax.ops.segment_sum(contrib, dev["hub_rows"],
                              num_segments=nbr * bm)
    return y + hub.reshape(nbr, bm, nv).astype(y.dtype)


def spmv(bsr: BSRMatrix, x: jax.Array, interpret: bool = False,
         use_ref: bool = False, impl: Optional[str] = None,
         accum: str = "f32") -> jax.Array:
    """y = PT @ x in the padded block layout (device arrays in/out).

    The historic boolean knobs (`interpret`/`use_ref`) are kept as the
    kernel tests' explicit override; pass `impl=` ("auto"/"pallas"/
    "interpret"/"ref") to go through the auto-detecting dispatch instead.
    """
    blocks, blk_cols = bsr.device()
    if impl is not None:
        return bsr_matvec(blocks, blk_cols, x, impl=impl, accum=accum)
    if use_ref:
        return bsr_spmv_ref(blocks, blk_cols, x, accum=accum)
    return bsr_spmv(blocks, blk_cols, x, interpret=interpret,
                    accum="f32" if accum == "f32" else "kahan")
