"""Block-CSR SpMV Pallas TPU kernel — the paper's per-iteration hot spot.

Hardware adaptation (DESIGN.md §3): a GPU CSR SpMV is a gather-heavy,
warp-per-row pattern with no TPU analogue; the MXU wants dense 128x128
tiles. We therefore store P^T (or any G-block) as *block*-CSR with dense
(bm, bn) = (128, 128) blocks and give every block-row a fixed budget of K
nonzero blocks (padding with zero blocks keeps the grid static — XLA/Pallas
needs static shapes). Web graphs with strong intra-site locality put most
mass near the diagonal, so real K is small.

Kernel structure:
  grid = (n_block_rows, K); the x block consumed by grid step (i, k) is
  selected by the *scalar-prefetched* column table entry for (i, k) —
  Pallas loads it HBM->VMEM ahead of the MXU multiply. Accumulation over k
  happens in the output VMEM block (revisited across the K inner steps).

  The prefetched table lives in SMEM (1 MiB on v5e). A 2-D (nbr, K) table
  is padded to 128 lanes there, so the Stanford-Web shape (nbr=2203, K=70)
  already overflows it; a flat 1-D table pads only to a few KiB. The grid
  is therefore split into block-row chunks whose flat table fits
  `SMEM_TABLE_BYTES`, one pallas_call per chunk. Every chunk reads the
  whole `blocks` array through a row-offset index map, so no slice of the
  (possibly HBM-filling) block array is ever copied.

  x carries nv lanes (n_block_cols, bn, nv): multi-vector SpMV amortizes the
  block loads over several teleportation vectors — the paper's
  personalization use-case ([17]) — and gives the MXU a (128, 128) @
  (128, nv) shape instead of a mat-vec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128

# the MXU's default f32 matmul is one bf16 pass (~3 significant digits):
# on v5e it left the power method's L1 residual stuck near 5e-4 on the
# Stanford-Web replica. HIGHEST is full f32; there it cost about 13% per
# power iteration on v5e (~66 ms against ~58 ms). The 3-pass HIGH was not
# tried
_PRECISION = jax.lax.Precision.HIGHEST

# flat column-table bytes per pallas_call: a quarter of v5e's 1 MiB SMEM,
# which leaves the rest to Mosaic's own scalar state
SMEM_TABLE_BYTES = 256 * 1024


def row_chunks(nbr: int, K: int) -> list:
    """[(row0, rows), ...] covering nbr block-rows, each chunk's flat
    (rows * K) int32 column table within SMEM_TABLE_BYTES."""
    per = max(1, SMEM_TABLE_BYTES // (4 * K))
    return [(r, min(per, nbr - r)) for r in range(0, nbr, per)]


def _kernel(blk_cols_ref, blocks_ref, x_ref, o_ref):
    """One (block-row i, slot k) step: o[i] += blocks[i,k] @ x[cols[i,k]]."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    blk = blocks_ref[0, 0]          # (bm, bn)
    xb = x_ref[0]                   # (bn, nv)
    o_ref[0] += jnp.dot(blk, xb, preferred_element_type=jnp.float32,
                        precision=_PRECISION).astype(o_ref.dtype)


def _kernel_kahan(blk_cols_ref, blocks_ref, x_ref, o_ref, c_ref):
    """Compensated (Kahan) accumulation over the K inner slots.

    The f32 MXU products carry a per-element running compensation term in a
    VMEM scratch block that persists across the K grid steps revisiting this
    output block, so the K-term summation error drops from O(K * eps) to
    O(eps) — the accumulation-noise half of the f32 residual floor.  (The
    other half, the f32 *representation* of blocks and x, is unchanged: ask
    the ref/einsum lane with accum="f64" for genuinely tighter arithmetic.)
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    blk = blocks_ref[0, 0]          # (bm, bn)
    xb = x_ref[0]                   # (bn, nv)
    prod = jnp.dot(blk, xb, preferred_element_type=jnp.float32,
                   precision=_PRECISION)
    y = prod - c_ref[...]
    t = o_ref[0] + y
    c_ref[...] = (t - o_ref[0]) - y
    o_ref[0] = t


@functools.partial(jax.jit, static_argnames=("interpret", "accum"))
def bsr_spmv(blocks: jax.Array, blk_cols: jax.Array, x: jax.Array,
             interpret: bool = False, accum: str = "f32") -> jax.Array:
    """y[i] = sum_k blocks[i, k] @ x[blk_cols[i, k]].

    blocks:   (nbr, K, bm, bn)
    blk_cols: (nbr, K) int32 — zero-padded slots MUST point at a valid block
              column (use 0) with an all-zero data block.
    x:        (nbc, bn, nv)
    accum:    "f32" (plain f32 accumulate, the MXU default) or "kahan"
              (compensated summation across the K slots — the tight-residual
              lane for relaxed-tolerance async device runs).
    returns   (nbr, bm, nv) float32
    """
    if accum not in ("f32", "kahan"):
        raise ValueError(f"unknown accum {accum!r}; the kernel renders "
                         "'f32' or 'kahan' (f64 accumulate is the ref lane)")
    nbr, K, bm, bn = blocks.shape
    nbc, bn2, nv = x.shape
    assert bn == bn2, (bn, bn2)

    kernel = _kernel if accum == "f32" else _kernel_kahan
    scratch = [] if accum == "f32" else [pltpu.VMEM((bm, nv), jnp.float32)]
    flat_cols = blk_cols.reshape(-1)

    def call(row0, rows):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(rows, K),
                in_specs=[
                    pl.BlockSpec((1, 1, bm, bn),
                                 lambda i, k, cols: (i + row0, k, 0, 0)),
                    pl.BlockSpec((1, bn, nv),
                                 lambda i, k, cols: (cols[i * K + k], 0, 0)),
                ],
                out_specs=pl.BlockSpec((1, bm, nv),
                                       lambda i, k, cols: (i, 0, 0)),
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((rows, bm, nv), jnp.float32),
            interpret=interpret,
        )(flat_cols[row0 * K:(row0 + rows) * K], blocks, x)

    parts = [call(row0, rows) for row0, rows in row_chunks(nbr, K)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
