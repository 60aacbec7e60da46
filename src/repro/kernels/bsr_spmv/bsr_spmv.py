"""Block-CSR SpMV Pallas TPU kernel — the paper's per-iteration hot spot.

Hardware adaptation (DESIGN.md §3): a GPU CSR SpMV is a gather-heavy,
warp-per-row pattern with no TPU analogue; the TPU wants dense 128x128
tiles streamed from HBM. We therefore store P^T (or any G-block) as
*block*-CSR with dense (bm, bn) = (128, 128) blocks. Every block-row holds
its stored blocks in slots 0..count-1 of a fixed budget of K slots (zero
blocks pad the rest, so the array shape is static); web graphs with strong
intra-site locality put most mass near the diagonal, so K is small.

Layout contract (lanes outermost, the block edge on the lane axis):
  x:   (nv, nbc, bn) — for nv = 1 a compact (nbc, bn) array, so the whole
       iterate sits in VMEM (1.13 MB at the Stanford-Web shape);
  out: (nv, nbr, bm).

Kernel structure — a grouped, ragged grid:
  grid = (n_block_rows, K // Kg). Step (i, g) streams the g-th group of
  Kg stored blocks of block-row i, one (1, Kg, bm, bn) DMA, and multiplies
  each against the x block its scalar-prefetched column table entry
  names, read from the VMEM-resident iterate (`x[:, pl.ds(col, 1), :]`).
  The row's sum accumulates in a VMEM scratch across its groups and is
  written to the VMEM-resident output once, at the row's last grid step.

  Ragged stop: a prefetched per-row group count ends each row at its last
  stored block. The blocks index map clamps the group index there, so the
  remaining steps repeat the block index (no new DMA) and `pl.when` skips
  their compute. Padded slots inside the last group are zero blocks, so
  they add exactly 0.

  Kg comes from a VMEM budget: `BLOCK_VMEM_BYTES` holds both pipeline
  buffers of a group (at most 10 blocks at bm = bn = 128), and K splits
  into the fewest groups that fit, made even (K = 70: Kg = 10).
  `build_bsr` pads K to a multiple of Kg with zero blocks.

  Product form, chosen by the lane count of the call:
    nv <= VPU_MAX_LANES: a VPU multiply-accumulate, acc(bm, bn) +=
      B * x_row(1, bn) per block and lane, then one lane reduction per
      row — exact f32 arithmetic at ~32 vector operations per block;
    otherwise the MXU product x(nv, bn) @ B^T at Precision.HIGHEST.
  Neither form drops below f32.

  The prefetched tables live in SMEM (1 MiB on v5e). A 2-D (nbr, K) table
  is padded to 128 lanes there, so the Stanford-Web shape (nbr=2203, K=70)
  already overflows it; flat 1-D tables pad only to a few KiB. The grid
  is therefore split into block-row chunks whose tables fit
  `SMEM_TABLE_BYTES`, one pallas_call per chunk. Every chunk reads the
  whole `blocks` array through a row-offset index map, so no slice of the
  (possibly HBM-filling) block array is ever copied. Lanes are split into
  calls of their own only when the resident iterate and output would pass
  `RESIDENT_VMEM_BYTES`.

  nv > 1 lanes amortize the block loads over several teleportation
  vectors — the paper's personalization use-case ([17]).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128

# the MXU's default f32 matmul is one bf16 pass (~3 significant digits):
# on v5e it left the power method's L1 residual stuck near 5e-4 on the
# Stanford-Web replica. HIGHEST is full f32
_PRECISION = jax.lax.Precision.HIGHEST

# flat column-table and group-count bytes per pallas_call: a quarter of
# v5e's 1 MiB SMEM, which leaves the rest to Mosaic's own scalar state
SMEM_TABLE_BYTES = 256 * 1024

# VMEM for both pipeline buffers of one group of f32 blocks: ten 128x128
# blocks. On v5e at the Stanford-Web shape an apply took 11.0, 11.1, 11.6,
# 11.8 and 13.4 ms at 8, 10, 16, 20 and 40 blocks a group: larger groups
# pay less per grid step but stream more padding past each row's end.
# Ten, not eight, divides that shape's K = 70, so no slot is padded in
BLOCK_VMEM_BYTES = 2 * 10 * 128 * 128 * 4

# VMEM for the resident iterate and output of one call (v5e: 128 MiB)
RESIDENT_VMEM_BYTES = 64 * 1024 * 1024

# lane counts up to this take the VPU form, larger ones the MXU form. On
# v5e at the Stanford-Web shape (16 blocks a group) the VPU form took
# 11.6, 11.9, 13.1, 19.9 and 42.2 ms an apply at 1, 2, 4, 8 and 16 lanes,
# the MXU form 14.0-14.2 ms at every lane count
VPU_MAX_LANES = 4


def group_size(K: int, bm: int, bn: int) -> int:
    """Kg: stored blocks per grid step. The VMEM budget caps it; within
    the fewest groups that cap allows, groups are made even, so padding
    K to a multiple of Kg adds fewer slots than there are groups (none at
    K = 70: seven groups of 10). A K so padded gives the same Kg back."""
    cap = max(1, BLOCK_VMEM_BYTES // (2 * 4 * bm * bn))
    K = max(K, 1)
    return -(-K // -(-K // cap))


def row_chunks(nbr: int, K: int) -> list:
    """[(row0, rows), ...] covering nbr block-rows, each chunk's flat
    (rows * K) column table and (rows,) group counts, int32, within
    SMEM_TABLE_BYTES."""
    per = max(1, SMEM_TABLE_BYTES // (4 * (K + 1)))
    return [(r, min(per, nbr - r)) for r in range(0, nbr, per)]


def lane_chunks(nv: int, nbc: int, bn: int, nbr: int, bm: int) -> list:
    """[(lane0, lanes), ...]: lanes per call whose resident iterate and
    output fit RESIDENT_VMEM_BYTES (all of them, but at huge n * nv)."""
    per = max(1, RESIDENT_VMEM_BYTES // (4 * (nbc * bn + nbr * bm)))
    return [(l, min(per, nv - l)) for l in range(0, nv, per)]


def _comp_add(s, c, v):
    """One compensated step (Knuth's TwoSum): s + v rounded, and its exact
    rounding error added to the running compensation c; the sum so far is
    s + c, as if carried in twice the precision."""
    t = s + v
    b = t - s
    return t, c + ((s - (t - b)) + (v - b))


def _lane_sum(acc, comp):
    """Row sums of acc (bm, bn) as a lane-dense (1, bm) row; with `comp`,
    compensated across the lanes too (each lane's sum is acc + comp)."""
    t = acc.T                                        # (bn, bm)
    if comp is None:
        return jnp.sum(t, axis=0, keepdims=True)
    ct = comp.T
    s, c = t[0:8], ct[0:8]
    for r in range(8, t.shape[0], 8):
        s, c = _comp_add(s, c + ct[r:r + 8], t[r:r + 8])
    s8, c8 = s, c                                    # (8, bm)
    s, c = s8[0:1], c8[0:1]
    for r in range(1, 8):
        s, c = _comp_add(s, c + c8[r:r + 1], s8[r:r + 1])
    return s + c


def _make_kernel(group: int, nv: int, form: str, accum: str):
    kahan = accum == "kahan"

    def kernel(cols_ref, groups_ref, blocks_ref, x_ref, o_ref, acc_ref,
               *comp_ref):
        i, g = pl.program_id(0), pl.program_id(1)
        n_g = pl.num_programs(1)
        comp_ref = comp_ref[0] if kahan else None

        @pl.when(g == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if kahan:
                comp_ref[...] = jnp.zeros_like(comp_ref)

        @pl.when(g < groups_ref[i])
        def _stream():
            base = (i * n_g + g) * group
            acc = acc_ref[...]
            comp = comp_ref[...] if kahan else None
            for k in range(group):
                col = cols_ref[base + k]
                blk = blocks_ref[0, k]                       # (bm, bn)
                xs = x_ref[:, pl.ds(col, 1), :].astype(blk.dtype)
                if form == "vpu":
                    # (nv, 1, bn) rows against (bm, bn): (nv, bm, bn)
                    prod = blk[None] * xs
                else:
                    prod = jax.lax.dot_general(
                        xs.reshape(nv, -1), blk, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=_PRECISION)                # (nv, bm)
                if kahan:
                    acc, comp = _comp_add(acc, comp, prod)
                else:
                    acc = acc + prod
            acc_ref[...] = acc
            if kahan:
                comp_ref[...] = comp

        @pl.when(g == n_g - 1)
        def _write():
            if form == "vpu":
                for j in range(nv):
                    o_ref[j, pl.ds(i, 1), :] = _lane_sum(
                        acc_ref[j], comp_ref[j] if kahan else None)
            else:
                acc = acc_ref[...] + (comp_ref[...] if kahan else 0.0)
                o_ref[:, pl.ds(i, 1), :] = acc[:, None, :]

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "accum"))
def bsr_spmv(blocks: jax.Array, blk_cols: jax.Array, x: jax.Array,
             row_groups: Optional[jax.Array] = None,
             interpret: bool = False, accum: str = "f32") -> jax.Array:
    """y[:, i] = sum_k blocks[i, k] @ x[:, blk_cols[i, k]] per lane.

    blocks:     (nbr, K, bm, bn), K a multiple of `group_size(K, bm, bn)`
    blk_cols:   (nbr, K) int32 — padded slots MUST point at a valid block
                column (use 0) with an all-zero data block
    x:          (nv, nbc, bn)
    row_groups: (nbr,) int32 groups of stored blocks per row (None: all
                K // Kg); a row's slots past them are never read
    accum:      "f32" or "kahan" (compensated summation across all K
                slots of a row, and across the lane reduction)
    returns     (nv, nbr, bm) float32
    """
    if accum not in ("f32", "kahan"):
        raise ValueError(f"unknown accum {accum!r}; the kernel renders "
                         "'f32' or 'kahan' (f64 accumulate is the ref lane)")
    nbr, K, bm, bn = blocks.shape
    nv, nbc, bn2 = x.shape
    assert bn == bn2, (bn, bn2)
    group = group_size(K, bm, bn)
    if K % group:
        raise ValueError(f"K={K} is not a multiple of the group size "
                         f"{group}; pack with build_bsr, which pads K")
    n_g = K // group
    if row_groups is None:
        row_groups = jnp.full((nbr,), n_g, jnp.int32)
    flat_cols = blk_cols.reshape(-1)
    block_bytes = 2 * group * bm * bn * blocks.dtype.itemsize

    def call(row0, rows, xl):
        lanes = xl.shape[0]
        f = "vpu" if lanes <= VPU_MAX_LANES else "mxu"
        acc = (lanes, bm, bn) if f == "vpu" else (lanes, bm)
        scratch = [pltpu.VMEM(acc, jnp.float32)] * (1 + (accum == "kahan"))
        vmem = (4 * lanes * (nbc * bn + rows * bm) + block_bytes
                + 4 * len(scratch) * math.prod(acc))
        return pl.pallas_call(
            _make_kernel(group, lanes, f, accum),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(rows, n_g),
                in_specs=[
                    # clamped at the row's last group: the repeated block
                    # index issues no DMA for the steps past it
                    pl.BlockSpec(
                        (1, group, bm, bn),
                        lambda i, g, cols, ng: (
                            i + row0,
                            jnp.minimum(g, jnp.maximum(ng[i] - 1, 0)),
                            0, 0)),
                    pl.BlockSpec(memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((lanes, rows, bm), jnp.float32),
            # what the call holds, and 16 MiB for Mosaic's own scratch
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem + 16 * 1024 * 1024),
            interpret=interpret,
            # one name for every chunk, form and lane: the profile shows
            # the kernel as `bsr_spmv` or `bsr_spmv.<n>`
            name="bsr_spmv",
        )(flat_cols[row0 * K:(row0 + rows) * K],
          row_groups[row0:row0 + rows], blocks, xl)

    outs = []
    for l0, nl in lane_chunks(nv, nbc, bn, nbr, bm):
        xl = x if nl == nv else x[l0:l0 + nl]
        parts = [call(row0, rows, xl) for row0, rows in row_chunks(nbr, K)]
        outs.append(parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=1))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
