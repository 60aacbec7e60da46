"""Pure-jnp oracle for the block-CSR SpMV kernel.

Layout (see bsr_spmv.py for the rationale):
  blocks:   (n_block_rows, K, bm, bn)  dense nonzero blocks, zero-padded
  blk_cols: (n_block_rows, K) int32    block-column index of each block
  x:        (nv, n_block_cols, bn)     the iterate(s), lanes outermost;
                                        nv > 1 computes several
                                        personalized PageRank vectors
                                        simultaneously
  out:      (nv, n_block_rows, bm)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bsr_spmv_ref(blocks: jnp.ndarray, blk_cols: jnp.ndarray,
                 x: jnp.ndarray, accum: str = "f32") -> jnp.ndarray:
    """accum selects the accumulation lane of the contraction:

      "f32"   — f32 accumulate (bitwise the historic oracle).
      "f64"   — inputs upcast, contraction accumulated in float64, result
                returned in x's dtype (float64 under enable_x64): the
                segment-sum-grade reference the compensated kernel lane is
                equivalence-tested against.
      "kahan" — the compensated-summation *limit*: f64 accumulate cast back
                to float32 (what an exactly-compensated f32 sum converges
                to; the Pallas kernel's accum="kahan" approximates this).
    """
    # gather the x block for every (row, k): (nv, nbr, K, bn)
    xg = x[:, blk_cols]
    if accum == "f32":
        # (nbr, K, bm, bn) @ (nv, nbr, K, bn) -> sum over K -> (nv, nbr, bm)
        return jnp.einsum("rkmn,vrkn->vrm", blocks, xg,
                          preferred_element_type=jnp.float32)
    if accum not in ("f64", "kahan"):
        raise ValueError(f"unknown accum {accum!r}; expected 'f32', "
                         "'f64' or 'kahan'")
    # canonicalize: float64 with x64 live, a silent float32 degrade (no
    # warning spam) when the caller never enabled it
    wide = jax.dtypes.canonicalize_dtype(jnp.float64)
    y = jnp.einsum("rkmn,vrkn->vrm", blocks.astype(wide), xg.astype(wide),
                   preferred_element_type=wide)
    return y.astype(jnp.float32 if accum == "kahan" else x.dtype)
