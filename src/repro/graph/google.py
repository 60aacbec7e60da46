"""Google-matrix pipeline: A -> P -> S -> G (paper §2), matrix-free.

G = alpha * S + (1 - alpha) * v e^T,   S = P^T + w d^T,  w = e/n.

We never form S or G: the iteration applies
    G x = alpha * P^T x + alpha * w (d^T x) + (1 - alpha) * v (e^T x)
and the linear-system (Jacobi/Richardson) form
    R x + b = alpha * (P^T x + w (d^T x)) + b,   b = (1 - alpha) * v.
Both preserve ||x||_1 = 1 for the power form when x0 is a distribution.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
import scipy.sparse as sp

from .csr import CSRGraph, TransitionT, pt_matvec

DEFAULT_ALPHA = 0.85


@dataclasses.dataclass(frozen=True)
class GoogleOperator:
    """Matrix-free Google matrix over a web graph."""

    pt: TransitionT
    alpha: float = DEFAULT_ALPHA
    v: Optional[np.ndarray] = None  # teleportation (personalization) vector

    @property
    def n(self) -> int:
        return self.pt.n

    def teleport(self) -> np.ndarray:
        if self.v is not None:
            return np.asarray(self.v, dtype=np.float64)
        return np.full(self.n, 1.0 / self.n, dtype=np.float64)

    def _cache(self) -> dict:
        cache = self.__dict__.get("_op_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_op_cache", cache)
        return cache

    def hybrid_bsr(self, bm: int = 128, bn: int = 128,
                   hub_quantile: float = 0.99):
        """Solve-grade hub-split BSR of P^T, built once per layout and
        memoized on the operator (the host-side packing is the expensive
        part of a BSR solve; repeated solves must not repeat it)."""
        from ..kernels.bsr_spmv import hybrid_from_transition
        key = ("hybrid", bm, bn, hub_quantile)
        cache = self._cache()
        if key not in cache:
            cache[key] = hybrid_from_transition(
                self.pt, bm=bm, bn=bn, hub_quantile=hub_quantile)
        return cache[key]

    # ---------------- numpy/scipy reference path ------------------------
    def to_scipy_pt(self) -> sp.csr_matrix:
        return self.pt.to_scipy()

    def apply_numpy(self, x: np.ndarray, pt_sp: Optional[sp.csr_matrix] = None
                    ) -> np.ndarray:
        """y = G x (dense vector or (n, nv) lane stack, matrix-free)."""
        pt_sp = self.to_scipy_pt() if pt_sp is None else pt_sp
        v = self.teleport()
        if x.ndim == 2 and v.ndim == 1:
            v = v[:, None]
        dangling_mass = x[self.pt.dangling].sum(axis=0)
        y = self.alpha * (pt_sp @ x)
        y += self.alpha * dangling_mass / self.n  # w = e/n
        y += (1.0 - self.alpha) * x.sum(axis=0) * v
        return y

    def apply_linear_numpy(self, x: np.ndarray,
                           pt_sp: Optional[sp.csr_matrix] = None,
                           v=None, rows: Optional[tuple] = None
                           ) -> np.ndarray:
        """y = R x + b with R = alpha S, b = (1 - alpha) v.

        `x` may be an (n, nv) stack; with a lane-stacked teleport `v` (the
        operator's own unless given: dense, or a scipy sparse (n, nv)
        stack of seeds) this is the host-side exact residual route for
        batched personalized solves (one spmm certifies every lane).
        `rows=(a, b)` computes rows a..b-1 of y alone."""
        pt_sp = self.to_scipy_pt() if pt_sp is None else pt_sp
        v = self.teleport() if v is None else v
        a, b = (0, self.n) if rows is None else rows
        if rows is not None:
            pt_sp = pt_sp.tocsr()
            ip = pt_sp.indptr
            pt_sp = sp.csr_matrix(
                (pt_sp.data[ip[a]:ip[b]], pt_sp.indices[ip[a]:ip[b]],
                 ip[a:b + 1] - ip[a]), shape=(b - a, pt_sp.shape[1]))
        dangling_mass = x[self.pt.dangling].sum(axis=0)
        y = self.alpha * (pt_sp @ x)
        y += self.alpha * dangling_mass / self.n
        if sp.issparse(v):
            c = v.tocoo()
            at = (c.row >= a) & (c.row < b)
            np.add.at(y, (c.row[at] - a, c.col[at]),
                      (1.0 - self.alpha) * c.data[at])
        else:
            if x.ndim == 2 and v.ndim == 1:
                v = v[:, None]
            y += (1.0 - self.alpha) * v[a:b]
        return y

    def residual_l1_numpy(self, x: np.ndarray,
                          pt_sp: Optional[sp.csr_matrix] = None, v=None,
                          workers: int = 8) -> np.ndarray:
        """||R x + b - x||_1 of each lane of an (n, nv) stack, exactly in
        float64: `apply_linear_numpy` over row blocks on `workers`
        threads (scipy's spmm releases the GIL), each block summed on its
        own, so no (n, nv) array is made."""
        from concurrent.futures import ThreadPoolExecutor
        pt_sp = self.to_scipy_pt() if pt_sp is None else pt_sp
        x = np.ascontiguousarray(x, dtype=np.float64)
        workers = max(1, min(workers, os.cpu_count() or 1))
        cuts = np.linspace(0, self.n, 4 * workers + 1).astype(np.int64)

        def block(a, b):
            r = self.apply_linear_numpy(x, pt_sp, v, rows=(a, b))
            r -= x[a:b]
            return np.abs(r, out=r).sum(axis=0)

        with ThreadPoolExecutor(workers) as pool:
            return sum(pool.map(block, cuts[:-1], cuts[1:]))

    def drop_layouts(self) -> None:
        """Let go of the memoized BSR pack and its device arrays, the
        largest state an operator holds (10.1 GB at Stanford-Web)."""
        cache = self._cache()
        for k in list(cache):
            if k[0] in ("hybrid", "bsr_dev"):
                del cache[k]

    # ---------------- JAX path ------------------------------------------
    def device_arrays(self, dtype=jnp.float32) -> dict:
        """Device arrays for the segment-sum apply, memoized per dtype (and
        per x64 mode) so repeated solves reuse the uploaded buffers."""
        key = ("dev", np.dtype(dtype).name,
               bool(jax.config.jax_enable_x64))
        cache = self._cache()
        hit = cache.get(key)
        if hit is not None:
            return dict(hit)
        dev = self.pt.device_arrays(dtype=dtype)
        dev["dangling"] = jnp.asarray(self.pt.dangling)
        dev["v"] = jnp.asarray(self.teleport(), dtype=dtype)
        cache[key] = dev
        return dict(dev)

    def apply_jax(self, dev: dict, x: jax.Array) -> jax.Array:
        n = self.n
        y = self.alpha * pt_matvec(dev, x, n)
        dangling_mass = jnp.sum(jnp.where(dev["dangling"], x, 0.0))
        y = y + self.alpha * dangling_mass / n
        y = y + (1.0 - self.alpha) * jnp.sum(x) * dev["v"]
        return y

    def apply_linear_jax(self, dev: dict, x: jax.Array) -> jax.Array:
        n = self.n
        y = self.alpha * pt_matvec(dev, x, n)
        dangling_mass = jnp.sum(jnp.where(dev["dangling"], x, 0.0))
        y = y + self.alpha * dangling_mass / n
        y = y + (1.0 - self.alpha) * dev["v"]
        return y


def exact_pagerank(op: GoogleOperator, tol: float = 1e-12,
                   maxiter: int = 10_000) -> np.ndarray:
    """High-precision reference PageRank (double precision power method)."""
    pt_sp = op.to_scipy_pt()
    n = op.n
    x = np.full(n, 1.0 / n, dtype=np.float64)
    for _ in range(maxiter):
        y = op.apply_numpy(x, pt_sp)
        if np.abs(y - x).sum() < tol:
            return y
        x = y
    return x
