"""Pluggable matvec backends for the Google-operator hot path.

The paper's per-iteration cost is one application of

    G x = alpha P^T x + alpha w (d^T x) + (1 - alpha) v (e^T x)

and every solver in this repo funnels through it. Two backends implement it:

  segment_sum : gather + segment-sum over the CSR edge list (the portable
                default — exact in any dtype, fastest single-vector path on
                CPU).
  bsr_pallas  : hub-split block-CSR (kernels.bsr_spmv). The site-local mass
                runs as dense (bm, bn) block multiplies — the Pallas MXU
                kernel on TPU, the identical blocked-einsum contraction
                under XLA elsewhere — and the in-degree-tail rows go through
                a fused segment-sum side path. The iterate stays resident in
                the padded (nbr, bm, nv) block layout across the whole
                while_loop; nothing is repacked between iterations, and nv
                teleport lanes share every block load (batched personalized
                PageRank).

A backend is addressed by a hashable BackendSpec so the fused solver loop
can jit once per (spec, shapes) and dispatch statically.

Layout contract (bsr_pallas):
  * square blocks (bm == bn) so y has the same layout as x and the loop
    never leaves (nbr, bm, nv);
  * padded rows/cols beyond n are exactly zero and stay zero: blocks and
    the hub COO never touch them, the teleport vector and the scalar
    dangling-mass correction are masked by `valid`;
  * arithmetic is float32 (the MXU accumulates in f32) — L1 residuals
    bottom out around 1e-7; ask segment_sum/float64 for tighter tolerances.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..graph.google import GoogleOperator
from ..graph.csr import pt_matvec
from ..kernels.bsr_spmv import hybrid_matvec, pad_x

BACKENDS = ("segment_sum", "bsr_pallas")


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Hashable backend selector (usable as a jit static argument)."""
    name: str = "segment_sum"
    impl: str = "auto"          # bsr_pallas only: auto | pallas | interpret | ref
    bm: int = 0                 # block edge; 0 = auto (128 on TPU, 8 on CPU)
    hub_quantile: float = 0.99  # rows above this row-nnz quantile bypass BSR

    def resolved(self) -> "BackendSpec":
        name = self.name
        if name not in BACKENDS:
            raise ValueError(f"unknown backend {name!r}; expected one of "
                             f"{BACKENDS}")
        impl, bm = self.impl, self.bm
        on_tpu = jax.default_backend() == "tpu"
        if impl == "auto":
            # the *solver* auto policy: compiled Pallas on TPU (the only
            # backend the Mosaic kernel compiles for), the fast
            # blocked-einsum oracle elsewhere (same
            # math; interpret mode is the kernel-faithful-but-slow lane
            # the kernel-level dispatch prefers — see
            # kernels.bsr_spmv.resolve_impl)
            impl = "pallas" if on_tpu else "ref"
        if bm == 0:
            # the MXU wants 128x128 tiles; the XLA einsum path wants the
            # highest fill (fewest padded flops/pages), which small blocks
            # give — measured optimum on CPU is bm=8
            bm = 128 if on_tpu else 8
        return dataclasses.replace(self, impl=impl, bm=bm)


def as_spec(backend) -> BackendSpec:
    """Coerce a user-facing backend argument (str or spec) to a resolved
    BackendSpec."""
    if isinstance(backend, BackendSpec):
        return backend.resolved()
    return BackendSpec(name=str(backend)).resolved()


# --------------------------------------------------------------------------
# Preparation: operator -> device state + layout metadata
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BackendMeta:
    """Static (hashable) layout info threaded through the jitted loop."""
    spec: BackendSpec
    n: int
    nv: int
    n_pad: int                  # nbr * bm for bsr, == n for segment_sum
    alpha: float


def _as_stack(a: np.ndarray, n: int, what: str) -> np.ndarray:
    a = np.asarray(a, dtype=np.float64)
    if a.ndim == 1:
        a = a[:, None]
    if a.shape[0] != n:
        raise ValueError(f"{what} has {a.shape[0]} rows, operator has {n}")
    return a


def seed_stack(n: int, seed_sets, weight_sets=None) -> np.ndarray:
    """Build an (n, nv) personalized-teleport stack from nv seed sets.

    Each column is a probability vector concentrated on that query's seeds
    (uniform over the set unless `weight_sets[i]` gives explicit weights,
    which are L1-normalized).  This is the lane layout `prepare` consumes:
    one fused solve over the stack amortizes every edge/block load across
    all nv personalized problems.
    """
    seed_sets = list(seed_sets)
    nv = len(seed_sets)
    if nv == 0:
        raise ValueError("seed_stack needs at least one seed set")
    v = np.zeros((n, nv), dtype=np.float64)
    for i, seeds in enumerate(seed_sets):
        seeds = np.asarray(seeds, dtype=np.int64).ravel()
        w = None if weight_sets is None else weight_sets[i]
        if w is None:
            v[seeds, i] = 1.0 / seeds.size
        else:
            w = np.asarray(w, dtype=np.float64).ravel()
            v[seeds, i] = w / w.sum()
    return v


def as_lane_tol(tol, nv: int) -> np.ndarray:
    """Coerce a scalar-or-per-lane tolerance to a validated (nv,) array.

    The fused solver loops accept a tolerance *per lane* so mixed-tol
    query batches share one solve: each lane stops (and may freeze out of
    the apply) at its own threshold instead of the whole stack running to
    the tightest one."""
    t = np.asarray(tol, dtype=np.float64).ravel()
    if t.size == 1:
        t = np.full(nv, float(t[0]))
    if t.size != nv:
        raise ValueError(f"tol has {t.size} entries for {nv} lanes")
    if not np.all(np.isfinite(t)) or np.any(t <= 0):
        raise ValueError("per-lane tol entries must be finite and > 0")
    return t


def prepare(op: GoogleOperator, spec: BackendSpec, dtype,
            v: Optional[np.ndarray] = None,
            x0: Optional[np.ndarray] = None
            ) -> Tuple[dict, BackendMeta, jax.Array]:
    """Build (device state, meta, x0 in backend layout) for a solve.

    `v`/`x0` may be (n,) vectors or (n, nv) stacks; lanes broadcast against
    each other. Structural state (edges, blocks, masks) is memoized on the
    operator; only the teleport stack is uploaded per call.
    """
    n = op.n
    v_stack = _as_stack(op.teleport() if v is None else v, n, "teleport v")
    nv = v_stack.shape[1]
    if x0 is None:
        x0_stack = np.full((n, nv), 1.0 / n, dtype=np.float64)
    else:
        x0_stack = _as_stack(x0, n, "x0")
    if x0_stack.shape[1] != nv:
        if x0_stack.shape[1] == 1:
            x0_stack = np.broadcast_to(x0_stack, (n, nv)).copy()
        elif nv == 1:
            v_stack = np.broadcast_to(v_stack, (n, x0_stack.shape[1])).copy()
            nv = v_stack.shape[1]
        else:
            raise ValueError(
                f"x0 has {x0_stack.shape[1]} lanes, v has {nv}")

    if spec.name == "segment_sum":
        dev = op.device_arrays(dtype=dtype)
        dev["v"] = jnp.asarray(v_stack, dtype=dtype)
        meta = BackendMeta(spec=spec, n=n, nv=nv, n_pad=n,
                           alpha=float(op.alpha))
        x0_dev = jnp.asarray(x0_stack, dtype=dtype)
        return dev, meta, x0_dev

    # ---- bsr_pallas ----------------------------------------------------
    bm = spec.bm
    hyb = op.hybrid_bsr(bm=bm, bn=bm, hub_quantile=spec.hub_quantile)
    cache = op._cache()
    key = ("bsr_dev", bm, spec.hub_quantile)
    dev_struct = cache.get(key)
    if dev_struct is None:
        dev_struct = hyb.device()
        nbr = hyb.bsr.nbr
        valid = np.zeros((nbr * bm, 1), dtype=np.float32)
        valid[:n] = 1.0
        dang = np.zeros((nbr * bm, 1), dtype=np.float32)
        dang[:n, 0] = op.pt.dangling.astype(np.float32)
        dev_struct["valid"] = jnp.asarray(valid.reshape(nbr, bm, 1))
        dev_struct["dang"] = jnp.asarray(dang.reshape(nbr, bm, 1))
        cache[key] = dev_struct
    dev = dict(dev_struct)
    nbr = hyb.bsr.nbr
    dev["v"] = jnp.asarray(pad_x(v_stack.astype(np.float32), n, bm))
    meta = BackendMeta(spec=spec, n=n, nv=nv, n_pad=nbr * bm,
                       alpha=float(op.alpha))
    x0_dev = jnp.asarray(pad_x(x0_stack.astype(np.float32), n, bm))
    return dev, meta, x0_dev


def from_layout(meta: BackendMeta, x_dev) -> np.ndarray:
    """Backend layout -> (n, nv) float64 numpy."""
    x = np.asarray(x_dev, dtype=np.float64)
    if meta.spec.name == "segment_sum":
        return x
    return x.reshape(meta.n_pad, meta.nv)[:meta.n]


# --------------------------------------------------------------------------
# The fused apply (jit-traceable; meta is static)
# --------------------------------------------------------------------------
def google_apply(meta: BackendMeta, dev: dict, x: jax.Array,
                 linear: bool) -> jax.Array:
    """One fused application of G (or R x + b for the linear form) in the
    backend's resident layout. Padding rows stay exactly zero."""
    alpha, n = meta.alpha, meta.n
    if meta.spec.name == "segment_sum":
        y = alpha * pt_matvec(dev, x, n)
        dmass = jnp.sum(jnp.where(dev["dangling"][:, None], x, 0.0), axis=0)
        y = y + alpha * dmass[None, :] / n
        if linear:
            y = y + (1.0 - alpha) * dev["v"]
        else:
            y = y + (1.0 - alpha) * jnp.sum(x, axis=0)[None, :] * dev["v"]
        return y

    # bsr_pallas: x is (nbr, bm, nv)
    y = alpha * hybrid_matvec(dev, x, impl=meta.spec.impl)
    dmass = jnp.sum(x * dev["dang"], axis=(0, 1))          # (nv,)
    y = y + (alpha / n) * dmass[None, None, :] * dev["valid"]
    if linear:
        y = y + (1.0 - alpha) * dev["v"]
    else:
        s = jnp.sum(x * dev["valid"], axis=(0, 1))         # (nv,)
        y = y + (1.0 - alpha) * s[None, None, :] * dev["v"]
    return y.astype(x.dtype)


def l1_residual(y: jax.Array, x: jax.Array) -> jax.Array:
    """Per-lane L1 residual ||y - x||_1, shape (nv,). Padding rows are zero
    in both layouts so no masking is needed."""
    d = jnp.abs(y - x)
    return jnp.sum(d, axis=tuple(range(d.ndim - 1)))


def take_lanes(meta: BackendMeta, dev: dict, x: jax.Array,
               idx: np.ndarray) -> Tuple[dict, BackendMeta, jax.Array]:
    """Slice the lane (last) axis of the per-solve state down to `idx`.

    Used by the per-lane-freezing driver: converged lanes are compacted out
    of the fused apply so the remaining lanes stop paying for them.  Only
    the teleport stack and the iterate carry a lane axis; the structural
    device state (edges, blocks, masks) is lane-invariant and shared.
    """
    idx = np.asarray(idx, dtype=np.int64)
    dev = dict(dev)
    dev["v"] = dev["v"][..., idx]
    meta = dataclasses.replace(meta, nv=int(idx.size))
    return dev, meta, x[..., idx]
