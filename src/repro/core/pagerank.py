"""PageRank solvers (paper §3): synchronous power method (eq. 4) and the
linear-system Jacobi/Richardson iteration derived from eq. (2), in JAX.

These are the single-program (device-side) solvers; the asynchronous
counterparts live in core.des (faithful message-level simulation) and
core.spmd (TPU-native bounded-staleness shard_map flavor).

The per-iteration operator apply is delegated to a pluggable backend
(core.backend): `segment_sum` (default) or `bsr_pallas` (hub-split block-CSR
— the MXU kernel on TPU). Both solvers accept (n, nv) teleport/initial
stacks, solving nv personalized PageRank problems in one fused loop.
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from ..graph.google import GoogleOperator
from .backend import (BackendSpec, BackendMeta, as_lane_tol, as_spec,
                      prepare, from_layout, google_apply, l1_residual,
                      take_lanes)


@dataclasses.dataclass
class SolveResult:
    x: np.ndarray                 # (n,) or (n, nv) normalized iterate(s)
    iters: int
    resid_l1: float               # max over lanes
    resid_per_vec: Optional[np.ndarray] = None  # (nv,) when nv > 1
    lane_iters: Optional[np.ndarray] = None     # (nv,) iterations per lane
                                                # (differs under freezing)


@partial(jax.jit, static_argnames=("meta", "linear", "max_iters"))
def _solve_jit(dev: dict, x0: jax.Array, tol: jax.Array, *,
               meta: BackendMeta, linear: bool, max_iters: int):
    """Fused fixed-point loop: the iterate never leaves the backend layout
    (for bsr_pallas that is the padded (nbr, bm, nv) block layout — no
    repacking between iterations).  `tol` is a traced (nv,) per-lane
    residual threshold (mixed-tol query batches share one compiled loop;
    a scalar tol also no longer triggers a recompile per value)."""
    def cond(state):
        _, resid, it = state
        return jnp.logical_and(jnp.any(resid > tol), it < max_iters)

    def body(state):
        x, _, it = state
        y = google_apply(meta, dev, x, linear)
        resid = l1_residual(y, x)
        return y, resid, it + 1

    resid0 = jnp.full((meta.nv,), jnp.inf, x0.dtype)
    state = (x0, resid0, jnp.asarray(0))
    x, resid, iters = jax.lax.while_loop(cond, body, state)
    return x, resid, iters


def _pow2(k: int) -> int:
    return 1 << max(k - 1, 0).bit_length()


# recheck cadences the adaptive driver may pick — `max_iters` is a static
# jit arg, so arbitrary chunk lengths would each compile a fresh fused
# loop; a pow2 menu bounds that axis to 6 entries shared across solves
_CHUNK_MENU = (8, 16, 32, 64, 128, 256)


def _adapt_chunk(prev_resid, resid, it: int, tol,
                 fallback: int) -> int:
    """Next recheck cadence from the observed per-lane convergence spread.

    Each surviving lane's geometric decay rate over the last chunk
    extrapolates to a predicted iterations-to-tol; the next host recheck
    lands just past the *fastest* survivor's predicted crossing — that is
    the earliest moment a freeze (and possibly a pow2 compaction) can
    pay.  Tightly-clustered lanes thus get long chunks (few host syncs),
    a wide spread gets short ones (fast lanes shed early).  `tol` may be
    a scalar or the survivors' per-lane threshold array.
    """
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        rate = (resid / prev_resid) ** (1.0 / max(it, 1))
        need = np.log(tol / resid) / np.log(rate)
    need = need[np.isfinite(need) & (need > 0)]
    if need.size == 0:              # stalled / non-contracting estimates
        return fallback
    k = 1.25 * float(need.min()) + 1.0   # margin: rates drift chunk-to-chunk
    for c in _CHUNK_MENU:
        if c >= k:
            return c
    return _CHUNK_MENU[-1]


def _solve_frozen(dev, x_dev, meta: BackendMeta, linear: bool,
                  tol: np.ndarray, max_iters: int, chunk):
    """Chunked driver that freezes converged lanes out of the fused apply.

    The fused while_loop only ever guarantees each lane's residual <= tol
    (it stops at max-over-lanes), so freezing a lane once its residual
    crosses tol preserves the solver contract exactly — fast lanes just
    stop paying for the slowest one.  Lanes are compacted at power-of-two
    stack widths (padding duplicates an active lane), bounding recompiles
    of the fused loop to log2(nv).

    `chunk` is the host recheck cadence: an int pins a fixed count, the
    default ``"auto"`` adapts it to the observed per-lane iteration
    spread (see `_adapt_chunk`) — the first chunk is a fixed probe, every
    later one is scheduled at the fastest survivor's predicted tol
    crossing.
    """
    nv = meta.nv
    n = meta.n
    adaptive = chunk == "auto"
    cur = 32 if adaptive else max(int(chunk), 1)
    x_out = np.empty((n, nv))
    resid_out = np.full(nv, np.inf)
    lane_iters = np.zeros(nv, dtype=np.int64)
    active = np.arange(nv)          # lane ids at stack positions 0..k-1
    width = _pow2(nv)
    stack_tol = tol.copy()          # per-lane threshold at stack positions
    if width > nv:
        pad = np.concatenate([np.arange(nv),
                              np.zeros(width - nv, np.int64)])
        dev, meta, x_dev = take_lanes(meta, dev, x_dev, pad)
        stack_tol = stack_tol[pad]
    it_total = 0
    prev_resid = None               # survivors' residuals a chunk ago
    while True:
        step = min(cur, max_iters - it_total)
        x_dev, resid_dev, it = _solve_jit(
            dev, x_dev, jnp.asarray(stack_tol, x_dev.dtype), meta=meta,
            linear=linear, max_iters=step)
        it = int(it)
        it_total += it
        lane_iters[active] += it
        resid_np = np.asarray(resid_dev, dtype=np.float64)[:active.size]
        done = resid_np <= tol[active]
        if done.all() or it_total >= max_iters:
            x_np = from_layout(meta, x_dev)
            x_out[:, active] = x_np[:, :active.size]
            resid_out[active] = resid_np
            break
        if adaptive and it > 0:
            if prev_resid is not None:
                cur = _adapt_chunk(prev_resid[~done], resid_np[~done],
                                   it, tol[active][~done], cur)
            prev_resid = resid_np
        new_width = _pow2(int((~done).sum()))
        if done.any() and new_width < width:
            # freeze + compact: record the converged lanes, keep the rest
            frozen = active[done]
            x_np = from_layout(meta, x_dev)
            x_out[:, frozen] = x_np[:, :active.size][:, done]
            resid_out[frozen] = resid_np[done]
            keep_pos = np.flatnonzero(~done)
            active = active[~done]
            if prev_resid is not None:
                prev_resid = prev_resid[~done]
            idx = np.concatenate([keep_pos,
                                  np.full(new_width - keep_pos.size,
                                          keep_pos[0], np.int64)])
            dev, meta, x_dev = take_lanes(meta, dev, x_dev, idx)
            stack_tol = stack_tol[idx]
            width = new_width
        # lanes at <= tol that do not trigger a compaction stay in the
        # stack (their slots exist anyway) and keep improving for free
    return x_out, resid_out, it_total, lane_iters


def solve_power(op: GoogleOperator, x0: Optional[np.ndarray] = None,
                tol: float = 1e-9, max_iters: int = 1000,
                dtype=jnp.float64,
                backend: Union[str, BackendSpec] = "segment_sum",
                v: Optional[np.ndarray] = None,
                reorder: Optional[str] = None,
                freeze_lanes: Union[bool, str] = "auto",
                freeze_chunk: Union[int, str] = "auto") -> SolveResult:
    """Normalization-free power method x <- G x (eq. 4).

    No per-step normalization is needed: G is column-stochastic so ||x||_1
    is invariant (paper §3) and there is no over/underflow risk.

    `v`/`x0` may be (n, nv) stacks — nv personalized PageRank problems share
    every operator load. `backend="bsr_pallas"` runs the hub-split BSR path
    (float32; L1 residuals floor near 1e-7). `reorder` ("rcm" | "indeg")
    solves in a block-densifying page permutation and maps the answer back.
    `tol` may be a scalar or an (nv,) per-lane array (mixed-tolerance query
    batches: each lane stops — and under freezing drops out of the fused
    apply — at its own threshold).

    `freeze_lanes` masks already-converged lanes out of the fused apply
    (chunked driver, power-of-two lane compaction) so large teleport
    batches stop paying for their slowest lane; "auto" enables it from
    nv >= 8.  Every lane still stops at residual <= tol.  `freeze_chunk`
    sets the host recheck cadence: an int pins a fixed count, "auto"
    (default) adapts it to the observed per-lane iteration spread — the
    next recheck is scheduled at the fastest unconverged lane's predicted
    tol crossing, so clustered lanes pay few host syncs and spread-out
    lanes freeze early.
    """
    return _solve(op, x0, tol, max_iters, linear=False, dtype=dtype,
                  backend=backend, v=v, reorder=reorder,
                  freeze_lanes=freeze_lanes, freeze_chunk=freeze_chunk)


def solve_linear(op: GoogleOperator, x0: Optional[np.ndarray] = None,
                 tol: float = 1e-9, max_iters: int = 1000,
                 dtype=jnp.float64,
                 backend: Union[str, BackendSpec] = "segment_sum",
                 v: Optional[np.ndarray] = None,
                 reorder: Optional[str] = None,
                 freeze_lanes: Union[bool, str] = "auto",
                 freeze_chunk: Union[int, str] = "auto") -> SolveResult:
    """Jacobi/Richardson on (I - R) x = b (eq. 2 / eq. 7 sync form)."""
    return _solve(op, x0, tol, max_iters, linear=True, dtype=dtype,
                  backend=backend, v=v, reorder=reorder,
                  freeze_lanes=freeze_lanes, freeze_chunk=freeze_chunk)


def _reordered(op: GoogleOperator, method: str):
    """Memoized (reordered op, perm) so repeated solves do not re-permute
    the graph or re-pack its BSR blocks."""
    from ..graph.reorder import reorder_operator
    cache = op._cache()
    key = ("reorder", method)
    if key not in cache:
        cache[key] = reorder_operator(op, method)
    return cache[key]


def _solve(op, x0, tol, max_iters, linear, dtype, backend="segment_sum",
           v=None, reorder=None, freeze_lanes="auto",
           freeze_chunk="auto") -> SolveResult:
    spec = as_spec(backend)
    squeeze = ((x0 is None or np.ndim(x0) == 1)
               and (v is None or np.ndim(v) == 1)
               and (v is not None or op.v is None or np.ndim(op.v) == 1))

    perm = None
    if reorder is not None:
        op, perm = _reordered(op, reorder)
        if v is not None:
            v = np.asarray(v, dtype=np.float64)
            vp = np.empty_like(v)
            vp[perm] = v
            v = vp
        if x0 is not None:
            x0 = np.asarray(x0, dtype=np.float64)
            xp = np.empty_like(x0)
            xp[perm] = x0
            x0 = xp

    # scope x64 to this solve — flipping the global flag poisons later
    # bf16/f32 model code in the same process. The bsr path is float32
    # end to end, so it never needs the x64 scope.
    use_x64 = dtype == jnp.float64 and spec.name == "segment_sum"
    ctx = jax.enable_x64(True) if use_x64 else contextlib.nullcontext()
    with ctx:
        dev, meta, x0_dev = prepare(op, spec, dtype=dtype, v=v, x0=x0)
        tol_vec = as_lane_tol(tol, meta.nv)
        freeze = (meta.nv >= 8 if freeze_lanes == "auto"
                  else bool(freeze_lanes)) and meta.nv > 1
        if freeze:
            x, resid, iters, lane_iters = _solve_frozen(
                dev, x0_dev, meta, linear, tol_vec, max_iters, freeze_chunk)
        else:
            x_dev, resid, iters = _solve_jit(
                dev, x0_dev, jnp.asarray(tol_vec, x0_dev.dtype), meta=meta,
                linear=linear, max_iters=max_iters)
            x = from_layout(meta, x_dev)
            resid = np.asarray(resid, dtype=np.float64)
            iters = int(iters)
            lane_iters = np.full(meta.nv, iters, dtype=np.int64)

    if perm is not None:
        x = x[perm]
    s = x.sum(axis=0)
    x = np.where(s > 0, x / np.where(s > 0, s, 1.0), x)
    nv = x.shape[1]
    if squeeze and nv == 1:
        x = x[:, 0]
    return SolveResult(x=x, iters=int(iters), resid_l1=float(resid.max()),
                       resid_per_vec=resid if nv > 1 else None,
                       lane_iters=lane_iters)


def rank_of(x: np.ndarray) -> np.ndarray:
    """Page ranking (descending PageRank value) — what actually matters for
    retrieval (paper §5.2: 'what is important are not the accurate values
    ... but their relative ranking')."""
    return np.argsort(-x, kind="stable")


def kendall_tau_topk(x: np.ndarray, y: np.ndarray, k: int = 1000) -> float:
    """Kendall-tau-b between two rankings restricted to the union of their
    top-k pages. Quantifies the paper's open question about relaxed
    thresholds vs rank quality."""
    import scipy.stats as st
    top = np.union1d(rank_of(x)[:k], rank_of(y)[:k])
    tau, _ = st.kendalltau(x[top], y[top])
    return float(tau)
