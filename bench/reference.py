"""The plain reference: PageRank in float64 with scipy, from the edges alone.

It imports nothing of the program and takes nothing the program made. The
Google matrix is the paper's (§2): G = alpha S + (1 - alpha) v e^T with
S = P^T + w d^T, P^T[j, i] = 1 / outdeg(i) for each link i -> j, d the
dangling pages and w = e / n. The power method runs until the L1 change
of one step is below `tol`; the answer is then within tol / (1 - alpha)
of the fixed point.

`pagerank_lowp` is the same iteration in JAX at a lower precision. It is
the control that the comparison deciding `correct` has to fail
(bench/control.py), and the benchmark's own runs never call it.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.sparse as sp

from edges import Graph


def transition(graph: Graph):
    """(P^T as a float64 CSR matrix, dangling mask)."""
    deg = graph.out_degree()
    pt = sp.csr_matrix((1.0 / deg[graph.src], (graph.dst, graph.src)),
                       shape=(graph.n, graph.n))
    return pt, deg == 0


def pagerank(graph: Graph, alpha: float, *, x0: Optional[np.ndarray] = None,
             tol: float = 1e-12, max_iters: int = 5000) -> np.ndarray:
    """Float64 power method with the uniform teleport, from `x0` (the
    uniform vector when omitted)."""
    pt, dangling = transition(graph)
    n = graph.n
    x = np.full(n, 1.0 / n) if x0 is None else np.array(x0, np.float64)
    for _ in range(max_iters):
        y = alpha * (pt @ x)
        y += alpha * x[dangling].sum() / n + (1.0 - alpha) * x.sum() / n
        change = np.abs(y - x).sum()
        x = y
        if change < tol:
            return x
    raise RuntimeError(f"reference did not reach {tol} in {max_iters} "
                       "steps")


def pagerank_lowp(graph: Graph, alpha: float, dtype: str, *,
                  tol: float = 1e-5, max_iters: int = 200) -> np.ndarray:
    """The reference's iteration with every array and sum in `dtype`
    (e.g. "bfloat16", "float32"), on the default device. Stops at `tol`
    or after `max_iters` steps (a low precision may never reach tol)."""
    import jax
    import jax.numpy as jnp

    n = graph.n
    deg = graph.out_degree()
    dt = jnp.dtype(dtype)
    w = jnp.asarray(1.0 / deg[graph.src], dt)
    src = jnp.asarray(graph.src, jnp.int32)
    dst = jnp.asarray(graph.dst, jnp.int32)
    dang = jnp.asarray(deg == 0)
    v = jnp.asarray(np.full(n, 1.0 / n), dt)     # teleport, and the start

    @jax.jit
    def solve(v):
        def step(state):
            x, _, k = state
            y = alpha * jax.ops.segment_sum(w * x[src], dst, num_segments=n)
            y = (y + alpha * jnp.sum(jnp.where(dang, x, 0)) / n
                 + (1.0 - alpha) * jnp.sum(x) * v).astype(dt)
            return y, jnp.sum(jnp.abs(y - x)), k + 1

        def cond(state):
            _, change, k = state
            return jnp.logical_and(change >= tol, k < max_iters)

        x, _, _ = jax.lax.while_loop(
            cond, step, (v, jnp.asarray(jnp.inf, dt), 0))
        return x

    x = np.asarray(solve(v).astype(jnp.float32), dtype=np.float64)
    return x / x.sum()


def l1(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.abs(np.asarray(a, np.float64) - b).sum())
