"""The plain reference of personalized PageRank, in float64 with scipy.

A query is a preference set of pages; its teleport v puts equal mass on
each. Its personalized PageRank is the fixed point of the Richardson
iteration of the paper's linear system (eq. 2),

    x = alpha S x + (1 - alpha) v,   S = P^T + w d^T,  w = e / n,

with P^T and the dangling pages d from the edges alone
(`reference.transition`). Every lane of a batch iterates until its L1
change of one step is below `tol`; the answer is then within
tol alpha / (1 - alpha) of the fixed point. It imports nothing of the
program and takes nothing the program made.

`certificates` is the exact L1 bound ||r||_1 / (1 - alpha) of any
answer, r = (1 - alpha) v + alpha S x - x. `lowp_solver` is the same
iteration in JAX at a lower precision, each lane normalized as the
program's answer is: the control that the comparison
deciding `correct` has to fail (bench/control.py); the benchmark's own
runs never call it.
"""
from __future__ import annotations

import numpy as np

from edges import Graph
from reference import transition


def teleports(n: int, seed_sets) -> np.ndarray:
    """(n, nv): column i spreads one unit evenly over seed set i."""
    v = np.zeros((n, len(seed_sets)))
    for i, seeds in enumerate(seed_sets):
        v[seeds, i] = 1.0 / len(seeds)
    return v


class Reference:
    def __init__(self, graph: Graph, alpha: float):
        self.n, self.alpha = graph.n, alpha
        self.pt, self.dangling = transition(graph)

    def apply(self, x: np.ndarray, v: np.ndarray) -> np.ndarray:
        """alpha S x + (1 - alpha) v, lane by lane."""
        a = self.alpha
        return (a * (self.pt @ x) + a * x[self.dangling].sum(axis=0) / self.n
                + (1.0 - a) * v)

    def solve(self, seed_sets, tol: float = 1e-10,
              max_iters: int = 5000) -> np.ndarray:
        """(n, nv): each lane iterated on its own until its L1 change of
        one step is below `tol` (one vector a lane: every array a step
        makes is a few MB, reused by the allocator, not fresh pages)."""
        return np.stack([self.solve_lane(s, tol, max_iters)
                         for s in seed_sets], axis=1)

    def solve_lane(self, seeds, tol: float, max_iters: int) -> np.ndarray:
        a, n = self.alpha, self.n
        dangling = np.flatnonzero(self.dangling)
        b = np.zeros(n)
        b[seeds] = (1.0 - a) / len(seeds)
        x = b / (1.0 - a)
        for _ in range(max_iters):
            y = self.pt @ x
            y *= a
            y += (a / n) * x[dangling].sum()
            y += b
            change = np.abs(y - x).sum()
            x = y
            if change < tol:
                return x
        raise RuntimeError(f"reference did not reach {tol} in {max_iters} "
                           "steps")

    def certificates(self, seed_sets, x: np.ndarray) -> np.ndarray:
        r = self.apply(x, teleports(self.n, seed_sets)) - x
        return np.abs(r).sum(axis=0) / (1.0 - self.alpha)


# one Reference per worker process of `solve_all`
_WORKER = {}


def _start_worker(n, src, dst, alpha):
    _WORKER["ref"] = Reference(Graph(n=n, src=src, dst=dst), alpha)


def _solve_lane_in_worker(seeds, tol):
    return _WORKER["ref"].solve_lane(seeds, tol, 5000)


def solve_all(graph: Graph, alpha: float, batches, tol: float = 1e-10,
              workers: int = 8):
    """The reference answer of each batch of seed sets, in order, one lane
    a task on up to `workers` processes (spawned: they import no JAX)."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    batches = list(batches)
    lanes = [seeds for sets in batches for seeds in sets]
    workers = max(1, min(workers, len(lanes)))
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn"),
            initializer=_start_worker,
            initargs=(graph.n, graph.src, graph.dst, alpha)) as pool:
        done = pool.map(_solve_lane_in_worker, lanes, [tol] * len(lanes))
        for sets in batches:
            yield np.stack([next(done) for _ in sets], axis=1)


def lowp_solver(graph: Graph, alpha: float, dtype: str, *,
                tol: float = 1e-5, max_iters: int = 200):
    """solve(seed_sets) -> (n, nv): the reference's iteration with every
    array and sum in `dtype` (e.g. "bfloat16"), on the default device,
    compiled once per lane count. Stops at `tol` or after `max_iters`
    steps (a low precision may never reach tol)."""
    import jax
    import jax.numpy as jnp

    n = graph.n
    deg = graph.out_degree()
    dt = jnp.dtype(dtype)
    w = jnp.asarray(1.0 / deg[graph.src], dt)[:, None]
    src = jnp.asarray(graph.src, jnp.int32)
    dst = jnp.asarray(graph.dst, jnp.int32)
    dang = jnp.asarray(deg == 0)[:, None]

    @jax.jit
    def run(v):
        def step(state):
            x, _, k = state
            y = alpha * jax.ops.segment_sum(w * x[src], dst, num_segments=n)
            y = (y + alpha * jnp.sum(jnp.where(dang, x, 0), axis=0) / n
                 + (1.0 - alpha) * v).astype(dt)
            return y, jnp.max(jnp.sum(jnp.abs(y - x), axis=0)), k + 1

        def cond(state):
            _, change, k = state
            return jnp.logical_and(change >= tol, k < max_iters)

        x, _, _ = jax.lax.while_loop(
            cond, step, (v, jnp.asarray(jnp.inf, dt), 0))
        return x

    def solve(seed_sets) -> np.ndarray:
        v = jnp.asarray(teleports(n, seed_sets), dt)
        x = np.asarray(run(v).astype(jnp.float32), dtype=np.float64)
        return x / x.sum(axis=0)

    return solve
