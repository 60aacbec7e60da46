"""Graph500 Kronecker graph, as LDBC Graphalytics' `graph500-*` sets hold it.

The edge list follows the Graph500 reference generator: `edge_factor *
2**scale` edges, each placed bit by bit in the initiator's quadrants with
probabilities A, B, C and D = 1 - A - B - C, then every vertex relabelled
by a random permutation. Graphalytics keeps such a graph undirected, so
each edge becomes a link in both directions; self-loops and duplicates
are dropped, and vertices left with no edge are not part of the set.

The edges are drawn on the default device, in one jitted call from the
generation seed (JAX's threefry bits). Drawn with numpy on a v5e host,
at scale 20, it took 7.5 s of a run's set-up, or 17.7 s in two runs of
five. The host drops self-loops, isolated vertices and duplicates.

`generate` makes the configuration's graph from one fixed generation seed
and then relabels its vertices by the run's seed, so every seed gets the
same graph under other vertex ids: the same work, in another order.
"""
from __future__ import annotations

import numpy as np

from edges import Graph


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int) -> np.ndarray:
    """(2, edge_factor * 2**scale) int64 endpoints of generation seed
    `seed`, already relabelled by the generator's own permutation."""
    import jax
    import jax.numpy as jnp

    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    @jax.jit
    def draw(key):
        k_bits, k_perm = jax.random.split(key)

        def bit(carry, kb):
            i, j, shift = carry
            k_i, k_j = jax.random.split(kb)
            ii = jax.random.uniform(k_i, (m,)) > ab
            jj = jax.random.uniform(k_j, (m,)) > jnp.where(ii, c_norm,
                                                          a_norm)
            return (i | (ii.astype(jnp.int32) << shift),
                    j | (jj.astype(jnp.int32) << shift), shift + 1), None

        zero = jnp.zeros((m,), jnp.int32)
        (i, j, _), _ = jax.lax.scan(bit, (zero, zero, jnp.int32(0)),
                                    jax.random.split(k_bits, scale))
        perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
        return jnp.stack([perm[i], perm[j]])

    return np.asarray(draw(jax.random.key(seed))).astype(np.int64)


def graph500(scale: int, edge_factor: int, a: float, b: float, c: float,
             seed: int, relabel_seed=None) -> Graph:
    """The graph of generation seed `seed`; with `relabel_seed`, its
    vertices relabelled by that seed's permutation, in the same pass."""
    ij = kronecker_edges(scale, edge_factor, a, b, c, seed)
    keep = ij[0] != ij[1]
    u, v = ij[0][keep], ij[1][keep]
    seen = np.zeros(1 << scale, dtype=bool)
    seen[u] = True
    seen[v] = True
    n = int(seen.sum())
    new_id = np.cumsum(seen) - 1
    perm = None
    if relabel_seed is not None:
        perm = np.random.default_rng(relabel_seed).permutation(n)
        new_id = perm[new_id]
    u, v = new_id[u], new_id[v]
    g = Graph.from_pairs(n, np.concatenate([u, v]), np.concatenate([v, u]))
    return g if perm is None else Graph(n=g.n, src=g.src, dst=g.dst,
                                        perm=perm)


def generate(params: dict, seed: int) -> Graph:
    return graph500(params["scale"], params["edge_factor"], params["A"],
                    params["B"], params["C"], params["generation_seed"],
                    relabel_seed=seed)
