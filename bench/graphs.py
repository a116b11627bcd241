"""Seeded graph generators for the benchmark's configurations.

The yardstick keeps its own copy of every generator a configuration uses,
so a change to ``repro.core.generators`` cannot move the traffic. Each
function takes a ``numpy.random.Generator`` and returns a dense ``(n, n)``
bool adjacency (symmetric, empty diagonal).

The thesis classes follow Mikuš 2015 §7 with the parameters of its
Figs 6-10: cliques, dense random (p = 0.5), sparse random (M = 20N
undirected edges), uniform random recursive trees, and random chordal
graphs (exact k-trees, k in {4, 16, 64, 128}). Every class is closed
under taking the induced prefix ``adj[:n, :n]``: a prefix of a clique is
a clique, of a recursive tree a tree, of a k-tree (vertices in insertion
order) a chordal graph, and the random classes stay random.
"""
from __future__ import annotations

import numpy as np


def _symmetric(upper: np.ndarray) -> np.ndarray:
    upper = np.triu(upper, 1)
    return upper | upper.T


def _from_edges(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    adj = np.zeros((n, n), dtype=bool)
    adj[src, dst] = True
    adj[dst, src] = True
    np.fill_diagonal(adj, False)
    return adj


def clique(n: int, rng: np.random.Generator | None = None) -> np.ndarray:
    adj = np.ones((n, n), dtype=bool)
    np.fill_diagonal(adj, False)
    return adj


def dense_random(n: int, rng: np.random.Generator,
                 p: float = 0.5) -> np.ndarray:
    """G(n, p): M = Θ(N²) (thesis §7, Fig 7)."""
    return _symmetric(rng.random((n, n), dtype=np.float32) < p)


def sparse_random(n: int, rng: np.random.Generator,
                  edges_per_vertex: int = 20) -> np.ndarray:
    """M = ``edges_per_vertex`` · N uniform random undirected edges, self
    loops and repeats dropped (thesis §7, Fig 8: M = 20N)."""
    m = edges_per_vertex * n
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    keep = src != dst
    return _from_edges(n, src[keep], dst[keep])


def random_tree(n: int, rng: np.random.Generator) -> np.ndarray:
    """Uniform random recursive tree: vertex i attaches to a uniform
    earlier vertex (thesis §7, Fig 9)."""
    child = np.arange(1, n)
    parent = (rng.random(n - 1) * child).astype(np.int64)
    return _from_edges(n, child, parent)


def k_tree(n: int, rng: np.random.Generator, k: int = 4) -> np.ndarray:
    """Exact random k-tree (thesis §7, Fig 10: chordal, M ≈ kN).

    Start from a (k+1)-clique; each new vertex joins a k-clique drawn
    uniformly from every k-clique registered so far: the k+1 k-subsets
    of the base, and for each later vertex u the k subsets of
    {u} ∪ K_u that contain u (K_u being the clique u joined).
    """
    if n <= k + 1:
        return clique(n)
    base = np.arange(k + 1)
    joined = np.zeros((n, k), dtype=np.int64)
    draws = rng.random(n)
    for v in range(k + 1, n):
        r = int(draws[v] * ((k + 1) + k * (v - k - 1)))
        if r <= k:
            kc = np.delete(base, r)
        else:
            u, j = divmod(r - (k + 1), k)
            u += k + 1
            kc = joined[u].copy()
            kc[j] = u
        joined[v] = kc
    adj = clique(k + 1)
    out = np.zeros((n, n), dtype=bool)
    out[: k + 1, : k + 1] = adj
    rows = np.repeat(np.arange(k + 1, n), k)
    cols = joined[k + 1:].ravel()
    out[rows, cols] = True
    out[cols, rows] = True
    return out


#: Generators a configuration's ``family.classes`` may name.
CLASSES = {
    "clique": clique,
    "dense_random": dense_random,
    "sparse_random": sparse_random,
    "random_tree": random_tree,
    "k_tree": k_tree,
}
