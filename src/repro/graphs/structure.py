"""Graph data structures.

Two representations are used throughout the framework:

* **Dense adjacency matrix** ``(N, N) bool`` — the paper's native format
  (its CUDA implementation stores ``Adj`` as an N x N boolean array and every
  thread owns one row). The chordality core operates on this.
* **Edge index** ``(2, E) int32`` + CSR (``indptr``/``indices``) — the
  sparse format; the CSR backend (``repro.sparse``) packs work units
  from it.

All constructors are host-side (numpy) because graph construction is a data
pipeline step; device code receives ``jnp`` arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Graph:
    """An undirected graph with optional dense/CSR/edge-list views.

    ``n_nodes`` is the logical vertex count; arrays may be padded beyond it
    (``adj`` is (N_pad, N_pad); padding vertices are isolated).
    """

    n_nodes: int
    adj: Optional[np.ndarray] = None          # (N_pad, N_pad) bool
    edges: Optional[np.ndarray] = None        # (2, E) int32, both directions
    indptr: Optional[np.ndarray] = None       # (N+1,) int32
    indices: Optional[np.ndarray] = None      # (E,) int32
    node_feat: Optional[np.ndarray] = None    # (N, F) float32
    labels: Optional[np.ndarray] = None       # (N,) int32

    @property
    def n_pad(self) -> int:
        if self.adj is not None:
            return self.adj.shape[0]
        return self.n_nodes

    @property
    def n_edges(self) -> int:
        """Number of directed edge entries (2x undirected count)."""
        if self.edges is not None:
            return self.edges.shape[1]
        if self.indices is not None:
            return len(self.indices)
        if self.adj is not None:
            return int(self.adj.sum())
        return 0

    def with_dense(self) -> "Graph":
        if self.adj is not None:
            return self
        adj = dense_from_edges(self.n_nodes, self.edges)
        return dataclasses.replace(self, adj=adj)

    def with_csr(self) -> "Graph":
        if self.indptr is not None:
            return self
        edges = self.edges
        if edges is None:
            edges = edges_from_dense(self.adj, self.n_nodes)
        indptr, indices = csr_from_edges(self.n_nodes, edges)
        return dataclasses.replace(self, edges=edges, indptr=indptr, indices=indices)


def dense_from_edges(n: int, edges: np.ndarray) -> np.ndarray:
    """(2, E) directed edge index -> (n, n) bool adjacency (symmetrized)."""
    adj = np.zeros((n, n), dtype=bool)
    if edges is not None and edges.size:
        src, dst = edges[0], edges[1]
        adj[src, dst] = True
        adj[dst, src] = True
    np.fill_diagonal(adj, False)
    return adj


def edges_from_dense(adj: np.ndarray, n: Optional[int] = None) -> np.ndarray:
    """(N,N) bool -> (2, E) int32 with both directions present."""
    n = n if n is not None else adj.shape[0]
    src, dst = np.nonzero(adj[:n, :n])
    return np.stack([src, dst]).astype(np.int32)


def csr_from_edges(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Build CSR (indptr, indices) from a directed (2, E) edge index."""
    src = edges[0]
    dst = edges[1]
    order = np.argsort(src, kind="stable")
    src_sorted = src[order]
    indices = dst[order].astype(np.int32)
    counts = np.bincount(src_sorted, minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(counts, out=indptr[1:])
    return indptr, indices


def pad_graph(g: Graph, n_pad: int) -> Graph:
    """Pad the dense adjacency to (n_pad, n_pad); padding vertices isolated.

    The chordality core requires fixed shapes under jit/vmap; padding vertices
    have empty neighborhoods, so they are trivially simplicial and never
    change the chordality verdict (each is visited with empty LN).
    """
    g = g.with_dense()
    n_old = g.adj.shape[0]
    if n_pad < g.n_nodes:
        raise ValueError(f"cannot pad to {n_pad} < n_nodes={g.n_nodes}")
    if n_pad == n_old:
        return g
    adj = np.zeros((n_pad, n_pad), dtype=bool)
    adj[:n_old, :n_old] = g.adj
    return dataclasses.replace(g, adj=adj)


def batch_graphs(graphs: Sequence[Graph], n_pad: Optional[int] = None) -> np.ndarray:
    """Stack graphs into a (B, n_pad, n_pad) bool batch for vmap'd chordality."""
    if n_pad is None:
        n_pad = max(g.n_nodes for g in graphs)
    out = np.zeros((len(graphs), n_pad, n_pad), dtype=bool)
    for i, g in enumerate(graphs):
        gd = pad_graph(g, n_pad)
        out[i] = gd.adj
    return out


# ---------------------------------------------------------------------------
# Size-bucketed batching (the engine's shape-planning substrate).
# ---------------------------------------------------------------------------
def bucket_npad(n: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Padding bucket for an n-vertex graph (powers of two; see
    ``repro.configs.shapes.ENGINE_NPAD_BUCKETS``)."""
    from repro.configs.shapes import engine_npad_bucket

    return engine_npad_bucket(
        n, tuple(buckets) if buckets is not None else None)


def bucket_graphs(
    graphs: Sequence[Graph], buckets: Optional[Sequence[int]] = None
) -> dict:
    """Group request indices by padding bucket: {n_pad: [indices...]}.

    Indices within a bucket keep arrival order, so a downstream batcher
    preserves request FIFO within each shape class.
    """
    by_bucket: dict = {}
    for i, g in enumerate(graphs):
        b = bucket_npad(max(g.n_nodes, 1), buckets)
        by_bucket.setdefault(b, []).append(i)
    return by_bucket
