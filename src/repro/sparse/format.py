"""CSR graph container — the sparse twin of the dense ``(N, N)`` adjacency.

``CSRGraph`` stores an undirected graph as ``row_ptr``/``col_idx`` int32
arrays (both edge directions present, columns sorted within each row, no
self-loops). It is the host-side currency of ``repro.sparse``: generators
and the engine planner build it straight from edge lists — the dense matrix
that caps practical N in the dense backends is never materialized on this
path. Device code receives the padded batch form (``packing.PackedCSRBatch``).

Row-sorted columns are an invariant, not a convenience: the PEO test's
membership queries binary-search rows (``peo_csr``), and the packed batch
derives flat sorted edge keys from it. All constructors enforce it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from repro.graphs.structure import Graph


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Compressed-sparse-row adjacency of an undirected simple graph.

    Attributes:
      n_nodes: vertex count N.
      row_ptr: (N+1,) int32; row v's neighbors live at
        ``col_idx[row_ptr[v]:row_ptr[v+1]]``.
      col_idx: (nnz,) int32, sorted ascending within each row; ``nnz`` counts
        directed entries (2x the undirected edge count).
    """

    n_nodes: int
    row_ptr: np.ndarray
    col_idx: np.ndarray

    # -- construction -------------------------------------------------------
    @classmethod
    def from_edges(cls, n: int, edges: Optional[np.ndarray]) -> "CSRGraph":
        """Build from a (2, E) edge index; symmetrizes, dedups, drops loops."""
        if edges is None or edges.size == 0:
            return cls(n, np.zeros(n + 1, dtype=np.int32),
                       np.zeros(0, dtype=np.int32))
        src = np.concatenate([edges[0], edges[1]]).astype(np.int64)
        dst = np.concatenate([edges[1], edges[0]]).astype(np.int64)
        keep = src != dst
        keys = np.unique(src[keep] * n + dst[keep])
        rows = (keys // n).astype(np.int32)
        cols = (keys % n).astype(np.int32)
        row_ptr = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(np.bincount(rows, minlength=n), out=row_ptr[1:])
        return cls(n, row_ptr, cols)

    @classmethod
    def from_dense(cls, adj: np.ndarray,
                   n_nodes: Optional[int] = None) -> "CSRGraph":
        """Build from a bool adjacency matrix (symmetrized, loops dropped)."""
        adj = np.asarray(adj, dtype=bool)
        n = n_nodes if n_nodes is not None else adj.shape[0]
        a = adj[:n, :n]
        a = a | a.T
        rows, cols = np.nonzero(a)          # row-major => row-sorted cols
        keep = rows != cols
        rows, cols = rows[keep], cols[keep]
        row_ptr = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(np.bincount(rows, minlength=n), out=row_ptr[1:])
        return cls(n, row_ptr, cols.astype(np.int32))

    @classmethod
    def from_graph(cls, g: Graph) -> "CSRGraph":
        """Build from a :class:`Graph`, preferring the cheapest stored view.

        Edge-list / CSR views skip the O(N²) dense scan entirely — this is
        the path that opens N beyond the dense representation's cap. A
        pre-padded dense ``adj`` is sliced to the logical ``n_nodes`` block
        (padding vertices are isolated by the Graph contract).
        """
        if g.edges is not None:
            return cls.from_edges(g.n_nodes, g.edges)
        if g.indptr is not None and g.indices is not None:
            n = g.n_nodes
            deg = np.diff(g.indptr[: n + 1]).astype(np.int64)
            rows = np.repeat(np.arange(n, dtype=np.int32), deg)
            edges = np.stack([rows, g.indices[: int(deg.sum())]])
            return cls.from_edges(n, edges)
        if g.adj is not None:
            return cls.from_dense(g.adj, g.n_nodes)
        return cls.from_edges(g.n_nodes, None)

    # -- views / conversions ------------------------------------------------
    def to_dense(self) -> np.ndarray:
        n = self.n_nodes
        adj = np.zeros((n, n), dtype=bool)
        rows = np.repeat(np.arange(n), self.degrees())
        adj[rows, self.col_idx] = True
        return adj

    def to_graph(self) -> Graph:
        rows = np.repeat(
            np.arange(self.n_nodes, dtype=np.int32), self.degrees())
        edges = np.stack([rows, self.col_idx]).astype(np.int32)
        return Graph(n_nodes=self.n_nodes, edges=edges,
                     indptr=self.row_ptr, indices=self.col_idx)

    def device_arrays(self):
        """(row_ptr, col_idx) as jnp int32 arrays for the device kernels."""
        import jax.numpy as jnp

        return jnp.asarray(self.row_ptr), jnp.asarray(self.col_idx)

    # -- statistics ---------------------------------------------------------
    @property
    def nnz(self) -> int:
        """Directed edge entries (2x undirected count)."""
        return int(self.row_ptr[-1])

    @property
    def n_edges(self) -> int:
        """Undirected edge count."""
        return self.nnz // 2

    def degrees(self) -> np.ndarray:
        return np.diff(self.row_ptr).astype(np.int32)

    @property
    def max_degree(self) -> int:
        return int(self.degrees().max()) if self.n_nodes else 0

    @property
    def density(self) -> float:
        """nnz / N² — the router's sparsity feature (0 for N = 0)."""
        n = self.n_nodes
        return self.nnz / (n * n) if n else 0.0

    def stats(self) -> Dict[str, float]:
        """Degree / fill statistics for routing and logging."""
        deg = self.degrees()
        n = self.n_nodes
        return {
            "n": n,
            "nnz": self.nnz,
            "n_edges": self.n_edges,
            "density": self.density,
            "max_degree": self.max_degree,
            "mean_degree": float(deg.mean()) if n else 0.0,
            "isolated": int((deg == 0).sum()),
            "dense_bytes": float(n) * n,          # bool (N, N)
            "csr_bytes": 4.0 * (n + 1 + self.nnz),  # int32 row_ptr+col_idx
        }
