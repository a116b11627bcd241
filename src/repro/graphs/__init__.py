"""Graph structures: dense and CSR constructors, padding and bucketing."""
from repro.graphs.structure import (
    Graph,
    dense_from_edges,
    edges_from_dense,
    csr_from_edges,
    pad_graph,
    batch_graphs,
    bucket_npad,
    bucket_graphs,
)

__all__ = [
    "Graph",
    "dense_from_edges",
    "edges_from_dense",
    "csr_from_edges",
    "pad_graph",
    "batch_graphs",
    "bucket_npad",
    "bucket_graphs",
]
