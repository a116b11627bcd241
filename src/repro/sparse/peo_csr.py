"""CSR perfect-elimination-order test — segment ops over the edge stream.

The dense test (``repro.core.peo``) materializes O(N²) matrices (LN, the
parent-row gather, the violation mask). On CSR the same §6.2 logic is
O(M log M) work over the directed edge stream:

* ``LN`` membership is an edge predicate: ``pos[col] < pos[row]``.
* The parent ``p_v`` (rightmost left-neighbor) is one
  ``jax.ops.segment_max`` over ``col_idx`` keyed by edge row.
* The containment test ``LN_v − {p_v} ⊆ N(p_v)`` becomes a batch of
  membership queries ``(p_v, z) ∈ E``, answered by a single
  ``searchsorted`` over flat sorted edge keys ``row·N + col`` (sorted by
  the packing contract — columns ascending within rows).

The violation count is per-directed-edge, hence **identical** to the dense
``peo_violations`` count on the same graph+order — asserted in tests.

Host twin (:func:`peo_violations_csr_numpy_batch`) evaluates the same
formula for a whole packed batch in ~15 numpy calls (flat concatenated
edges, ``maximum.reduceat`` as the segment max); it is the CPU fast path
the ``csr`` backend pairs with the host LexBFS.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# int32 edge keys row·n + col require n² < 2³¹.
_MAX_N_DEVICE = 46340


@jax.jit
def peo_violations_csr(row_ptr: jnp.ndarray, col_idx: jnp.ndarray,
                       order: jnp.ndarray) -> jnp.ndarray:
    """Violation count of ``order`` as a PEO over padded CSR adjacency.

    Args:
      row_ptr: (n+1,) int32 (packing contract: padded rows empty).
      col_idx: (nnz_pad,) int32, row-sorted columns, sentinel tail.
      order: (n,) int32 visit order (a PEO iff the count is 0).
    """
    n = row_ptr.shape[0] - 1
    if n > _MAX_N_DEVICE:
        raise ValueError(
            f"n_pad {n} overflows int32 edge keys (max {_MAX_N_DEVICE})")
    nnz_pad = col_idx.shape[0]
    big = jnp.int32(2 ** 31 - 1)
    pos = jnp.zeros(n, dtype=jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    e = jnp.arange(nnz_pad, dtype=jnp.int32)
    row = jnp.searchsorted(row_ptr[1:], e, side="right").astype(jnp.int32)
    valid = e < row_ptr[n]
    rowc = jnp.clip(row, 0, n - 1)
    col = jnp.where(valid, col_idx, 0)
    ln_e = valid & (pos[col] < pos[rowc])        # col ∈ LN_row
    score = jnp.where(ln_e, pos[col], jnp.int32(-1))
    p_pos = jax.ops.segment_max(score, rowc, num_segments=n,
                                indices_are_sorted=True)
    p = order[jnp.clip(jnp.maximum(p_pos, -1), 0, n - 1)]
    pu = p[rowc]                                  # parent of each edge's row
    edge_keys = jnp.where(valid, rowc * n + col_idx, big)
    need = ln_e & (col != pu)                     # z ∈ LN_v − {p_v}
    qk = jnp.where(need, pu * n + col, big)       # query (p_v, z) ∈ E ?
    loc = jnp.searchsorted(edge_keys, qk)
    found = edge_keys[jnp.clip(loc, 0, nnz_pad - 1)] == qk
    return jnp.sum((need & ~found).astype(jnp.int32))


@jax.jit
def peo_check_csr(row_ptr: jnp.ndarray, col_idx: jnp.ndarray,
                  order: jnp.ndarray) -> jnp.ndarray:
    """True iff ``order`` is a perfect elimination order (device)."""
    return peo_violations_csr(row_ptr, col_idx, order) == 0


def peo_violations_csr_batched(row_ptr, col_idx, orders):
    """vmap'd violation counts over a PackedCSRBatch's arrays."""
    return jax.vmap(peo_violations_csr)(row_ptr, col_idx, orders)


# ---------------------------------------------------------------------------
# Host twin, vectorized across the batch.
# ---------------------------------------------------------------------------
def peo_violations_csr_numpy_batch(
    row_ptr: np.ndarray, col_idx: np.ndarray, orders: np.ndarray
) -> np.ndarray:
    """(B,) violation counts over a packed batch, all-numpy.

    Works on the flat concatenation of every graph's real edges (graph-
    major, row-major, columns ascending — globally sorted keys), so each
    step is one vectorized call regardless of B.
    """
    b, np1 = row_ptr.shape
    n = np1 - 1
    nnz = row_ptr[:, -1].astype(np.int64)
    total = int(nnz.sum())
    if total == 0:
        return np.zeros(b, dtype=np.int64)
    deg = np.diff(row_ptr, axis=1).astype(np.int64)
    rows = np.repeat(np.tile(np.arange(n, dtype=np.int64), b), deg.ravel())
    gid = np.repeat(np.arange(b, dtype=np.int64), nnz)
    cols = col_idx[
        np.arange(col_idx.shape[1])[None, :] < nnz[:, None]].astype(np.int64)
    pos = np.empty((b, n), dtype=np.int64)
    pos[np.arange(b)[:, None], orders] = np.arange(n)[None, :]
    posu = pos[gid, rows]
    posz = pos[gid, cols]
    ln_e = posz < posu
    score = np.where(ln_e, posz, -1)
    # Segment max over (graph, row): edges are segment-sorted => reduceat
    # over the non-empty rows only (their starts are strictly increasing,
    # so each segment ends where the next begins and the last at the end).
    off = np.concatenate([[0], np.cumsum(nnz)[:-1]])
    seg_starts = (row_ptr[:, :n].astype(np.int64) + off[:, None]).ravel()
    nonempty = deg.ravel() > 0
    p_pos = np.full(b * n, -1, dtype=np.int64)
    p_pos[nonempty] = np.maximum.reduceat(score, seg_starts[nonempty])
    p_pos = p_pos.reshape(b, n)
    p = orders.astype(np.int64)[
        np.arange(b)[:, None], np.clip(p_pos, 0, n - 1)]
    pu = p[gid, rows]
    edge_keys = (gid * n + rows) * n + cols
    need = ln_e & (cols != pu)
    qk = (gid * n + pu) * n + cols
    loc = np.searchsorted(edge_keys, qk)
    found = np.zeros(total, dtype=bool)
    inb = loc < total
    found[inb] = edge_keys[loc[inb]] == qk[inb]
    bad = need & ~found
    return np.bincount(gid[bad], minlength=b).astype(np.int64)


def peo_violations_csr_numpy(row_ptr: np.ndarray, col_idx: np.ndarray,
                             order: np.ndarray) -> int:
    """Single-graph host violation count (batch-of-one convenience)."""
    return int(peo_violations_csr_numpy_batch(
        row_ptr[None, :], col_idx[None, :], order[None, :])[0])
