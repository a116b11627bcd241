"""Parallel Lexicographic Breadth-First Search (paper §6.1), TPU-native.

The paper's CUDA design keeps vertices in a linked list of *classes* (sets of
equal-label vertices) mutated by N threads with four barrier-separated
kernels per iteration. On TPU we re-derive the identical partition process on
a dense **rank representation** (see DESIGN.md §2):

* ``rank[v]`` = index of v's class in the lexicographic (ascending) order of
  labels. Larger rank ⇔ lexicographically larger label.
* One iteration of the main (inherently sequential) loop:

  1. ``current = argmax(rank)``             — paper kernel 4's selection
     (any member of the lexicographically last class is valid; fixed argmax
     tie-breaking makes the order deterministic, which the paper's racy
     ``current ← x`` write is not). Visited lanes park at negative ranks,
     so no masked score temporary is needed.
  2. ``key = 2·rank + Adj[current]``        — paper kernels 1–3: each class
     splits; neighbors of ``current`` move into a class inserted right after
     their old class (paper Lemma 6.1 / Observation 6.2). Arithmetically:
     ``2r+1 > 2r`` within the class, and ``2·`` preserves inter-class order.
  3. rank compaction — the paper's empty-set deletion (Lemma 6.3): any
     order-isomorphic remap back into ``[0, N)`` keeps step 2 inside int32.

Two device implementations share that arithmetic and produce
**bit-identical orders** (identical first-index argmax tie-breaking over
order-isomorphic rank vectors — asserted against the numpy twin and each
other in tests):

* :func:`lexbfs_scan` — the paper-faithful form: compaction *every*
  iteration via scatter-histogram + prefix sum over 2N bins, one
  ``lax.scan``. This is the reference the engine's ``jax_faithful``
  backend serves, and the differential anchor for everything below.
* :func:`lexbfs_batched` / :func:`lexbfs` — the serving hot path
  (PR 5 restructure): batch-major ``fori_loop`` over (B, N) state with
  **lazy compaction** (cheap iterations ``rank' = 2·rank + bit`` until
  int32 headroom runs out, see EXPERIMENTS.md §Perf A2) and a **sort-free
  comparator** dense rank — ``rank[v] ← #{active u : rank_u < rank_v}``,
  a pure compare-and-reduce with no scatter, no sort, and no
  ``cumsum(2N)`` per step. The same formulation runs inside the fused
  Pallas kernel (``repro.kernels.lexbfs_fused``), where it is the only
  option: Mosaic has neither a sort nor an efficient scatter primitive.
  Above :data:`COMPARATOR_MAX_N` the batched path switches to the
  sort-based dense rank (the comparator's O(N²)-per-compaction work stops
  paying); both remaps are order-isomorphic, so the order is unchanged.

Work: O(N) per cheap iteration, O(N²·N/K) comparator total (K ≈ 30−log₂N
cheap steps per compaction) — the extra factor buys scatter-free,
lane-parallel inner loops that measure faster on both CPU and VPU at the
engine's bucket sizes. Depth per
iteration is O(log N) (the argmax/compare reductions), vs the paper's O(1)
PRAM claim; total O(N log N) depth (honest delta, DESIGN.md §7/§11).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


#: Largest N for which the batched/lazy compaction uses the sort-free
#: comparator (matching the fused Pallas kernel bit for bit in formulation,
#: not just in output). Above it, one O(N²) comparator per compaction
#: outgrows the O(N log N) sort-based dense rank on every host we measured,
#: so the sort takes over — the remaps are order-isomorphic either way.
COMPARATOR_MAX_N = 512


def _lexbfs_step(adj: jnp.ndarray, state, _):
    """One paper-faithful LexBFS iteration. state = (rank, active).

    Visited lanes park at ``rank = -1`` so the selection argmax reads
    ``rank`` directly (the masked ``score`` temporary of the original form
    is gone), and the adjacency row comes out via a contiguous
    ``dynamic_slice`` row copy instead of a one-hot gather.
    """
    rank, active = state
    n = rank.shape[0]
    # --- kernel 4 (paper): select current = any vertex of the last class.
    current = jnp.argmax(rank).astype(jnp.int32)
    # --- kernel 1 (paper): mark current visited.
    active = active.at[current].set(False)
    # --- kernels 2+3 (paper): split classes — neighbors of current move up.
    adjrow = jax.lax.dynamic_slice_in_dim(adj, current, 1, axis=0)[0]
    key = 2 * rank + (adjrow & active).astype(jnp.int32)  # active: [0, 2N)
    # --- empty-set deletion (paper Lemma 6.3) = dense-rank compaction.
    # Visited lanes carry key < 0, which wraps to a high bin with weight 0
    # and is masked back to -1 below — they never perturb active classes.
    cnt = jnp.zeros(2 * n, dtype=jnp.int32).at[key].add(
        active.astype(jnp.int32)
    )
    class_idx = jnp.cumsum((cnt > 0).astype(jnp.int32)) - 1  # (2N,)
    rank = jnp.where(active, jnp.take(class_idx, key), jnp.int32(-1))
    return (rank, active), current


@functools.partial(jax.jit, static_argnames=("return_pos",))
def lexbfs_scan(adj: jnp.ndarray, return_pos: bool = False):
    """Paper-faithful parallel LexBFS: per-iteration compaction, one scan.

    The differential reference for the restructured paths below — every
    other implementation (batched fori, fused Pallas kernel, CSR twins)
    must match its orders bit for bit.
    """
    n = adj.shape[0]
    adj = adj.astype(bool)
    rank0 = jnp.zeros(n, dtype=jnp.int32)
    active0 = jnp.ones(n, dtype=bool)
    (_, _), order = jax.lax.scan(
        functools.partial(_lexbfs_step, adj), (rank0, active0), None, length=n
    )
    order = order.astype(jnp.int32)
    if return_pos:
        return order, lexbfs_pos(order)
    return order


def lexbfs_batched_scan(adj_batch: jnp.ndarray) -> jnp.ndarray:
    """vmap-of-scan over (B, N, N) — the pre-restructure batched form.

    Kept as a second differential reference in tests.
    """
    return jax.vmap(lambda a: lexbfs_scan(a))(adj_batch)


# ---------------------------------------------------------------------------
# Restructured hot path (PR 5): batch-major fori_loop + lazy compaction with
# a sort-free comparator dense rank. Bit-identical orders to lexbfs_scan.
# ---------------------------------------------------------------------------
def _comparator_rank(rank: jnp.ndarray) -> jnp.ndarray:
    """Sort-free dense order statistic over a (B, N) rank batch.

    ``rank[v] ← #{u : 0 ≤ rank_u < rank_v}`` — order-isomorphic to the
    histogram compaction (ties stay ties, order is preserved) and bounded
    by N−1, which is all lazy compaction needs. Negative (visited) lanes
    collapse to the −1 sentinel. Pure compare-and-reduce: the same
    formulation runs inside the fused Pallas kernel, where neither sort
    nor scatter exists.
    """
    active = rank >= 0
    less = active[:, None, :] & (rank[:, None, :] < rank[:, :, None])
    cnt = jnp.sum(less.astype(jnp.int32), axis=2)
    return jnp.where(active, cnt, jnp.int32(-1))


def _sorted_rank(rank: jnp.ndarray) -> jnp.ndarray:
    """Sort-based dense rank over a (B, N) batch (large-N compaction)."""
    return jax.vmap(_dense_rank)(rank)


def lexbfs_inner_block(n: int) -> int:
    """Cheap iterations between compactions before ``2·rank + bit``
    overflows int32 (ranks start < N after a compaction and double each
    step)."""
    return max(1, 30 - int(np.ceil(np.log2(max(n, 2)))))


@functools.partial(jax.jit, static_argnames=("return_pos",))
def lexbfs_batched(adj_batch: jnp.ndarray, return_pos: bool = False):
    """Batch-major parallel LexBFS over a (B, N, N) bool batch.

    One ``fori_loop`` drives all B graphs in lockstep on (B, N) state —
    no vmap-of-scan, no per-step scatter histogram, no ``cumsum(2N)``.
    Orders are bit-identical to :func:`lexbfs_scan` (order-isomorphic
    ranks, same first-index argmax tie-breaking; asserted in tests).

    Args:
      adj_batch: (B, N, N) bool, symmetric, zero diagonal per slot.
        Padding vertices (isolated, highest indices) are visited last.
      return_pos: also return the (B, N) inverse permutations, fused into
        this call so callers never run a second scatter pass.

    Returns:
      orders: (B, N) int32 — or ``(orders, pos)`` with ``return_pos``.
    """
    b, n = adj_batch.shape[0], adj_batch.shape[1]
    adj_batch = adj_batch.astype(bool)
    k_inner = lexbfs_inner_block(n)
    compact = (
        _comparator_rank if n <= COMPARATOR_MAX_N else _sorted_rank
    )
    rows = jnp.arange(b, dtype=jnp.int32)

    def step(i, state):
        rank, order = state
        current = jnp.argmax(rank, axis=1).astype(jnp.int32)  # (B,)
        order = order.at[:, i].set(current)
        adjrow = jnp.take_along_axis(
            adj_batch, current[:, None, None], axis=1
        )[:, 0, :]
        # Unconditional update (§Perf A3): visited lanes stay negative
        # under 2·rank + bit, so no select is needed.
        rank = rank.at[rows, current].set(jnp.int32(-1))
        rank = 2 * rank + adjrow.astype(jnp.int32)
        rank = jax.lax.cond(
            (i % k_inner) == (k_inner - 1), compact, lambda r: r, rank
        )
        return rank, order

    rank0 = jnp.zeros((b, n), dtype=jnp.int32)
    order0 = jnp.zeros((b, n), dtype=jnp.int32)
    _, order = jax.lax.fori_loop(0, n, step, (rank0, order0))
    if return_pos:
        pos = (
            jnp.zeros((b, n), dtype=jnp.int32)
            .at[rows[:, None], order]
            .set(jnp.arange(n, dtype=jnp.int32)[None, :])
        )
        return order, pos
    return order


@functools.partial(jax.jit, static_argnames=("return_pos",))
def lexbfs(adj: jnp.ndarray, return_pos: bool = False):
    """Parallel LexBFS over a dense bool adjacency matrix.

    The single-graph view of :func:`lexbfs_batched` (B = 1) — the
    restructured hot path every device pipeline (``jax_fast``,
    ``pallas_peo``, the witness kernels) consumes. For the paper-faithful
    per-iteration-compaction form, use :func:`lexbfs_scan`; orders are
    bit-identical either way.

    Args:
      adj: (N, N) bool, symmetric, zero diagonal. Padding vertices
        (isolated, at the highest indices) are visited last and do not
        perturb the order of real vertices.
      return_pos: also return the inverse permutation ``pos`` with
        ``pos[v] = i ⇔ order[i] = v``.

    Returns:
      order: (N,) int32 — a valid LexBFS order (satisfies the LB-property).
    """
    out = lexbfs_batched(adj[None], return_pos=return_pos)
    if return_pos:
        return out[0][0], out[1][0]
    return out[0]


def lexbfs_fast(adj: jnp.ndarray) -> jnp.ndarray:
    """Optimized parallel LexBFS — alias of :func:`lexbfs`.

    Historically the lazy-compaction variant next to a faithful ``lexbfs``;
    the PR 5 restructure made lazy compaction *the* ``lexbfs``, so this
    name survives only for callers (and the ``jax_fast`` backend) that
    import it. Same bit-identical orders.
    """
    return lexbfs(adj)


def _dense_rank(rank: jnp.ndarray) -> jnp.ndarray:
    """Compact values to [0, #distinct-nonneg); any negative -> -1.

    Visited lanes carry negative sentinels that drift (the cheap update is
    applied unconditionally; negatives map to negatives because
    ``2·r + bit < 0`` for every r ≤ -1), so compaction treats ALL negative
    values as one sentinel class. Sort-based — used by the CSR LexBFS and
    by :func:`lexbfs_batched` above :data:`COMPARATOR_MAX_N`."""
    s = jnp.sort(rank)
    distinct_before = jnp.cumsum(
        jnp.concatenate([jnp.zeros(1, jnp.int32),
                         (s[1:] != s[:-1]).astype(jnp.int32)]))
    idx = jnp.searchsorted(s, rank)
    dense = jnp.take(distinct_before, idx)
    # shift by the number of distinct negative values so actives start at 0
    first_nonneg = jnp.searchsorted(s, 0)
    n_neg_classes = jnp.where(
        first_nonneg > 0, jnp.take(distinct_before, first_nonneg), 0)
    dense = dense - n_neg_classes
    return jnp.where(rank < 0, -1, dense).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Dense numpy reference of the SAME rank-refinement algorithm. Serves as
# (a) a C-speed sequential CPU baseline for dense graphs in the benchmark
# harness, and (b) a step-by-step oracle for the JAX implementations
# (identical tie-breaking ⇒ identical order).
# ---------------------------------------------------------------------------
def lexbfs_numpy_dense(adj: np.ndarray) -> np.ndarray:
    adj = np.asarray(adj, dtype=bool)
    n = adj.shape[0]
    rank = np.zeros(n, dtype=np.int64)
    active = np.ones(n, dtype=bool)
    order = np.empty(n, dtype=np.int32)
    for i in range(n):
        score = np.where(active, rank, -1)
        current = int(np.argmax(score))
        order[i] = current
        active[current] = False
        key = 2 * rank + (adj[current] & active)
        cnt = np.bincount(key[active], minlength=2 * n)
        class_idx = np.cumsum(cnt > 0) - 1
        rank = np.where(active, class_idx[key], rank)
    return order


def lexbfs_pos(order: jnp.ndarray) -> jnp.ndarray:
    """Inverse permutation of an order."""
    n = order.shape[0]
    return (
        jnp.zeros(n, dtype=jnp.int32)
        .at[order]
        .set(jnp.arange(n, dtype=jnp.int32))
    )
