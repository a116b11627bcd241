"""Pallas TPU kernel: the entire verdict hot path in ONE kernel (§6.1+§6.2).

Through PR 4 the per-graph pipeline was an n-step ``lax.scan`` (LexBFS,
re-reading the adjacency from HBM every iteration) followed by two Pallas
kernels (parents + violations) — three host-level dispatches per graph and
O(N²) HBM traffic *per LexBFS step*. This kernel runs the whole thing in a
single ``pallas_call``:

* **Grid** ``(B/G,)`` — the work-unit batch is the leading (and only) grid
  axis; each program owns G graphs (G = 1 unpacked, G = the pack factor
  for tiny buckets). Pallas stages those graphs' (N, N) int8 adjacency
  blocks from HBM into VMEM once; every one of the N iterations then
  reads on-chip rows only.
* **State residency** — ``rank`` and ``pos`` live in (G, N) int32 VMEM
  scratch for the program's lifetime; nothing O(N) round-trips to HBM
  inside the loop. This is the design "Computing Treewidth on the GPU"
  (van der Zanden & Bodlaender) and the chordless-cycle enumerator of
  Jradi et al. use for their sequential outer loops (PAPERS.md).
* **Sort-free compaction** — Mosaic has no sort and no efficient scatter,
  so the paper's histogram + ``cumsum(2N)`` empty-set deletion is replaced
  by the comparator dense order statistic
  ``rank[v] ← #{u : 0 ≤ rank_u < rank_v}`` (see ``repro.core.lexbfs``),
  evaluated blockwise so the (N, N) compare never materializes: a
  (U, N) tile at a time, U = :data:`compaction_block`. Lazy cadence —
  every ``k_inner = 30 − ⌈log₂N⌉`` steps — keeps ``2·rank + bit`` inside
  int32 between compactions.
* **Fused PEO test** — at the moment vertex ``v`` is visited, its
  left-neighborhood LN(v) is exactly ``Adj[v] ∧ visited``, its parent
  ``p_v`` the visited neighbor with max ``pos``, and the paper's
  ``testing`` kernel reduces to two on-chip row reads
  (``Adj[v]``, ``Adj[p_v]``) and a masked count — so the violation total
  accumulates *inside* the LexBFS loop and no parent/violation kernels
  (nor the (N,) parent vector) ever leave the chip.

One visit-loop body (:func:`_visit_kernel`) serves all three entry points:
unpacked (G = 1), packed (G graphs lock-stepped, one per sublane row) and
witness (G = 1 plus certificate raw material).

Outputs per graph: the LexBFS order (bit-identical to every other
implementation in the repo — asserted in tests) and the violation count
(0 ⇔ chordal). Outputs are laid out ``(B/G, G, ·)`` so every block's last
two dims equal the array's, as Mosaic's (8, 128) tiling rule requires.
VMEM budget and the bucket cap this implies are derived in
``repro.configs.shapes.fused_vmem_bytes`` and documented in DESIGN.md §11.

Everything is masked explicitly; correctness does not rely on Pallas
zero-padding semantics, and padded (isolated) vertices are visited last
contributing zero violations — any engine bucket shape is a valid input.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def compaction_block(n: int) -> int:
    """Comparator tile height U: the (U, N) compare tile staged per inner
    step. Largest power-of-two divisor of N up to 512 (engine buckets are
    powers of two; odd direct-call sizes fall back to one full tile)."""
    for u in (512, 256, 128, 64, 32, 16, 8):
        if n % u == 0 and u < max(n, 2):
            return u
    return n


def _row_tile(n: int) -> int:
    """Rows per aligned adjacency load: an int8 VMEM tile is (32, 128), so
    a row is read as part of its 32-row slab (or the largest divisor of N
    below that, for small or odd direct-call sizes)."""
    for t in (32, 16, 8, 4, 2):
        if n % t == 0:
            return t
    return 1


def _first_argmax(x, lane):
    """(G, N) int32 -> (G, 1): first lane holding each row's max.

    Mosaic lowers ``argmax`` for float32 only; max + min-lane keeps
    ``jnp.argmax``'s first-occurrence tie-break, so orders stay
    bit-identical to the jnp implementations.
    """
    m = jnp.max(x, axis=1, keepdims=True)
    return jnp.min(jnp.where(x == m, lane, x.shape[1]), axis=1, keepdims=True)


def _scalar(col, j):
    """Row ``j`` of a (G, 1) int32 vector as a scalar (full reduction)."""
    return jnp.max(col[j:j + 1, :])


def _load_row(adj_ref, j, r, n):
    """Row ``r`` of graph ``j``'s int8 adjacency block as (1, N) int32.

    Reads the aligned slab that holds the row and selects it with a
    sublane mask: Mosaic has no dynamic single-row int8 load.
    """
    t = _row_tile(n)
    base = pl.multiple_of((r // t) * t, t)
    slab = adj_ref[j, pl.ds(base, t), :].astype(jnp.int32)    # (t, N)
    sub = jax.lax.broadcasted_iota(jnp.int32, (t, n), 0)
    return jnp.max(jnp.where(sub == r - base, slab, 0), axis=0, keepdims=True)


def _store_row(ref, r, row, n):
    """Write (1, N) int32 ``row`` as int8 row ``r`` of ``ref[0]`` by a
    read-modify-write of its aligned slab (no dynamic single-row store)."""
    t = _row_tile(n)
    base = pl.multiple_of((r // t) * t, t)
    slab = ref[0, pl.ds(base, t), :].astype(jnp.int32)
    sub = jax.lax.broadcasted_iota(jnp.int32, (t, n), 0)
    ref[0, pl.ds(base, t), :] = jnp.where(
        sub == r - base, row, slab).astype(jnp.int8)


def _stack_rows(rows):
    """G (1, N) rows -> (G, N) by sublane selects (no concatenate)."""
    g, n = len(rows), rows[0].shape[1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (g, n), 0)
    out = jnp.zeros((g, n), jnp.int32)
    for j, row in enumerate(rows):
        out = jnp.where(sub == j, row, out)
    return out


def _gather_rows(adj_ref, idx, n):
    """(G, 1) vertex ids -> (G, N) int32: graph j's adjacency row idx[j]."""
    return _stack_rows([_load_row(adj_ref, j, _scalar(idx, j), n)
                        for j in range(idx.shape[0])])


def _compact(rank, u_block):
    """Sort-free comparator: rank[v] <- #{u : 0 <= rank_u < rank_v} per row.

    The (1, U) tile of comparands turns into a (U, 1) column through a
    diagonal select — a lane-to-sublane reshape Mosaic does not lower.
    """
    g, n = rank.shape
    ri = jax.lax.broadcasted_iota(jnp.int32, (u_block, u_block), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (u_block, u_block), 1)
    rows = []
    for j in range(g):
        r = rank[j:j + 1, :]                                   # (1, N)
        cnt = jnp.zeros((1, n), jnp.int32)
        for s in range(0, n, u_block):
            blk = jnp.broadcast_to(r[:, s:s + u_block], (u_block, u_block))
            col = jnp.sum(jnp.where(ri == ci, blk, 0), axis=1, keepdims=True)
            less = jnp.where((col >= 0) & (col < r), 1, 0)     # (U, N)
            cnt = cnt + jnp.sum(less, axis=0, keepdims=True)
        rows.append(cnt)
    return jnp.where(rank >= 0, _stack_rows(rows), jnp.int32(-1))


def _visit_kernel(n, g, k_inner, u_block, witness, adj_ref, order_ref,
                  viol_ref, *refs):
    """One program = G graphs' full LexBFS + PEO verdict, lock-stepped.

    adj_ref:   (G, N, N) int8   adjacency (VMEM-staged by the grid)
    order_ref: (1, G, N) int32  LexBFS order (out)
    viol_ref:  (1, G, 1) int32  PEO violation count (out)
    witness outputs (G = 1 only; DESIGN.md §12), no extra adjacency reads:
      ln_ref:     (1, N, N) int8  LN(v) membership row, stored at row v
                                  the moment v is visited — ``Adj[v] ∧
                                  visited`` at visit time IS the final row;
      parent_ref: (1, 1, N) int32 rightmost-left-neighbor p(v) (0 when LN
                                  is empty — the host producers' argmax
                                  convention);
      triple_ref: (1, 1, 3) int32 latest violating (v, p(v), w); visits
                                  run in increasing pos, so the survivor is
                                  the deterministic triple the host twin
                                  picks. (-1, -1, -1) when the order is a
                                  PEO.
    rank_ref, pos_ref: (G, N) int32 VMEM scratch — the resident state.
    ``n``/``g``/``k_inner``/``u_block``/``witness`` are static (baked per
    bucket shape). The per-step selection is a per-row argmax, so every
    graph visits its own vertex each iteration.
    """
    if witness:
        ln_ref, parent_ref, triple_ref, rank_ref, pos_ref = refs
    else:
        rank_ref, pos_ref = refs
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, n), 1)

    # Scratch persists across grid steps: re-arm per program.
    rank_ref[...] = jnp.zeros_like(rank_ref)
    pos_ref[...] = jnp.zeros_like(pos_ref)
    viol_ref[...] = jnp.zeros_like(viol_ref)
    order_ref[...] = jnp.zeros_like(order_ref)
    if witness:
        ln_ref[...] = jnp.zeros_like(ln_ref)
        parent_ref[...] = jnp.zeros_like(parent_ref)
        triple_ref[...] = jnp.full_like(triple_ref, -1)
        tlane = jax.lax.broadcasted_iota(jnp.int32, (1, 3), 1)

    def step(i, _):
        rank = rank_ref[...]                            # (G, N)
        pos = pos_ref[...]
        # Selection (paper kernel 4): visited lanes are negative, so the
        # row max picks the lexicographically last active class.
        current = _first_argmax(rank, lane)             # (G, 1)
        nbr = _gather_rows(adj_ref, current, n) != 0    # (G, N)
        # Fused PEO test (paper §6.2) at visit time: LN(current) is the
        # visited neighborhood, p the member with max pos.
        visited = rank < 0
        ln = nbr & visited
        cand = jnp.where(ln, pos, jnp.int32(-1))
        p = _first_argmax(cand, lane)                   # unique: pos distinct
        prow = _gather_rows(adj_ref, p, n)
        bad = ln & (lane != p) & (prow == 0)            # LN empty -> all 0
        nbad = jnp.sum(jnp.where(bad, 1, 0), axis=1, keepdims=True)
        viol_ref[0] += nbad
        is_cur = lane == current
        if witness:
            # Certificate raw material rides the same row reads.
            _store_row(ln_ref, _scalar(current, 0), jnp.where(ln, 1, 0), n)
            parent_ref[0] = jnp.where(is_cur, p, parent_ref[0])
            w = _first_argmax(jnp.where(bad, pos, jnp.int32(-1)), lane)
            new_triple = jnp.where(
                tlane == 0, current, jnp.where(tlane == 1, p, w))
            triple_ref[0] = jnp.where(nbad > 0, new_triple, triple_ref[0])
        # Record the visit; split classes (paper kernels 1-3, lazy form).
        order_ref[0] = jnp.where(lane == i, current, order_ref[0])
        pos_ref[...] = jnp.where(is_cur, i, pos)
        rank = jnp.where(is_cur, jnp.int32(-1), rank)
        rank = 2 * rank + jnp.where(nbr, 1, 0)
        rank = jax.lax.cond(
            (i % k_inner) == (k_inner - 1),
            lambda r: _compact(r, u_block), lambda r: r, rank)
        rank_ref[...] = rank
        return 0

    jax.lax.fori_loop(0, n, step, 0)


def _visit_call(adj_i8, *, name, pack, k_inner, u_block, witness,
                interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, n = adj_i8.shape[0], adj_i8.shape[1]
    if b % pack:
        raise ValueError(f"batch {b} not a multiple of pack factor {pack}")
    groups = b // pack
    kernel = lambda *refs: _visit_kernel(  # noqa: E731
        n, pack, k_inner, u_block, witness, *refs)
    row = lambda w: pl.BlockSpec((1, pack, w), lambda i: (i, 0, 0))  # noqa
    out_specs = [row(n), row(1)]
    out_shape = [
        jax.ShapeDtypeStruct((groups, pack, n), jnp.int32),
        jax.ShapeDtypeStruct((groups, pack, 1), jnp.int32),
    ]
    if witness:
        out_specs += [pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
                      row(n), row(3)]
        out_shape += [
            jax.ShapeDtypeStruct((b, n, n), jnp.int8),
            jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, 3), jnp.int32),
        ]
    outs = pl.pallas_call(
        kernel,
        name=name,
        grid=(groups,),
        in_specs=[pl.BlockSpec((pack, n, n), lambda i: (i, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((pack, n), jnp.int32),
            pltpu.VMEM((pack, n), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(adj_i8)
    outs = [o.reshape(b, -1) for o in outs[:2]] + list(outs[2:])
    if witness:
        outs[3:] = [o.reshape(b, -1) for o in outs[3:]]
    return outs


def lexbfs_peo_fused_call(adj_i8, *, k_inner: int, u_block: int,
                          interpret: Optional[bool] = None):
    """Raw pallas_call: (B, N, N) int8 -> (orders (B, N), viols (B, 1))."""
    return _visit_call(adj_i8, name="lexbfs_peo_fused", pack=1,
                       k_inner=k_inner, u_block=u_block, witness=False,
                       interpret=interpret)


def lexbfs_peo_fused_witness_call(adj_i8, *, k_inner: int, u_block: int,
                                  interpret: Optional[bool] = None):
    """Raw pallas_call: (B, N, N) int8 ->
    (orders (B, N), viols (B, 1), ln (B, N, N) i8, parent (B, N),
    triple (B, 3))."""
    return _visit_call(adj_i8, name="lexbfs_peo_fused_witness", pack=1,
                       k_inner=k_inner, u_block=u_block, witness=True,
                       interpret=interpret)


def lexbfs_peo_fused_packed_call(adj_i8, *, pack: int, k_inner: int,
                                 u_block: int,
                                 interpret: Optional[bool] = None):
    """Raw pallas_call over a (B/G,) grid of G-graph packed programs.

    B must be a multiple of ``pack`` (the public wrapper pads with empty
    graphs). Outputs match :func:`lexbfs_peo_fused_call` exactly.
    """
    return _visit_call(adj_i8, name="lexbfs_peo_fused_packed", pack=pack,
                       k_inner=k_inner, u_block=u_block, witness=False,
                       interpret=interpret)
