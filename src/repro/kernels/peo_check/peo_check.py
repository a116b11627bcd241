"""Pallas TPU kernels for the parallel PEO test (paper §6.2).

The PEO test is the paper's O(N²)-work hot spot: an N×N boolean tensor
computation. The pure-jnp version (``repro.core.peo``) materializes three
N×N intermediates in HBM (``ln``, ``adj_p`` selection mask, ``bad``). These
kernels tile the computation over VMEM blocks so that only the adjacency
matrix (and the gathered parent rows) are ever read from HBM, and nothing
N×N is written back:

* ``parent_kernel``  — paper's ``preparationLNandP``: running blockwise
  argmax of ``pos[u]`` over the left-neighbor mask ⇒ ``p_v`` (+ max pos).
* ``violation_kernel`` — paper's ``testing``: blockwise fused
  ``LN ∧ (z ≠ p_v) ∧ ¬Adj[p_v, z]`` reduced to a single violation count.

Block shapes are (128, 128) by default — aligned to the TPU VPU lane/sublane
tiling for int8/int32 operands (the mask math is all VPU; no MXU use).
Both kernels run in interpret mode on CPU for validation and compile
through Mosaic on a TPU (``tests/test_tpu_compile.py``); the BlockSpecs
below are the real TPU tiling.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


DEFAULT_BLOCK_V = 128
DEFAULT_BLOCK_Z = 128


# ---------------------------------------------------------------------------
# Kernel 1: parents (preparationLNandP)
# ---------------------------------------------------------------------------
def _parent_kernel(n, adj_ref, pos_v_ref, pos_z_ref, best_pos_ref, p_ref):
    """Grid (nv, nz), z fastest. Running argmax over z-blocks.

    adj_ref:   (BV, BZ) int8     adjacency block
    pos_v_ref: (BV, 1) int32     positions of the v-tile (column)
    pos_z_ref: (1, BZ) int32     positions of the z-tile (row)
    best_pos_ref, p_ref: (BV, 1) int32 accumulators (same block ∀ z-steps)
    ``n`` (static) masks the ragged edge blocks — we do not rely on Pallas
    zero-padding out-of-bounds loads. Masks are built from int32 operands:
    an int8 compare yields a mask in the int8 layout, which Mosaic cannot
    relayout to meet the int32 ones.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_pos_ref[...] = jnp.full_like(best_pos_ref, -1)
        p_ref[...] = jnp.zeros_like(p_ref)

    adj = adj_ref[...].astype(jnp.int32)        # (BV, BZ)
    pos_v = pos_v_ref[...]                      # (BV, 1)
    pos_z = pos_z_ref[...]                      # (1, BZ)
    z_ids = j * adj.shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, adj.shape, 1)
    ln = (adj != 0) & (z_ids < n) & (pos_z < pos_v)
    cand = jnp.where(ln, pos_z, -1)             # (BV, BZ)
    row_best = jnp.max(cand, axis=1, keepdims=True)
    # index of the max within the block → global vertex id
    row_arg = jnp.max(jnp.where(cand == row_best, z_ids, -1), axis=1,
                      keepdims=True)
    better = row_best > best_pos_ref[...]
    best_pos_ref[...] = jnp.where(better, row_best, best_pos_ref[...])
    p_ref[...] = jnp.where(better, row_arg, p_ref[...])


def peo_parents_pallas(
    adj_i8: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    block_v: int = DEFAULT_BLOCK_V,
    block_z: int = DEFAULT_BLOCK_Z,
    interpret: Optional[bool] = None,
):
    """(p, best_pos) per vertex. adj_i8: (N, N) int8; pos: (N,) int32."""
    n = adj_i8.shape[0]
    nv, nz = pl.cdiv(n, block_v), pl.cdiv(n, block_z)
    out_shape = [
        jax.ShapeDtypeStruct((n, 1), jnp.int32),  # best_pos
        jax.ShapeDtypeStruct((n, 1), jnp.int32),  # p
    ]
    best_pos, p = pl.pallas_call(
        functools.partial(_parent_kernel, n),
        grid=(nv, nz),
        in_specs=[
            pl.BlockSpec((block_v, block_z), lambda i, j: (i, j)),
            pl.BlockSpec((block_v, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_z), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_v, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, 1), lambda i, j: (i, 0)),
        ],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(adj_i8, pos.reshape(n, 1), pos.reshape(1, n))
    return p[:, 0], best_pos[:, 0]


# ---------------------------------------------------------------------------
# Kernel 2: violations (testing)
# ---------------------------------------------------------------------------
def _violation_kernel(
    n, adj_ref, adjp_ref, pos_v_ref, pos_z_ref, p_ref, count_ref
):
    """Grid (nv, nz). Fused LN ∧ (z≠p_v) ∧ ¬Adj[p_v,z] count-reduce.

    adj_ref:  (BV, BZ) int8   Adj[vtile, ztile]
    adjp_ref: (BV, BZ) int8   Adj[p[vtile], ztile]  (rows pre-gathered)
    pos_v_ref, p_ref: (BV, 1) int32 columns; pos_z_ref: (1, BZ) int32 row
    count_ref: (1, 1) int32   global violation count accumulator (a
                              vector block: Mosaic stores no scalars to
                              VMEM)
    ``n`` (static) masks ragged edge blocks in both dimensions.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    adj = adj_ref[...].astype(jnp.int32)
    adjp = adjp_ref[...].astype(jnp.int32)
    bv, bz = adj.shape
    v_ids = i * bv + jax.lax.broadcasted_iota(jnp.int32, adj.shape, 0)
    z_ids = j * bz + jax.lax.broadcasted_iota(jnp.int32, adj.shape, 1)
    valid = (v_ids < n) & (z_ids < n)
    ln = (adj != 0) & (pos_z_ref[...] < pos_v_ref[...]) & valid
    bad = ln & (z_ids != p_ref[...]) & (adjp == 0)
    count_ref[...] += jnp.sum(jnp.where(bad, 1, 0), keepdims=True)


def peo_violations_pallas(
    adj_i8: jnp.ndarray,
    adjp_i8: jnp.ndarray,
    pos: jnp.ndarray,
    p: jnp.ndarray,
    *,
    block_v: int = DEFAULT_BLOCK_V,
    block_z: int = DEFAULT_BLOCK_Z,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Violation count. All inputs device arrays; adj/adjp int8 (N, N)."""
    n = adj_i8.shape[0]
    nv, nz = pl.cdiv(n, block_v), pl.cdiv(n, block_z)
    count = pl.pallas_call(
        functools.partial(_violation_kernel, n),
        grid=(nv, nz),
        in_specs=[
            pl.BlockSpec((block_v, block_z), lambda i, j: (i, j)),
            pl.BlockSpec((block_v, block_z), lambda i, j: (i, j)),
            pl.BlockSpec((block_v, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_z), lambda i, j: (0, j)),
            pl.BlockSpec((block_v, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(adj_i8, adjp_i8, pos.reshape(n, 1), pos.reshape(1, n), p.reshape(n, 1))
    return count[0, 0]
