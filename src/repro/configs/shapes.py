"""Shape planning for the chordality engine: the padding buckets every
work unit is compiled against, and the VMEM and host-memory models that
cap which kernel serves a bucket.
"""
from __future__ import annotations

from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Engine shape planning: the size-bucketed padding grid.
#
# The chordality engine (repro.engine) pads every request graph up to the
# smallest power-of-two bucket, so jit compilation is amortized across all
# requests that land in the same bucket instead of recompiling per exact N.
# ---------------------------------------------------------------------------
ENGINE_NPAD_BUCKETS: Tuple[int, ...] = tuple(2 ** k for k in range(4, 14))
# 16, 32, 64, ..., 8192 — covers the paper's N=1k..11k sweep with headroom.

ENGINE_BATCH_BUCKETS: Tuple[int, ...] = tuple(2 ** k for k in range(0, 11))
# 1, 2, 4, ..., 1024 — trailing partial chunks round up to one of these.


def engine_npad_bucket(
    n: int, buckets: Optional[Tuple[int, ...]] = None
) -> int:
    """Smallest padding bucket holding an n-vertex graph.

    Falls back to the next power of two when n exceeds the largest
    configured bucket (huge one-off requests still get a fixed shape).
    """
    if n <= 0:
        raise ValueError(f"graph size must be positive, got {n}")
    for b in buckets if buckets is not None else ENGINE_NPAD_BUCKETS:
        if n <= b:
            return b
    return 1 << (n - 1).bit_length()


def engine_batch_bucket(b: int, max_batch: int) -> int:
    """Round a chunk size up to a batch bucket, capped at max_batch."""
    if b <= 0:
        raise ValueError(f"batch must be positive, got {b}")
    for bb in ENGINE_BATCH_BUCKETS:
        if b <= bb:
            return min(bb, max_batch)
    return max_batch


# ---------------------------------------------------------------------------
# Sparse (CSR) shape planning: the second bucket axis.
#
# The CSR backend (repro.sparse) compiles against padded edge streams, so a
# work unit's shape is 2-D: (n_pad, nnz_pad). nnz buckets follow the same
# power-of-two rule as n_pad buckets; a third, derived axis (deg_pad — the
# padded max row degree, which sizes the per-vertex neighbor window) is also
# bucketed so ragged degree distributions compile to few shapes.
# ---------------------------------------------------------------------------
ENGINE_NNZ_BUCKETS: Tuple[int, ...] = tuple(2 ** k for k in range(5, 25))
# 32, 64, ..., 16M directed edge slots — covers M = 20N at N = 8192 (the
# paper's sparse class) with headroom.

ENGINE_DEG_MIN_BUCKET: int = 8
# Smallest deg_pad bucket: below this, window padding costs less than the
# extra compiled shapes would.


def engine_nnz_bucket(
    nnz: int, buckets: Optional[Tuple[int, ...]] = None
) -> int:
    """Smallest edge-slot bucket holding ``nnz`` directed entries.

    nnz = 0 (empty graphs / warmup probes) lands in the smallest bucket;
    beyond the grid it falls back to the next power of two, mirroring
    :func:`engine_npad_bucket`.
    """
    if nnz < 0:
        raise ValueError(f"nnz must be non-negative, got {nnz}")
    grid = buckets if buckets is not None else ENGINE_NNZ_BUCKETS
    for b in grid:
        if nnz <= b:
            return b
    return 1 << (nnz - 1).bit_length()


# ---------------------------------------------------------------------------
# Fused-pipeline VMEM budget (DESIGN.md §11).
#
# The single-pass LexBFS+PEO kernel (repro.kernels.lexbfs_fused) keeps one
# graph's full adjacency plus its rank/pos state resident in VMEM for the
# whole sequential loop. VMEM is ~16 MB/core and Pallas double-buffers the
# streamed adjacency block across grid steps, so the bucket cap follows
# from 2·N² (int8 adj) + comparator tile + O(N) state fitting the budget.
# ---------------------------------------------------------------------------
TPU_VMEM_BYTES: int = 16 * 1024 * 1024


def fused_vmem_bytes(n_pad: int, u_block: int = 512) -> int:
    """Worst-case VMEM bytes one fused-kernel program needs at ``n_pad``.

    2× the (n_pad, n_pad) int8 adjacency block (grid double-buffering),
    the (u_block, n_pad) int32 comparator tile, the rank/pos scratch and
    order output rows (int32), and the violation cell.
    """
    adj = 2 * n_pad * n_pad                       # int8, double-buffered
    comparator = min(u_block, n_pad) * n_pad * 4  # (U, N) int32 tile
    state = 3 * n_pad * 4                         # rank + pos + order rows
    return adj + comparator + state + 4


FUSED_MAX_NPAD: int = max(
    (b for b in ENGINE_NPAD_BUCKETS if fused_vmem_bytes(b) <= TPU_VMEM_BYTES),
    default=ENGINE_NPAD_BUCKETS[0],
)
# 2048 with the default grids: 2·4 MB adjacency + 4 MB comparator tile
# (512·2048·4 B) + ~24 KB state ≈ 12.6 MB fits the 16 MB budget; 4096
# (2·16 MB adjacency alone) does not. Bigger buckets take the split
# (LexBFS + two-kernel PEO) pipeline instead — see DESIGN.md §11.


def fused_witness_vmem_bytes(n_pad: int, u_block: int = 512) -> int:
    """VMEM bytes for the fused *witness* kernel program at ``n_pad``.

    The witness variant (DESIGN.md §12) streams one extra (n_pad, n_pad)
    int8 output — the per-vertex LN membership rows, double-buffered like
    the adjacency input — plus the parent row and the 3-cell triple on
    top of the verdict kernel's footprint.
    """
    ln_out = 2 * n_pad * n_pad                    # int8, double-buffered
    extra = n_pad * 4 + 3 * 4                     # parent row + triple
    return fused_vmem_bytes(n_pad, u_block) + ln_out + extra


FUSED_WITNESS_MAX_NPAD: int = max(
    (b for b in ENGINE_NPAD_BUCKETS
     if fused_witness_vmem_bytes(b) <= TPU_VMEM_BYTES),
    default=ENGINE_NPAD_BUCKETS[0],
)
# 1024 with the default grids: the 2 MB LN output block joins the 2 MB
# adjacency + 2 MB comparator tile well under budget at 1024, while 2048
# (8 MB adjacency + 8 MB LN + 4 MB comparator) blows it. Bigger certified
# buckets fall back to the batch-major jnp witness executable.


FUSED_PACK_FACTOR: int = 8
# Graphs per packed program: tiny buckets pack G block-diagonal units into
# one grid step so the (B/G,) grid amortizes launch/pipeline overhead.

FUSED_PACK_MAX_NPAD: int = 64
# Packing pays off only while G adjacency blocks stay trivially VMEM-
# resident and the per-step argmax stays lane-parallel; 64 is the largest
# bucket where G=8 blocks plus state stay under ~1% of the VMEM budget.


def fused_packed_vmem_bytes(
    n_pad: int, pack: int = FUSED_PACK_FACTOR, u_block: int = 512
) -> int:
    """VMEM bytes for one packed program: ``pack`` block-diagonal graphs.

    Every term of :func:`fused_vmem_bytes` scales by the pack factor —
    the (G, n_pad, n_pad) adjacency block, (G, n_pad) state rows, and the
    (G, U, n_pad) comparator tile.
    """
    adj = 2 * pack * n_pad * n_pad
    comparator = pack * min(u_block, n_pad) * n_pad * 4
    state = 3 * pack * n_pad * 4
    return adj + comparator + state + 4 * pack


# ---------------------------------------------------------------------------
# Recognition subsystem (DESIGN.md §13): host-side AT-scan memory plan.
#
# The interval property's asteroidal-triple scan is a host finalizer
# (repro.recognition.sweeps.at_free_numpy). Its triple pass would build
# N³-bool temporaries if done naively, so it chunks the z axis: each block
# materializes a few (chunk, N, N) bool planes and nothing larger.
# ---------------------------------------------------------------------------
INTERVAL_TRIPLE_CHUNK: int = 64
# 64 rows/block keeps the peak at ~3·64·N² bools — 200 MB at N = 1024,
# i.e. host-RAM-bound like the witness finalizers, never N³.


def interval_triple_scan_bytes(
    n_pad: int, chunk: int = INTERVAL_TRIPLE_CHUNK
) -> int:
    """Peak temporary bytes of one AT triple-scan block at ``n_pad``.

    Three (chunk, n_pad, n_pad) bool membership planes (the per-complement
    pair masks) plus the (n_pad, n_pad) int64 component-label table.
    """
    planes = 3 * min(chunk, n_pad) * n_pad * n_pad
    labels = n_pad * n_pad * 8
    return planes + labels


def engine_deg_bucket(deg: int, n_pad: int) -> int:
    """Power-of-two bucket for the padded max row degree, capped at n_pad.

    deg_pad sizes the fixed neighbor window the CSR LexBFS slices per
    visited vertex; the cap holds because a simple graph's degree is < N.
    """
    if deg < 0:
        raise ValueError(f"degree must be non-negative, got {deg}")
    b = ENGINE_DEG_MIN_BUCKET
    while b < deg:
        b <<= 1
    return min(b, max(n_pad, 1))

