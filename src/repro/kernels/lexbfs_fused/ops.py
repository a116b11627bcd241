"""Jit'd public wrappers around the fused LexBFS+PEO Pallas kernel.

``lexbfs_peo_fused(adjs)`` maps a (B, N, N) bool work unit to
``(verdicts (B,), orders (B, N), violations (B,))`` in **one device
dispatch** — the whole per-bucket hot path behind a single ``pallas_call``
(grid over the batch). Orders are bit-identical to every other LexBFS in
the repo; verdicts to every PEO test (asserted in
tests/test_lexbfs_fused.py).

``interpret=None`` (default) resolves through
:func:`repro.kernels.resolve_interpret`: interpreted on CPU hosts,
compiled by Mosaic on a TPU. The module-level :data:`dispatch_counter` ticks once
per host-level launch — benchmarks read it to report measured
dispatches-per-unit (``BENCH_kernels.json``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch_counter, resolve_interpret
from repro.kernels.lexbfs_fused.lexbfs_fused import (
    compaction_block,
    lexbfs_peo_fused_call,
    lexbfs_peo_fused_packed_call,
    lexbfs_peo_fused_witness_call,
)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused(adjs: jnp.ndarray, *, interpret: bool):
    from repro.core.lexbfs import lexbfs_inner_block

    n = adjs.shape[1]
    orders, viols = lexbfs_peo_fused_call(
        adjs.astype(jnp.int8),
        k_inner=lexbfs_inner_block(n),
        u_block=compaction_block(n),
        interpret=interpret,
    )
    return viols[:, 0] == 0, orders, viols[:, 0]


def lexbfs_peo_fused(adjs: jnp.ndarray, *,
                     interpret: Optional[bool] = None):
    """(B, N, N) bool -> (verdicts (B,), orders (B, N), violations (B,)).

    One ``pallas_call`` per call — the one-dispatch-per-bucket contract
    the ``pallas_peo`` backend's ``pipeline="fused"`` serves.
    """
    dispatch_counter.tick()
    return _fused(adjs, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_witness(adjs: jnp.ndarray, *, interpret: bool):
    from repro.core.lexbfs import lexbfs_inner_block

    n = adjs.shape[1]
    orders, viols, ln, parent, triple = lexbfs_peo_fused_witness_call(
        adjs.astype(jnp.int8),
        k_inner=lexbfs_inner_block(n),
        u_block=compaction_block(n),
        interpret=interpret,
    )
    return viols[:, 0] == 0, orders, viols[:, 0], ln, parent, triple


def lexbfs_peo_fused_witness(adjs: jnp.ndarray, *,
                             interpret: Optional[bool] = None):
    """(B, N, N) bool -> (verdicts, orders, violations, ln, parent, triple).

    The certified hot path: one ``pallas_call`` emits the verdict *and*
    the certificate raw material (per-vertex LN rows, parent pointers,
    latest violating triple) — ``witness=True`` traffic costs the same
    single dispatch as verdict-only. Host finalization lives in
    ``repro.witness.witness_batch_from_fused_raw``.
    """
    dispatch_counter.tick()
    return _fused_witness(adjs, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("pack", "interpret"))
def _fused_packed(adjs: jnp.ndarray, *, pack: int, interpret: bool):
    from repro.core.lexbfs import lexbfs_inner_block

    n = adjs.shape[1]
    orders, viols = lexbfs_peo_fused_packed_call(
        adjs.astype(jnp.int8),
        pack=pack,
        k_inner=lexbfs_inner_block(n),
        u_block=compaction_block(n),
        interpret=interpret,
    )
    return viols[:, 0] == 0, orders, viols[:, 0]


def lexbfs_peo_fused_packed(
    adjs: jnp.ndarray, *, pack: int = 0, interpret: Optional[bool] = None
):
    """Packed tiny-bucket dispatch: G graphs per grid program.

    Same outputs as :func:`lexbfs_peo_fused`; the batch is padded up to a
    multiple of the pack factor with empty (trivially chordal) graphs and
    cropped back. Still one ``pallas_call`` — the dispatch counter ticks
    once regardless of grid size.
    """
    from repro.configs.shapes import FUSED_PACK_FACTOR

    g = pack or FUSED_PACK_FACTOR
    b = adjs.shape[0]
    b_pad = -(-b // g) * g
    if b_pad != b:
        adjs = jnp.concatenate(
            [adjs, jnp.zeros((b_pad - b,) + adjs.shape[1:], adjs.dtype)],
            axis=0)
    dispatch_counter.tick()
    verdicts, orders, viols = _fused_packed(
        adjs, pack=g, interpret=resolve_interpret(interpret))
    return verdicts[:b], orders[:b], viols[:b]
