"""Jit'd public wrappers around the peo_check Pallas kernels.

``peo_check_pallas(adj, order)`` is a drop-in replacement for
``repro.core.peo.peo_check`` that never materializes an N×N boolean
intermediate in HBM: parents are computed by a blockwise argmax kernel, the
parent rows ``Adj[p]`` are gathered once (XLA gather), and the violation
count is a fused blockwise masked reduce.

``interpret=None`` (default) resolves through
:func:`repro.kernels.resolve_interpret`: interpreted on CPU hosts,
compiled by Mosaic on a TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.peo_check.peo_check import (
    peo_parents_pallas,
    peo_violations_pallas,
)


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_z", "interpret")
)
def peo_violations_count(
    adj: jnp.ndarray,
    order: jnp.ndarray,
    *,
    block_v: int = 128,
    block_z: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    n = adj.shape[0]
    adj_i8 = adj.astype(jnp.int8)
    pos = (
        jnp.zeros(n, dtype=jnp.int32)
        .at[order]
        .set(jnp.arange(n, dtype=jnp.int32))
    )
    p, _ = peo_parents_pallas(
        adj_i8, pos, block_v=block_v, block_z=block_z, interpret=interpret
    )
    adjp_i8 = jnp.take(adj_i8, p, axis=0)  # (N, N) row gather — once
    return peo_violations_pallas(
        adj_i8, adjp_i8, pos, p,
        block_v=block_v, block_z=block_z, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_z", "interpret")
)
def peo_check_pallas(
    adj: jnp.ndarray,
    order: jnp.ndarray,
    *,
    block_v: int = 128,
    block_z: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """True iff ``order`` is a PEO of ``adj`` (Pallas-fused path)."""
    return (
        peo_violations_count(
            adj, order,
            block_v=block_v, block_z=block_z, interpret=interpret,
        )
        == 0
    )
