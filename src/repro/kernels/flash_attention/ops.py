"""Jit'd batched/GQA wrapper around the flash attention kernel.

``flash_attention(q, k, v)`` with
  q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D), Hq % Hkv == 0
vmaps the single-head kernel over batch and heads, repeating kv heads per
GQA group. This is the TPU-target path; the model code selects between this
kernel (``attention_impl="pallas"``), a chunked-scan XLA implementation, and
the naive reference depending on platform/size (see repro.models.attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_single_head,
)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, f"GQA requires Hq % Hkv == 0, got {hq} % {hkv}"
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)

    fn = functools.partial(
        flash_attention_single_head,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        interpret=interpret,
    )
    return jax.vmap(jax.vmap(fn))(q, kr, vr)
