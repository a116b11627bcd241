"""Blockwise (flash) attention Pallas TPU kernel.

Used by the LM architectures for training and 32k prefill: materializing the
(S, S) score matrix at 32k sequence length is ~4 GB bf16 per head — blockwise
online softmax keeps the working set at (BQ, BKV) in VMEM.

Features: causal masking, sliding-window attention (h2o-danube), GQA handled
by the wrapper (q heads grouped onto kv heads). fp32 accumulation regardless
of input dtype. Block sizes default to (512, 512) — MXU-aligned (multiples
of 128) and small enough that q/k/v/acc blocks fit VMEM comfortably:
3·(512·128)·2B + (512·512)·4B ≈ 1.4 MB ≪ 16 MB v5e VMEM.

Grid: (num_q_blocks, num_kv_blocks), kv fastest. Running (m, l, acc) live in
VMEM scratch and persist across the kv sweep of one q block (TPU grid is
sequential). Causal + window skipping is done both at block granularity
(``pl.when`` — whole-block skip) and elementwise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def _flash_kernel(
    causal, window, scale, seq_kv,
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr
):
    """One (q-block, kv-block) step.

    q_ref: (BQ, D); k_ref/v_ref: (BKV, D); o_ref: (BQ, D)
    m_scr/l_scr: (BQ, 1) f32; acc_scr: (BQ, D) f32
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    nkv = pl.num_programs(1)
    bq = q_ref.shape[0]
    bkv = k_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * bq
    kv_start = j * bkv

    # Block-level relevance: skip kv blocks fully masked out.
    #   causal: kv_start > q_end  -> skip
    #   window: kv_end <= q_start - window -> skip
    q_end = q_start + bq - 1
    kv_end = kv_start + bkv - 1
    relevant = jnp.bool_(True)
    if causal:
        relevant = relevant & (kv_start <= q_end)
    if window is not None:
        relevant = relevant & (kv_end >= q_start - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        # Ragged edge: zero padded kv rows. Padded lanes may be NaN (interpret
        # mode pads with NaN on purpose) and 0·NaN = NaN in the p@v matmul.
        kv_valid = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (bkv, 1), 0
        ) < seq_kv
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BKV)

        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kv_ids = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_ids < seq_kv  # ragged edge
        if causal:
            mask = mask & (kv_ids <= q_ids)
        if window is not None:
            mask = mask & (kv_ids > q_ids - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows (all NEG_INF): keep exp at 0.
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == nkv - 1)
    def _finalize():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


def flash_attention_single_head(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (Sq, D), k/v: (Skv, D) -> (Sq, D). Assumes Sq == Skv offsets
    aligned (self-attention; decode uses the XLA path, not this kernel)."""
    sq, d = q.shape
    skv = k.shape[0]
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    grid = (pl.cdiv(sq, block_q), pl.cdiv(skv, block_kv))
    kernel = functools.partial(
        _flash_kernel, causal, window, scale, skv
    )
    import jax.experimental.pallas.tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_kv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_kv, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
