"""Pallas TPU flash attention: blocked online softmax with causal /
sliding-window masking and gemma2-style logit softcapping.

Grid: (batch*q_heads, q_blocks, kv_blocks) — the kv dimension is the
innermost (sequential) axis; running max/denominator/accumulator live in VMEM
scratch and persist across kv steps (standard TPU flash pattern). GQA is
handled by an index map: kv tensors are laid out (batch*kv_heads, S, hd) and
q head ``h`` reads kv head ``h // group_size``.

Block sizes default to 128 (MXU tile) — q block (128, hd), k/v blocks
(128, hd), f32 accumulator (128, hd): ~4 * 128 * hd * 4B of VMEM, well under
the ~16 MB/core budget for hd <= 256.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                      # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)                      # (BK, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (BQ, 128)
    m_cur = jnp.max(s, axis=1, keepdims=True)             # (BQ, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])         # (BQ, 1)
    p = jnp.exp(s - m_new[:, :1])                         # (BQ, BK)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, group_size: int = 1, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """q: (BH, Sq, hd); k, v: (BH // group_size, Skv, hd).
    Returns (BH, Sq, hd). Positions are 0-based within each tensor; causal
    masking assumes Sq == Skv (training/prefill self-attention)."""
    bh, sq, hd = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    kv_steps = skv // bk
    grid = (bh, sq // bq, kv_steps)
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, kv_steps=kv_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda h, i, j, gs=group_size: (h // gs, j, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda h, i, j, gs=group_size: (h // gs, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
        ],
        interpret=jax.default_backend() == "cpu",
    )(q, k, v)
