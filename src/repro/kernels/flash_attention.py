"""Pallas TPU flash-attention kernel (blockwise online softmax).

LISA mapping: KV blocks stream through VMEM like row buffers through the
LISA links — the Pallas grid pipeline double-buffers the next KV block's DMA
against the current block's MXU work (the LISA-LIP idle-resource-recruitment
property, DESIGN.md Sec. 5.4).

Layout: q (B, H, S, D), k/v (B, K, T, D) with H = K*G (GQA: the index map
routes each q-head block to its kv head — no KV broadcast in HBM).
Causal and sliding-window masks are applied from block coordinates; fully
masked blocks skip their FLOPs via ``pl.when``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lowered_for_platform

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, seq_q: int, seq_kv: int,
            causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level mask decision: q rows are at the *tail* of the kv sequence
    # (cache layout), so q_pos = ki_offset + (seq_kv - seq_q).
    q_off = qi * block_q + (seq_kv - seq_q)
    k_off = ki * block_k
    fully_masked = False
    if causal:
        fully_masked = k_off > q_off + block_q - 1
    if window > 0:
        fully_masked = fully_masked | (k_off + block_k - 1 <= q_off - window)

    @pl.when(jnp.logical_not(jnp.asarray(fully_masked)))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        valid = jnp.ones((block_q, block_k), bool)
        if causal:
            valid &= k_pos <= q_pos
        if window > 0:
            valid &= k_pos > q_pos - window
        valid &= k_pos < seq_kv                            # kv padding
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + p @ v

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,H,S,D); k/v: (B,K,T,D).  Returns (B,H,S,D)."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    nq = -(-S // block_q)
    nk = -(-T // block_k)
    if S % block_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * block_q - S), (0, 0)))
    if T % block_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * block_k - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * block_k - T), (0, 0)))

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, seq_q=S, seq_kv=T,
        causal=causal, window=window, scale=D ** -0.5)

    qs = q.reshape(B * H, nq * block_q, D)
    ks = k.reshape(B * K, nk * block_k, D)
    vs = v.reshape(B * K, nk * block_k, D)

    # GQA routing: q-head block bh -> kv row (batch * K + head // G).
    kv_row = lambda bh: (bh // H) * K + (bh % H) // G

    out = lowered_for_platform(functools.partial(
        pl.pallas_call,
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (kv_row(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (kv_row(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # running sum
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ]),
        qs, ks, vs, interpret=interpret)
    return out.reshape(B, H, nq * block_q, D)[:, :, :S]
