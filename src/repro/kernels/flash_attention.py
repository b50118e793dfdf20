"""Pallas TPU flash attention (forward) with GQA, causal and windowed masks.

Grid: ``(batch * q_heads, num_q_blocks, num_kv_blocks)`` — kv innermost so
the online-softmax carry (m, l, acc) lives in VMEM scratch across kv steps.
Block shapes are MXU-aligned (q/kv blocks multiples of 128 where the
problem allows; head_dim is kept whole).

This is the TPU adaptation of the serving/prefill hot spot: HBM->VMEM
tiling replaces the GPU shared-memory tiling of standard FlashAttention,
and the MXU consumes (bq x hd) @ (hd x bkv) tiles directly.

Numerics: f32 accumulation regardless of input dtype; masked positions get
-1e30 before the running max.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, causal, window, bq, bkv, q_offset, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # [bq, hd]
    k = k_ref[0].astype(jnp.float32)              # [bkv, hd]
    v = v_ref[0].astype(jnp.float32)              # [bkv, hd]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = k_pos < kv_len                          # padded kv columns
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # [bq, bkv]

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        # fully-masked rows (e.g. padding) have l == 0 -> emit zeros
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    bq=128, bkv=128, kv_len=None, interpret=False):
    """q [B, H, Sq, hd]; k, v [B, KV, Skv, hd] -> [B, H, Sq, hd].

    GQA: H = KV * G; kv block index maps h -> h // G. ``kv_len`` masks
    padded kv columns (defaults to Skv). Sq/Skv must be divisible by bq/bkv
    (ops.py pads).
    """
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    kv_len = Skv if kv_len is None else kv_len
    assert H % KV == 0, (H, KV)
    G = H // KV
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)
    scale = hd ** -0.5

    qf = q.reshape(B * H, Sq, hd)
    kf = k.reshape(B * KV, Skv, hd)
    vf = v.reshape(B * KV, Skv, hd)

    grid = (B * H, Sq // bq, Skv // bkv)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bkv=bkv, q_offset=q_offset, kv_len=kv_len)

    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bkv, hd), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, bkv, hd), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),  # running numerator acc
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd)
