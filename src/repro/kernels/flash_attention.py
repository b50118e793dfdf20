"""Pallas TPU flash attention (forward) with GQA, causal and windowed masks.

Grid: ``(batch * kv_heads, num_q_blocks, num_kv_blocks)``, kv innermost so
the online-softmax carry (m, l, acc) lives in VMEM scratch across kv steps.
One step takes the G query heads that share a KV head together: a
``[G * bq, hd]`` q tile against one ``[bkv, hd]`` K/V tile, so each K/V
block is fetched once per group, not once per query head.

Blocks follow the shapes (``block_sizes``): about 2048 q rows a step at
head_dim 128 (fewer for wider heads), never more rows than the padded
prompt has (a 16-token prompt takes a 16-row block), and kv blocks of up
to 1024 columns, narrower when a sliding window is shorter.

Masked blocks: a (q block, kv block) pair that lies wholly above the
causal diagonal, wholly left of the window or wholly in the padded kv
tail does no work, and the K/V index map clamps it to the nearest needed
block, so the resident block is re-used and no DMA is issued. Only
blocks that cross the diagonal, the window's edge or the kv tail build
the position mask; interior blocks skip it.

Numerics: q·kᵀ takes the operands in the dtype they arrive in (bf16 on
the MXU for bf16 inputs) with f32 accumulation. The softmax state, the
exponentials and the scaling are f32, and p·v takes p in f32 at the
MXU's default precision, which on a TPU v5e rounds f32 operands to bf16
in one pass (on the chip, bitwise what a bf16 cast of p gives; the
f32-upcast kernel this one replaced did the same). Masked positions get
-1e30 before the running max; rows that no computed block reaches emit
zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_Q_ROWS = 2048          # G * bq rows a step at head_dim 128
_MAX_BQ = 512
_MAX_BKV = 1024
_VMEM_LIMIT = 64 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_sizes(Sq: int, Skv: int, G: int, hd: int, window=None):
    """(bq, bkv) for a [Sq] x [Skv] problem with G query heads per KV head.

    bq is a multiple of 16 (the bf16 sublane tile, so [G, bq, hd] folds
    into [G * bq, hd] for free) and at most the prompt rounded up to 16;
    bkv is a multiple of 128 (the lane width of the score tile)."""
    rows = max(16, _Q_ROWS * 128 // hd)
    bq = max(16, min(_MAX_BQ, rows // G) // 16 * 16)
    bq = min(bq, _round_up(Sq, 16))
    bkv = _MAX_BKV if window is None else min(_MAX_BKV, _round_up(window, 128))
    bkv = min(bkv, _round_up(Skv, 128))
    return bq, bkv


def _kv_range(qi, *, causal, window, bq, bkv, q_offset, kv_len):
    """First and last kv block that q block ``qi`` needs (hi < lo: none)."""
    lo = jnp.int32(0)
    hi = jnp.int32((kv_len - 1) // bkv)
    if causal:
        hi = jnp.minimum(hi, (q_offset + (qi + 1) * bq - 1) // bkv)
    if window is not None:
        lo = jnp.maximum(lo, (q_offset + qi * bq - window + 1) // bkv)
    return lo, hi


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, causal, window, G, bq, bkv, q_offset, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    hd = q_ref.shape[-1]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(masked: bool):
        q = q_ref[0].reshape(G * bq, hd)              # [G*bq, hd], input dtype
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = (q_offset + qi * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
            k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            mask = k_pos < kv_len
            if causal:
                mask &= q_pos >= k_pos
            if window is not None:
                mask &= (q_pos - k_pos) < window
            s = jnp.where(mask[None], s.reshape(G, bq, bkv), NEG_INF)
            s = s.reshape(G * bq, bkv)

        m_prev = m_ref[...]                            # [G*bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # [G*bq, bkv] f32
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    lo, hi = _kv_range(qi, causal=causal, window=window, bq=bq, bkv=bkv,
                       q_offset=q_offset, kv_len=kv_len)
    run = (ki >= lo) & (ki <= hi)
    q_first = q_offset + qi * bq
    edges = []                                     # this block needs a mask
    if kv_len % bkv:
        edges.append(ki == (kv_len - 1) // bkv)
    if causal:
        edges.append((ki + 1) * bkv - 1 > q_first)
    if window is not None:
        edges.append(q_first + bq - 1 - ki * bkv >= window)
    if edges:
        edge = functools.reduce(jnp.logical_or, edges)
        pl.when(run & edge)(functools.partial(update, masked=True))
        pl.when(run & jnp.logical_not(edge))(
            functools.partial(update, masked=False))
    else:
        pl.when(run)(functools.partial(update, masked=False))

    @pl.when(ki == nk - 1)
    def _finish():
        # rows no computed block reached have l == 0 -> emit zeros
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).reshape(G, bq, hd).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    bq=None, bkv=None, kv_len=None, interpret=False):
    """q [B, H, Sq, hd]; k, v [B, KV, Skv, hd] -> [B, H, Sq, hd].

    GQA: H = KV * G, query head h reads KV head h // G. ``kv_len`` masks
    padded kv columns (defaults to Skv). Blocks default to
    ``block_sizes``; Sq/Skv must be divisible by bq/bkv (ops.py pads).
    """
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    kv_len = Skv if kv_len is None else kv_len
    assert H % KV == 0, (H, KV)
    G = H // KV
    auto_bq, auto_bkv = block_sizes(Sq, Skv, G, hd, window)
    bq = bq or auto_bq
    bkv = bkv or auto_bkv
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)
    scale = hd ** -0.5

    qf = q.reshape(B * KV, G, Sq, hd)
    kf = k.reshape(B * KV, Skv, hd)
    vf = v.reshape(B * KV, Skv, hd)

    kv_range = functools.partial(_kv_range, causal=causal, window=window,
                                 bq=bq, bkv=bkv, q_offset=q_offset,
                                 kv_len=kv_len)

    def kv_index(g, qi, ki):
        # skipped steps re-use the block a needed step left resident
        lo, hi = kv_range(qi)
        j = jnp.clip(jnp.minimum(jnp.maximum(ki, lo), hi), 0, Skv // bkv - 1)
        return g, j, 0

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window, G=G,
        bq=bq, bkv=bkv, q_offset=q_offset, kv_len=kv_len)

    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B * KV, Sq // bq, Skv // bkv),
        in_specs=[
            pl.BlockSpec((1, G, bq, hd), lambda g, qi, ki: (g, 0, qi, 0)),
            pl.BlockSpec((1, bkv, hd), kv_index),
            pl.BlockSpec((1, bkv, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, G, bq, hd), lambda g, qi, ki: (g, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((G * bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((G * bq, hd), jnp.float32),  # running numerator acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd)
