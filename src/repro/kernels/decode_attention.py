"""Pallas TPU decode attention: one query token vs a long KV cache.

Grid ``(B, KV, num_kv_blocks)`` — cache blocks innermost with the
flash-combine carry in VMEM scratch. Each step processes the whole GQA
group at once: the q block is ``[G, hd]`` (all query heads sharing one KV
head), so the MXU sees ``(G x hd) @ (hd x bs)`` tiles instead of degenerate
single-row matmuls.

``lengths`` rides in scalar-prefetch (SMEM) and serves two purposes:

- inside a block it masks cache slots past the per-sequence length;
- it makes the kernel *length-aware*: KV blocks wholly past a sequence's
  length are skipped. The k/v index maps clamp the block index to the last
  block that holds any valid entry for this sequence (a revisited block
  issues no new DMA), and the block body is ``pl.when``-guarded so the
  skipped iterations do no compute. Decode cost is therefore proportional
  to the actual context length, not ``max_seq``. Skipping is numerically
  exact: a fully-masked trailing block contributes ``alpha == 1`` and
  ``p == exp(NEG_INF - m) == 0`` to the flash combine, i.e. nothing.

``lengths`` must be >= 1 (the engine always passes ``cache_len + 1``); a
zero length would skip every block and emit zeros.

This kernel is the per-shard body of the context-parallel decode path: on
a sequence-sharded cache each shard runs it over its local slice and the
(m, l, acc) partials combine with small collectives (the pure-jnp path
lets GSPMD derive the same combine).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]

    # length-aware skip: blocks wholly past this sequence's length do no
    # compute (their k/v index maps also re-fetch the last valid block, so
    # they issue no DMA either)
    @pl.when(ki * bs < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)        # [bs, hd]
        v = v_ref[0, 0].astype(jnp.float32)        # [bs, hd]
        hd = q.shape[-1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * hd ** -0.5

        pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def _paged_decode_kernel(bt_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, bs):
    """Paged variant: same flash-combine body as :func:`_decode_kernel`,
    but the KV blocks arrive via the block-table lookup in the index maps
    (``bt_ref`` rides scalar prefetch next to ``lengths``). ``bs`` is the
    page size, so one grid step consumes exactly one pool page."""
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]

    # length-aware skip, identical to the linear kernel: pages wholly past
    # this sequence's length re-request the last valid page (no DMA) and
    # do no compute
    @pl.when(ki * bs < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)        # [bs, hd]
        v = v_ref[0, 0].astype(jnp.float32)        # [bs, hd]
        hd = q.shape[-1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * hd ** -0.5

        pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                           interpret=False):
    """Block-table decode attention over a shared KV page pool.

    q [B, H, hd]; k_pool, v_pool [N, P, KV, hd] (N pages of P tokens);
    block_table [B, nb] maps each sequence's page index to a pool page
    (entries >= N mark unallocated pages — only reachable for positions
    past the sequence length, where the clamped index map's data is
    masked anyway); lengths [B] -> [B, H, hd].

    Grid ``(B, KV, nb)`` — one grid step per page, with the same
    length-aware skipping as the linear kernel: decode cost scales with
    the sequence's *actual* page count, not the table width.
    """
    B, H, hd = q.shape
    N, P, KV = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    nb = block_table.shape[1]
    assert H % KV == 0
    G = H // KV

    qg = q.reshape(B, KV, G, hd)
    kt = jnp.swapaxes(k_pool, 1, 2)                # [N, KV, P, hd]
    vt = jnp.swapaxes(v_pool, 1, 2)

    def kv_index(b, h, ki, bt_ref, lens_ref):
        # clamp to the last page holding a valid entry, then translate
        # through the block table; a revisited page issues no new DMA
        last = jnp.maximum((lens_ref[b] + P - 1) // P - 1, 0)
        page = jnp.minimum(ki, last)
        blk = jnp.clip(bt_ref[b, page], 0, N - 1)  # sentinel -> any page
        return (blk, h, 0, 0)

    grid = (B, KV, nb)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=P),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, P, hd), kv_index),
                pl.BlockSpec((1, 1, P, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, ki, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, H, hd)


def decode_attention(q, k, v, lengths, *, bs=256, interpret=False):
    """q [B, H, hd]; k, v [B, S, KV, hd]; lengths [B] -> [B, H, hd]."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    assert S % bs == 0, (S, bs)

    qg = q.reshape(B, KV, G, hd)
    kt = jnp.swapaxes(k, 1, 2)                     # [B, KV, S, hd]
    vt = jnp.swapaxes(v, 1, 2)

    def kv_index(b, h, ki, lens_ref):
        # clamp to the last block holding a valid entry for sequence b:
        # iterations past it re-request the same block (no new DMA) and the
        # body's pl.when guard skips their compute
        last = jnp.maximum((lens_ref[b] + bs - 1) // bs - 1, 0)
        return (b, h, jnp.minimum(ki, last), 0)

    grid = (B, KV, S // bs)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs),
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bs, hd), kv_index),
                pl.BlockSpec((1, 1, bs, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, H, hd)
