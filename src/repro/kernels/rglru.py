"""Pallas TPU RG-LRU linear-recurrence kernel.

Grid ``(B, W / bw, S / bt)`` — time innermost; the hidden state carries in
VMEM scratch across time blocks, so HBM sees each (a, b) element exactly
once (the recurrence is memory-bound: 2 reads + 1 write per element). The
channel (W) dimension is blocked to the VPU lane width; the within-block
time loop is sequential (the recurrence's data dependence), which on TPU
pipelines against the next block's DMA.

Inputs are the precomputed per-step decay ``a`` and drive ``b`` (see
models/rglru.py::_gates); h0 allows chunked prefill continuation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref, carry_ref, *, bt):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        carry_ref[...] = h0_ref[0]

    a = a_ref[0]                                    # [bt, bw] f32
    b = b_ref[0]
    h = carry_ref[...]                              # [1, bw]

    def step(t, carry):
        h, out = carry
        h = a[t][None, :] * h + b[t][None, :]
        out = jax.lax.dynamic_update_slice(out, h, (t, 0))
        return h, out

    out0 = jnp.zeros_like(a)
    h, out = jax.lax.fori_loop(0, bt, step, (h, out0))
    h_ref[0] = out
    carry_ref[...] = h

    @pl.when(ti == nt - 1)
    def _finish():
        hlast_ref[0] = h


def rglru_scan(a, b, h0=None, *, bt=128, bw=512, interpret=False):
    """a, b [B, S, W] f32; h0 [B, W] -> (h [B, S, W], h_last [B, W])."""
    B, S, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    bw = min(bw, W)
    bt = min(bt, S)
    assert S % bt == 0 and W % bw == 0, (S, bt, W, bw)

    grid = (B, W // bw, S // bt)
    h, hlast = pl.pallas_call(
        functools.partial(_rglru_kernel, bt=bt),
        name="rglru_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, 1, bw), lambda bi, wi, ti: (bi, 0, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, 1, bw), lambda bi, wi, ti: (bi, 0, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
    return h, hlast[:, 0]
