"""Pallas TPU RWKV-6 WKV kernel (data-dependent-decay linear attention).

Grid ``(B * H, T / bt)`` — time blocks innermost-only; the [N, N] state
matrix carries in VMEM scratch across blocks (N = 64 -> 16 KB f32, far
under VMEM). Within a block the recurrence is sequential (true data
dependence through the per-channel decay); each step is rank-1 outer
product + matvec on the VPU/MXU.

Layout note: inputs arrive as [B, H, T, N] (ops.py transposes from the
model's [B, T, H, N]) so that a (bh, ti) grid cell reads a contiguous
[bt, N] tile — one DMA per operand per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sout_ref, s_ref, *, bt):
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)               # [bt, N]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)[:, None]      # [N, 1] (broadcast over j)

    def step(t, carry):
        s, y = carry                               # s [N, N], y [bt, N]
        kt = k[t][:, None]                         # [N, 1]
        vt = v[t][None, :]                         # [1, N]
        kv = kt * vt                               # [N, N]
        yt = (r[t][None, :] @ (s + u * kv))        # [1, N]
        y = jax.lax.dynamic_update_slice(y, yt, (t, 0))
        s = w[t][:, None] * s + kv
        return s, y

    s, y = jax.lax.fori_loop(
        0, bt, step, (s_ref[...], jnp.zeros_like(r)))
    y_ref[0] = y.astype(y_ref.dtype)
    s_ref[...] = s

    @pl.when(ti == nt - 1)
    def _finish():
        sout_ref[0] = s


def wkv_scan(r, k, v, w, u, s0=None, *, bt=128, interpret=False):
    """r/k/v/w [B, H, T, N] f32; u [H, N]; s0 [B, H, N, N] ->
    (y [B, H, T, N], s_final [B, H, N, N])."""
    B, H, T, N = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, N, N), jnp.float32)
    bt = min(bt, T)
    assert T % bt == 0, (T, bt)

    rf = r.reshape(B * H, T, N)
    kf = k.reshape(B * H, T, N)
    vf = v.reshape(B * H, T, N)
    wf = w.reshape(B * H, T, N)
    sf = s0.reshape(B * H, N, N)

    grid = (B * H, T // bt)
    y, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, bt=bt),
        name="rwkv6_wkv",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, N), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, bt, N), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, bt, N), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, bt, N), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, N), lambda bh, ti, H=H: (bh % H, 0)),
            pl.BlockSpec((1, N, N), lambda bh, ti: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, N), lambda bh, ti: (bh, ti, 0)),
            pl.BlockSpec((1, N, N), lambda bh, ti: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, N), r.dtype),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, u, sf)
    return y.reshape(B, H, T, N), s_out.reshape(B, H, N, N)
