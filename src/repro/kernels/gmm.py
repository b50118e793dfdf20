"""Pallas TPU grouped matmul (MoE expert compute): [E,C,d] @ [E,d,f].

Grid ``(E, C/bc, f/bf, d/bd)`` with the contraction blocks innermost and an
f32 accumulator tile in VMEM scratch — the canonical MXU matmul schedule,
batched over experts. This is the hot spot of the scatter-dispatch MoE
path (models/moe.py); the dispatch/combine gathers stay in XLA where they
fuse with the surrounding layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref):
    di = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def gmm(x, w, *, bc=128, bf=128, bd=256, interpret=False):
    """x [E, C, d]; w [E, d, f] -> [E, C, f]."""
    E, C, d = x.shape
    f = w.shape[2]
    bc, bf, bd = min(bc, C), min(bf, f), min(bd, d)
    assert C % bc == 0 and f % bf == 0 and d % bd == 0, (C, bc, f, bf, d, bd)

    grid = (E, C // bc, f // bf, d // bd)
    out = pl.pallas_call(
        _gmm_kernel,
        name="gmm",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((1, bd, bf), lambda e, ci, fi, di: (e, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, ci, fi, di: (e, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out
