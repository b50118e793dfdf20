"""Jit'd public wrappers around the Pallas kernels.

Each op pads inputs to kernel block multiples, dispatches to the Pallas
kernel or the pure-jnp oracle, and unpads. The backend follows the
platform: ``pallas`` when JAX's default backend is a TPU, ``ref`` (the
oracle) everywhere else. It is decided when the first kernel dispatch
asks for it, never at import, because importing must not initialise JAX.

``set_backend("pallas" | "ref" | "interpret")`` is a test hook: tests use
it to force interpret mode through real model code, or to compile the
Pallas path for a described TPU from a CPU process. A caller that switches
the backend restores the previous value (``set_backend(None)`` returns to
following the platform).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.decode_attention import (
    paged_decode_attention as _paged_decode_pallas,
)
from repro.kernels.flash_attention import block_sizes as _flash_blocks
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.gmm import gmm as _gmm_pallas
from repro.kernels.rglru import rglru_scan as _rglru_pallas
from repro.kernels.rwkv6 import wkv_scan as _wkv_pallas

_BACKENDS = ("pallas", "ref", "interpret")
_BACKEND: Optional[str] = None      # None: not decided yet


def set_backend(name: Optional[str]):
    """Force the kernel backend (test hook); ``None`` follows the platform
    again. Model code reads the backend at trace time, so a change reaches
    only programs traced after it."""
    global _BACKEND
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    global _BACKEND
    if _BACKEND is None:
        _BACKEND = "pallas" if jax.default_backend() == "tpu" else "ref"
    return _BACKEND


@contextlib.contextmanager
def use_backend(name: str):
    """Run the body with ``name`` forced, then restore the previous
    backend (test hook)."""
    prev = _BACKEND
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def _pad_to(x, axis: int, multiple: int, value=0.0):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """q [B, H, Sq, hd]; k, v [B, KV, Skv, hd] -> [B, H, Sq, hd]."""
    if get_backend() == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)
    (_, H, Sq, hd), (KV, Skv) = q.shape, k.shape[1:3]
    bq, bkv = _flash_blocks(Sq, Skv, H // KV, hd, window)
    qp, _ = _pad_to(q, 2, bq)
    kp, _ = _pad_to(k, 2, bkv)
    vp, _ = _pad_to(v, 2, bkv)
    out = _flash_pallas(qp, kp, vp, causal=causal, window=window,
                        q_offset=q_offset, bq=bq, bkv=bkv, kv_len=Skv,
                        interpret=(get_backend() == "interpret"))
    return out[:, :, :Sq]


def decode_attention(q, k, v, lengths, *, bs=256):
    """q [B, H, hd]; k, v [B, S, KV, hd]; lengths [B] -> [B, H, hd]."""
    if get_backend() == "ref":
        return _ref.decode_attention_ref(q, k, v, lengths)
    S = k.shape[1]
    kp, _ = _pad_to(k, 1, bs)
    vp, _ = _pad_to(v, 1, bs)
    # padded slots have position >= S >= lengths -> masked by lengths
    return _decode_pallas(q, kp, vp, lengths, bs=min(bs, kp.shape[1]),
                          interpret=(get_backend() == "interpret"))


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths):
    """q [B, H, hd]; k_pool, v_pool [N, P, KV, hd]; block_table [B, nb];
    lengths [B] -> [B, H, hd]. Pages are already kernel-block-sized, so no
    padding is needed — the page size IS the block size."""
    if get_backend() == "ref":
        return _ref.paged_decode_attention_ref(q, k_pool, v_pool,
                                               block_table, lengths)
    return _paged_decode_pallas(q, k_pool, v_pool, block_table, lengths,
                                interpret=(get_backend() == "interpret"))


def rglru_scan(a, b, h0=None, *, bt=128, bw=512):
    """a, b [B, S, W] -> (h [B, S, W], h_last [B, W])."""
    if get_backend() == "ref":
        h = _ref.rglru_ref(a, b, h0)
        return h, h[:, -1]
    S = a.shape[1]
    ap, ps = _pad_to(a, 1, bt)
    bp, _ = _pad_to(b, 1, bt)
    h, hlast = _rglru_pallas(ap, bp, h0, bt=bt, bw=bw,
                             interpret=(get_backend() == "interpret"))
    if ps:
        # padded steps have a=0, b=0 -> h collapses to 0; true last state is
        # at S-1
        hlast = h[:, S - 1]
    return h[:, :S], hlast


def wkv_scan(r, k, v, w, u, s0=None, *, bt=128):
    """r/k/v/w [B, T, H, N]; u [H, N] -> (y [B,T,H,N], s [B,H,N,N])."""
    if get_backend() == "ref":
        return _ref.rwkv6_ref(r, k, v, w, u, s0)
    # kernel layout is [B, H, T, N]
    tr = lambda t: jnp.swapaxes(t, 1, 2)
    T = r.shape[1]
    rp, pt = _pad_to(tr(r), 2, bt)
    kp, _ = _pad_to(tr(k), 2, bt)
    vp, _ = _pad_to(tr(v), 2, bt)
    # padded steps must leave the state unchanged: decay w=1, k=0
    wp, _ = _pad_to(tr(w), 2, bt, value=1.0)
    y, s = _wkv_pallas(rp, kp, vp, wp, u, s0, bt=bt,
                       interpret=(get_backend() == "interpret"))
    return jnp.swapaxes(y[:, :, :T], 1, 2), s


def gmm(x, w, *, bc=128, bf=128, bd=256):
    """x [E, C, d]; w [E, d, f] -> [E, C, f]."""
    if get_backend() == "ref":
        return _ref.gmm_ref(x, w)
    C, d, f = x.shape[1], x.shape[2], w.shape[2]
    xp, _ = _pad_to(x, 1, bc)
    xp, _ = _pad_to(xp, 2, bd)
    wp, _ = _pad_to(w, 1, bd)
    wp, _ = _pad_to(wp, 2, bf)
    out = _gmm_pallas(xp, wp, bc=bc, bf=bf, bd=bd,
                      interpret=(get_backend() == "interpret"))
    return out[:, :C, :f]
