"""Pallas kernel sweeps (interpret=True) vs pure-jnp oracles.

Per the deliverable: every kernel sweeps shapes AND dtypes with
assert_allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.rglru import rglru_scan
from repro.kernels.rwkv6 import wkv_scan
from repro.kernels.gmm import gmm


def _rand(shape, seed, dtype=jnp.float32, scale=1.0):
    x = np.random.default_rng(seed).normal(size=shape) * scale
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,Sq,Skv,hd,causal,window", [
    (2, 4, 2, 256, 256, 64, True, None),
    (1, 8, 1, 128, 384, 128, True, None),     # MQA, rectangular
    (2, 4, 4, 256, 256, 64, False, None),     # MHA bidirectional
    (1, 2, 2, 256, 256, 64, True, 100),       # sliding window
    (1, 2, 1, 384, 384, 256, True, None),     # RG-style head_dim 256
])
def test_flash_attention_sweep(B, H, KV, Sq, Skv, hd, causal, window, dtype):
    q = _rand((B, H, Sq, hd), 1, dtype)
    k = _rand((B, KV, Skv, hd), 2, dtype)
    v = _rand((B, KV, Skv, hd), 3, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_flash_attention_padding_path():
    """ops wrapper pads ragged seq lens; padded kv must be masked."""
    with ops.use_backend("interpret"):
        q = _rand((1, 4, 100, 64), 1)
        k = _rand((1, 2, 100, 64), 2)
        v = _rand((1, 2, 100, 64), 3)
        for causal in (True, False):
            out = ops.flash_attention(q, k, v, causal=causal)
            expect = ref.attention_ref(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                       atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,hd", [
    (2, 8, 2, 512, 64),
    (1, 4, 4, 256, 128),
    (3, 16, 1, 1024, 64),
])
def test_decode_attention_sweep(B, H, KV, S, hd, dtype):
    q = _rand((B, H, hd), 1, dtype)
    k = _rand((B, S, KV, hd), 2, dtype)
    v = _rand((B, S, KV, hd), 3, dtype)
    lengths = jnp.asarray(
        np.random.default_rng(4).integers(1, S + 1, B), jnp.int32)
    out = decode_attention(q, k, v, lengths, bs=256, interpret=True)
    expect = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,W,bt,bw", [
    (2, 256, 512, 128, 512),
    (1, 512, 1024, 64, 256),
    (4, 128, 256, 128, 256),
])
def test_rglru_kernel_sweep(B, S, W, bt, bw):
    a = _rand((B, S, W), 1).__abs__().clip(0.5, 0.999)
    b = _rand((B, S, W), 2, scale=0.1)
    h0 = _rand((B, W), 3, scale=0.1)
    h, hlast = rglru_scan(a, b, h0, bt=bt, bw=bw, interpret=True)
    expect = ref.rglru_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(expect),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hlast), np.asarray(expect[:, -1]),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("B,T,H,N,bt", [
    (2, 256, 4, 64, 128),
    (1, 128, 2, 128, 64),
    (3, 64, 8, 64, 64),
])
def test_wkv_kernel_sweep(B, T, H, N, bt):
    r = _rand((B, H, T, N), 1)
    k = _rand((B, H, T, N), 2, scale=0.2)
    v = _rand((B, H, T, N), 3)
    w = _rand((B, H, T, N), 4).__abs__().clip(0.9, 0.999)
    u = _rand((H, N), 5)
    s0 = _rand((B, H, N, N), 6, scale=0.1)
    y, s = wkv_scan(r, k, v, w, u, s0, bt=bt, interpret=True)
    tr = lambda t: jnp.swapaxes(t, 1, 2)
    y_ref, s_ref = ref.rwkv6_ref(tr(r), tr(k), tr(v), tr(w), u, s0)
    np.testing.assert_allclose(np.asarray(tr(y)), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,d,f", [
    (4, 128, 256, 512),
    (8, 256, 128, 128),
    (2, 384, 512, 256),
])
def test_gmm_kernel_sweep(E, C, d, f, dtype):
    x = _rand((E, C, d), 1, dtype)
    w = _rand((E, d, f), 2, dtype)
    out = gmm(x, w, interpret=True)
    expect = ref.gmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_ops_padding_gmm():
    with ops.use_backend("interpret"):
        x = _rand((3, 60, 100), 1)
        w = _rand((3, 100, 300), 2)
        out = ops.gmm(x, w)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.gmm_ref(x, w)),
                                   atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("platform,expect", [("tpu", "pallas"),
                                             ("cpu", "ref"),
                                             ("gpu", "ref")])
def test_backend_follows_platform(monkeypatch, platform, expect):
    """Undecided, the backend is read from JAX's default backend on first
    use; a forced backend is restored by use_backend on exit."""
    monkeypatch.setattr(ops, "_BACKEND", None)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.get_backend() == expect
    with ops.use_backend("interpret"):
        assert ops.get_backend() == "interpret"
    assert ops.get_backend() == expect
    with pytest.raises(ValueError):
        ops.set_backend("mosaic")
