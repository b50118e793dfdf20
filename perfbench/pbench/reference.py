"""Plain float32 forward of a dense GQA decoder, independent of the program.

The layer is the Llama / DeepSeek LLM / Qwen3 one as published: pre-norm
RMSNorm, rotary embeddings on the two halves of each head (``rotate_half``),
grouped-query causal softmax attention (query head h reads key/value head
h // (H / KV)), optional per-head RMSNorm on queries and keys before the
rotation (Qwen3's qk_norm), a SwiGLU MLP ``w_down(silu(x w_gate) * x w_up)``,
a final RMSNorm and a head that is the embedding's transpose when tied.
The embedding is not scaled. Everything runs in float32 with matmuls at
``highest`` precision.

``quant`` puts a fake-quantizer on both operands of every matmul; the
fp8 control passes ``fp8``, the reference the identity.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30
Q_CHUNK = 256              # query rows per attention block


def ident(x, axes=None):
    return x


def fp8(x, axes=None):
    """Round to float8_e4m3fn with one scale per slice over ``axes`` (all
    axes when None): the operand a fp8 matmul would see."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


QUANT = {"f32": ident, "fp8": fp8}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [N, T, heads, hd] at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv            # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, quant: Callable):
    """Causal GQA attention. q [N, T, H, hd]; k, v [N, T, KV, hd]."""
    N, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = quant(q, -1).reshape(N, T, KV, G, hd)
    kq, vq = quant(k, -1), quant(v, -1)
    outs = []
    for s in range(0, T, Q_CHUNK):
        qc = qg[:, s:s + Q_CHUNK]
        n = qc.shape[1]
        sc = jnp.einsum("nqkgh,nskh->nkgqs", qc, kq) * hd ** -0.5
        mask = (s + jnp.arange(n))[:, None] >= jnp.arange(T)[None, :]
        sc = jnp.where(mask, sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("nkgqs,nskh->nqkgh", quant(p, -1), vq)
        outs.append(o.reshape(N, n, H, hd))
    return jnp.concatenate(outs, axis=1)


@partial(jax.jit, static_argnames=("eps", "theta", "qk_norm", "q"))
def layer(w: Dict[str, Any], h, *, eps: float, theta: float, qk_norm: bool,
          q: str):
    """One decoder layer on hidden states h [N, T, d] (float32)."""
    quant = QUANT[q]
    w = jax.tree.map(lambda a: a.astype(F32), w)
    a, m = w["attn"], w["mlp"]
    with jax.default_matmul_precision("highest"):
        x = quant(rms_norm(h, w["ln1"], eps), -1)
        qh = jnp.einsum("ntd,dhe->nthe", x, quant(a["wq"]))
        kh = jnp.einsum("ntd,dke->ntke", x, quant(a["wk"]))
        vh = jnp.einsum("ntd,dke->ntke", x, quant(a["wv"]))
        if qk_norm:
            qh = rms_norm(qh, a["q_norm"], eps)
            kh = rms_norm(kh, a["k_norm"], eps)
        qh, kh = rope(qh, theta), rope(kh, theta)
        o = attention(qh, kh, vh, quant)
        h = h + jnp.einsum("nthe,hed->ntd", quant(o, (-2, -1)),
                           quant(a["wo"]))
        x = quant(rms_norm(h, w["ln2"], eps), -1)
        g = jnp.einsum("ntd,df->ntf", x, quant(m["w_gate"]))
        u = jnp.einsum("ntd,df->ntf", x, quant(m["w_up"]))
        y = quant(jax.nn.silu(g) * u, -1)
        return h + jnp.einsum("ntf,fd->ntd", y, quant(m["w_down"]))


@partial(jax.jit, static_argnames=("vocab",))
def embed(top: Dict[str, Any], tokens, *, vocab: int):
    return top["embed"][:vocab].astype(F32)[tokens]


@partial(jax.jit, static_argnames=("eps", "vocab", "tied", "q"))
def logits(top: Dict[str, Any], h, *, eps: float, vocab: int, tied: bool,
           q: str):
    """Logits over the logical vocabulary for hidden states h [N, S, d]."""
    quant = QUANT[q]
    head = (top["embed"][:vocab].T if tied
            else top["lm_head"][:, :vocab]).astype(F32)
    with jax.default_matmul_precision("highest"):
        x = quant(rms_norm(h, top["final_norm"], eps), -1)
        return jnp.einsum("nsd,dv->nsv", x, quant(head))
