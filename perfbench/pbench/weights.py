"""Weights made by the benchmark from ``--seed``, never by the program.

One generator serves both sides. ``program_params`` builds the whole tree
the program serves, on the device in one jitted call, in the served dtype
and in the program's layout (layers stacked on a leading axis, RMSNorm
gains stored zero-centred, as ``1 + stored``). ``layer``/``top`` rebuild
the same values one layer at a time for the reference, with the gains in
their plain form. Each leaf's values come from its own key, folded from
the seed, the layer and the leaf's position, so a leaf is the same
whether it is made in the stacked call or alone.

Matrices are N(0, 1/fan_in); the embedding is N(0, 1), or N(0, 1/d) when
the head is tied to it so that logits keep unit scale; gains are
1 + 0.1 N(0, 1).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
GAIN_STD = 0.1
TOP_KEY = 1 << 20          # fold-in index of the non-layer leaves


def seed31(seed: int) -> int:
    """The 31-bit key seed of any ``--seed``, which may exceed 32 bits."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed31(seed))


def _sizes(c: Dict[str, Any]) -> Tuple[int, ...]:
    return (c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"],
            c["d_ff"])


def layer_spec(c: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's leaves: (kind, shape, fan_in) in the program's layout."""
    d, H, KV, hd, F = _sizes(c)
    attn = {"wq": ("mat", (d, H, hd), d), "wk": ("mat", (d, KV, hd), d),
            "wv": ("mat", (d, KV, hd), d), "wo": ("mat", (H, hd, d), H * hd)}
    if c.get("qk_norm"):
        attn["q_norm"] = ("gain", (hd,), 0)
        attn["k_norm"] = ("gain", (hd,), 0)
    return {"ln1": ("gain", (d,), 0), "ln2": ("gain", (d,), 0),
            "attn": attn,
            "mlp": {"w_gate": ("mat", (d, F), d), "w_up": ("mat", (d, F), d),
                    "w_down": ("mat", (F, d), F)}}


def top_spec(c: Dict[str, Any], vocab_rows: int) -> Dict[str, Any]:
    d = c["d_model"]
    std = 1.0 / math.sqrt(d) if c.get("tie_embeddings") else 1.0
    spec = {"embed": ("embed", (vocab_rows, d), std),
            "final_norm": ("gain", (d,), 0)}
    if not c.get("tie_embeddings"):
        spec["lm_head"] = ("mat", (d, vocab_rows), d)
    return spec


def _is_leaf(x) -> bool:
    return isinstance(x, tuple)


def _make(spec, key, dtype, *, plain: bool):
    """Leaves of ``spec`` from ``key``; gains plain (g) or zero-centred
    (g - 1, the program's storage)."""
    leaves, tree = jax.tree.flatten(spec, is_leaf=_is_leaf)
    out = []
    for i, (kind, shape, arg) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        if kind == "gain":
            g = GAIN_STD * z
            out.append(1.0 + g if plain else g)
        elif kind == "mat":
            out.append((z / math.sqrt(arg)).astype(dtype))
        else:
            out.append((z * arg).astype(dtype))
    return jax.tree.unflatten(tree, out)


def dtype_of(c: Dict[str, Any]):
    return jnp.dtype(c["weights_dtype"])


@partial(jax.jit, static_argnums=(1, 2))
def _program_params(key, cfg_items, vocab_rows):
    c = dict(cfg_items)
    dt = dtype_of(c)
    L = c["num_layers"]
    layers = jax.vmap(lambda i: _make(layer_spec(c), jax.random.fold_in(key, i),
                                      dt, plain=False))(jnp.arange(L))
    params = _make(top_spec(c, vocab_rows), jax.random.fold_in(key, TOP_KEY),
                   dt, plain=False)
    params["layers"] = layers
    return params


def _frozen(c: Dict[str, Any]):
    """Hashable form of the sizes the generator reads (a jit static)."""
    keys = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
            "num_layers", "qk_norm", "tie_embeddings", "weights_dtype")
    return tuple((k, c.get(k)) for k in keys)


def program_params(c: Dict[str, Any], key: jax.Array, vocab_rows: int):
    """The whole served tree from ``seed_key(seed)``, made on the device in
    one jitted call. The key is an argument, never a constant of the
    program, so one compiled program serves every seed."""
    return _program_params(key, _frozen(c), vocab_rows)


@partial(jax.jit, static_argnums=(1,))
def _layer(key, cfg_items, i):
    c = dict(cfg_items)
    return _make(layer_spec(c), jax.random.fold_in(key, i), dtype_of(c),
                 plain=True)


def layer(c: Dict[str, Any], seed: int, i: int):
    """Layer ``i`` as served, with plain gains: the reference's weights."""
    return _layer(seed_key(seed), _frozen(c), jnp.int32(i))


@partial(jax.jit, static_argnums=(1, 2))
def _top(key, cfg_items, vocab_rows):
    c = dict(cfg_items)
    return _make(top_spec(c, vocab_rows), jax.random.fold_in(key, TOP_KEY),
                 dtype_of(c), plain=True)


def top(c: Dict[str, Any], seed: int, vocab_rows: int):
    """Embedding, final gain and (untied) head, with plain gains."""
    return _top(seed_key(seed), _frozen(c), vocab_rows)
