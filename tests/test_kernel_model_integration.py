"""Whole reduced models through the Pallas (interpret) backend must match
the pure-jnp reference backend."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED
from repro.configs.base import reduce_for_smoke
from repro.kernels import ops
from repro.models import build_model

ARCHS = ["qwen3-4b", "qwen3-moe-235b-a22b", "recurrentgemma-9b",
         "rwkv6-7b", "whisper-large-v3", "internvl2-2b"]


@pytest.mark.parametrize("name", ARCHS)
def test_forward_kernel_backend_matches_ref(name, rng):
    cfg = reduce_for_smoke(ASSIGNED[name])
    model = build_model(cfg, cache_dtype=jnp.float32)
    params = model.init(rng)
    B, S = 2, 64
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            rng, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            rng, (B, cfg.num_image_tokens, cfg.d_model), jnp.float32)

    with ops.use_backend("ref"):
        ref_logits, _ = model.forward(params, batch)
    with ops.use_backend("interpret"):
        k_logits, _ = model.forward(params, batch)
    err = float(jnp.max(jnp.abs(ref_logits - k_logits)))
    assert err < 5e-4, f"{name}: kernel backend diverges, err={err}"


@pytest.mark.parametrize("name", ["qwen3-4b", "rwkv6-7b"])
def test_decode_kernel_backend_matches_ref(name, rng):
    cfg = reduce_for_smoke(ASSIGNED[name])
    model = build_model(cfg, cache_dtype=jnp.float32)
    params = model.init(rng)
    toks = jax.random.randint(rng, (2, 12), 0, cfg.vocab_size)

    def run():
        lg, cache = model.prefill(params, {"tokens": toks[:, :8]}, cache_len=16)
        outs = [lg]
        for t in range(8, 12):
            lg, cache = model.decode_step(params, cache, toks[:, t])
            outs.append(lg)
        return jnp.stack(outs)

    with ops.use_backend("ref"):
        a = run()
    with ops.use_backend("interpret"):
        b = run()
    assert float(jnp.max(jnp.abs(a - b))) < 5e-4
