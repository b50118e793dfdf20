"""Ahead-of-time compiles for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts: unaligned block
slices, kernels that overflow fast memory, programs that overflow HBM.
These tests compile the served qwen3-4b kernels at their real widths in
bf16, and the full-width fused decode chunk at the size ``chip_smoke.py``
serves, for one chip of a ``v5e:2x2`` topology. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports every
test file. Every compile here runs in the test's own process.
"""

import importlib.util
import os
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED
from repro.kernels import ops
from repro.models import build_model
from repro.serving.engine import GenerationEngine

CFG = ASSIGNED["qwen3-4b"]
H, KV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
V5E_HBM = 15.75 * 2**30        # HBM XLA may use on one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke_size():
    """(max_batch, max_seq) that chip_smoke.py deploys."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.MAX_BATCH, mod.MAX_SEQ


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    with ops.use_backend("pallas"):
        return jax.jit(fn).lower(*args).compile()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= V5E_HBM, f"{total / 2**30:.2f} GiB > 15.75 GiB"
    return total


@pytest.mark.parametrize("S", [2048, 4096])
def test_decode_attention_compiles(one_chip, S):
    B = 8
    c = _compile(ops.decode_attention,
                 _sds((B, H, HD), jnp.bfloat16, one_chip),
                 _sds((B, S, KV, HD), jnp.bfloat16, one_chip),
                 _sds((B, S, KV, HD), jnp.bfloat16, one_chip),
                 _sds((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def test_paged_decode_attention_compiles(one_chip):
    B, P, S = 8, 16, 2048
    N = B * S // P
    c = _compile(ops.paged_decode_attention,
                 _sds((B, H, HD), jnp.bfloat16, one_chip),
                 _sds((N, P, KV, HD), jnp.bfloat16, one_chip),
                 _sds((N, P, KV, HD), jnp.bfloat16, one_chip),
                 _sds((B, S // P), jnp.int32, one_chip),
                 _sds((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


@pytest.mark.parametrize("S", [16, 128, 2048])
def test_flash_prefill_compiles(one_chip, S):
    c = _compile(ops.flash_attention,
                 _sds((1, H, S, HD), jnp.bfloat16, one_chip),
                 _sds((1, KV, S, HD), jnp.bfloat16, one_chip),
                 _sds((1, KV, S, HD), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_full_width_decode_chunk_fits_one_chip(one_chip, smoke_size, paged):
    """The fused decode chunk the engine serves at full width, with bf16
    weights, compiles with Pallas kernels and fits one chip's HBM: weights,
    KV cache and the chunk's new cache together."""
    B, S = smoke_size
    model = build_model(CFG, param_dtype=jnp.bfloat16)
    place = partial(jax.tree.map,
                    lambda x: _sds(x.shape, x.dtype, one_chip))
    # the engine allocates its cache through init_cache: hand it shapes
    shaped = model._replace(init_cache=lambda *a, **k: jax.eval_shape(
        lambda: model.init_cache(*a, **k)))
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = GenerationEngine(shaped, params, max_batch=B, max_seq=S,
                           decode_chunk=8, paged=paged)
    assert eng.paged == paged
    vec = partial(_sds, (B,), sharding=one_chip)
    c = _compile(partial(eng._chunk_impl, 8), params, place(eng._cache),
                 vec(jnp.int32), _sds((2,), jnp.uint32, one_chip),
                 vec(jnp.float32), vec(jnp.int32), vec(jnp.bool_))
    assert "tpu_custom_call" in c.as_text()
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    assert _fits(c) > weights
