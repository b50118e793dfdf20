"""The plain reference against the published implementations of the layer
(Hugging Face ``transformers``' Llama and Qwen3), on the benchmark's own
weights at a small size."""

import math
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from pbench import reference, weights  # noqa: E402

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

SMALL = {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 32, "d_ff": 256, "vocab_size": 512, "norm_eps": 1e-6,
         "weights_dtype": "bfloat16"}
LLAMA = dict(SMALL, qk_norm=False, tie_embeddings=False, rope_theta=1e4)
QWEN3 = dict(SMALL, qk_norm=True, tie_embeddings=True, rope_theta=1e6)


def _t(a):
    return torch.tensor(np.asarray(jnp.asarray(a, jnp.float32)))


def _published(c, top, layers):
    d, L = c["d_model"], c["num_layers"]
    kw = dict(vocab_size=c["vocab_size"], hidden_size=d,
              intermediate_size=c["d_ff"], num_hidden_layers=L,
              num_attention_heads=c["num_heads"],
              num_key_value_heads=c["num_kv_heads"], head_dim=c["head_dim"],
              rms_norm_eps=c["norm_eps"], rope_theta=c["rope_theta"],
              tie_word_embeddings=c["tie_embeddings"],
              max_position_embeddings=4096, attention_bias=False)
    if c["qk_norm"]:
        model = transformers.Qwen3ForCausalLM(
            transformers.Qwen3Config(use_sliding_window=False, **kw))
    else:
        model = transformers.LlamaForCausalLM(
            transformers.LlamaConfig(mlp_bias=False, **kw))
    sd = {"model.embed_tokens.weight": _t(top["embed"]),
          "model.norm.weight": _t(top["final_norm"]),
          "lm_head.weight": _t(top["embed"]) if c["tie_embeddings"]
          else _t(top["lm_head"]).T}
    for i, w in enumerate(layers):
        a, m, p = w["attn"], w["mlp"], f"model.layers.{i}."
        sd[p + "self_attn.q_proj.weight"] = _t(a["wq"]).reshape(d, -1).T
        sd[p + "self_attn.k_proj.weight"] = _t(a["wk"]).reshape(d, -1).T
        sd[p + "self_attn.v_proj.weight"] = _t(a["wv"]).reshape(d, -1).T
        sd[p + "self_attn.o_proj.weight"] = _t(a["wo"]).reshape(-1, d).T
        if c["qk_norm"]:
            sd[p + "self_attn.q_norm.weight"] = _t(a["q_norm"])
            sd[p + "self_attn.k_norm.weight"] = _t(a["k_norm"])
        sd[p + "input_layernorm.weight"] = _t(w["ln1"])
        sd[p + "post_attention_layernorm.weight"] = _t(w["ln2"])
        sd[p + "mlp.gate_proj.weight"] = _t(m["w_gate"]).T
        sd[p + "mlp.up_proj.weight"] = _t(m["w_up"]).T
        sd[p + "mlp.down_proj.weight"] = _t(m["w_down"]).T
    model.load_state_dict(sd, strict=True)
    return model.eval()


@pytest.mark.parametrize("c", [LLAMA, QWEN3], ids=["llama", "qwen3"])
def test_reference_is_the_published_layer(c):
    V = c["vocab_size"]
    top = weights.top(c, 11, V)
    layers = [weights.layer(c, 11, i) for i in range(c["num_layers"])]
    toks = np.random.default_rng(0).integers(0, V, size=(2, 300))
    h = reference.embed(top, jnp.asarray(toks, jnp.int32), vocab=V)
    for w in layers:
        h = reference.layer(w, h, eps=c["norm_eps"], theta=c["rope_theta"],
                            qk_norm=c["qk_norm"], q="f32")
    mine = np.asarray(reference.logits(
        top, h, eps=c["norm_eps"], vocab=V, tied=c["tie_embeddings"],
        q="f32"))
    with torch.no_grad():
        theirs = _published(c, top, layers)(
            torch.tensor(toks, dtype=torch.long)).logits.numpy()
    scale = np.abs(theirs).max()
    assert scale > 1.0
    np.testing.assert_allclose(mine, theirs, atol=1e-4 * scale, rtol=0)


def test_fp8_rounds_to_three_mantissa_bits():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(64, 128)),
                    jnp.float32)
    q = reference.fp8(x, -1)
    rel = np.abs(np.asarray(q - x)) / np.maximum(np.abs(np.asarray(x)), 1e-3)
    big = np.abs(np.asarray(x)) > 0.1 * np.abs(np.asarray(x)).max()
    assert rel[big].max() <= 2.0 ** -4 + 1e-6
    assert np.asarray(q != x).mean() > 0.5
    assert math.isclose(float(jnp.abs(q).max()), float(jnp.abs(x).max()),
                        rel_tol=1e-6)
