"""Concrete wrappers + the populated exchange.

Every assigned architecture is registered as a MAX asset (the paper's "30+
wrapped models" catalogue, here 12+). Builders default to the REDUCED
config (same family, 2 layers, float32 weights) with seeded random weights
so every asset is buildable and servable on CPU. ``smoke=False`` builds the
published widths with bfloat16 weights, which is how a model is served on
a chip: ``chip_smoke.py`` deploys qwen3-4b this way on one TPU v5e. A
model must fit the device it is placed on; nothing here shards it.

Wrapper types mirror the paper's demo zoo:
- TextGenerationWrapper     (LLM assets; object-detector analogue of "apply
                             model, return structured JSON")
- TextClassificationWrapper (max-sentiment — paper Fig. 3 verbatim envelope)
- ImageCaptionWrapper       (max-caption / internvl2 — Fig. 2b analogue)
- AudioTranscriptionWrapper (whisper)
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import CONFIGS, ASSIGNED, DEMOS
from repro.configs.base import ModelConfig, reduce_for_smoke
from repro.core.registry import EXCHANGE, ModelAsset
from repro.core.wrapper import MAXError, MAXModelWrapper, ModelMetadata
from repro.data.tokenizer import TOKENIZER
from repro.models import build_model
from repro.serving import GenerationEngine, GenerationResult

_TYPE_BY_FAMILY = {
    "dense": "Text Generation",
    "moe": "Text Generation",
    "hybrid": "Text Generation",
    "ssm": "Text Generation",
    "vlm": "Image Captioning",
    "audio": "Speech Transcription",
}


def _stub_image_embeds(cfg: ModelConfig, image_id: int) -> jnp.ndarray:
    """Deterministic stand-in for the (stubbed) vision encoder output."""
    key = jax.random.PRNGKey(image_id)
    return jax.random.normal(key, (1, cfg.num_image_tokens, cfg.d_model),
                             jnp.float32)


def _stub_frames(cfg: ModelConfig, audio_id: int) -> jnp.ndarray:
    key = jax.random.PRNGKey(audio_id)
    return jax.random.normal(key, (1, cfg.encoder_seq, cfg.d_model),
                             jnp.float32)


class _EngineWrapper(MAXModelWrapper):
    """Shared plumbing: model + params + generation engine."""

    def __init__(self, asset: ModelAsset, *, smoke: bool = True,
                 max_batch: int = 4, max_seq: int = 128, seed: int = 0,
                 decode_chunk: int = 8, paged: bool = False,
                 page_size: int = 16, kv_pool_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefix_cache_pages: Optional[int] = None):
        cfg = asset.config
        if smoke and cfg.name in ASSIGNED:
            cfg = reduce_for_smoke(cfg)
        self.cfg = cfg
        # full width serves bf16 weights: f32 doubles the bytes every decode
        # step reads, and qwen3-4b in f32 does not fit a 16 GB chip
        self.model = build_model(
            cfg, param_dtype=jnp.float32 if smoke else jnp.bfloat16)
        # jitted: at full width an eager init would hold f32 copies of
        # whole layer stacks next to the weights already built
        self.params = jax.jit(self.model.init)(jax.random.PRNGKey(seed))
        self.engine = GenerationEngine(self.model, self.params,
                                       max_batch=max_batch, max_seq=max_seq,
                                       eos_id=TOKENIZER.eos_id,
                                       decode_chunk=decode_chunk,
                                       paged=paged, page_size=page_size,
                                       kv_pool_blocks=kv_pool_blocks,
                                       prefix_cache=prefix_cache,
                                       prefix_cache_pages=prefix_cache_pages)
        self.MODEL_META_DATA = asset.metadata

    def place(self, device) -> None:
        """Commit weights and KV cache to ``device`` (replica placement):
        every program the engine dispatches then runs there."""
        self.engine.place(device)
        self.params = self.engine.params

    def _result(self, tokens: List[int], prompt_len: int) -> GenerationResult:
        return GenerationResult(tokens=list(tokens), prompt_len=prompt_len,
                                steps=len(tokens), finished=True)

    def format_stream_delta(self, token_ids: List[int]):
        # byte-level tokenizer: chunk decodes concatenate to the full text
        # (multi-byte codepoints split across chunks render as replacement
        # chars in the delta only — clients always get the exact ids too)
        return TOKENIZER.decode(token_ids)


class TextGenerationWrapper(_EngineWrapper):
    def _pre_process(self, inp: Any) -> Dict[str, Any]:
        if isinstance(inp, str):
            inp = {"text": inp}
        if not isinstance(inp, dict) or "text" not in inp:
            raise MAXError("input must be a string or {'text': ...}")
        toks = TOKENIZER.encode(str(inp["text"]))
        # longest ADMISSIBLE prompt, not max_seq-1: ring-cache families
        # (ssm/hybrid/sliding-window) pad prompts to their bucket and treat
        # the padding as context, so a max_seq-1 truncation could still
        # bucket to max_seq and leave zero generation headroom
        max_len = self.engine.max_prompt_len()
        return {
            "tokens": toks[:max_len],
            "max_new_tokens": int(inp.get("max_new_tokens", 16)),
            "temperature": float(inp.get("temperature", 0.0)),
        }

    def _predict(self, x: Dict[str, Any]) -> Any:
        res = self.engine.generate(
            [x["tokens"]], max_new_tokens=x["max_new_tokens"],
            temperature=x["temperature"])
        return res[0]

    def _post_process(self, r) -> Any:
        out = {"generated_text": TOKENIZER.decode(r.tokens),
               "generated_tokens": len(r.tokens),
               "prompt_tokens": r.prompt_len}
        if r.first_token_s is not None:     # engine-measured TTFT (sync
            out["ttft_ms"] = round(r.first_token_s * 1e3, 3)   # path only)
        return [out]

    # generation protocol — lets BatchedService coalesce concurrent HTTP
    # requests into one decode batch instead of calling engine.generate
    # per request
    def prepare_generation(self, inp: Any):
        x = self._pre_process(inp)
        return x["tokens"], {"max_new_tokens": x["max_new_tokens"],
                             "temperature": x["temperature"]}, None

    def format_generation(self, tokens: List[int], prompt_len: int) -> Any:
        return self._post_process(self._result(tokens, prompt_len))


class TextClassificationWrapper(_EngineWrapper):
    """max-sentiment: reproduces the paper's Fig. 3 JSON exactly:
    predictions = [[{"positive": p, "negative": n}], ...] per input."""

    POS_TOKEN, NEG_TOKEN = 80, 78   # 'P', 'N' byte ids as label tokens

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        # one compiled program per length bucket — the serving hot path
        self._score = jax.jit(self._score_impl)

    def _score_impl(self, tokens, length):
        logits, _ = self.model.forward(self.params, {"tokens": tokens})
        last = jnp.take_along_axis(
            logits, (length - 1)[None, None, None], axis=1)[0, 0]
        pair = last[jnp.asarray([self.POS_TOKEN, self.NEG_TOKEN])]
        return jax.nn.softmax(pair)

    def _pre_process(self, inp: Any) -> List[List[int]]:
        if isinstance(inp, str):
            inp = [inp]
        if isinstance(inp, dict):
            inp = inp.get("text", inp.get("texts"))
            if isinstance(inp, str):
                inp = [inp]
        if not isinstance(inp, list):
            raise MAXError("input must be text or list of texts")
        max_len = self.engine.max_seq - 1
        return [TOKENIZER.encode(str(t))[:max_len] for t in inp]

    def _predict(self, token_lists: List[List[int]]) -> List[Dict[str, float]]:
        out = []
        for toks in token_lists:
            bucket = 16
            while bucket < len(toks):
                bucket *= 2
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : len(toks)] = toks
            p = self._score(jnp.asarray(padded),
                            jnp.asarray(len(toks), jnp.int32))
            out.append({"positive": float(p[0]), "negative": float(p[1])})
        return out

    def _post_process(self, scores) -> Any:
        return [[s] for s in scores]   # paper Fig. 3 nesting

    def labels(self):
        return ["positive", "negative"]


class ImageCaptionWrapper(_EngineWrapper):
    def _pre_process(self, inp: Any) -> Dict[str, Any]:
        if not isinstance(inp, dict):
            inp = {"image_id": int(inp) if str(inp).isdigit() else 0}
        return {
            "image_id": int(inp.get("image_id", 0)),
            "max_new_tokens": int(inp.get("max_new_tokens", 16)),
        }

    def _predict(self, x) -> Any:
        embeds = _stub_image_embeds(self.cfg, x["image_id"])
        prompt = [TOKENIZER.bos_id] * (self.cfg.num_image_tokens + 1)
        res = self.engine.generate(
            [prompt], max_new_tokens=x["max_new_tokens"],
            extras=[{"image_embeds": embeds}])
        return res[0]

    def _post_process(self, r) -> Any:
        return [{"caption": TOKENIZER.decode(r.tokens),
                 "index": 0, "probability": 1.0}]   # MAX caption schema

    def prepare_generation(self, inp: Any):
        x = self._pre_process(inp)
        embeds = _stub_image_embeds(self.cfg, x["image_id"])
        prompt = [TOKENIZER.bos_id] * (self.cfg.num_image_tokens + 1)
        return prompt, {"max_new_tokens": x["max_new_tokens"]}, \
            {"image_embeds": embeds}

    def format_generation(self, tokens: List[int], prompt_len: int) -> Any:
        return self._post_process(self._result(tokens, prompt_len))


class AudioTranscriptionWrapper(_EngineWrapper):
    def _pre_process(self, inp: Any) -> Dict[str, Any]:
        if not isinstance(inp, dict):
            inp = {"audio_id": 0}
        return {
            "audio_id": int(inp.get("audio_id", 0)),
            "max_new_tokens": int(inp.get("max_new_tokens", 16)),
        }

    def _predict(self, x) -> Any:
        frames = _stub_frames(self.cfg, x["audio_id"])
        res = self.engine.generate(
            [[TOKENIZER.bos_id]], max_new_tokens=x["max_new_tokens"],
            extras=[{"frames": frames}])
        return res[0]

    def _post_process(self, r) -> Any:
        return [{"transcript": TOKENIZER.decode(r.tokens)}]

    def prepare_generation(self, inp: Any):
        x = self._pre_process(inp)
        frames = _stub_frames(self.cfg, x["audio_id"])
        return [TOKENIZER.bos_id], {"max_new_tokens": x["max_new_tokens"]}, \
            {"frames": frames}

    def format_generation(self, tokens: List[int], prompt_len: int) -> Any:
        return self._post_process(self._result(tokens, prompt_len))


_WRAPPER_BY_TYPE = {
    "Text Generation": TextGenerationWrapper,
    "Text Classification": TextClassificationWrapper,
    "Image Captioning": ImageCaptionWrapper,
    "Speech Transcription": AudioTranscriptionWrapper,
}


def _make_asset(cfg: ModelConfig, *, type_: Optional[str] = None,
                description: str = "", labels: tuple = ()) -> ModelAsset:
    t = type_ or _TYPE_BY_FAMILY[cfg.family]
    meta = ModelMetadata(
        id=cfg.name,
        name=cfg.name.replace("-", " ").title(),
        description=description or
        f"{cfg.family} backbone, {cfg.num_layers}L d={cfg.d_model} "
        f"({cfg.param_count() / 1e9:.1f}B params)",
        type=t,
        source=cfg.source,
        labels=labels,
    )
    cls = _WRAPPER_BY_TYPE[t]
    return ModelAsset(metadata=meta, config=cfg,
                      builder=lambda asset, **kw: cls(asset, **kw),
                      tags=(cfg.family,))


def populate_exchange():
    if len(EXCHANGE) > 0:
        return EXCHANGE
    for cfg in ASSIGNED.values():
        EXCHANGE.register(_make_asset(cfg))
    EXCHANGE.register(_make_asset(
        DEMOS["max-sentiment"], type_="Text Classification",
        description="MAX demo: text sentiment classifier (paper Fig. 3)",
        labels=("positive", "negative")))
    EXCHANGE.register(_make_asset(
        DEMOS["max-caption"], type_="Image Captioning",
        description="MAX demo: image caption generator (paper Fig. 2b)"))
    return EXCHANGE


populate_exchange()
