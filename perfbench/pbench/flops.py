"""Peaks of the chips the benchmark runs on, and the model's operations.

Peaks are keyed by JAX's ``device_kind``; a device that is not in the
table is an error, never a default.
"""

from __future__ import annotations

from typing import Any, Dict

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and
    # 819 GB/s of HBM bandwidth per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak known for device kind {device_kind!r}; "
                       f"add it to PEAKS with its source")
    return PEAKS[device_kind]


def layer_params(c: Dict[str, Any]) -> int:
    """Matmul weights of one dense GQA layer (q, k, v, o and SwiGLU)."""
    d, H, KV, hd, F = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                       c["head_dim"], c["d_ff"])
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def _attn_per_key(c: Dict[str, Any]) -> int:
    # scores and weighted values: 2 matmuls of 2 * H * hd per key, per layer
    return 4 * c["num_heads"] * c["head_dim"] * c["num_layers"]


def head_flops(c: Dict[str, Any]) -> int:
    return 2 * c["d_model"] * c["vocab_size"]


def prefill_flops(c: Dict[str, Any], n: int) -> int:
    """Model operations of a prompt of ``n`` tokens: every token through
    every layer, causal attention over its prefix, the head once."""
    return (2 * c["num_layers"] * layer_params(c) * n
            + _attn_per_key(c) * n * (n + 1) // 2 + head_flops(c))


def decode_flops(c: Dict[str, Any], context: int) -> int:
    """Model operations of one generated token attending ``context`` keys."""
    return (2 * c["num_layers"] * layer_params(c)
            + _attn_per_key(c) * context + head_flops(c))
