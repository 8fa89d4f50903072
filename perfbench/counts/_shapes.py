"""Sizes shared by the count functions."""
from __future__ import annotations

from perfbench.models.qwen2 import dims as sizes

BF16 = 2


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    s = sizes(cfg)
    qkv = s["d"] * (s["H"] + 2 * s["KV"]) * s["hd"]
    attn = qkv + s["H"] * s["hd"] * s["d"]
    return attn + 3 * s["d"] * s["F"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the served (bf16) weights, embedding and head included."""
    s = sizes(cfg)
    per_layer = layer_matmul_params(cfg) + (s["H"] + 2 * s["KV"]) * s["hd"] \
        + 2 * s["d"]
    n = s["L"] * per_layer + s["V"] * s["d"] + s["d"]
    if not s["tied"]:
        n += s["d"] * s["V"]
    return n * BF16
