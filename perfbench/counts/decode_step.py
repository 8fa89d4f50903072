"""One decode step of the model (``models/steps.py`` fused decode): every
busy row multiplies one token through every layer and the read-out, and
attends to its valid context. Bytes: the weights once, each busy row's
valid K/V, and the new K/V written."""
from __future__ import annotations

from perfbench.counts import decode_attention
from perfbench.counts._shapes import BF16, layer_matmul_params, sizes, \
    weight_bytes


def flops_bytes(cfg: dict, contexts) -> tuple[float, float]:
    s = sizes(cfg)
    rows = len(contexts)
    per_token = s["L"] * layer_matmul_params(cfg) + s["d"] * s["V"]
    a_flops, a_bytes = decode_attention.flops_bytes(cfg, contexts)
    new_kv = 2.0 * rows * s["L"] * s["KV"] * s["hd"] * BF16
    flops = 2.0 * rows * per_token + a_flops
    return flops, weight_bytes(cfg) + a_bytes + new_kv
