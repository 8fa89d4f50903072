"""Split-K decode attention (``kernels/decode_attention.py``), one call per
layer per decode step: each busy row's query heads against its valid
context of ``n`` keys (positions 0..pos, the new key included)."""
from __future__ import annotations

from perfbench.counts._shapes import BF16, sizes


def flops_bytes(cfg: dict, contexts) -> tuple[float, float]:
    """contexts: valid key count of each busy row → (flops, bytes) summed
    over the layers of one call of the step."""
    s = sizes(cfg)
    n = float(sum(contexts))
    rows = len(contexts)
    flops = 4.0 * s["H"] * s["hd"] * n                  # q·k and p·v
    kv = 2.0 * s["KV"] * s["hd"] * n * BF16            # K and V read once
    qo = 2.0 * rows * s["H"] * s["hd"] * BF16          # q read, out written
    return s["L"] * flops, s["L"] * (kv + qo)
