"""Sampler: bytes the engine materialized from the device (the step's
tokens, sampled rows' and admissions' logits rows) per token it emitted,
over the window (the engine's ``EngineStats`` counters), B/token."""
from __future__ import annotations


def read(ctx):
    stats = ctx["win"]["stats"] or {}
    emitted = stats.get("emitted_tokens")
    if not emitted or stats.get("pulled_bytes") is None:
        return None
    return float(stats["pulled_bytes"]) / emitted
