"""Sampler: host time per decode tick spent drawing sampled rows' tokens
from their pulled logits (the program's ``serve.host_draw`` spans nested in
``serve.tick``), ms."""
from __future__ import annotations

from perfbench.metrics import _program


def read(ctx):
    return _program.per_tick_ms(ctx, "serve.host_draw")
