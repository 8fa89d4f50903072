"""Sampler: host time per decode tick spent pulling sampled rows' logits
to the host (the program's ``serve.row_pull`` spans nested in
``serve.tick``), ms."""
from __future__ import annotations

from perfbench.metrics import _program


def read(ctx):
    return _program.per_tick_ms(ctx, "serve.row_pull")
