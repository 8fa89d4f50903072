"""Model step: host time per decode tick blocked until the step's tokens
are on the host (the program's ``serve.decode_wait`` spans nested in
``serve.tick``), ms."""
from __future__ import annotations

from perfbench.metrics import _program


def read(ctx):
    return _program.per_tick_ms(ctx, "serve.decode_wait")
