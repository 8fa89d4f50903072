"""Engine: host time per decode tick spent building the step's inputs,
putting them on the device and enqueueing the step (the program's
``serve.decode_dispatch`` spans nested in ``serve.tick``), ms."""
from __future__ import annotations

from perfbench.metrics import _program


def read(ctx):
    return _program.per_tick_ms(ctx, "serve.decode_dispatch")
