"""Device: 1 − the union of the intervals in which an op ran on the chip
over the traced window, %."""
from __future__ import annotations


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
