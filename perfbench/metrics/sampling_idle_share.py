"""Device: share of the traced window in which the device is idle while
the host pulls a logits row or draws a token from it (the union of the
program's ``serve.row_pull`` and ``serve.host_draw`` spans), %."""
from __future__ import annotations

from perfbench import trace as tr
from perfbench.metrics import _program


def read(ctx):
    lo, hi = ctx["trace_lo"], ctx["trace_hi"]
    sampling = [s[:2] for s in _program.spans(ctx)
                if s[2] in _program.SAMPLING]
    if not sampling or hi <= lo:
        return None
    idle = _program.overlap_ns(tr.union(sampling, lo, hi),
                               tr.gaps(ctx["ops"], lo, hi))
    return 100.0 * idle / (hi - lo)
