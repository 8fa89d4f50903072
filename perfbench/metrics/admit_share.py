"""Engine: share of the traced window's wall time spent inside admission
(``ServingEngine.admit``: prefill, pool write, first-token sampling), %."""
from __future__ import annotations

from perfbench.metrics._common import records


def read(ctx):
    r = records(ctx, "bench.admit")
    t0, t1 = ctx["span_window"]
    if not r or t1 <= t0:
        return None
    return 100.0 * sum(b - a for _, a, b, _ in r) / (t1 - t0)
