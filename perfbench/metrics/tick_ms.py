"""Engine: mean host wall time of one ``ServingEngine.tick`` (the decode
step, waiting for its tokens, host sampling and bookkeeping), from the
harness's span around the call."""
from __future__ import annotations

from perfbench.metrics._common import records


def read(ctx):
    r = records(ctx, "bench.tick")
    return 1e3 * sum(t1 - t0 for _, t0, t1, _ in r) / len(r) if r else None
