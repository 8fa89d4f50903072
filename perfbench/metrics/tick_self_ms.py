"""Engine: host time per decode tick outside every span nested in it (slot
bookkeeping, staging the next tokens): each ``serve.tick`` less the union
of its children, summed over the ticks ÷ ticks, ms."""
from __future__ import annotations

from perfbench import trace as tr
from perfbench.metrics import _program


def read(ctx):
    tk = _program.ticks(ctx)
    if not tk:
        return None
    ns = 0
    for t, kids in tk:
        covered = tr.union([k[:2] for k in kids], t[0], t[1])
        ns += (t[1] - t[0]) - sum(e - s for s, e in covered)
    return ns / len(tk) / 1e6
