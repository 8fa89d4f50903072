"""The program's own spans (``serve.*``, recorded by the serving engine as
profiler annotations) out of the run's trace, and their reduction per
decode tick.

A span is ``(start_ns, end_ns, name, stats)`` on the host plane, on the
clock of the device's ops. A program that records no such spans (an older
engine) gives none, and every reader built on them returns None.
"""
from __future__ import annotations

import bisect
import functools
import os

from perfbench import harness
from perfbench import trace as tr

PREFIX = "serve."
TICK = "serve.tick"
SAMPLING = ("serve.row_pull", "serve.host_draw")


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> tuple:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name,
                                {k: v for k, v in e.stats}))
    return tuple(sorted(out, key=lambda x: (x[0], -x[1])))


def spans(ctx) -> tuple:
    """Every ``serve.*`` span of the trace the run wrote under
    ``harness.TRACE_DIR`` (the readers' ``ctx`` holds only its reduction);
    () without a trace."""
    try:
        path = tr.find_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return ()
    return _load(path, os.path.getmtime(path))


def ticks(ctx) -> list[tuple]:
    """[(tick, [spans nested in it])] for the ``serve.tick`` spans that lie
    whole inside the traced window ``[trace_lo, trace_hi]``."""
    lo, hi = ctx["trace_lo"], ctx["trace_hi"]
    sp = spans(ctx)
    starts = [s[0] for s in sp]
    out = []
    for t in sp:
        if t[2] != TICK or t[0] < lo or t[1] > hi:
            continue
        i, j = bisect.bisect_left(starts, t[0]), bisect.bisect_right(
            starts, t[1])
        out.append((t, [c for c in sp[i:j] if c is not t and c[1] <= t[1]]))
    return out


def per_tick_ms(ctx, name: str):
    """Σ of the named spans nested in ticks ÷ ticks, ms; None without
    ticks."""
    tk = ticks(ctx)
    if not tk:
        return None
    ns = sum(c[1] - c[0] for _, kids in tk for c in kids if c[2] == name)
    return ns / len(tk) / 1e6


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint
    [start, end) intervals."""
    i = j = n = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            n += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return n
