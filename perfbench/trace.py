"""Reduction of a profiler trace to device busy time, per-op time and idle
gaps attributed to the harness span open at the time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX
alone, into plain tuples; everything else works on those tuples, so the
tests check it on small hand-made traces.

An op is ``(start_ns, end_ns, name, module)`` on one device; a span is
``(start_ns, end_ns, name)`` on the host. Both come from the same trace and
so share its clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: dict[int, list]          # device id -> [(start, end, op, module)]
    modules: dict[int, list]      # device id -> [(start, end, module, "")]
    spans: list                   # [(start, end, name)] harness spans


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(text: str) -> str:
    """The op's own name out of an HLO line (``%fusion.78 = f32[...] ...``
    → ``fusion.78``)."""
    m = re.match(r"^%?([^\s=]+)", text)
    return m.group(1) if m else text


def op_shape(text: str) -> str:
    """The op's result type without layouts (``f32[8,151936]``)."""
    m = re.match(r"^%?[^\s=]+ = (\S+)", text)
    return re.sub(r"\{[^}]*\}", "", m.group(1))[:60] if m else ""


def module_name(text: str) -> str:
    """``jit_fused_decode_step(1176...)`` → ``jit_fused_decode_step``."""
    return re.sub(r"\(\d+\)$", "", text)


def _with_modules(ops, modules):
    """Attach to each op the module whose execution covers it."""
    out, j = [], 0
    for s, e, name in ops:
        while j < len(modules) and modules[j][1] <= s:
            j += 1
        mod = modules[j][2] if j < len(modules) and modules[j][0] <= s else ""
        out.append((s, e, name, mod))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    modules: dict[int, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                rows = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
                         e.name) for e in line.events]
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = sorted(
                        (s, e, op_name(n) + " " + op_shape(n))
                        for s, e, n in rows)
                else:
                    modules[int(m.group(1))] = sorted(
                        (s, e, module_name(n), "") for s, e, n in rows)
            elif not m and plane.name.startswith("/host"):
                spans.extend((int(e.start_ns), int(e.start_ns)
                              + int(e.duration_ns), e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    ops = {d: _with_modules(v, modules.get(d, [])) for d, v in ops.items()}
    spans.sort()
    return Trace(ops=ops, modules=modules, spans=spans)


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list[list[int]] = []
    for iv in sorted(intervals):
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(ops, lo, hi))


def gaps(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of [lo, hi): no op runs on the device."""
    out, t = [], lo
    for s, e in union(ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class SpanIndex:
    """Looks up the innermost harness span open at a time. Spans nest
    (a step holds an admission, which holds a prefill), so the spans open
    at ``t`` are among the last few that started before it."""

    LOOKBACK = 256

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t: int) -> str:
        """Name of the shortest span covering ``t``, or ``outside_spans``."""
        i = bisect.bisect_right(self.starts, t)
        best = None
        for s, e, name in reversed(self.spans[max(0, i - self.LOOKBACK):i]):
            if e > t and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "outside_spans"


def idle_by_span(ops, spans, lo: int, hi: int) -> dict[str, int]:
    """Idle nanoseconds of [lo, hi), split by the innermost harness span
    open at each idle point. An idle gap that crosses span boundaries is
    cut at them."""
    index = SpanIndex(spans)
    cuts = sorted({t for s, e, _ in spans for t in (s, e) if lo < t < hi})
    out: dict[str, int] = {}
    for gs, ge in gaps(ops, lo, hi):
        lo_i, hi_i = bisect.bisect_right(cuts, gs), bisect.bisect_left(cuts, ge)
        inner = cuts[lo_i:hi_i]
        pts = [gs] + inner + [ge]
        for a, b in zip(pts, pts[1:]):
            name = index.at((a + b) // 2)
            out[name] = out.get(name, 0) + (b - a)
    return out


def stable_op_name(name: str) -> str:
    """An op name without its compiler-given number (``fusion.123 f32[8]``
    → ``fusion f32[8]``), so that names survive a recompile."""
    head, _, shape = name.partition(" ")
    head = re.sub(r"[.\-_]\d+$", "", head)
    return f"{head} {shape}" if shape else head


# ops that only hold other ops: their time is their body's
CONTAINERS = ("while", "conditional", "call")


def op_time_by_name(ops, lo: int, hi: int) -> dict[str, int]:
    """Device nanoseconds per ``module/op`` stable name, clipped to [lo, hi).
    Control-flow ops, which contain the ops of their bodies, are left out."""
    out: dict[str, int] = {}
    for s, e, name, module in ops:
        d = min(e, hi) - max(s, lo)
        base = stable_op_name(name)
        if d > 0 and base.split(" ")[0] not in CONTAINERS:
            key = f"{module}/{base}" if module else base
            out[key] = out.get(key, 0) + d
    return out


def time_of(events, name: str, lo: int, hi: int) -> tuple[int, int]:
    """(device nanoseconds, count) of the ops or modules whose stable name
    (number and shape dropped) is ``name``, within [lo, hi)."""
    t = n = 0
    for s, e, full, _ in events:
        if s >= lo and e <= hi and \
                stable_op_name(full).split(" ")[0] == name:
            t += e - s
            n += 1
    return t, n
