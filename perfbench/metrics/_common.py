"""Helpers shared by the readers."""
from __future__ import annotations

from perfbench import trace as tr


def records(ctx, name: str) -> list:
    """Harness span records ``(name, t0, t1, info)`` of one kind, from the
    traced part of the window."""
    return [r for r in ctx["spans"] if r[0] == name]


def device_ns(ctx, kind: str, name: str) -> tuple[int, int]:
    """(nanoseconds, count) of one module (kind "modules") or op (kind
    "ops") on the device, within the trace."""
    return tr.time_of(ctx[kind], name, ctx["trace_lo"], ctx["trace_hi"])


def roofline_share(ctx, flops: float, nbytes: float, ns: int):
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the time taken, in %; None without a timing."""
    if ns <= 0 or flops <= 0:
        return None
    p = ctx["peaks"]
    bound = max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * bound / (ns / 1e9)


def mfu(ctx, flops: float, ns: int):
    """Model operations over device time, as a share of the bf16 peak, %."""
    if ns <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (ns / 1e9) / ctx["peaks"]["bf16_flops_per_s"]
