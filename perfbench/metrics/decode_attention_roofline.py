"""Kernels: the split-K decode attention kernel (``decode_attention_bkgd``
in the trace) against its roofline: the larger of its operations over the
peak rate and its bytes (each busy row's valid K/V) over the peak
bandwidth, divided by its device time, %. Decode attention is bound by
memory."""
from __future__ import annotations

from perfbench.counts import decode_attention
from perfbench.metrics._common import device_ns, records, roofline_share


def read(ctx):
    fl = by = 0.0
    for c in records(ctx, "bench.decode"):
        f, b = decode_attention.flops_bytes(ctx["config"], c[3])
        fl, by = fl + f, by + b
    ns, _ = device_ns(ctx, "ops", "decode_attention_bkgd")
    return roofline_share(ctx, fl, by, ns)
