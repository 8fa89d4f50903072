"""Model step: operations of the fused decode steps (busy rows, valid
contexts; ``counts/decode_step.py``) over their device time, as a share of
the chip's bf16 peak, %."""
from __future__ import annotations

from perfbench.counts import decode_step
from perfbench.metrics._common import device_ns, mfu, records


def read(ctx):
    calls = records(ctx, "bench.decode")
    flops = sum(decode_step.flops_bytes(ctx["config"], c[3])[0]
                for c in calls)
    ns, _ = device_ns(ctx, "modules", "jit_fused_decode_step")
    return mfu(ctx, flops, ns)
