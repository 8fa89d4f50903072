"""Scheduler: busy slots over slots, averaged over the window's ticks
(the engine's ``EngineStats`` window), in %."""
from __future__ import annotations


def read(ctx):
    stats = ctx["win"]["stats"]
    if not stats or stats.get("slot_util") is None:
        return None
    return 100.0 * float(stats["slot_util"])
