"""Named host spans of the serving engine.

Each span is a ``jax.profiler.TraceAnnotation``: with no profiler session
it costs about a microsecond and records nothing; under
``jax.profiler.start_trace`` it lands on the profiler's host plane, on the
same clock as the device's ops, so every idle gap of the device can be put
down to what the engine's host thread was doing in it.  Spans nest by
containment on that one thread; per-request spans carry the request's
``rid`` and ``slot`` as annotation metadata.

Operators read them from the trace by name (docs/operations.md):

  serve.step             one ``ServingEngine.step``: admission + one tick
  serve.admit            one admission (rid, slot)
  serve.prefill          enqueue of the admission's prefill program
  serve.pool_write       the admission's pool write
  serve.tick             one decode tick with at least one busy slot
  serve.decode_dispatch  the tick's inputs built, put on the device, the
                         step enqueued
  serve.decode_wait      host blocked until the step's tokens are on it
  serve.row_pull         one logits row to the host (rid, slot): a
                         ``top_k > 0`` temperature row's
  serve.host_draw        one host-side draw from a pulled row (rid, slot)

A tick's own bookkeeping (slot advance, next-token staging) is its self
time: ``serve.tick`` less the union of the spans inside it.
"""
from __future__ import annotations

import jax

NAMES = ("serve.step", "serve.admit", "serve.prefill", "serve.pool_write",
         "serve.tick", "serve.decode_dispatch", "serve.decode_wait",
         "serve.row_pull", "serve.host_draw")


def span(name: str, **meta):
    """A host span named ``name`` (one of ``NAMES``), with ``meta`` as
    annotation metadata; use as a context manager."""
    return jax.profiler.TraceAnnotation(name, **meta)
