"""Per-layer metric readers, one module per metric named as in
``BENCHMARK.json``. Each has ``read(ctx) -> float | None``: ``ctx`` is
``harness.trace_context``'s mapping (the reduced device trace of the traced
part of the window, the harness's spans and the program's counters). A
reader that finds nothing to read returns None, and the metric is left out
of the result line."""
