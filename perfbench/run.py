"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload qwen2.5-3b.gen --seed 7 \
        --seconds 51 --trace 0

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout;
its configuration, traffic mix and load come from the files named there.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; the numbers compared against the reference come last, under
``checks``, and again as the last lines of standard error. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import harness

    try:
        cell = harness.load_cell(args.workload)
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START)
    except (harness.NoChip, FileNotFoundError, KeyError,
            ModuleNotFoundError) as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
