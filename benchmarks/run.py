"""Benchmark harness: one module per paper table/figure (DESIGN.md §9).

    PYTHONPATH=src python -m benchmarks.run [--only name] [--json out.json]

Prints one CSV line per benchmark:  name,us_per_call,derived
and writes the full detail records to results/benchmarks.json.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

MODULES = [
    "deployment_efficiency",     # §4.1.1  45 min -> 28 min
    "resource_utilization",      # §4.1.1  58% -> 82%
    "cost_per_inference",        # §4.1.1  $0.12 -> $0.074
    "serving_latency",           # §4.1.1  250 ms -> 180 ms
    "load_testing",              # §4.2.1  1k -> 100k RPS under 200 ms
    "adaptation",                # §4.2.2  reallocation < 30 s
    "feature_importance",        # §4.4    35/30/20/15
    "multi_region",              # §4.1.2  five regions
    "allocator_ablation",        # §3.3.1  planner vs rl vs hybrid modes
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument("--json", default="results/benchmarks.json")
    args = ap.parse_args(argv)
    from repro.launch.runtime import setup_compile_cache
    setup_compile_cache()

    names = args.only.split(",") if args.only else MODULES
    print("name,us_per_call,derived")
    records, failed = [], []
    for name in names:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            t0 = time.time()
            rec = mod.run()
            rec["wall_s"] = round(time.time() - t0, 2)
            records.append(rec)
            print(f"{rec['name']},{rec['us_per_call']:.2f},\"{rec['derived']}\"",
                  flush=True)
        except Exception:
            failed.append(name)
            print(f"{name},NaN,\"FAILED\"", flush=True)
            traceback.print_exc()
    if args.json:
        out = Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(records, indent=1, default=str))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
