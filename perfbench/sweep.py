"""Knee sweep: the cell's traffic offered at several fixed rates, in one
process, to find the highest rate the system sustains.

    python3 perfbench/sweep.py --workload qwen2.5-3b.gen \
        --rates 0.6,0.9,1.2 --seconds 30 --seed 5

For each rate a fresh engine (the same weights and compiled programs) takes
the mix at that rate, after the cell's warm-in, for ``--seconds``. One JSON
line per rate: requests queued when the window opened and when it closed
(a growing backlog is a rate above the knee), completions and output
tokens per second, and the time to first token. The cell's rate is then
set from the knee by hand, into ``cells/<workload>.json``; the benchmark's
own runs never search for a rate.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import harness

    cell = harness.load_cell(args.workload)
    core = harness.build(cell, args.seed).core
    table = cell.mix["output_tokens"]["table"]
    mean_out = sum(table) / len(table)
    for rate in (float(r) for r in args.rates.split(",")):
        eng = harness.new_engine(cell, core)
        queued = {}

        def hooks(event, eng=eng):
            queued["open"] = eng.scheduler.depth

        served, win = harness.run_load(
            eng, harness.plan_load(cell, args.seed, args.seconds, rate),
            time.perf_counter(), harness.WARM_IN_S, args.seconds, hooks=hooks)
        e2e, info = harness.end_to_end(served, win)
        print(json.dumps({
            "rate_req_s": rate, "queued_at_open": queued.get("open"),
            "queued_at_close": win["queued_at_close"],
            "completed_per_s": info["completed"] / args.seconds,
            "output_tokens_per_s": e2e["output_tokens_per_s"],
            "knee_from_tokens_req_s": e2e["output_tokens_per_s"] / mean_out,
            "ttft_p50_ms": info["ttft_p50_ms"],
            "ttft_p95_ms": e2e["ttft_p95_ms"],
            "ttft_censored": info["ttft_censored"],
            "itl_mean_ms": e2e["itl_mean_ms"], "itl_p95_ms": e2e["itl_p95_ms"],
            "slot_util": win["stats"]["slot_util"]}), flush=True)
        del eng, served
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
