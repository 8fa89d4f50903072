"""Readings that set a cell's limits: the program's, the control's and
those of planted faults, each judged by the predicate that decides a run's
``correct`` (``harness.verdict``).

    python3 perfbench/control.py --workload qwen2.5-3b.gen \
        --seeds 11,12,13 --seconds 30 [--fault-runs state_unchanged]

For each seed, in one process: the cell's weights and traffic from the
seed, a window of the cell's own load through the served path, the sample
of finished requests that a run checks, and then, over the same prompts
and served tokens, the readings of the program and of each substitute in
``harness.SUBSTITUTES`` put in its place: the control (the reference in
float8 e4m3: the token it puts first, or draws at the request's
temperature) and three faults of host sampling (greedy instead of a draw,
a draw at temperature 1, the token of another request's row).

``--fault-runs`` names faults of ``faults.py`` planted in the program; each
then makes one whole run of the cell (``harness.run``) on the first seed.

One JSON line per seed and per fault run. The benchmark's own runs do not
run this; it is how the limits in ``cells/<workload>.json`` were set
(``PERF.md``).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seeds, seconds: float, require_chip: bool = True):
    """Yield one dict per seed: for the program and each substitute, the
    verdict and the numbers compared."""
    import numpy as np

    from perfbench import harness

    core = None
    for seed in seeds:
        b = harness.build(cell, seed, require_chip=require_chip, core=core)
        core = b.core
        served, win = harness.run_load(b.eng, harness.plan_load(cell, seed,
                                                                seconds),
                                       time.perf_counter(), harness.WARM_IN_S,
                                       seconds)
        failed, fin = harness.check_outputs(
            served, win, cell.config, cell.config["serving"]["max_seq"])
        sample = harness.pick_sample(fin, seed)
        w = b.w
        del b
        core.params = None
        gc.collect()
        r = harness.reference_readings(cell.config, w, sample,
                                       harness.SUBSTITUTES)
        out = {"seed": seed, "requests": len(sample)}
        for name, rd in r.items():
            correct, checks = harness.verdict(rd, failed, cell)
            d, v = rd["d"], rd["v"]
            out[name] = {"correct": correct,
                         **{k: c["value"] for k, c in checks.items()},
                         "sampled_z_signed": (float(d.sum() / np.sqrt(v.sum()))
                                              if d.size else None)}
        yield out
        del w, sample, served
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault-runs", default="",
                    help="comma-separated names from faults.py")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import faults, harness

    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    for r in readings(cell, seeds, args.seconds):
        print(json.dumps(r), flush=True)
    gc.collect()
    for name in filter(None, args.fault_runs.split(",")):
        r = harness.run(cell, seeds[0], args.seconds, False,
                        time.perf_counter(), fault=faults.ALL[name])
        print(json.dumps({"fault_run": name, "seed": seeds[0],
                          "correct": r["correct"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
