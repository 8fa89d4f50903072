"""Serving driver CLI over the repro.serving subsystem.

The engine itself lives in repro/serving/ (continuous batching, chunked
prefill, per-slot ring positions, seeded sampling); this module keeps the
seed's CLI surface and re-exports ServingEngine/_write_slot for backward
compatibility.  Per-request latency (p50/p95), throughput, and slot
utilization are reported — the same metrics the paper's monitoring stream
consumes (core/monitoring).

Smoke size on the CPU (jnp reference path):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen2.5-3b --smoke --requests 24 --max-seq 96

Full width on a TPU — bf16 weights and the Pallas kernels; without
``--smoke`` the run fails unless JAX holds a TPU:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --pallas --slots 8 --max-seq 2048 --prompt-len 200 --gen-len 32
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.runtime import require_platform, setup_compile_cache
from repro.models.config import DTYPES
from repro.serving import SamplingParams, ServingEngine, synthetic_requests
from repro.serving.slots import write_slot as _write_slot  # noqa: F401 (compat)
from repro.sim.serving import WorkloadSpec

__all__ = ["ServingEngine", "_write_slot", "main"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="stream prompts through the decode tick in chunks")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--arrival-rps", type=float, default=100.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--param-dtype", choices=sorted(DTYPES), default=None,
                    help="weight dtype (default: bfloat16 at full width, "
                         "the config's own with --smoke)")
    ap.add_argument("--pallas", action="store_true",
                    help="Pallas kernels (compiled on a TPU, interpreted "
                         "on the CPU) instead of the jnp reference")
    args = ap.parse_args(argv)

    setup_compile_cache()
    if not args.smoke:
        require_platform("tpu")
    overrides = {"use_pallas": args.pallas}
    param_dtype = args.param_dtype or (None if args.smoke else "bfloat16")
    if param_dtype:
        overrides["param_dtype"] = param_dtype
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch,
                                                          **overrides)
    eng = ServingEngine(cfg, slots=args.slots, max_seq=args.max_seq,
                        seed=args.seed, prefill_chunk=args.prefill_chunk)
    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rps,
                                         args.requests))
    spec = WorkloadSpec(prompt_len=args.prompt_len, gen_len=args.gen_len)
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              seed=args.seed)
    requests = synthetic_requests(spec, args.requests, cfg.vocab, rng=rng,
                                  sampling=sampling)

    t0 = time.time()

    def clock():
        return time.time() - t0

    submitted = 0
    finished: list = []
    while len(finished) < args.requests:
        now = clock()
        while submitted < args.requests and arrivals[submitted] <= now:
            eng.submit(requests[submitted], now=arrivals[submitted])
            submitted += 1
        if eng.idle:
            time.sleep(0.001)
            continue
        # the clock itself: each request is stamped when its token lands
        finished.extend(eng.step(now=clock))

    total = time.time() - t0
    lats = np.array(sorted(r.latency_s for r in finished))
    toks = sum(len(r.tokens_out) for r in finished)
    print(f"requests={args.requests} gen_tokens={toks} wall={total:.2f}s "
          f"throughput={toks / total:.1f} tok/s")
    print(f"latency p50={np.percentile(lats, 50) * 1e3:.0f}ms "
          f"p95={np.percentile(lats, 95) * 1e3:.0f}ms "
          f"slot_util={eng.stats.slot_utilization:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
