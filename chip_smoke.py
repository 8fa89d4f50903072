#!/usr/bin/env python3
"""Smoke check of the serving data plane on a TPU, at full model width.

    python chip_smoke.py             # one chip: Qwen2.5-3B, inproc engine
    python chip_smoke.py --chips 4   # the sharded topology over 4 chips

One chip: Qwen2.5-3B at its published widths (36 layers, d_model 2048,
vocab 151936) with bf16 weights generated from ``--seed`` and the Pallas
kernels compiled for the chip.  The phases, each of which exits non-zero
on failure:

  1. the first device must be a TPU — nothing runs on any other platform;
  2. the compiled fused decode step must contain ``tpu_custom_call`` (the
     kernels compiled, they were not interpreted);
  3. parity: the prompts below are prefilled and decoded for PARITY_STEPS
     steps through the Pallas path and the jnp reference path
     (``use_pallas=False``, the same weights), both fed the Pallas path's
     greedy tokens; every logits row must agree within LOGIT_TOL, and the
     greedy tokens must be equal wherever the reference's top-2 margin
     exceeds 2·LOGIT_TOL (below that the two tokens tie within tolerance);
  4. serving: the same prompts go through ``ServingEngine.submit/step``
     (greedy rows plus one temperature row); each greedy stream must open
     with the parity phase's Pallas tokens.

Four chips (``--chips 4``): the sharded topology (a ("data",) mesh over
the four devices) against an inproc replica on one device — token streams
must be equal, and every leaf of the sharded pool's cache must span all
four devices.  No other phase runs.

Compile seconds, peak HBM and tokens/s are printed as smoke figures, not
benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
SLOTS = 8
MAX_SEQ = 2048
# 200 is over the 128-token flash block and not a multiple of it
PROMPT_LENS = (200, 64, 17, 200, 64, 17, 200, 64)
GEN_LENS = (32, 40, 24, 48, 32, 36, 28, 44)
TEMP_ROW, TEMPERATURE = 3, 0.8
PARITY_STEPS = 8
LOGIT_TOL = 0.25      # absolute; random-weight logits have std ~0.9


def log(msg: str):
    print(msg, flush=True)


def prompts(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def cache_counter():
    """Counts of JAX persistent-cache hits and misses in this process."""
    import jax

    counts = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def full_width_config(use_pallas: bool):
    from repro.configs import get_config

    return get_config(ARCH, param_dtype="bfloat16", use_pallas=use_pallas)


def check_kernels_compiled(core, slots: int):
    """Lower and compile the fused decode step; → compile seconds."""
    import jax.numpy as jnp

    from repro.serving.slots import make_pool

    pool = make_pool(core.cfg, slots, core.max_seq)
    vec = jnp.zeros((slots,), jnp.int32)
    t0 = time.perf_counter()
    text = core.fused_decode.lower(
        core.params, jnp.zeros((slots, 1), jnp.int32), pool.cache, vec, vec,
        vec, jnp.zeros((slots,), jnp.float32)).compile().as_text()
    dt = time.perf_counter() - t0
    n = text.count("tpu_custom_call")
    log(f"[kernels] fused decode step compiled in {dt:.2f}s; "
        f"tpu_custom_call sites: {n}")
    if n == 0:
        raise SystemExit("the compiled decode step holds no tpu_custom_call "
                         "— the Pallas kernels did not compile for the chip")
    return dt


def parity(core, ref_core, toks_in):
    """Teacher-forced Pallas-vs-reference comparison; → the Pallas path's
    greedy streams (prefill token + PARITY_STEPS decode tokens) per row."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.slots import make_pool

    pools = [make_pool(c.cfg, SLOTS, c.max_seq) for c in (core, ref_core)]
    rows = []
    for slot, p in enumerate(toks_in):
        out = []
        for c, pool in zip((core, ref_core), pools):
            logits, one = c.prefill(c.params, {"tokens": jnp.asarray(p[None])})
            pool.write(one, slot, index=len(p))
            out.append(np.asarray(logits[0, -1], np.float32))
        rows.append(out)
    streams = [[int(np.argmax(lp))] for lp, _ in rows]
    worst, n_tok, n_eq, n_tie = 0.0, 0, 0, 0

    def compare(lp, lr):
        nonlocal worst, n_tok, n_eq, n_tie
        worst = max(worst, float(np.max(np.abs(lp - lr))))
        top2 = np.partition(lr, -2)[-2:]
        n_tok += 1
        if int(np.argmax(lp)) == int(np.argmax(lr)):
            n_eq += 1
        elif top2[1] - top2[0] <= 2 * LOGIT_TOL:
            n_tie += 1
        else:
            raise SystemExit(f"greedy tokens differ with a reference margin "
                             f"of {top2[1] - top2[0]:.4f} > 2*LOGIT_TOL")

    for lp, lr in rows:
        compare(lp, lr)
    log(f"[parity] prefill logits std {np.std(rows[0][1]):.4f}")
    zero = jnp.zeros((SLOTS,), jnp.int32)
    temp = jnp.zeros((SLOTS,), jnp.float32)
    for _ in range(PARITY_STEPS):
        tok = jnp.asarray([[s[-1]] for s in streams], jnp.int32)
        got = []
        for c, pool in zip((core, ref_core), pools):
            toks, logits, pool.cache = c.fused_decode(
                c.params, tok, pool.cache, zero, zero, zero, temp)
            got.append((np.asarray(toks),
                        np.asarray(logits[:, 0], np.float32)))
        (tp, lp), (_, lr) = got
        for b in range(SLOTS):
            compare(lp[b], lr[b])
            streams[b].append(int(tp[b]))
    log(f"[parity] {n_tok} greedy tokens: {n_eq} equal, {n_tie} ties within "
        f"2*LOGIT_TOL; max |logit diff| {worst:.5f} (LOGIT_TOL {LOGIT_TOL})")
    if worst > LOGIT_TOL:
        raise SystemExit(f"Pallas and reference logits differ by {worst} > "
                         f"LOGIT_TOL {LOGIT_TOL}")
    return streams


def serve(engine, toks_in, seed: int):
    """Drive requests through submit/step; → (finished requests, seconds)."""
    from repro.serving import Request, SamplingParams

    reqs = [Request(rid=i, prompt=p, gen_len=g,
                    sampling=SamplingParams(
                        temperature=TEMPERATURE if i == TEMP_ROW else 0.0,
                        seed=seed))
            for i, (p, g) in enumerate(zip(toks_in, GEN_LENS))]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r, now=0.0)
    done, ticks = [], 0
    while len(done) < len(reqs):
        ticks += 1
        if ticks > 10 * max(GEN_LENS):
            raise SystemExit("serving did not finish")
        done.extend(engine.step(now=float(ticks)))
    return sorted(done, key=lambda r: r.rid), time.perf_counter() - t0


def run_one_chip(dev, seed: int):
    import jax
    import numpy as np

    from repro.serving import ServingEngine
    from repro.serving.engine import EngineCore

    cfg = full_width_config(use_pallas=True)
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, slots=SLOTS, max_seq=MAX_SEQ, seed=seed)
    core = engine.core
    wbytes = sum(x.nbytes for x in jax.tree.leaves(core.params))
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(core.params)})
    jax.block_until_ready(core.params)
    log(f"[config] {cfg.name}: layers {cfg.n_layers}, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab}, weights {wbytes} bytes {dtypes}, "
        f"use_pallas {cfg.use_pallas}; init {time.perf_counter() - t0:.2f}s")
    if dtypes != ["bfloat16"]:
        raise SystemExit(f"serving weights must be bf16, got {dtypes}")
    ref_cfg = dataclasses.replace(cfg, use_pallas=False)
    ref_core = EngineCore(ref_cfg, MAX_SEQ, params=core.params)

    compile_s = check_kernels_compiled(core, SLOTS)
    toks_in = prompts(cfg.vocab, seed)
    t0 = time.perf_counter()
    streams = parity(core, ref_core, toks_in)
    log(f"[parity] phase {time.perf_counter() - t0:.2f}s (compiles included)")

    done, wall = serve(engine, toks_in, seed)
    n_tok = sum(len(r.tokens_out) for r in done)
    for r, want_len in zip(done, GEN_LENS):
        toks = np.asarray(r.tokens_out)
        if len(toks) != want_len or toks.min() < 0 or toks.max() >= cfg.vocab:
            raise SystemExit(f"request {r.rid}: bad stream {r.tokens_out}")
        if r.rid != TEMP_ROW and r.tokens_out[:PARITY_STEPS + 1] != \
                streams[r.rid]:
            raise SystemExit(f"request {r.rid}: served stream "
                             f"{r.tokens_out[:PARITY_STEPS + 1]} != parity "
                             f"stream {streams[r.rid]}")
    log(f"[serve] {len(done)} requests, {n_tok} tokens in {wall:.3f}s; "
        f"greedy streams open with the parity tokens")
    stats = dev.memory_stats() or {}
    log(f"[smoke figures, not benchmark numbers] decode compile "
        f"{compile_s:.2f}s; serve {n_tok / wall:.1f} tokens/s; peak HBM "
        f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")


def run_four_chips(seed: int):
    import jax

    from repro.serving import ReplicaRouter, Request, SamplingParams

    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    cfg = full_width_config(use_pallas=True)
    toks_in = prompts(cfg.vocab, seed)

    def drive(topology):
        t0 = time.perf_counter()
        router = ReplicaRouter.from_topology(cfg, topology, slots=SLOTS,
                                             max_seq=MAX_SEQ, seed=seed)
        for i, (p, g) in enumerate(zip(toks_in, GEN_LENS)):
            router.submit(Request(
                rid=i, prompt=p, gen_len=g // 2,
                sampling=SamplingParams(
                    temperature=TEMPERATURE if i == TEMP_ROW else 0.0,
                    seed=seed)), now=0.0)
        done, now = [], 0.0
        while len(done) < len(toks_in):
            now += 1.0
            if now > 10 * max(GEN_LENS):
                raise SystemExit(f"{topology}: serving did not finish")
            done.extend(router.step(now))
        streams = {r.rid: list(r.tokens_out) for r in done}
        log(f"[{topology}] {len(done)} requests, "
            f"{sum(map(len, streams.values()))} tokens in "
            f"{time.perf_counter() - t0:.2f}s (compiles included)")
        return router, streams

    router, want = drive("inproc")
    del router              # frees the one-device weight copy
    gc.collect()
    router, got = drive("sharded")
    if got != want:
        bad = [rid for rid in want if got.get(rid) != want[rid]]
        raise SystemExit(f"sharded streams differ from inproc for rids {bad}")
    log(f"[sharded] token streams equal the inproc replica's "
        f"({len(want)} requests)")
    cache = router.replicas[0].engine.pool.cache
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        n = len(leaf.sharding.device_set)
        log(f"[sharded] cache leaf {jax.tree_util.keystr(path)} "
            f"{leaf.shape} on {n} devices")
        if leaf.sharding.device_set != set(devs):
            raise SystemExit("a cache leaf does not span all 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.runtime import require_platform, setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    dev = require_platform("tpu")
    log(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}")
    counts = cache_counter()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(dev, args.seed)
    log(f"[cache] persistent compile cache hits {counts['hits']}, misses "
        f"{counts['misses']}; total {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
