"""End-to-end integration: (1) the DNN-powered allocator beats the reactive
threshold baseline on the roofline-grounded simulator (the paper's headline
claim, small scale); (2) the training driver runs, checkpoints, and resumes
deterministically; (3) the serving engine serves real batched requests.
"""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.allocation.allocator import AllocatorConfig, PredictiveAllocator
from repro.core.dnn.features import deploy_vector
from repro.core.scaling.scaler import ScalingConstraints
from repro.sim import (
    Cluster, RooflineDB, ServiceProfile, ServingModel, TraceConfig,
    ThresholdAutoscaler, WorkloadSpec, generate_trace,
)

REPO = Path(__file__).resolve().parents[1]
DRYRUN = REPO / "results" / "dryrun"


def run_fleet(decider, n_ticks=400, seed=0, tick_s=60.0):
    """Tick loop: trace → serving model → metrics → decider → cluster."""
    db = RooflineDB(DRYRUN)
    prof = ServiceProfile.from_db(db, "qwen2.5-3b")
    w = WorkloadSpec(prompt_len=512, gen_len=64)
    cap1 = prof.requests_per_s(w)                      # rps one replica serves
    trace = generate_trace(TraceConfig(base_rps=cap1 * 10, ticks_per_day=96,
                                       seed=seed), n_ticks)
    model = ServingModel(prof, w, slo_ms=30_000.0, tick_s=tick_s, seed=seed)
    cluster = Cluster(chips_per_replica=prof.chips_per_replica, tick_s=tick_s,
                      seed=seed)
    cluster.scale_to(8)
    cluster.tick = 10**6                               # start warm
    utils, lats, served, errs = [], [], 0, 0
    for t in range(n_ticks):
        ready = max(cluster.ready_replicas(), 1)
        r = model.tick(ready, trace[t])
        metrics = {
            "rps": trace[t], "rps_window": trace[max(0, t - 8):t + 1],
            "flop_util": r.utilization, "hbm_util": r.utilization,
            "ici_util": r.utilization * 0.5, "mem_frac": 0.5,
            "latency_p50": float(np.median(r.latency_ms_samples)),
            "latency_p95": float(np.percentile(r.latency_ms_samples, 95)),
            "throughput": r.served, "error_rate": r.errors / max(r.served, 1),
            "queue_depth": r.queue_depth,
            "replicas_frac": cluster.total_replicas() / 64,
        }
        target = decider(metrics, cluster.total_replicas(), model)
        cluster.scale_to(target)
        cluster.advance()
        utils.append(r.utilization)
        lats.append(metrics["latency_p95"])
        served += r.served
        errs += r.errors
    return {
        "util": float(np.mean(utils)),
        "p95_ms": float(np.mean(lats)),
        "cost_per_req": cluster.spend_usd / max(served, 1),
        "error_rate": errs / max(served + errs, 1),
        "spend": cluster.spend_usd,
    }


def test_dnn_allocator_beats_threshold_baseline():
    """The paper's §4.1.1 comparison at test scale: proactive DNN allocation
    must improve utilization AND cost-per-inference without raising errors."""
    slo = 30_000.0

    thr = ThresholdAutoscaler(hi=0.75, lo=0.25, patience=3, max_step=2,
                              max_replicas=64)
    base = run_fleet(lambda m, cur, model: thr.decide(m, cur))

    db = RooflineDB(DRYRUN)
    prof = ServiceProfile.from_db(db, "qwen2.5-3b")
    model_holder = {}

    def perf_model(replicas, rps):
        return model_holder["m"].latency_util(replicas, rps)

    alloc = PredictiveAllocator(
        perf_model, ScalingConstraints(max_replicas=64, slo_ms=slo),
        deploy_vector(model_params_b=3, family="dense", mesh_model=16,
                      mesh_data=16, region_idx=0, slo_ms=slo, cost_weight=0.5),
        cfg=AllocatorConfig(mode="planner"))

    def dnn_decide(metrics, current, model):
        model_holder["m"] = model
        alloc.replicas = current
        alloc.observe(metrics)
        d = alloc.decide(metrics)
        alloc.apply(d)
        return d.target_replicas

    ours = run_fleet(dnn_decide)

    assert ours["util"] > base["util"] * 1.05, (ours, base)
    assert ours["cost_per_req"] < base["cost_per_req"] * 0.95, (ours, base)
    assert ours["error_rate"] <= base["error_rate"] + 0.01


def test_train_driver_checkpoints_and_resumes(tmp_path):
    """launch.train main(): run 6 steps, kill, resume — the resumed run must
    continue from the checkpoint step and produce finite losses."""
    from repro.launch.train import main
    log1 = tmp_path / "a.jsonl"
    rc = main(["--arch", "qwen2.5-3b", "--smoke", "--steps", "6",
               "--seq", "32", "--batch", "2", "--ckpt-dir", str(tmp_path / "ck"),
               "--ckpt-every", "3", "--log", str(log1)])
    assert rc == 0
    from repro.checkpoint import CheckpointManager
    assert CheckpointManager(tmp_path / "ck").latest_step() == 6

    log2 = tmp_path / "b.jsonl"
    rc = main(["--arch", "qwen2.5-3b", "--smoke", "--steps", "9",
               "--seq", "32", "--batch", "2", "--ckpt-dir", str(tmp_path / "ck"),
               "--resume", "--log", str(log2)])
    assert rc == 0
    recs = [json.loads(l) for l in log2.read_text().splitlines()]
    assert recs[-1]["step"] == 9
    assert all(np.isfinite(r["loss"]) for r in recs)


def test_serve_driver_end_to_end():
    """launch.serve: real model, batched continuous decode, requests finish."""
    from repro.launch.serve import main
    rc = main(["--arch", "qwen2.5-3b", "--smoke", "--requests", "6",
               "--slots", "2", "--max-seq", "48", "--prompt-len", "12",
               "--gen-len", "6", "--arrival-rps", "50"])
    assert rc == 0


def test_serve_driver_pallas_bf16_smoke():
    """The chip path's flags at smoke size: bf16 weights and the Pallas
    kernels (interpreted on the CPU backend)."""
    from repro.launch.serve import main
    rc = main(["--arch", "qwen2.5-3b", "--smoke", "--pallas",
               "--param-dtype", "bfloat16", "--requests", "3",
               "--slots", "2", "--max-seq", "40", "--prompt-len", "9",
               "--gen-len", "3", "--arrival-rps", "50"])
    assert rc == 0


def test_serve_driver_full_width_needs_a_tpu():
    """Without --smoke the driver is on the chip path: on the CPU it must
    fail before building the 3B-parameter model, not serve on the CPU."""
    from repro.launch.runtime import PlatformError
    from repro.launch.serve import main
    with pytest.raises(PlatformError):
        main(["--arch", "qwen2.5-3b", "--pallas"])


def test_serving_engine_decode_matches_single_request():
    """Slot-batched decode must produce the same tokens as a fresh
    single-request engine for the same prompt (batching is transparent)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.launch.serve import ServingEngine

    cfg = get_smoke_config("qwen2.5-3b")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab, size=10).astype(np.int32)
               for _ in range(2)]

    def gen(engine, slot, prompt, n):
        engine.admit(slot, prompt, n)
        out = []
        while engine.active[slot]:
            tok_before = int(engine.tokens[slot, 0])
            out.append(tok_before)
            engine.tick()
        return out

    e1 = ServingEngine(cfg, slots=2, max_seq=32, seed=0)
    # run both prompts concurrently in different slots
    e1.admit(0, prompts[0], 4)
    e1.admit(1, prompts[1], 4)
    toks_concurrent = {0: [int(e1.tokens[0, 0])], 1: [int(e1.tokens[1, 0])]}
    for _ in range(4):
        e1.tick()
        toks_concurrent[0].append(int(e1.tokens[0, 0]))
        toks_concurrent[1].append(int(e1.tokens[1, 0]))

    e2 = ServingEngine(cfg, slots=2, max_seq=32, seed=0)
    solo = gen(e2, 0, prompts[0], 4)
    assert toks_concurrent[0][:4] == solo[:4]
