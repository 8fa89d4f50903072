"""One run of one benchmark cell: set-up, open-loop load, the measured
window, the check against the reference, and the result line.

The system under test is the single-replica served path
(``ServingEngine.submit`` / ``step``) on the dense pool, with bf16 weights
and the compiled Pallas kernels. The benchmark makes the weights itself
(``models/qwen2.py``) and hands them to the engine; the reference reads the
same weights under their published names and imports nothing of the
program.

Times are taken on the host clock after each ``step`` returns: a token is
delivered when the step that produced it hands control back. Requests are
timed from their scheduled arrival, not from when the loop got to them.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".perfbench_trace"
TRACE_SECONDS = 10.0           # the traced part: the end of the window
WARM_RID = 1 << 30             # request ids of warm-up requests
BUCKET = 256                   # reference sequences are padded to this
WARM_IN_S = 5.0                # load offered before the window opens
SAMPLE_TOKENS = 400            # the check's sample: at least these served
SAMPLE_MIN_REQUESTS = 8        # tokens and requests, greedy and sampled each
MEAN_LOGIT_GAP = 0.01          # limit on the mean gap of greedy tokens


class NoChip(RuntimeError):
    """JAX holds no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- the cell

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict               # configs/<config>.json
    mix: dict                  # traffic/<traffic>.json
    load: dict                 # cells/<workload>.json
    end_to_end: list           # BENCHMARK.json metrics reported by this cell
    per_layer: list


def _for_cell(metrics: list, name: str) -> list:
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(workload: str,
              bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_path}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    read = lambda p: json.loads((ROOT / p).read_text())
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=read(conf["file"]),
        mix=read(f"perfbench/traffic/{w['traffic']}.json"),
        load=read(f"perfbench/cells/{workload}.json"),
        end_to_end=_for_cell(bench["end_to_end"], workload),
        per_layer=_for_cell(bench["per_layer"], workload))


# ------------------------------------------------------------- set-up

def setup_jax_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program,
    however quick its compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    """→ the first ``n`` devices, or NoChip: never a fallback to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX holds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


class CompileCounter:
    """Counts the programs this process compiled or loaded (each one fires
    the backend-compile event), and of them the persistent-cache loads."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_loads = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_loads


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: its published
    sizes, bf16 weights and compute, the Pallas kernels."""
    from repro.models import ModelConfig

    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=H,
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg.get("head_dim", d // H), qkv_bias=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        dtype=cfg["serving"]["dtype"], param_dtype=cfg["serving"]["dtype"],
        use_pallas=True)


def program_params(w: dict, pcfg):
    """The benchmark's weights re-keyed into the program's parameter tree
    (the same arrays, no copy); checked leaf by leaf against the tree that
    the program's own init would build."""
    import jax

    from repro.models import LM

    blocks = {
        "ln1": {"scale": w["input_layernorm"]},
        "attn": {"wq": {"w": w["q_proj"], "b": w["q_bias"]},
                 "wk": {"w": w["k_proj"], "b": w["k_bias"]},
                 "wv": {"w": w["v_proj"], "b": w["v_bias"]},
                 "wo": {"w": w["o_proj"]}},
        "ln2": {"scale": w["post_attention_layernorm"]},
        "mlp": {"gate": {"w": w["gate_proj"]}, "up": {"w": w["up_proj"]},
                "down": {"w": w["down_proj"]}},
    }
    tree = {"embed": {"table": w["embed_tokens"]}, "blocks": blocks,
            "ln_f": {"scale": w["norm"]}}
    if "lm_head" in w:
        tree["lm_head"] = {"w": w["lm_head"]}
    want = jax.eval_shape(lambda k: LM.init(k, pcfg)[0],
                          jax.ShapeDtypeStruct((2,), np.uint32))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    need = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != need:
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")
    return tree


@dataclasses.dataclass
class Built:
    devs: list
    w: dict                    # the benchmark's weights, as the reference reads
    core: object               # EngineCore: the compiled programs
    eng: object                # ServingEngine, warmed up
    seconds: dict              # set-up phases: weights, warm-up


def new_engine(cell: Cell, core):
    """A fresh ServingEngine (empty pool and queue) over ``core``'s
    compiled programs."""
    from repro.serving import ServingEngine

    srv = cell.config["serving"]
    return ServingEngine(core.cfg, slots=int(srv["slots"]),
                         max_seq=int(srv["max_seq"]), core=core)


def build(cell: Cell, seed: int, require_chip: bool = True,
          core=None) -> Built:
    """Set-up of one cell from ``seed``: the weights in one jitted draw,
    the engine over them and, unless ``core`` (already warm) is given to
    take the new weights, the warm-up of every program the traffic uses."""
    setup_jax_cache()
    import jax

    from perfbench.models import qwen2
    from repro.serving.engine import EngineCore

    devs = require_chips(cell.chips) if require_chip else jax.devices()[:1]
    cfg = cell.config
    t0 = time.perf_counter()
    w = qwen2.make_weights(cfg, seed)
    jax.block_until_ready(w)
    t_w = time.perf_counter()
    pcfg = program_config(cfg)
    tree = program_params(w, pcfg)
    warm = core is None
    if warm:
        core = EngineCore(pcfg, int(cfg["serving"]["max_seq"]), params=tree)
    else:
        core.params = tree
    eng = new_engine(cell, core)
    if warm:
        warm_up(eng, core, cfg, cell.mix, int(cfg["serving"]["slots"]))
    return Built(devs=devs, w=w, core=core, eng=eng,
                 seconds={"weights": t_w - t0,
                          "warm_up": time.perf_counter() - t_w})


def plan_load(cell: Cell, seed: int, seconds: float, rate: float | None = None):
    """The cell's requests from ``seed``: the standing backlog, then the mix
    at the cell's rate (or ``rate``) through the warm-in and the window."""
    from perfbench import generator

    return generator.plan(
        cell.mix, float(rate if rate is not None else cell.load["rate_req_s"]),
        WARM_IN_S + seconds, cell.config["vocab_size"], seed,
        backlog=int(cell.load.get("backlog", 0)))


# ------------------------------------------------------------- spans

class Spans:
    """Harness spans around its calls into the program: host wall time per
    call, and a ``jax.profiler.TraceAnnotation`` of the same name so the
    device trace can say what the host was doing in each idle gap."""

    def __init__(self):
        self.records: list[tuple[str, float, float, object]] = []

    def wrap(self, name: str, fn, info=None):
        import jax

        def wrapped(*args, **kwargs):
            extra = info(*args, **kwargs) if info is not None else None
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kwargs)
            self.records.append((name, t0, time.perf_counter(), extra))
            return out

        return wrapped


def instrument(eng, spans: Spans):
    """Wrap the engine's layers, from the harness side: admission (prefill,
    pool write, first token), the decode tick (the fused step and host
    sampling), and record what each call computes."""
    core = eng.core
    eng.admit = spans.wrap("bench.admit", eng.admit)
    eng.tick = spans.wrap("bench.tick", eng.tick)
    core.prefill = spans.wrap(
        "bench.prefill", core.prefill,
        info=lambda params, inputs: int(inputs["tokens"].shape[1]))
    eng.prefill = core.prefill
    eng.pool.write = spans.wrap("bench.pool_write", eng.pool.write)
    # one emitted token: a greedy row's device token, or a sampled row's
    # logits pull and host draw
    eng._emit = spans.wrap("bench.emit", eng._emit)

    def decode_info(*_a, **_k):
        busy = np.nonzero(eng.active)[0]
        return [int(eng.pos[s]) + 1 for s in busy]

    core.fused_decode = spans.wrap("bench.decode", core.fused_decode,
                                   info=decode_info)


# ------------------------------------------------------------- load

@dataclasses.dataclass
class Served:
    plan: object
    request: object
    arrival: float                 # absolute host-clock seconds
    submitted: float = 0.0
    seen: int = 0
    token_times: list = dataclasses.field(default_factory=list)
    done_at: float | None = None


def warm_up(eng, core, cfg: dict, mix: dict, slots: int):
    """Compile (or load) every program the cell's traffic uses and no
    other: one prefill per length in the mix's table, every slot's pool
    write and first-token read, the fused decode step and, where the mix
    samples, every slot's logits-row pull."""
    import jax
    import jax.numpy as jnp

    from repro.serving import Request, SamplingParams

    lengths = sorted(set(mix["prompt_tokens"]["table"]))
    for n in lengths:
        logits, _ = core.prefill(core.params,
                                 {"tokens": jnp.zeros((1, n), jnp.int32)})
        jax.block_until_ready(logits)
    temp = float(mix["sampled"]["temperature"]) if mix["sampled"]["share"] \
        else 0.0
    rng = np.random.default_rng(0)
    for s in range(slots):
        eng.submit(Request(
            rid=WARM_RID + s,
            prompt=rng.integers(0, cfg["vocab_size"], size=lengths[0],
                                dtype=np.int32),
            gen_len=3, sampling=SamplingParams(temperature=temp, seed=s)))
    for _ in range(20):
        eng.step(now=time.perf_counter())
        if eng.idle:
            break
    if not eng.idle:
        raise RuntimeError("warm-up requests did not finish")


def run_load(eng, planned, t_load: float, warm_in: float, seconds: float,
             tracer=None, hooks=None):
    """Open loop: each planned request is submitted once the host clock
    passes its arrival, then ``step`` runs while there is work. Stops at
    the window's close. → (served records, window dict)."""
    from repro.serving import Request, SamplingParams

    clock = time.perf_counter
    t_open = t_load + warm_in
    t_close = t_open + seconds
    trace_from = max(t_open, t_close - TRACE_SECONDS)
    served: list[Served] = []
    by_rid: dict[int, Served] = {}
    win = {"t_open": t_open, "t_close": t_close, "opened": False,
           "trace": None}
    i = 0
    while True:
        now = clock()
        if now >= t_close:
            break
        if not win["opened"] and now >= t_open:
            win["opened"] = True
            eng.stats.drain_window()
            if hooks:
                hooks("open")
        if tracer is not None and win["trace"] is None and now >= trace_from:
            win["trace"] = tracer.start()
        while i < len(planned) and t_load + planned[i].arrival_s <= now:
            p = planned[i]
            r = Request(rid=p.rid, prompt=p.prompt, gen_len=p.gen_len,
                        sampling=SamplingParams(temperature=p.temperature,
                                                seed=p.sample_seed))
            s = Served(plan=p, request=r, arrival=t_load + p.arrival_s,
                       submitted=now)
            eng.submit(r, now=s.arrival)
            served.append(s)
            by_rid[p.rid] = s
            i += 1
        if eng.idle:
            nxt = (t_load + planned[i].arrival_s if i < len(planned)
                   else t_close)
            if not win["opened"]:
                nxt = min(nxt, t_open)
            if tracer is not None and win["trace"] is None:
                nxt = min(nxt, trace_from)
            time.sleep(max(0.0, min(nxt, t_close) - clock()))
            continue
        done = eng.step(now=now)
        t_ret = clock()
        for r in list(eng.slot_owner.values()) + done:
            s = by_rid.get(r.rid)
            if s is None:
                continue
            n = len(r.tokens_out)
            if n > s.seen:
                s.token_times.extend([t_ret] * (n - s.seen))
                s.seen = n
        for r in done:
            s = by_rid.get(r.rid)
            if s is not None:
                s.done_at = t_ret
    if tracer is not None and win["trace"] is not None:
        win["trace"] = tracer.stop(win["trace"])
    win["stats"] = eng.stats.drain_window()
    win["queued_at_close"] = eng.scheduler.depth
    return served, win


# ------------------------------------------------------------- metrics

def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(served: list[Served], win: dict) -> tuple[dict, dict]:
    """→ (the end-to-end metrics, what the earlier lines print)."""
    t_open, t_close = win["t_open"], win["t_close"]
    seconds = t_close - t_open
    n_tok, gaps = 0, []
    for s in served:
        tt = s.token_times
        n_tok += sum(1 for t in tt if t_open <= t < t_close)
        gaps.extend(b - a for a, b in zip(tt, tt[1:]) if t_open <= b < t_close)
    arrived = [s for s in served if t_open <= s.arrival < t_close]
    ttft = [((s.token_times[0] if s.token_times and s.token_times[0] < t_close
              else t_close) - s.arrival) for s in arrived]
    out = {"output_tokens_per_s": n_tok / seconds,
           "itl_mean_ms": 1e3 * float(np.mean(gaps)) if gaps else None,
           "itl_p95_ms": 1e3 * pct(gaps, 95) if gaps else None,
           "ttft_p95_ms": 1e3 * pct(ttft, 95) if ttft else None}
    info = {"window_s": seconds, "tokens": n_tok, "gaps": len(gaps),
            "arrived": len(arrived),
            "ttft_censored": sum(1 for s in arrived if not s.token_times
                                 or s.token_times[0] >= t_close),
            "ttft_p50_ms": 1e3 * pct(ttft, 50) if ttft else None,
            "completed": sum(1 for s in served if s.done_at is not None
                             and t_open <= s.done_at < t_close)}
    late = [s.submitted - s.arrival for s in served]
    info["generator_late_ms_p50"] = 1e3 * pct(late, 50) if late else None
    info["generator_late_ms_max"] = 1e3 * max(late) if late else None
    return out, info


# ------------------------------------------------------------- checks

def check_outputs(served: list[Served], win: dict, cfg: dict, max_seq: int):
    """Every request the run finished (in the warm-in or the window) has its
    full count of tokens, each a vocabulary id. → (failed count, finished
    records)."""
    V = cfg["vocab_size"]
    fin = [s for s in served if s.done_at is not None
           and s.done_at < win["t_close"]]
    bad = 0
    for s in fin:
        toks = s.request.tokens_out
        want = min(s.plan.gen_len, max_seq - len(s.plan.prompt))
        if len(toks) != want or min(toks) < 0 or max(toks) >= V:
            bad += 1
    return bad, fin


def pick_sample(fin: list[Served], seed: int) -> list[Served]:
    """Finished requests to check, greedy and sampled apart: of each kind
    the longest, then others in a seeded order until the kind holds enough
    requests and served tokens."""
    rng = np.random.default_rng([int(seed), 0x636b])
    out = []
    for kind in (lambda s: s.plan.temperature == 0.0,
                 lambda s: s.plan.temperature > 0.0):
        group = [s for s in fin if kind(s)]
        if not group:
            continue
        group.sort(key=lambda s: (
            -(len(s.plan.prompt) + len(s.request.tokens_out)), s.plan.rid))
        rest = [group[j] for j in rng.permutation(len(group) - 1) + 1]
        got, n = [group[0]], len(group[0].request.tokens_out)
        for s in rest:
            if n >= SAMPLE_TOKENS and len(got) >= SAMPLE_MIN_REQUESTS:
                break
            got.append(s)
            n += len(s.request.tokens_out)
        out.extend(got)
    return out


# What can stand in the program's place at the check's positions: the
# control (float8 in the reference's place: its argmax for greedy requests,
# its own draw at the request's temperature for sampled ones), and three
# faults of host sampling, each acting on sampled requests only.
SUBSTITUTES = ("fp8", "greedy", "temperature_1", "wrong_row")


def reference_readings(cfg: dict, w: dict, sample,
                       substitutes: tuple = ()) -> dict:
    """Each sampled request's prompt and served tokens, run once through the
    float32 reference. → for the program and each of ``substitutes``
    (``SUBSTITUTES``), the readings at every served position:

    * ``gaps`` (greedy requests): how far the token's logit lies below the
      reference's best;
    * ``d``, ``v`` (sampled requests, temperature T): ``E[y] - y_token``
      and ``Var[y]`` for ``y = logit / T`` under the reference's
      ``softmax(y)``. For a token drawn from that distribution ``d`` has
      mean 0 and variance ``v``, so ``sum(d) / sqrt(sum(v))`` is about a
      standard normal; a token drawn from another distribution moves it
      by about the square root of the count.

    A substitute changes the tokens scored, never the sequence run."""
    from perfbench.models import qwen2

    names = ("program",) + tuple(substitutes)
    acc = {n: {"gaps": [], "d": [], "v": []} for n in names}
    sampled = [s for s in sample if s.plan.temperature > 0.0]
    for s in sample:
        prompt = np.asarray(s.plan.prompt, np.int32)
        toks = np.asarray(s.request.tokens_out, np.int32)
        seq = np.concatenate([prompt, toks[:-1]])
        P, n = len(prompt), len(toks)
        S = -(-len(seq) // BUCKET) * BUCKET
        rows = slice(P - 1, P - 1 + n)
        tokens = np.zeros(S, np.int32)
        tokens[:len(seq)] = seq
        T = float(s.plan.temperature)
        cols = {"program": toks}
        if "wrong_row" in names and T > 0 and len(sampled) > 1:
            # every position takes a token that another request's row
            # gave, repeated where that request is the shorter
            other = sampled[(sampled.index(s) + 1) % len(sampled)]
            cols["wrong_row"] = np.resize(
                np.asarray(other.request.tokens_out, np.int32), n)
        if "fp8" in names:
            r8 = qwen2.logit_scan(cfg, w, tokens, np.full((S, 1), -1),
                                  "fp8", draw_seed=s.plan.sample_seed,
                                  draw_temperature=T or 1.0)
            cols["fp8"] = np.asarray(r8["draw" if T > 0 else "argmax"])[rows]
        keys = list(cols)
        targets = np.full((S, len(keys)), -1, np.int32)
        for k, name in enumerate(keys):
            targets[rows, k] = cols[name]
        r = qwen2.logit_scan(cfg, w, tokens, targets, temperature=T or 1.0,
                             draw_seed=s.plan.sample_seed + 1,
                             draw_temperature=1.0)
        r = {k: np.asarray(v) for k, v in r.items()}
        best = r["best"][rows]
        for name in names:
            if T > 0 and name == "greedy":
                logit = best
            elif T > 0 and name == "temperature_1":
                logit = r["draw_logit"][rows]
            else:
                logit = r["target"][rows, keys.index(
                    name if name in cols else "program")]
            if T > 0:
                acc[name]["d"].append(r["mean_y"][rows] - logit / T)
                acc[name]["v"].append(r["var_y"][rows])
            else:
                acc[name]["gaps"].append(best - logit)
    return {n: {k: np.concatenate(v) if v else np.zeros(0)
                for k, v in a.items()} for n, a in acc.items()}


def verdict(readings: dict, failed: int, cell: Cell) -> tuple[bool, dict]:
    """The one predicate that decides ``correct``, from one set of
    ``reference_readings`` and the count of malformed requests. → (correct,
    each number compared beside its limit)."""
    limits = cell.load["check"]
    g, d, v = readings["gaps"], readings["d"], readings["v"]
    checks = {
        "max_logit_gap": {"value": float(g.max()) if g.size else None,
                          "limit": limits["max_logit_gap"]},
        "mean_logit_gap": {"value": float(g.mean()) if g.size else None,
                           "limit": MEAN_LOGIT_GAP},
        "greedy_tokens": {"value": int(g.size), "limit": SAMPLE_TOKENS},
    }
    ok = (g.size >= SAMPLE_TOKENS
          and checks["max_logit_gap"]["value"] <= limits["max_logit_gap"]
          and checks["mean_logit_gap"]["value"] <= MEAN_LOGIT_GAP)
    if cell.mix["sampled"]["share"] > 0:
        z = abs(float(d.sum())) / float(np.sqrt(v.sum())) if d.size else None
        checks["sampled_z"] = {"value": z, "limit": limits["max_sampled_z"]}
        checks["sampled_tokens"] = {"value": int(d.size),
                                    "limit": SAMPLE_TOKENS}
        ok = (ok and d.size >= SAMPLE_TOKENS
              and z <= limits["max_sampled_z"])
    checks["malformed_requests"] = {"value": failed, "limit": 0}
    return bool(ok and failed == 0), checks


# ------------------------------------------------------------- trace

class Tracer:
    def __init__(self, path: Path):
        self.path = path

    def start(self):
        import jax

        shutil.rmtree(self.path, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.path), profiler_options=opts)
        return time.perf_counter()

    def stop(self, t_start):
        import jax

        jax.profiler.stop_trace()
        return (t_start, time.perf_counter())


def per_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric is read by ``metrics/<name>.py``; a reader
    that finds nothing returns None and the metric is left out."""
    import importlib

    out = {}
    for m in cell.per_layer:
        mod = importlib.import_module(f"perfbench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------- the run

def log(msg: str):
    print(msg, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, fault=None) -> dict:
    """One run of ``cell``. ``t_start``: the process's start on the
    ``time.perf_counter`` clock. ``fault``: a hook that breaks the timed
    path (given the engine) before the load starts (``faults.py``)."""
    setup_jax_cache()
    counter = CompileCounter()
    t_jax = time.perf_counter()
    b = build(cell, seed, require_chip=require_chip)
    devs = b.devs
    dev = devs[0]
    cfg = cell.config
    max_seq = int(cfg["serving"]["max_seq"])
    eng = b.eng
    log(f"[setup] start to JAX on the device {t_jax - t_start:.3f}s; "
        f"weights {b.seconds['weights']:.3f}s; warm-up "
        f"{b.seconds['warm_up']:.3f}s; programs {counter.compiles}, of them "
        f"loaded from the cache {counter.cache_loads}")
    spans = Spans()
    if trace:
        instrument(eng, spans)
    if fault is not None:
        fault(eng)

    planned = plan_load(cell, seed, seconds)
    at_open = {}

    def hooks(event):
        at_open["compiles"] = counter.snapshot()

    tracer = Tracer(TRACE_DIR) if trace else None
    served, win = run_load(eng, planned, time.perf_counter(), WARM_IN_S,
                           seconds, tracer=tracer, hooks=hooks)
    setup_s = win["t_open"] - t_start
    c1 = counter.snapshot()
    c0 = at_open.get("compiles", c1)
    e2e, info = end_to_end(served, win)
    stats = win["stats"]
    log(f"[window] {info['window_s']:.3f}s: {info['arrived']} arrivals, "
        f"{info['completed']} completed, {info['tokens']} tokens, "
        f"{info['gaps']} inter-token gaps; queue at close "
        f"{win['queued_at_close']}; slot occupancy {stats['slot_util']:.4f}")
    log(f"[window] generator late p50 {info['generator_late_ms_p50']} ms, "
        f"max {info['generator_late_ms_max']} ms; ttft p50 "
        f"{info['ttft_p50_ms']} ms, p95 {e2e['ttft_p95_ms']} ms "
        f"({info['ttft_censored']} censored at close)")
    log(f"[window] programs compiled or loaded inside the window "
        f"{c1[0] - c0[0]} (should be 0)")

    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    failed, fin = check_outputs(served, win, cfg, max_seq)
    sample = pick_sample(fin, seed)

    ctx = None
    if trace:
        ctx = trace_context(cell, served, win, spans, eng, dev)

    # free the program's state (pool, compiled steps) before the reference
    # runs; the weights stay, the reference reads them
    w = b.w
    del b, eng, spans
    gc.collect()
    t_ref = time.perf_counter()
    readings = reference_readings(cfg, w, sample)["program"]
    t_ref = time.perf_counter() - t_ref
    correct, checks = verdict(readings, failed, cell)
    log(f"[check] {len(sample)} requests, {readings['gaps'].size} greedy and "
        f"{readings['d'].size} sampled tokens against the float32 reference "
        f"in {t_ref:.3f}s")

    metrics = {}
    if trace:
        metrics = per_layer_metrics(cell, ctx)
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    log(f"[metrics] setup_s {setup_s:.4f}; end to end "
        + json.dumps({k: v for k, v in e2e.items()}))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": info["arrived"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx["busy_s"]
        device["window_s"] = ctx["window_s"]
        result["breakdown"] = ctx["breakdown"]
    result["checks"] = checks
    return result


def trace_context(cell: Cell, served, win, spans: Spans, eng, dev) -> dict:
    """What the per-layer readers read: the reduced device trace of the
    traced part of the window, the harness spans, and the counters."""
    from perfbench import trace as tr

    t0, t1 = win["trace"]
    tdata = tr.load(tr.find_xplane(str(TRACE_DIR)))
    dev_id = int(getattr(dev, "id", 0))
    ops = tdata.ops.get(dev_id) or next(iter(tdata.ops.values()), [])
    modules = tdata.modules.get(dev_id) or next(iter(tdata.modules.values()),
                                                [])
    host = [s for s in tdata.spans]
    # the trace's own clock: from its first to its last recorded event
    stamps = [x[0] for x in ops] + [x[1] for x in ops] + \
        [x[0] for x in host] + [x[1] for x in host]
    lo, hi = (min(stamps), max(stamps)) if stamps else (0, 0)
    window_ns = hi - lo
    busy = tr.busy_ns(ops, lo, hi)
    by_op = tr.op_time_by_name(ops, lo, hi)
    idle = tr.idle_by_span(ops, host, lo, hi)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    rec = [r for r in spans.records if t0 <= r[1] and r[2] <= t1]
    return {
        "cell": cell, "config": cell.config, "served": served, "win": win,
        "spans": rec, "span_window": (t0, t1), "ops": ops,
        "modules": modules, "trace_lo": lo, "trace_hi": hi,
        "busy_s": busy / 1e9, "window_s": window_ns / 1e9,
        "peaks": peaks_for(dev.device_kind),
        "breakdown": {"device_ops": [[k, v / 1e9] for k, v in top],
                      "idle_gaps": [[k, v / 1e9] for k, v in top_idle]},
    }


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]
