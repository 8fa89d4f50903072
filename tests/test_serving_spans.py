"""The engine's own spans and counters: a tiny ServingEngine serves greedy
and sampled requests under ``jax.profiler.start_trace``, and the written
trace holds every ``serve.*`` span, nested as the host loop runs them and
tagged with the request's id; ``EngineStats`` counts the emitted tokens
and the bytes pulled from the device as the shapes say.  Also the stamps:
a request's first-token and completion times are taken when its token is
on the host, on the clock the caller passes."""
import glob
import time

import jax
import numpy as np
import pytest

from repro.serving import Request, SamplingParams, ServingEngine
from repro.serving.engine import EngineCore
from repro.serving.spans import NAMES

from conftest import TINY_CFGS

MAX_SEQ, SLOTS, PROMPT, GEN = 24, 2, 6, 4
CFG = TINY_CFGS["dense"]
V = CFG.vocab


@pytest.fixture(scope="module")
def core():
    return EngineCore(CFG, MAX_SEQ, seed=0)


def _requests(prompt=None):
    """Two greedy and two sampled requests, each fitting one prefill; of
    the sampled, one draws over the full vocabulary (on the device) and one
    over its top 8 (on the host)."""
    rng = np.random.default_rng(0)
    out = []
    for i in range(4):
        p = (np.asarray(prompt, np.int32) if prompt is not None else
             rng.integers(3, V, size=PROMPT).astype(np.int32))
        out.append(Request(rid=100 + i, prompt=p, gen_len=GEN,
                           sampling=SamplingParams(
                               temperature=0.8 if i % 2 else 0.0,
                               top_k=8 if i == 3 else 0, seed=i)))
    return out


def _engine(core, path):
    eng = ServingEngine(CFG, slots=SLOTS, max_seq=MAX_SEQ, core=core,
                        spec_k=2 if path == "verify" else 0, spec_ngram=1)
    if path == "legacy":
        inner = core.decode
        eng.decode = lambda *a: inner(*a)     # a replaced decode step
    return eng


def _serve_traced(eng, reqs, trace_dir):
    for r in reqs:
        eng.submit(r, now=0.0)
    done, t = [], 0.0
    jax.profiler.start_trace(str(trace_dir))
    try:
        while len(done) < len(reqs):
            t += 1.0
            done.extend(eng.step(now=t))
    finally:
        jax.profiler.stop_trace()
    return done


def _spans(trace_dir):
    """[(start, end, name, stats)] of every ``serve.*`` event on the host."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name,
                                {k: v for k, v in e.stats}))
    return sorted(out)


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1] and inner is not outer


def _parents(sp, spans, name):
    return [o for o in spans if o[2] == name and _inside(sp, o)]


@pytest.mark.parametrize("path", ["fused", "verify", "legacy"])
def test_spans_nest_carry_rids_and_counters_match_shapes(core, path,
                                                         tmp_path):
    # repeated prompt tokens give the verify path drafts to check
    reqs = _requests(prompt=[7] * PROMPT if path == "verify" else None)
    eng = _engine(core, path)
    done = _serve_traced(eng, reqs, tmp_path)
    spans = _spans(tmp_path)
    names = {s[2] for s in spans}
    assert names == set(NAMES), set(NAMES) ^ names

    by_rid = {r.rid: r for r in reqs}
    sampled = {r.rid for r in reqs if r.sampling.temperature > 0}
    host_drawn = {r.rid for r in reqs if r.rid in sampled
                  and r.sampling.top_k > 0}
    assert host_drawn and sampled - host_drawn
    for sp in spans:
        name, meta = sp[2], sp[3]
        if name in ("serve.row_pull", "serve.host_draw"):
            holders = (_parents(sp, spans, "serve.tick")
                       + _parents(sp, spans, "serve.admit"))
            assert len(holders) == 1, sp
            assert len(_parents(holders[0], spans, "serve.step")) == 1
        if name in ("serve.admit", "serve.row_pull", "serve.host_draw"):
            assert meta["rid"] in by_rid, sp
            assert eng.slots > meta["slot"] >= 0
        if name in ("serve.decode_dispatch", "serve.decode_wait"):
            assert len(_parents(sp, spans, "serve.tick")) == 1, sp
        if name in ("serve.prefill", "serve.pool_write"):
            assert len(_parents(sp, spans, "serve.admit")) == 1, sp
    # a host draw is a top_k request's; a full-vocabulary sampled request
    # takes the device's draw and pulls no row; an admission's spans share
    # its rid
    pulls = {sp[3]["rid"] for sp in spans
             if sp[2] in ("serve.row_pull", "serve.host_draw")}
    assert pulls == host_drawn
    draws = [sp for sp in spans if sp[2] == "serve.host_draw"]
    in_ticks = [sp for sp in draws if _parents(sp, spans, "serve.tick")]
    assert {sp[3]["rid"] for sp in in_ticks} == host_drawn
    for adm in (sp for sp in spans if sp[2] == "serve.admit"):
        inner = [sp for sp in spans if _inside(sp, adm)
                 and sp[2] in ("serve.row_pull", "serve.host_draw")]
        assert {sp[3]["rid"] for sp in inner} == (
            {adm[3]["rid"]} & host_drawn)
        assert bool(inner) == (adm[3]["rid"] in host_drawn)

    win, life = eng.stats.drain_window(), eng.lifetime()
    emitted = sum(len(r.tokens_out) for r in done)
    assert win["emitted_tokens"] == life["emitted_tokens"] == emitted \
        == len(reqs) * GEN
    assert win["device_draws"] == life["device_draws"] \
        == len(sampled - host_drawn) * GEN
    ticks = sum(1 for sp in spans if sp[2] == "serve.tick")
    row = V * 4                                  # one float32 logits row
    # an admission pulls one int32, or a host-drawn request's row
    admitted = (len(reqs) - len(host_drawn)) * 4 + len(host_drawn) * row
    if path in ("fused", "legacy"):
        tick_rows = len(host_drawn) * (GEN - 1)  # the admission drew one
        want = ticks * SLOTS * 4 + tick_rows * row + admitted
        assert win["rows_pulled"] == eng.logits_pulls == tick_rows
    else:
        # (slots, W) int32 tokens per verify tick, W >= 2
        assert eng.stats.total_spec_proposed > 0
        tick_rows = len(in_ticks)
        assert win["rows_pulled"] == eng.logits_pulls == tick_rows
        want = None
        assert win["pulled_bytes"] >= \
            ticks * SLOTS * 4 + tick_rows * row + admitted
    if want is not None:
        assert win["pulled_bytes"] == life["pulled_bytes"] == want
    assert life["logits_pulls"] == eng.logits_pulls


def test_engine_serves_and_counts_with_no_profiler_session(core):
    """With no session the spans record nothing and the counters still
    count."""
    eng = _engine(core, "fused")
    reqs = _requests()
    for r in reqs:
        eng.submit(r, now=0.0)
    done, t = [], 0.0
    while len(done) < len(reqs):
        t += 1.0
        done.extend(eng.step(now=t))
    assert sorted(r.rid for r in done) == [r.rid for r in reqs]
    assert eng.stats.drain_window()["emitted_tokens"] == len(reqs) * GEN


def test_stamps_are_taken_when_the_token_is_on_the_host(core):
    """With the caller's clock passed in, ``t_done - t_submit`` covers the
    tick that produced the request's last token (and the first-token stamp
    the step that produced the first)."""
    eng = _engine(core, "fused")
    clock = time.perf_counter
    landed: dict[int, list[float]] = {}
    emit, admit = eng._emit, eng.admit

    def timed_emit(slot, req, tok_dev, fetch_row):
        tok = emit(slot, req, tok_dev, fetch_row)
        landed.setdefault(req.rid, []).append(clock())
        return tok

    def timed_admit(slot, prompt, gen_len, request=None, frames=None):
        admit(slot, prompt, gen_len, request=request, frames=frames)
        landed.setdefault(request.rid, []).append(clock())

    eng._emit, eng.admit = timed_emit, timed_admit
    reqs = _requests()
    for r in reqs:
        eng.submit(r, now=clock())
    done = []
    while len(done) < len(reqs):
        done.extend(eng.step(now=clock))
    for r in done:
        assert len(landed[r.rid]) == GEN
        assert r.t_first_token >= landed[r.rid][0] > r.t_submit
        assert r.t_done >= landed[r.rid][-1]
        assert r.latency_s >= landed[r.rid][-1] - r.t_submit


def test_a_clock_reading_stamps_the_whole_round_at_it(core):
    """A virtual clock (one reading per round, as the control-plane loops
    pass) takes no time inside a round: every stamp reads the round's
    ``now``, so same-seed simulations stay exactly repeatable."""
    eng = _engine(core, "fused")
    (r,) = _requests()[:1]
    r.gen_len = 1
    eng.submit(r, now=2.0)
    (done,) = eng.step(now=5.0)
    assert (done.t_admit, done.t_first_token, done.t_done) == (5.0, 5.0, 5.0)
    assert done.latency_s == 3.0
