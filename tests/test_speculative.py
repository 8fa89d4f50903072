"""Speculative decoding: the whole bit-equality contract.

Speculation is a pure latency optimization — exact-match acceptance means a
spec-on engine must emit byte-identical token streams to a plain engine for
EVERY sampling mode, pool layout, and model family (ineligible families
silently serve the plain path).  The suite pins that contract, the
rejected-tail rewind invariant (pool index == host positions after every
tick), the n-gram proposer's match-preference rules (longest-suffix-first,
newest-first, full-follow over truncated), the fused in-kernel sampler
against its jnp reference and the host sampler, the (seed, position)
stateless-sampling regression, and the StreamBuilder round-trip for the
acceptance/prefix-sharing metric channels.

A deterministic fuzz over ngram_propose always runs; hypothesis (when
installed) widens the same property.
"""
import functools

import numpy as np
import pytest

try:                       # degrade to the fixed grid, never to a dead module
    from hypothesis import given, settings, strategies as st
except ImportError:
    st = None

from repro.core.dnn.features import PERF_KEYS, RESOURCE_KEYS, StreamBuilder
from repro.kernels import ops, ref
from repro.serving import Request, SamplingParams, ServingEngine, sample_token
from repro.serving.draft import ngram_propose
from repro.serving.engine import EngineCore

from conftest import TINY_CFGS

MAX_SEQ = 32
# rewindable full-ring caches — the eligibility gate lets these speculate
SPEC_FAMILIES = ["dense", "vlm", "moe"]
# sliding-window rings wrap, SSM/hybrid recurrence can't roll back
GATED_FAMILIES = ["swa", "ssm2", "hybrid"]


@functools.lru_cache(maxsize=None)
def core_for(family: str) -> EngineCore:
    return EngineCore(TINY_CFGS[family], MAX_SEQ, seed=0)


def make_engine(family: str, *, spec_k=0, slots=2, pool="dense",
                **kw) -> ServingEngine:
    core = core_for(family)
    if pool == "paged":
        kw.update(pool="paged", block_size=4,
                  num_blocks=slots * (MAX_SEQ // 4) + 1)
    return ServingEngine(core.cfg, slots=slots, max_seq=MAX_SEQ, core=core,
                         spec_k=spec_k, **kw)


def echo_requests(family: str, n, *, prompt_len=12, gen_len=10, period=4,
                  seed=0, sampling=None):
    """Prompts that tile a short random phrase — the workload prompt lookup
    is built for, so drafts actually fire."""
    cfg = TINY_CFGS[family]
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        phrase = rng.integers(3, cfg.vocab, size=period)
        prompt = np.tile(phrase, prompt_len // period + 1)[:prompt_len]
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            gen_len=gen_len,
                            sampling=sampling or SamplingParams()))
    return reqs


def run_to_completion(eng, n, max_steps=500):
    done, now = [], 0.0
    for _ in range(max_steps):
        now += 1.0
        done.extend(eng.step(now=now))
        if len(done) >= n and eng.idle:
            return {r.rid: r.tokens_out for r in done}
    raise AssertionError(f"only {len(done)}/{n} completed")


def run_pair(family, reqs_fn, *, spec_k=3, pool="dense", **kw):
    plain = make_engine(family, spec_k=0, **kw)
    spec = make_engine(family, spec_k=spec_k, pool=pool, **kw)
    n = None
    for eng in (plain, spec):
        reqs = reqs_fn()
        n = len(reqs)
        for r in reqs:
            eng.submit(r, now=0.0)
    return run_to_completion(plain, n), run_to_completion(spec, n), spec


# ------------------------------------------------- spec-vs-plain bit equality


@pytest.mark.parametrize("family", SPEC_FAMILIES)
def test_spec_matches_plain_greedy(family):
    """Greedy streams must be bit-identical with speculation on — and the
    spec engine must actually have speculated (the workload is draftable),
    else the equality is vacuous."""
    want, got, spec = run_pair(family, lambda: echo_requests(family, 3))
    assert got == want
    assert spec.stats.total_spec_proposed > 0
    assert 0 <= spec.stats.total_spec_accepted \
        <= spec.stats.total_spec_proposed


@pytest.mark.parametrize("family", GATED_FAMILIES)
def test_ineligible_families_silently_serve_plain(family):
    """spec_k on a non-rewindable cache is a no-op knob, never an error:
    the gate disables speculation and the stream is the plain stream."""
    want, got, spec = run_pair(family, lambda: echo_requests(family, 2))
    assert not spec._spec_ok
    assert got == want
    assert spec.stats.total_spec_proposed == 0


def test_spec_matches_plain_temperature():
    """Exact-match acceptance is sampling-mode agnostic: seeded temperature
    rows accept a draft token iff the host sample equals it, so the stream
    stays identical to plain decode."""
    sampling = SamplingParams(temperature=0.8, top_k=8, seed=11)
    want, got, spec = run_pair(
        "dense", lambda: echo_requests("dense", 2, sampling=sampling))
    assert got == want


def test_spec_matches_plain_paged_pool():
    """Paged block tables rewind through the same index-vector contract as
    dense rings — paged spec-on == dense plain, token for token."""
    want, got, spec = run_pair("dense", lambda: echo_requests("dense", 3),
                               pool="paged")
    assert got == want
    assert spec.stats.total_spec_proposed > 0


def test_greedy_decode_pulls_no_host_logits():
    """The fused in-kernel sampler keeps ticks devicebound: a plain greedy
    run materializes ZERO host logits rows, and so does a temperature run
    over the full vocabulary, whose every token is the device's draw; a
    ``top_k > 0`` temperature run pulls (host sampling is its contract)."""
    eng = make_engine("dense", slots=2)
    for r in echo_requests("dense", 2):
        eng.submit(r, now=0.0)
    run_to_completion(eng, 2)
    assert eng.logits_pulls == 0
    assert eng.stats.total_device_draws == 0
    hot = make_engine("dense", slots=2)
    for r in echo_requests("dense", 2,
                           sampling=SamplingParams(temperature=0.9, seed=1)):
        hot.submit(r, now=0.0)
    out = run_to_completion(hot, 2)
    assert hot.logits_pulls == 0
    assert hot.stats.total_device_draws == sum(map(len, out.values())) \
        == hot.lifetime()["device_draws"] == 20
    top = make_engine("dense", slots=2)
    for r in echo_requests("dense", 2, sampling=SamplingParams(
            temperature=0.9, top_k=8, seed=1)):
        top.submit(r, now=0.0)
    run_to_completion(top, 2)
    assert top.logits_pulls > 0
    assert top.stats.total_device_draws == 0


@pytest.mark.parametrize("prefill_chunk", [None, 4])
@pytest.mark.parametrize("temperature", [0.05, 0.3])
def test_spec_matches_plain_device_temperature(temperature, prefill_chunk):
    """Full-vocabulary temperature rows take the device's draw in both
    paths: each verify lane draws from the counters of the output position
    it would emit, so the stream equals the plain tick's — with accepted
    drafts in the run, and with prompt tails streamed through the window
    (``prefill_chunk``), whose first token is drawn at position 0."""
    sampling = SamplingParams(temperature=temperature, seed=11)
    want, got, spec = run_pair(
        "dense", lambda: echo_requests("dense", 2, sampling=sampling),
        prefill_chunk=prefill_chunk)
    assert got == want
    assert spec.stats.total_spec_accepted > 0
    assert spec.logits_pulls == 0


# ------------------------------------------------------- rejected-tail rewind


def test_rewind_restores_pool_index_every_tick():
    """After EVERY tick the pool index vector must equal the host position
    vector for active rows — rejected (and unconsumed) speculative writes
    sit past the index and get re-covered by later writes.  The run must
    contain at least one rejection, else the invariant is untested."""
    eng = make_engine("dense", slots=2, spec_k=3)
    for r in echo_requests("dense", 2, gen_len=12, seed=3):
        eng.submit(r, now=0.0)
    now, done = 0.0, []
    for _ in range(200):
        now += 1.0
        done.extend(eng.step(now=now))
        active = np.nonzero(eng.active)[0]
        np.testing.assert_array_equal(
            np.asarray(eng.pool.index)[active], eng.pos[active])
        if len(done) >= 2 and eng.idle:
            break
    assert len(done) == 2
    st_ = eng.stats
    assert st_.total_spec_proposed > st_.total_spec_accepted  # saw rejects


def test_rewound_cache_rows_match_plain_engine():
    """The valid cache region [0, pos) of a spec engine must equal the plain
    engine's after identical traffic — speculation may only leave garbage at
    rows the index has been rewound past."""
    engines = {}
    for spec_k in (0, 3):
        eng = make_engine("dense", slots=1, spec_k=spec_k)
        [r] = echo_requests("dense", 1, gen_len=8, seed=5)
        eng.submit(r, now=0.0)
        run_to_completion(eng, 1)
        engines[spec_k] = eng
    k0 = np.asarray(engines[0].pool.cache["layers"]["k"], np.float32)
    k3 = np.asarray(engines[3].pool.cache["layers"]["k"], np.float32)
    pos = int(engines[0].pool.index[0])
    assert int(engines[3].pool.index[0]) == pos
    # k layout: (layers, slots, Smax, KV, hd) — slice the position axis
    np.testing.assert_allclose(k3[:, :, :pos], k0[:, :, :pos], atol=1e-6)
    # and the garbage really is past the index (the diff exists at all)
    assert np.abs(k3[:, :, pos:] - k0[:, :, pos:]).max() > 0.0


# ------------------------------------------------------------- ngram_propose


def test_ngram_empty_cases():
    assert ngram_propose([1, 2, 3], k=0).size == 0
    assert ngram_propose([7], k=3).size == 0
    assert ngram_propose([], k=3).size == 0
    # all-unique history: no earlier occurrence of any suffix n-gram
    assert ngram_propose(list(range(10)), k=3).size == 0


def test_ngram_longest_suffix_wins():
    # order-3 match exists (follow [5,1,2]); order-1 [3] also matches at
    # i=1 (follow 9) — the more specific match must win
    h = [7, 3, 9, 1, 2, 3, 5, 1, 2, 3]
    assert ngram_propose(h, k=3, ngram=3).tolist() == [5, 1, 2]


def test_ngram_newest_match_wins_within_order():
    # [1,2] occurs twice with full follows; the newer occurrence (follow 6)
    # must win — recency tracks local context
    h = [1, 2, 5, 1, 2, 6, 1, 2]
    assert ngram_propose(h, k=1, ngram=2).tolist() == [6]


def test_ngram_prefers_full_follow_over_truncated():
    # period-2 cycle: the newest [2,1,2] match (i=3) has only a 2-token
    # follow; one cycle earlier (i=1) the same continuation is available at
    # full length — the full follow must win, not the newer truncated one
    h = [1, 2, 1, 2, 1, 2, 1, 2]
    assert ngram_propose(h, k=3, ngram=3).tolist() == [1, 2, 1]


def test_ngram_truncated_fallback_when_no_full_follow():
    # the only match sits too close to the end for k=4 — the truncated
    # follow is still proposed (a short draft beats no draft)
    h = [9, 8, 1, 2, 3, 1, 2, 3]
    assert ngram_propose(h, k=4, ngram=3).tolist() == [1, 2, 3]


def test_ngram_list_and_array_inputs_agree():
    h = [1, 2, 1, 2, 1, 2]
    a = ngram_propose(h, k=2, ngram=2)
    b = ngram_propose(np.asarray(h, np.int32), k=2, ngram=2)
    assert a.dtype == np.int32 and a.tolist() == b.tolist()


def _check_proposal_is_valid_continuation(h, k, ngram):
    d = ngram_propose(h, k=k, ngram=ngram)
    assert 0 <= d.size <= max(k, 0)
    if d.size == 0:
        return
    T = len(h)
    follow = d.tolist()
    ok = False
    for n in range(1, min(ngram, T - 1) + 1):
        tail = h[T - n:]
        for i in range(T - n):
            if h[i:i + n] == tail and h[i + n:i + n + len(follow)] == follow:
                ok = True
    assert ok, f"proposal {follow} is not the follow of any suffix match"


def test_ngram_fuzz_deterministic():
    rng = np.random.default_rng(0)
    for _ in range(300):
        T = int(rng.integers(0, 40))
        h = rng.integers(0, int(rng.integers(2, 8)), size=T).tolist()
        _check_proposal_is_valid_continuation(
            h, int(rng.integers(0, 6)), int(rng.integers(1, 5)))


if st is not None:
    @settings(max_examples=200, deadline=None)
    @given(h=st.lists(st.integers(0, 5), max_size=48),
           k=st.integers(0, 6), ngram=st.integers(1, 5))
    def test_ngram_fuzz_hypothesis(h, k, ngram):
        _check_proposal_is_valid_continuation(h, k, ngram)


# ------------------------------------------------- sampling: host and fused


def test_sample_token_stateless_fallback_advances_with_position():
    """Regression: the rng-less fallback seeds from (seed, position).
    Seeding from ``seed`` alone rebuilt the identical generator every call
    and emitted the same token forever."""
    params = SamplingParams(temperature=1.0, seed=3)
    logits = np.zeros(32)                       # uniform — pure randomness
    draws = [sample_token(logits, params, position=p) for p in range(12)]
    assert len(set(draws)) > 1                  # positions advance the stream
    again = [sample_token(logits, params, position=p) for p in range(12)]
    assert draws == again                       # and it's reproducible


@pytest.mark.kernels
def test_fused_sample_kernel_matches_ref_bitwise():
    """The Pallas sampler and the independently-written jnp reference must
    agree BITWISE on mixed greedy/temperature rows; greedy rows must equal
    the host sampler's f32 argmax (the engine relies on this to fuse greedy
    ticks without changing streams)."""
    rng = np.random.default_rng(7)
    B, V = 6, 64
    logits = rng.standard_normal((B, V)).astype(np.float32)
    seed = np.arange(B, dtype=np.int32)
    rid = (np.arange(B, dtype=np.int32) * 13) % 7
    pos = np.arange(B, dtype=np.int32) + 2
    temp = np.array([0.0, 0.7, 0.0, 1.3, 0.05, 0.0], np.float32)
    got = np.asarray(ops.fused_sample(logits, seed, rid, pos, temp,
                                      interpret=True))
    want = np.asarray(ref.fused_sample_ref(logits, seed, rid, pos, temp))
    np.testing.assert_array_equal(got, want)
    for b in np.nonzero(temp == 0.0)[0]:
        assert got[b] == sample_token(logits[b], SamplingParams())


# ------------------------------------------- metric channels / StreamBuilder


def test_stream_builder_round_trips_spec_and_prefix_channels():
    """The acceptance-rate and prefix-sharing channels must occupy stable
    columns in the DNN input streams: push a record with distinct values
    per key and pin each one to its column, then check the stream shapes
    the model was sized for."""
    assert "prefix_hits" in RESOURCE_KEYS and "tokens_shared" in RESOURCE_KEYS
    assert "accept_rate" in PERF_KEYS
    sb = StreamBuilder(window=4)
    rec = {k: float(i + 1) for i, k in enumerate(RESOURCE_KEYS)}
    rec.update({k: float(100 + i) for i, k in enumerate(PERF_KEYS)})
    sb.push(rec)
    assert sb.res_hist[-1].tolist() == [float(i + 1)
                                        for i in range(len(RESOURCE_KEYS))]
    assert sb.perf_hist[-1].tolist() == [float(100 + i)
                                         for i in range(len(PERF_KEYS))]
    # missing keys (e.g. dense fleets report no prefix stats) default to 0
    sb.push({"flop_util": 0.5})
    assert sb.res_hist[-1][RESOURCE_KEYS.index("prefix_hits")] == 0.0
    streams = sb.streams(np.zeros(12, np.float32))
    assert streams["resource"].shape == (1, 4, len(RESOURCE_KEYS))
    assert streams["perf"].shape == (1, 4, len(PERF_KEYS))


def test_engine_lifetime_reports_spec_counters():
    eng = make_engine("dense", slots=2, spec_k=3)
    for r in echo_requests("dense", 2):
        eng.submit(r, now=0.0)
    run_to_completion(eng, 2)
    life = eng.lifetime()
    assert life["spec_proposed"] == eng.stats.total_spec_proposed > 0
    assert 0 <= life["spec_accepted"] <= life["spec_proposed"]
    assert life["logits_pulls"] == 0            # greedy run stayed fused
