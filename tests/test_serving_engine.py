"""Serving engine: FCFS admission, slot lifecycle/reuse, chunked-prefill
equivalence (chunked vs one-shot prefill produce identical greedy tokens),
generic slot-pool writes across every family's cache pytree, per-slot
positions (staggered admission must not perturb a request's tokens), the
seeded sampling layer, and the Pallas data path (use_pallas=True in interpret
mode must reproduce the jnp reference token streams end to end).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LM
from repro.models.steps import (
    make_chunked_prefill_step, make_prefill_step, make_sample_step,
)
from repro.serving import (
    Request, SamplingParams, ServingEngine, SlotPool, sample_token,
)
from repro.serving.engine import EngineCore

from conftest import TINY_CFGS

MAX_SEQ = 24
# the issue's five families: dense, dense+sliding-window, vlm, moe, hybrid/ssm
FIVE_FAMILIES = ["dense", "swa", "vlm", "moe", "hybrid"]


@functools.lru_cache(maxsize=None)
def core_for(family: str, use_pallas: bool) -> EngineCore:
    cfg = TINY_CFGS[family]
    if use_pallas:
        cfg = dataclasses.replace(cfg, use_pallas=True)
    return EngineCore(cfg, MAX_SEQ, seed=0)


def make_engine(family: str, *, slots=2, prefill_chunk=None,
                use_pallas=False) -> ServingEngine:
    core = core_for(family, use_pallas)
    return ServingEngine(core.cfg, slots=slots, max_seq=MAX_SEQ,
                         prefill_chunk=prefill_chunk, core=core)


def make_requests(family: str, n, prompt_len=8, gen_len=4, seed=0,
                  sampling=SamplingParams()):
    cfg = TINY_CFGS[family]
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(3, cfg.vocab,
                                        size=prompt_len).astype(np.int32),
                    gen_len=gen_len, sampling=sampling) for i in range(n)]


def run_to_completion(eng, n, max_steps=500):
    done, now = [], 0.0
    for _ in range(max_steps):
        now += 1.0
        done.extend(eng.step(now=now))
        if len(done) >= n and eng.idle:
            return done
    raise AssertionError(f"only {len(done)}/{n} completed")


# ---------------------------------------------------------------- scheduler


def test_fcfs_admission_order():
    eng = make_engine("dense", slots=2)
    reqs = make_requests("dense", 5, gen_len=3)
    for r in reqs:
        eng.submit(r, now=0.0)
    eng.step(now=1.0)
    assert {r.rid for r in eng.slot_owner.values()} == {0, 1}
    done = run_to_completion(eng, 5)
    # FCFS: admission timestamps are monotone in rid
    admits = [r.t_admit for r in sorted(done, key=lambda r: r.rid)]
    assert admits == sorted(admits)
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]


def test_slot_reuse_and_owner_cleared_on_release():
    eng = make_engine("dense", slots=1)
    r0, r1 = make_requests("dense", 2, gen_len=2)
    eng.submit(r0, now=0.0)
    done = []
    now = 0.0
    while not done:
        now += 1.0
        done = eng.step(now=now)
    # slot released: owner cleared, phase free, prompt buffer dropped
    assert eng.slot_owner == {}
    assert not eng.active[0]
    assert eng._prompt[0] is None
    eng.submit(r1, now=now)
    done2 = run_to_completion(eng, 1)
    assert done2[0].rid == 1 and done2[0].replica_id == eng.replica_id
    assert eng.slot_owner == {}


def test_admit_rejects_busy_slot_and_bad_prompts():
    eng = make_engine("dense", slots=1)
    eng.admit(0, np.arange(3, 8, dtype=np.int32), 2)
    with pytest.raises(ValueError):
        eng.admit(0, np.arange(3, 8, dtype=np.int32), 2)
    eng2 = make_engine("dense", slots=1)
    with pytest.raises(ValueError):
        eng2.admit(0, np.zeros(0, np.int32), 2)
    with pytest.raises(ValueError):  # full-attention prompt must fit max_seq
        eng2.admit(0, np.full(MAX_SEQ, 3, np.int32), 2)


def test_gen_len_clamped_to_cache_for_full_attention():
    eng = make_engine("dense", slots=1)
    [r] = make_requests("dense", 1, prompt_len=MAX_SEQ - 4, gen_len=100)
    eng.submit(r, now=0.0)
    done = run_to_completion(eng, 1)
    assert len(done[0].tokens_out) == 4          # max_seq - prompt_len


# ------------------------------------------------- chunked-prefill equivalence


@pytest.mark.parametrize("family", FIVE_FAMILIES + ["ssm2"])
def test_chunked_prefill_step_matches_one_shot(family):
    cfg = TINY_CFGS[family]
    params = core_for(family, False).params
    rng = np.random.default_rng(3)
    prompt = rng.integers(3, cfg.vocab, size=12).astype(np.int32)
    inputs = {"tokens": jnp.asarray(prompt[None])}
    if cfg.family == "vlm":
        inputs["patches"] = jnp.zeros(
            (1, cfg.n_vision_patches, cfg.d_model), cfg.cdtype)
    one_l, one_c = make_prefill_step(cfg, MAX_SEQ)(params, inputs)
    chunk = 6 if cfg.family != "vlm" else cfg.n_vision_patches + 2
    chk_l, chk_c = make_chunked_prefill_step(cfg, MAX_SEQ, chunk)(params,
                                                                  inputs)
    assert int(jnp.argmax(one_l[0, -1])) == int(jnp.argmax(chk_l[0, -1]))
    assert int(one_c["index"]) == int(chk_c["index"]) == len(prompt)
    np.testing.assert_allclose(np.asarray(one_l[:, -1], np.float32),
                               np.asarray(chk_l[:, -1], np.float32),
                               atol=5e-5, rtol=5e-5)


def test_chunked_prefill_step_rejects_chunk_inside_patch_prefix():
    cfg = TINY_CFGS["vlm"]
    with pytest.raises(ValueError):
        make_chunked_prefill_step(cfg, MAX_SEQ, cfg.n_vision_patches)


@pytest.mark.parametrize("family", FIVE_FAMILIES)
def test_engine_streamed_prefill_matches_one_shot(family):
    """Admission with a small prefill chunk streams the prompt tail through
    the decode tick — the full greedy token stream must be identical to a
    whole-prompt prefill."""
    reqs = make_requests(family, 2, prompt_len=10, gen_len=4, seed=7)
    reqs[1].prompt = reqs[0].prompt.copy()
    one = make_engine(family, slots=1, prefill_chunk=None)
    one.submit(reqs[0], now=0.0)
    [done_one] = run_to_completion(one, 1)
    chunked = make_engine(family, slots=1, prefill_chunk=3)
    chunked.submit(reqs[1], now=0.0)
    [done_chk] = run_to_completion(chunked, 1)
    assert done_one.tokens_out == done_chk.tokens_out
    assert len(done_chk.tokens_out) == 4
    # streamed prefill takes decode ticks, so TTFT comes later but exists
    assert done_chk.t_first_token is not None


# ------------------------------------------------------------- slot pool


@pytest.mark.parametrize("family", FIVE_FAMILIES + ["ssm2"])
def test_write_slot_axis_detection_per_family(family):
    # (audio/enc-dec is covered by the dedicated enc-dec tests below: its
    # prefill cross K/V is encoder-length and write_slot zero-pads it up to
    # the max_seq-sized pool spec, so the exact-row comparison here — pool
    # row == one-cache row — would not hold leaf-for-leaf)
    cfg = TINY_CFGS[family]
    params = core_for(family, False).params
    rng = np.random.default_rng(0)

    def one_cache(n):
        inputs = {"tokens": jnp.asarray(
            rng.integers(3, cfg.vocab, size=n).astype(np.int32)[None])}
        if cfg.family == "vlm":
            inputs["patches"] = jnp.zeros(
                (1, cfg.n_vision_patches, cfg.d_model), cfg.cdtype)
        if cfg.enc_dec:
            inputs["frames"] = jnp.zeros((1, n, cfg.d_model), cfg.cdtype)
        return LM.prefill(params, inputs, cfg, MAX_SEQ)[1]

    c0, c2 = one_cache(6), one_cache(5)
    pool = SlotPool(cfg, 3, MAX_SEQ)
    pool.write(c0, 0)
    pool.write(c2, 2)
    assert [int(v) for v in pool.index] == [6, 0, 5]

    def batch_axis(pool_leaf, one_leaf):
        for ax in range(pool_leaf.ndim):
            if one_leaf.shape[ax] == 1 and pool_leaf.shape[ax] != 1:
                return ax
        raise AssertionError("no batch axis found")

    rest_pool = {k: v for k, v in pool.cache.items() if k != "index"}
    rest_one0 = {k: v for k, v in c0.items() if k != "index"}
    checked = []

    def check(p, o):
        p, o = np.asarray(p), np.asarray(o)
        ax = batch_axis(p, o)
        np.testing.assert_array_equal(np.take(p, 0, axis=ax),
                                      np.take(o, 0, axis=ax))
        np.testing.assert_array_equal(np.take(p, 1, axis=ax),
                                      np.zeros_like(np.take(p, 1, axis=ax)))
        checked.append(ax)
        return p

    jax.tree.map(check, rest_pool, rest_one0)
    assert checked                                  # every family has leaves
    if family == "hybrid":                          # mamba states: batch at 2
        assert 2 in checked and 1 in checked


def test_write_slot_single_slot_pool_is_overwrite():
    """A 1-slot pool has identical pool/one shapes; the seed's axis scan
    silently dropped the write — it must be a whole-pool overwrite."""
    cfg = TINY_CFGS["dense"]
    params = core_for("dense", False).params
    prompt = np.arange(3, 9, dtype=np.int32)
    _, one = LM.prefill(params, {"tokens": jnp.asarray(prompt[None])}, cfg,
                        MAX_SEQ)
    pool = SlotPool(cfg, 1, MAX_SEQ)
    assert float(jnp.abs(pool.cache["layers"]["k"]).sum()) == 0.0
    pool.write(one, 0)
    np.testing.assert_array_equal(pool.cache["layers"]["k"],
                                  one["layers"]["k"])
    assert int(pool.index[0]) == len(prompt)


# ------------------------------------------------------- per-slot positions


@pytest.mark.parametrize("family", ["dense", "swa", "vlm"])
def test_staggered_admission_does_not_perturb_tokens(family):
    """A request admitted mid-flight (other slots deep into decode) must
    produce exactly the tokens it produces alone — per-slot ring positions,
    RoPE angles, and validity masks (the seed's shared scalar index failed
    this)."""
    ra, rb, rb_solo = make_requests(family, 3, prompt_len=8, gen_len=6,
                                    seed=11)
    rb_solo.prompt = rb.prompt.copy()

    solo = make_engine(family, slots=2)
    solo.submit(rb_solo, now=0.0)
    [done_solo] = run_to_completion(solo, 1)

    eng = make_engine(family, slots=2)
    eng.submit(ra, now=0.0)
    now = 0.0
    for _ in range(3):                              # ra is 3 tokens deep
        now += 1.0
        eng.step(now=now)
    eng.submit(rb, now=now)
    done = run_to_completion(eng, 2)
    by_rid = {r.rid: r for r in done}
    assert by_rid[rb.rid].tokens_out == done_solo.tokens_out


# ------------------------------------------------- pallas engine equivalence


def _staggered_run(family: str, use_pallas: bool):
    """Staggered-admission run: 3 requests through 2 slots, the third
    admitted while the first two are mid-decode — exercises the vector-index
    decode path (mixed per-row ring positions) every tick."""
    reqs = make_requests(family, 3, prompt_len=8, gen_len=5, seed=23)
    eng = make_engine(family, slots=2, use_pallas=use_pallas)
    eng.submit(reqs[0], now=0.0)
    eng.submit(reqs[1], now=0.0)
    now = 0.0
    for _ in range(2):                          # first two are 2 tokens deep
        now += 1.0
        eng.step(now=now)
    eng.submit(reqs[2], now=now)
    done = run_to_completion(eng, 3)
    return {r.rid: r.tokens_out for r in done}


@pytest.mark.parametrize("family", FIVE_FAMILIES)
def test_pallas_engine_matches_jnp_token_streams(family):
    """ServingEngine with use_pallas=True (fused vector-index decode kernel +
    ring-scatter K/V write, interpret mode) must emit exactly the token
    streams of the jnp reference engine under staggered admission."""
    want = _staggered_run(family, use_pallas=False)
    got = _staggered_run(family, use_pallas=True)
    assert got == want


def test_pallas_vector_decode_tick_matches_jnp_cache():
    """One decode tick over a staggered pool: the pallas engine's KV cache
    and the jnp engine's must agree (the ring scatter wrote the same slots)."""
    engines = {}
    for use_pallas in (False, True):
        reqs = make_requests("dense", 2, prompt_len=6, gen_len=4, seed=29)
        eng = make_engine("dense", slots=2, use_pallas=use_pallas)
        eng.submit(reqs[0], now=0.0)
        eng.step(now=1.0)                       # slot 0 one tick ahead
        eng.submit(reqs[1], now=1.0)
        eng.step(now=2.0)
        engines[use_pallas] = eng
    k_ref = np.asarray(engines[False].pool.cache["layers"]["k"], np.float32)
    k_pal = np.asarray(engines[True].pool.cache["layers"]["k"], np.float32)
    np.testing.assert_allclose(k_pal, k_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(engines[True].pool.index), np.asarray(engines[False].pool.index))


# ------------------------------------------------------------- enc-dec


def _audio_request(rid, enc_len, *, prompt_len=6, gen_len=4, seed=0):
    cfg = TINY_CFGS["audio"]
    rng = np.random.default_rng((seed, rid))
    return Request(
        rid=rid,
        prompt=rng.integers(3, cfg.vocab, size=prompt_len).astype(np.int32),
        gen_len=gen_len,
        frames=rng.standard_normal((enc_len, cfg.d_model)).astype(np.float32))


def test_enc_dec_slot_serving():
    """The PR-2 gap, closed: the engine admits ``frames`` and the slot pool
    zero-pads prefill's encoder-length cross K/V up to the max_seq-sized
    pool spec (the pad rows sit past cross_len and are masked at decode).
    Two requests with DIFFERENT encoder lengths, staggered so their ring
    positions and cross lengths differ every tick, must each produce
    exactly the tokens they produce alone."""
    solo = {}
    for rid, enc_len in ((0, 5), (1, 9)):
        eng = make_engine("audio", slots=2, prefill_chunk=4)
        eng.submit(_audio_request(rid, enc_len), now=0.0)
        [done] = run_to_completion(eng, 1)
        solo[rid] = done.tokens_out

    eng = make_engine("audio", slots=2, prefill_chunk=4)
    eng.submit(_audio_request(0, 5), now=0.0)
    now = 0.0
    for _ in range(2):                     # request 0 is 2 tokens deep
        now += 1.0
        eng.step(now=now)
    eng.submit(_audio_request(1, 9), now=now)
    done = run_to_completion(eng, 2)
    assert {r.rid: r.tokens_out for r in done} == solo


def test_enc_dec_slot_serving_seamless_m4t_smoke():
    """The same staggered mixed-encoder-length check on the repo's actual
    seamless-m4t smoke config (tied embeddings, LayerNorm family path)."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("seamless-m4t-medium")
    eng_solo = ServingEngine(cfg, slots=2, max_seq=MAX_SEQ, prefill_chunk=4)
    rng = np.random.default_rng(3)

    def req(rid, enc_len):
        r = np.random.default_rng((3, rid))
        return Request(rid=rid,
                       prompt=r.integers(3, cfg.vocab, size=6
                                         ).astype(np.int32),
                       gen_len=4,
                       frames=r.standard_normal(
                           (enc_len, cfg.d_model)).astype(np.float32))

    eng_solo.submit(req(1, 9), now=0.0)
    [solo] = run_to_completion(eng_solo, 1)

    eng = ServingEngine(cfg, slots=2, max_seq=MAX_SEQ, prefill_chunk=4,
                        core=eng_solo.core)
    eng.submit(req(0, 5), now=0.0)
    now = 0.0
    for _ in range(2):
        now += 1.0
        eng.step(now=now)
    eng.submit(req(1, 9), now=now)
    done = run_to_completion(eng, 2)
    by_rid = {r.rid: r.tokens_out for r in done}
    assert by_rid[1] == solo.tokens_out
    assert all(len(t) == 4 for t in by_rid.values())


def test_enc_dec_streamed_prefill_matches_one_shot():
    """The decoder-prompt tail streams through the decode tick (cross K/V
    are already pooled from admission's one-shot encoder pass) — chunked
    and whole-prompt admission must emit identical tokens."""
    one = make_engine("audio", slots=1, prefill_chunk=None)
    one.submit(_audio_request(0, 7, prompt_len=10), now=0.0)
    [done_one] = run_to_completion(one, 1)
    chunked = make_engine("audio", slots=1, prefill_chunk=3)
    chunked.submit(_audio_request(0, 7, prompt_len=10), now=0.0)
    [done_chk] = run_to_completion(chunked, 1)
    assert done_one.tokens_out == done_chk.tokens_out


def test_enc_dec_submit_rejects_missing_or_oversized_frames():
    eng = make_engine("audio", slots=1)
    cfg = TINY_CFGS["audio"]
    req = _audio_request(0, 5)
    req.frames = None
    with pytest.raises(ValueError):
        eng.submit(req, now=0.0)
    with pytest.raises(ValueError):        # encoder must fit the cross pool
        eng.submit(_audio_request(1, MAX_SEQ + 1), now=0.0)
    with pytest.raises(ValueError):        # d_model mismatch
        bad = _audio_request(2, 5)
        bad.frames = np.zeros((5, cfg.d_model + 1), np.float32)
        eng.submit(bad, now=0.0)


# ------------------------------------------------------------- sampling


def test_greedy_sampling_is_argmax():
    logits = np.array([0.1, 2.0, -1.0, 2.0])
    assert sample_token(logits, SamplingParams()) == 1        # first max wins
    # top_k=1 collapses to the (unique) max regardless of temperature
    assert sample_token(np.array([0.1, 3.0, -1.0, 2.0]),
                        SamplingParams(temperature=0.7, top_k=1),
                        np.random.default_rng(0)) == 1


def test_seeded_sampling_is_deterministic_per_request():
    sampling = SamplingParams(temperature=0.9, top_k=4, seed=5)
    [r1] = make_requests("dense", 1, gen_len=6, sampling=sampling)
    [r2] = make_requests("dense", 1, gen_len=6, sampling=sampling)
    e1, e2 = make_engine("dense", slots=1), make_engine("dense", slots=1)
    e1.submit(r1, now=0.0)
    e2.submit(r2, now=0.0)
    [d1] = run_to_completion(e1, 1)
    [d2] = run_to_completion(e2, 1)
    assert d1.tokens_out == d2.tokens_out
    assert len(d1.tokens_out) == 6


def test_temperature_zero_matches_greedy_engine_default():
    [r_explicit] = make_requests("dense", 1, gen_len=5,
                                 sampling=SamplingParams(temperature=0.0))
    [r_default] = make_requests("dense", 1, gen_len=5)
    e1, e2 = make_engine("dense", slots=1), make_engine("dense", slots=1)
    e1.submit(r_explicit, now=0.0)
    e2.submit(r_default, now=0.0)
    [d1] = run_to_completion(e1, 1)
    [d2] = run_to_completion(e2, 1)
    assert d1.tokens_out == d2.tokens_out


def test_device_temperature_stream_is_keyed_by_seed_rid_and_position():
    """A full-vocabulary temperature stream is the device's draw from
    (seed, rid, output position): two engines emit the same tokens for the
    same (seed, rid), whatever slot and neighbour the request has; a
    request preempted mid-stream and admitted again replays the stream;
    another seed draws another."""
    def request(seed=5):
        [r] = make_requests("dense", 1, gen_len=8, seed=3,
                            sampling=SamplingParams(temperature=0.8,
                                                    seed=seed))
        return r

    alone = make_engine("dense", slots=1)
    alone.submit(request(), now=0.0)
    [want] = run_to_completion(alone, 1)
    assert len(set(want.tokens_out)) > 1          # a draw, not a constant

    beside = make_engine("dense", slots=2)
    [neighbour] = make_requests("dense", 1, gen_len=8, seed=9)
    neighbour.rid = 7
    beside.submit(neighbour, now=0.0)            # takes slot 0
    beside.submit(request(), now=0.0)
    got = {r.rid: r for r in run_to_completion(beside, 2)}
    assert got[0].tokens_out == want.tokens_out

    again = make_engine("dense", slots=1)
    r = request()
    again.submit(r, now=0.0)
    now = 0.0
    while len(r.tokens_out) < 3:
        now += 1.0
        again.step(now=now)
    assert again.preempt_slot(0) is r and r.tokens_out == []
    again.submit(r, now=now)
    [replayed] = run_to_completion(again, 1)
    assert replayed.tokens_out == want.tokens_out

    other = make_engine("dense", slots=1)
    other.submit(request(seed=6), now=0.0)
    [reseeded] = run_to_completion(other, 1)
    assert reseeded.tokens_out != want.tokens_out


@pytest.mark.parametrize("sampling,row_pulled,device_draws", [
    (SamplingParams(), False, 0),
    (SamplingParams(temperature=0.8, seed=2), False, 1),
    (SamplingParams(temperature=0.8, top_k=4, seed=2), True, 0),
])
def test_admission_pulls_one_int32_not_a_row(sampling, row_pulled,
                                              device_draws):
    """An admission whose prompt fits one prefill draws its first token on
    the device and pulls 4 bytes; only a ``top_k > 0`` temperature request
    pulls the (V,) float32 row to draw on the host.  A greedy first token
    is the prefill logits' argmax."""
    V = TINY_CFGS["dense"].vocab
    eng = make_engine("dense", slots=1)
    [r] = make_requests("dense", 1, sampling=sampling)
    eng.admit(0, r.prompt, r.gen_len, request=r)
    life = eng.lifetime()
    assert len(r.tokens_out) == life["emitted_tokens"] == 1
    assert life["pulled_bytes"] == (4 * V if row_pulled else 4)
    assert life["logits_pulls"] == 0             # not a tick's pull
    assert life["device_draws"] == device_draws
    if sampling.temperature == 0.0:
        logits, _ = eng.prefill(eng.params,
                                {"tokens": jnp.asarray(r.prompt[None])})
        assert r.tokens_out[0] == int(np.argmax(np.asarray(logits[0, -1])))
    bare = make_engine("dense", slots=1)         # no Request: greedy
    bare.admit(0, r.prompt, r.gen_len)
    assert bare.lifetime()["pulled_bytes"] == 4


# the upper 1e-6 tail of chi-square with 31 degrees of freedom
CHI2_LIMIT_31 = 83.6


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("temperature", [0.7, 1.0])
def test_device_draw_follows_softmax_at_temperature(temperature, use_pallas):
    """The device draw at T samples softmax(x / T): one fixed row of 32
    logits drawn at 4096 (rid, pos) counters gives token counts that pass a
    chi-square test against softmax(x / T) and fail it against the other
    temperature's softmax (the test can tell the two apart)."""
    V, N = 32, 4096
    x = np.random.default_rng(0).permutation(
        np.linspace(-1.0, 1.0, V)).astype(np.float32)
    cfg = dataclasses.replace(TINY_CFGS["dense"], use_pallas=use_pallas)
    sample = jax.jit(make_sample_step(cfg))
    rid = np.repeat(np.arange(64, dtype=np.int32), N // 64)
    pos = np.tile(np.arange(N // 64, dtype=np.int32), 64)
    toks = np.asarray(sample(jnp.asarray(np.broadcast_to(x, (N, 1, V))),
                             np.full(N, 7, np.int32), rid, pos,
                             np.full(N, temperature, np.float32)))
    counts = np.bincount(toks, minlength=V)
    assert counts.sum() == N and counts.size == V

    def chi2(t):
        p = np.exp(x / t - np.max(x / t))
        expected = N * p / p.sum()                # >= 39 per token here
        return float(np.sum((counts - expected) ** 2 / expected))

    assert chi2(temperature) < CHI2_LIMIT_31
    assert chi2(1.7 - temperature) > CHI2_LIMIT_31
