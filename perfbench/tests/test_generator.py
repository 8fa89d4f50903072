"""The traffic generator: same seed, same inputs; another seed, the same
sizes in another order."""
import json
from collections import Counter
from pathlib import Path

import numpy as np

from perfbench import generator

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_same_seed_same_inputs():
    m = mix("gen")
    a = generator.plan(m, 0.7, 200, 151936, 2**33 + 1, backlog=32)
    b = generator.plan(m, 0.7, 200, 151936, 2**33 + 1, backlog=32)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.gen_len == y.gen_len
        assert np.array_equal(x.prompt, y.prompt)
        assert x.temperature == y.temperature
        assert x.sample_seed == y.sample_seed


def test_other_seeds_offer_the_same_work_with_other_tokens():
    m = mix("gen")
    a, b = (generator.plan(m, 1.1, 100, 151936, seed, backlog=32)
            for seed in (1, 2**31 + 5))
    assert [(x.arrival_s, len(x.prompt), x.gen_len, x.temperature)
            for x in a] == \
        [(x.arrival_s, len(x.prompt), x.gen_len, x.temperature) for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert a[0].sample_seed != b[0].sample_seed


def test_a_block_uses_each_table_entry_once():
    m = mix("gen")
    p = generator.plan(m, 5.0, 10_000, 151936, 3)[:64]
    assert Counter(len(x.prompt) for x in p) == \
        Counter(m["prompt_tokens"]["table"])
    assert Counter(x.gen_len for x in p) == \
        Counter(m["output_tokens"]["table"])


def test_gaps_are_independent_exponentials_at_the_rate():
    # plain Poisson: blocks of 64 arrivals do not all last the same time
    m = mix("gen")
    p = generator.plan(m, 2.0, 50_000, 1000, 3)
    gaps = np.diff([0.0] + [x.arrival_s for x in p])
    assert abs(gaps.mean() - 0.5) < 0.02
    assert abs(gaps.std() - 0.5) < 0.03
    blocks = [gaps[i:i + 64].sum() for i in range(0, len(gaps) - 64, 64)]
    assert np.std(blocks) > 1.0            # 64 gaps of std 0.5: about 4 s


def test_backlog_arrives_at_zero_then_the_stream_starts():
    m = mix("gen")
    p = generator.plan(m, 0.7, 100, 1000, 3, backlog=32)
    assert all(x.arrival_s == 0.0 for x in p[:32])
    assert all(x.arrival_s > 0.0 for x in p[32:])
    arr = [x.arrival_s for x in p]
    assert arr == sorted(arr)
    assert sum(x.temperature > 0 for x in p[:32]) == 16


def test_tables_hold_the_stated_quantiles_and_mean():
    m = mix("gen")
    for key in ("prompt_tokens", "output_tokens"):
        t = m[key]
        assert t["table"] == generator.quantile_table(t["mean"], t["sigma"])
        # the quantiles leave out the far tail: a percent or two below
        assert 0.97 * t["mean"] < np.mean(t["table"]) < t["mean"]
