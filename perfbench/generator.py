"""The one traffic generator: a mix file's parameters → an arrival schedule.

A mix (``perfbench/traffic/<name>.json``) gives tables of prompt and output
lengths, the share of requests sampled at a temperature (spread evenly
over the arrival order), and the arrival process; the cell
(``perfbench/cells/<workload>.json``) gives the offered rate. Requests come
in blocks of ``BLOCK``; within a block each table entry is used once, in a
permutation drawn from ``ORDER_SEED``. Arrivals are Poisson: independent
exponential gaps, drawn from the same generator.

So the sizes, the gaps and their order are one fixed sample of the mix's
distributions, replayed as a recorded trace would be, and every seed
offers the same work; ``--seed`` draws the token ids, the sampling seeds
(and, elsewhere, the weights). With the order drawn from ``--seed`` as
well, the 95th percentile of TTFT under a prefill-heavy mix spread over
45% from seed to seed against 2% between two runs of one seed (PERF.md).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

BLOCK = 64
ORDER_SEED = 1


@dataclasses.dataclass
class Planned:
    rid: int
    arrival_s: float          # after the start of load
    prompt: np.ndarray        # int32 token ids
    gen_len: int
    temperature: float
    sample_seed: int


def quantile_table(mean: float, sigma: float, n: int = BLOCK) -> list[int]:
    """n lengths at the quantiles (k + 1/2) / n of the lognormal with the
    given mean and log-spread, rounded, at least 1."""
    from statistics import NormalDist

    median = mean * math.exp(-sigma * sigma / 2)
    z = [NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)]
    return [max(1, round(median * math.exp(sigma * v))) for v in z]


def plan(mix: dict, rate: float, horizon_s: float, vocab: int, seed: int,
         backlog: int = 0) -> list[Planned]:
    """Requests arriving in [0, horizon_s) at ``rate`` req/s, after
    ``backlog`` requests that all arrive at 0 (a queue standing when the
    load starts). The backlog takes whole blocks of its own, so the stream
    after it starts on a fresh block."""
    order_rng = np.random.default_rng([ORDER_SEED, 0x6f72])
    rng = np.random.default_rng([int(seed), 0x7261])
    prompts = np.asarray(mix["prompt_tokens"]["table"], np.int64)
    outputs = np.asarray(mix["output_tokens"]["table"], np.int64)
    if len(prompts) != BLOCK or len(outputs) != BLOCK:
        raise ValueError(f"length tables must hold {BLOCK} entries")
    # sampled requests evenly spaced in arrival order, the same for every
    # seed, so that a batch holds about the mix's share of them throughout
    share = float(mix["sampled"]["share"])
    idx = np.arange(BLOCK)
    temps = np.where(np.floor((idx + 1) * share) > np.floor(idx * share),
                     float(mix["sampled"]["temperature"]), 0.0)
    out: list[Planned] = []
    t = 0.0
    stream_starts = -(-backlog // BLOCK) * BLOCK
    drawn = 0
    while True:
        order = [order_rng.permutation(BLOCK) for _ in range(2)]
        gaps = order_rng.exponential(1.0 / rate, BLOCK)
        for k in range(BLOCK):
            drawn += 1
            if drawn <= stream_starts:
                if drawn > backlog:
                    continue
                arrival = 0.0
            else:
                t += float(gaps[k])
                arrival = t
                if t >= horizon_s:
                    return out
            p = int(prompts[order[0][k]])
            out.append(Planned(
                rid=len(out), arrival_s=arrival,
                prompt=rng.integers(0, vocab, size=p, dtype=np.int32),
                gen_len=int(outputs[order[1][k]]),
                temperature=float(temps[k]),
                sample_seed=int(rng.integers(0, 2**31 - 1))))
