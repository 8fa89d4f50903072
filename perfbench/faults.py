"""Faults planted in the timed path, to show that the check catches them:
each takes the warmed-up engine and breaks one thing before the load
starts (``harness.run(..., fault=...)``). The benchmark's own runs plant
none; the tests and ``control.py`` do.

The decode step's faults wrap ``EngineCore.fused_decode``; those of host
sampling wrap ``ServingEngine._emit`` for temperature rows only.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _wrap_decode(eng, change):
    inner = eng.core.fused_decode

    def broken(params, tokens, cache, *rest):
        return change(inner, params, tokens, cache, *rest)

    eng.core.fused_decode = broken


def token_altered(eng):
    """Every decoded token is replaced by the next vocabulary id."""
    V = eng.cfg.vocab

    def change(inner, params, tokens, cache, *rest):
        toks, logits, new = inner(params, tokens, cache, *rest)
        return (toks + 1) % V, logits, new
    _wrap_decode(eng, change)


def state_unchanged(eng):
    """The decode step hands back the K/V pool it was given."""
    import jax
    import jax.numpy as jnp

    def change(inner, params, tokens, cache, *rest):
        kept = jax.tree.map(jnp.copy, cache)
        toks, logits, _ = inner(params, tokens, cache, *rest)
        return toks, logits, kept
    _wrap_decode(eng, change)


def half_batch_left_out(eng):
    """The step serves the first half of the batch only: each row of the
    second half gets a first-half row's token and logits."""
    import jax.numpy as jnp

    def change(inner, params, tokens, cache, *rest):
        toks, logits, new = inner(params, tokens, cache, *rest)
        rows = jnp.arange(toks.shape[0]) % max(1, toks.shape[0] // 2)
        return toks[rows], logits[rows], new
    _wrap_decode(eng, change)


def _wrap_sampled(eng, change):
    from repro.serving import Request

    inner = eng._emit

    def emit(slot, req, tok_dev, fetch_row):
        if isinstance(req, Request) and req.sampling.temperature > 0.0:
            return change(inner, slot, req, tok_dev, fetch_row)
        return inner(slot, req, tok_dev, fetch_row)

    eng._emit = emit


def sampled_greedy(eng):
    """Temperature rows take their row's argmax instead of a draw."""
    def change(inner, slot, req, tok_dev, fetch_row):
        tok = int(np.argmax(fetch_row(slot)))
        req.tokens_out.append(tok)
        return tok
    _wrap_sampled(eng, change)


def sampled_wrong_row(eng):
    """Temperature rows draw from the next slot's logits row."""
    def change(inner, slot, req, tok_dev, fetch_row):
        return inner(slot, req, tok_dev,
                     lambda s: fetch_row((s + 1) % eng.slots))
    _wrap_sampled(eng, change)


def temperature_skipped(eng):
    """Temperature rows draw at temperature 1."""
    def change(inner, slot, req, tok_dev, fetch_row):
        if req.sampling.temperature != 1.0:
            req.sampling = dataclasses.replace(req.sampling, temperature=1.0)
        return inner(slot, req, tok_dev, fetch_row)
    _wrap_sampled(eng, change)


ALL = {f.__name__: f for f in (token_altered, state_unchanged,
                               half_batch_left_out, sampled_greedy,
                               sampled_wrong_row, temperature_skipped)}
