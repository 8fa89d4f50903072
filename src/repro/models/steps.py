"""Step functions (train / prefill / decode) and ShapeDtypeStruct input specs.

These are the units the launcher jits: ``jax.jit(train_step, in_shardings=…)
.lower(**input_specs(...)).compile()`` is exactly what the multi-pod dry-run
exercises for all 40 (arch × shape) cells.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, ShapeCfg
from repro.models.transformer import LM
from repro.models.attention import Attention
from repro.optim import adamw, apply_updates, clip_by_global_norm
from repro.sharding import constrain


class TrainState(NamedTuple):
    params: object
    opt_state: object
    step: jax.Array


def cross_entropy(logits, labels, ignore_id: int = -1):
    """logits: (B, S, V) fp32 (possibly vocab-sharded); labels: (B, S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum((logz - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


CE_CHUNK = 1024


def chunked_cross_entropy(params, h, labels, cfg, *, ignore_id: int = -1,
                          chunk: int = CE_CHUNK):
    """CE from hidden states with per-seq-chunk logits (lax.map), so the
    (B, S, V) fp32 logits tensor never materializes — at 4k×256 with a 152k
    vocab that tensor alone is ~40 GB/device (EXPERIMENTS.md §Perf C4).
    Numerically identical to cross_entropy(LM._logits(h))."""
    B, S, d = h.shape
    n = S // chunk
    hc = jnp.moveaxis(h.reshape(B, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    def one(args):
        hi, li = args
        logits = LM._logits(params, hi, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, li[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
        mask = (li != ignore_id).astype(jnp.float32)
        return jnp.sum((logz - ll) * mask), jnp.sum(mask)

    num, den = jax.lax.map(one, (hc, lc))
    return jnp.sum(num) / jnp.maximum(jnp.sum(den), 1.0)


def model_inputs(cfg: ModelConfig, batch: int, seq: int, *, with_labels: bool):
    """Concrete-input template as (shape, dtype) dicts; family-aware."""
    specs = {"tokens": ((batch, seq), jnp.int32)}
    if cfg.family == "vlm" and seq > 1:
        specs["patches"] = ((batch, cfg.n_vision_patches, cfg.d_model), cfg.cdtype)
    if cfg.enc_dec:
        specs["frames"] = ((batch, seq, cfg.d_model), cfg.cdtype)
    if with_labels:
        specs["labels"] = ((batch, seq), jnp.int32)
    return specs


def input_sharding_axes(cfg: ModelConfig, *, with_labels: bool):
    axes = {"tokens": ("batch", "seq")}
    if cfg.family == "vlm":
        axes["patches"] = ("batch", None, "embed_act")
    if cfg.enc_dec:
        axes["frames"] = ("batch", "seq", "embed_act")
    if with_labels:
        axes["labels"] = ("batch", "seq")
    return axes


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, *, lr=3e-4, weight_decay: float = 0.1,
                    grad_clip: float = 1.0):
    opt_init, opt_update = adamw(lr, weight_decay=weight_decay)
    params_axes = None
    if cfg.cdtype != cfg.pdtype:
        params_axes, _ = params_axes_and_structs(cfg)

    def cast_params_sharded(params):
        """Mixed-precision FSDP: cast fp32 masters to the compute dtype WITH
        the sharded layout pinned, so the per-layer weight all-gathers move
        bf16 instead of fp32 — halves the dominant training collective
        (EXPERIMENTS.md §Perf A2).  No-op when pdtype == cdtype."""
        if params_axes is None:
            return params
        def one(p, ax):
            if p.dtype == jnp.float32:
                return constrain(p.astype(cfg.cdtype), ax)
            return p
        return jax.tree.map(one, params, params_axes)

    def train_step(state: TrainState, batch):
        def loss_fn(params):
            p_c = cast_params_sharded(params)
            S = batch["labels"].shape[1]
            if S > CE_CHUNK and S % CE_CHUNK == 0:
                h, aux = LM.apply(p_c, batch, cfg, return_hidden=True)
                ce = chunked_cross_entropy(p_c, h, batch["labels"], cfg)
            else:
                logits, aux = LM.apply(p_c, batch, cfg)
                ce = cross_entropy(logits, batch["labels"])
            loss = ce
            if cfg.moe is not None:
                loss = (loss + cfg.moe.router_aux_coef * aux["lb_loss"]
                        + cfg.moe.router_z_coef * aux["z_loss"])
            return loss, (ce, aux)

        (loss, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        updates, opt_state = opt_update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        metrics = {"loss": loss, "ce": ce, "grad_norm": gnorm,
                   "lb_loss": aux["lb_loss"], "drop_frac": aux["drop_frac"]}
        return TrainState(params, opt_state, state.step + 1), metrics

    return train_step, (opt_init, opt_update)


def init_train_state(key, cfg: ModelConfig, opt_init):
    params, _ = LM.init(key, cfg)
    return TrainState(params=params, opt_state=opt_init(params),
                      step=jnp.zeros((), jnp.int32))


def params_axes_and_structs(cfg: ModelConfig):
    """(logical-axes pytree, ShapeDtypeStruct pytree) for the params — built
    under eval_shape so nothing is allocated (the 72B config included)."""
    captured = {}

    def f(key):
        params, axes = LM.init(key, cfg)
        captured["axes"] = axes
        return params

    structs = jax.eval_shape(f, jax.random.PRNGKey(0))
    return captured["axes"], structs


def train_state_axes(cfg: ModelConfig):
    """Logical-axes pytree mirroring TrainState (params + AdamW moments)."""
    params_axes, _ = params_axes_and_structs(cfg)
    from repro.optim.adamw import AdamWState
    return TrainState(
        params=params_axes,
        opt_state=AdamWState(step=(), mu=params_axes, nu=params_axes),
        step=())


# ---------------------------------------------------------------------------
# serve (prefill + decode)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, max_seq: int):
    def prefill_step(params, batch):
        return LM.prefill(params, batch, cfg, max_seq)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, tokens, cache):
        return LM.decode(params, tokens, cfg, cache)
    return decode_step


def make_verify_step(cfg: ModelConfig):
    """Speculative-decode verify: feed a (B, W) window of tokens — per row,
    the committed next input token followed by up to W-1 draft proposals —
    through W chained decode steps in ONE jitted program, returning the
    per-position sampled tokens (B, W) int32, the per-position logits
    (B, W, V), and the advanced cache.

    This is ``make_chunked_prefill_step``'s scan body promoted to a
    standalone step: each position runs the SAME ``LM.decode`` the plain
    tick runs, so the logits at position j (given the same fed prefix) are
    bit-identical to the non-speculative path's.  Each lane draws its token
    with the jnp sampler (``kernels.ref.fused_sample_ref``, pinned bitwise
    to the fused step's kernel) from the row's (seed, rid) and that lane's
    ``pos`` (B, W) counter — the draw the plain tick would make at the same
    output position — so acceptance, exact-match on sampled tokens, keeps
    speculative streams bit-identical to plain ones.  The device cache
    advances W positions for every row; the engine rewinds each row to its
    true position afterwards (``pool.set_index``) — the same mechanism
    preemption/evacuation uses — so rejected-tail K/V is simply re-covered.
    jit retraces per distinct W; an engine uses one W.
    """
    from repro.kernels import ref as kernel_ref

    def verify_step(params, tokens, cache, seed, rid, pos, temperature):
        B, W = tokens.shape
        tail = jnp.moveaxis(tokens[:, :, None], 1, 0)     # (W, B, 1)

        def body(cache, tok):
            logits, cache = LM.decode(params, tok, cfg, cache)
            return cache, logits[:, 0]                    # (B, V)

        # W is tiny (spec_k+1, single digits): full unroll removes the XLA
        # while-loop's per-iteration dispatch, which at serving batch sizes
        # costs more than the chained decodes themselves on CPU
        cache, ls = jax.lax.scan(body, cache, tail, unroll=True)
        ls = jnp.moveaxis(ls, 0, 1)                       # (B, W, V)

        def lanes(a):
            return jnp.repeat(a, W)                       # (B·W,) row-major

        toks = kernel_ref.fused_sample_ref(
            ls.reshape(B * W, -1).astype(jnp.float32), lanes(seed),
            lanes(rid), pos.reshape(-1), lanes(temperature))
        return toks.reshape(B, W), ls, cache

    return verify_step


def _row_sampler(cfg: ModelConfig):
    """The sampler every serving step draws with: (B, V) float32 rows and
    (B,) counters → (B,) int32 tokens.  The Pallas fused-sample kernel
    under ``cfg.use_pallas``, the jnp oracle otherwise — the two are
    pinned bitwise-equal."""
    from repro.kernels import ops as kernel_ops
    from repro.kernels import ref as kernel_ref

    return (kernel_ops.fused_sample if cfg.use_pallas
            else kernel_ref.fused_sample_ref)


def make_sample_step(cfg: ModelConfig):
    """Draw one token per row from a step's last-position logits (B, S, V)
    with the fused decode step's sampler and counters: the admission's
    first token off the prefill's (1, 1, V) logits, and the tokens of a
    replaced decode step's (B, 1, V) logits.  One program per (B, V)."""
    sample = _row_sampler(cfg)

    def sample_step(logits, seed, rid, pos, temperature):
        return sample(logits[:, -1].astype(jnp.float32), seed, rid, pos,
                      temperature)

    return sample_step


def make_fused_decode_step(cfg: ModelConfig):
    """One decode step with sampling fused into the tail: returns the
    per-row sampled tokens (B,) int32 ALONGSIDE the logits, so a serving
    tick pulls B int32s instead of (B, 1, V) floats.  The device draw is
    the sampler: greedy rows and temperature rows over the full vocabulary
    emit its token; the logits stay device-resident for the rows
    (temperature > 0 with ``top_k > 0``) that still sample host-side with
    their stateful per-request RNG.

    seed/rid/pos are (B,) int32 stateless RNG counters, so every draw is a
    pure function of (seed, request, output position); temperature is (B,)
    float32, 0 → greedy argmax, bit-compatible with the host
    ``sampling.sample_token``.  Dispatches to the Pallas fused-sample
    kernel under ``cfg.use_pallas`` and to the jnp oracle otherwise — the
    two are pinned bitwise-equal.
    """
    sample = _row_sampler(cfg)

    def fused_decode_step(params, tokens, cache, seed, rid, pos, temperature):
        logits, cache = LM.decode(params, tokens, cfg, cache)
        toks = sample(logits[:, 0].astype(jnp.float32), seed, rid, pos,
                      temperature)
        return toks, logits, cache

    return fused_decode_step


def make_chunked_prefill_step(cfg: ModelConfig, max_seq: int, chunk: int):
    """Prefill with bounded per-step work: a one-shot prefill of the first
    ``chunk`` tokens builds the cache, then the remaining prompt streams
    through the decode path one token per step (lax.scan).  Produces the same
    (last-position logits, cache) as ``make_prefill_step``.

    This is the REFERENCE form of the equivalence the serving engine exploits
    (ServingEngine.admit/tick interleave the same per-token continuation with
    live decode slots, which a self-contained scan cannot express) — the
    engine test suite pins both implementations against one-shot prefill.

    Constraints: enc-dec prefills one-shot (the encoder needs every frame);
    vlm needs ``chunk > n_vision_patches`` so the patch prefix lands in the
    one-shot portion.
    """
    if cfg.family == "vlm" and chunk <= cfg.n_vision_patches:
        raise ValueError(
            f"vlm chunked prefill needs chunk > n_vision_patches "
            f"({chunk} <= {cfg.n_vision_patches})")

    def chunked_prefill(params, inputs):
        tokens = inputs["tokens"]
        S = tokens.shape[1]
        if S <= chunk or cfg.enc_dec:
            return LM.prefill(params, inputs, cfg, max_seq)
        first = dict(inputs)
        first["tokens"] = tokens[:, :chunk]
        logits, cache = LM.prefill(params, first, cfg, max_seq)
        tail = jnp.moveaxis(tokens[:, chunk:, None], 1, 0)  # (S-chunk, B, 1)

        def body(carry, tok):
            _, cache = carry
            logits, cache = LM.decode(params, tok, cfg, cache)
            return (logits, cache), None

        (logits, cache), _ = jax.lax.scan(body, (logits, cache), tail)
        return logits, cache

    return chunked_prefill


def cache_axes(cfg: ModelConfig, batch: int, max_seq: int):
    spec = LM.cache_spec(cfg, batch, max_seq)
    return jax.tree.map(lambda s: s[2], spec,
                        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
                        and isinstance(x[0], tuple))


def cache_structs(cfg: ModelConfig, batch: int, max_seq: int):
    spec = LM.cache_spec(cfg, batch, max_seq)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], s[1]), spec,
                        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
                        and isinstance(x[0], tuple))


def input_structs(cfg: ModelConfig, shape: ShapeCfg):
    """ShapeDtypeStruct stand-ins for one dry-run cell (no allocation)."""
    if shape.kind == "train":
        t = model_inputs(cfg, shape.global_batch, shape.seq_len, with_labels=True)
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in t.items()}
    if shape.kind == "prefill":
        t = model_inputs(cfg, shape.global_batch, shape.seq_len, with_labels=False)
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in t.items()}
    # decode: one token + cache at seq_len
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    cache = cache_structs(cfg, shape.global_batch, shape.seq_len)
    return {"tokens": tokens, "cache": cache}
