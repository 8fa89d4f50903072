"""Plain reference for the Qwen2 architecture, and its seeded weights.

The published Qwen2 decoder (hf ``Qwen2ForCausalLM``): token embedding,
then per layer a pre-norm GQA attention block (RMSNorm, q/k/v projections
with bias, GPT-NeoX half-rotation RoPE, causal softmax attention, output
projection without bias) and a pre-norm SwiGLU MLP
(``down(silu(gate(x)) * up(x))``), a final RMSNorm, and a read-out that is
the embedding's transpose when ``tie_word_embeddings`` and an untied head
otherwise.

Written in straightforward ``jax.numpy`` with no kernels, cache or
batching, and independent of the code under test. ``logit_scan`` runs one
sequence at float32 with the highest matmul precision and reads, at every
position, what the check needs of the next-token distribution;
``precision="fp8"`` rounds every matmul operand to float8 e4m3 (per-tensor
scales for weights, per-row scales for activations): the control that a
lower precision must fail. Weights stay in the type they are served in (bfloat16) and are cast
one layer at a time inside the layer scan, so the float32 copy of the model
never exists whole.

Weights are laid out ``(in, out)`` per matrix and stacked on a leading layer
axis, under the names of the published checkpoint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0          # largest finite float8_e4m3fn
VOCAB_CHUNKS = 8        # the read-out is evaluated in this many vocab slices


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": H,
            "KV": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // H),
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "tied": bool(cfg["tie_word_embeddings"])}


def weight_shapes(cfg: dict) -> dict:
    """name -> shape of every weight, per-layer ones stacked on axis 0."""
    m = dims(cfg)
    L, d, H, KV, hd, F, V = (m[k] for k in
                             ("L", "d", "H", "KV", "hd", "F", "V"))
    shapes = {
        "embed_tokens": (V, d),
        "input_layernorm": (L, d),
        "q_proj": (L, d, H * hd), "q_bias": (L, H * hd),
        "k_proj": (L, d, KV * hd), "k_bias": (L, KV * hd),
        "v_proj": (L, d, KV * hd), "v_bias": (L, KV * hd),
        "o_proj": (L, H * hd, d),
        "post_attention_layernorm": (L, d),
        "gate_proj": (L, d, F), "up_proj": (L, d, F), "down_proj": (L, F, d),
        "norm": (d,),
    }
    if not m["tied"]:
        shapes["lm_head"] = (d, V)
    return shapes


def _init_scale(name: str, shape: tuple) -> tuple[float, float]:
    """(mean, std) of a weight: fan-in scaled matrices, embedding std 0.02
    (logits of std about 1 through a tied read-out), biases std 0.1 and
    norm scales 1 ± 0.1 so that both paths are exercised."""
    if name.endswith("layernorm") or name == "norm":
        return 1.0, 0.1
    if name.endswith("_bias"):
        return 0.0, 0.1
    if name == "embed_tokens":
        return 0.0, 0.02
    return 0.0, shape[-2] ** -0.5


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, 64-bit ones too."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight drawn on the device from ``seed`` in one jitted call,
    directly in the type it is served in."""
    shapes = weight_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            mean, std = _init_scale(name, shapes[name])
            z = jax.random.normal(k, shapes[name], dtype)
            scale, shift = jnp.asarray(std, dtype), jnp.asarray(mean, dtype)
            out[name] = z * scale + shift
        return out

    return draw(seed_key(seed))


# ----------------------------------------------------------------- forward

def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, precision):
    """x (..., k) @ w (k, n) in float32, or with both operands rounded to
    float8 (weights per tensor, activations per row) for the control."""
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = _q8(x, -1)
        w = _q8(w, None)
    return x @ w


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (S, heads, hd); GPT-NeoX half rotation."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, precision, x, w):
    m = dims(cfg)
    H, KV, hd = m["H"], m["KV"], m["hd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, w["input_layernorm"], eps)
    q = _mm(h, w["q_proj"], precision) + w["q_bias"].astype(jnp.float32)
    k = _mm(h, w["k_proj"], precision) + w["k_bias"].astype(jnp.float32)
    v = _mm(h, w["v_proj"], precision) + w["v_bias"].astype(jnp.float32)
    q = _rope(q.reshape(S, H, hd), pos, theta)
    k = _rope(k.reshape(S, KV, hd), pos, theta)
    v = v.reshape(S, KV, hd)
    q = q.reshape(S, KV, H // KV, hd)
    s = jnp.einsum("skgh,tkh->kgst", q, k) * hd ** -0.5
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkh->skgh", p, v).reshape(S, H * hd)
    x = x + _mm(o, w["o_proj"], precision)
    h = _rms(x, w["post_attention_layernorm"], eps)
    g = _mm(h, w["gate_proj"], precision)
    u = _mm(h, w["up_proj"], precision)
    return x + _mm(jax.nn.silu(g) * u, w["down_proj"], precision)


LAYER_KEYS = ("input_layernorm", "q_proj", "q_bias", "k_proj", "k_bias",
              "v_proj", "v_bias", "o_proj", "post_attention_layernorm",
              "gate_proj", "up_proj", "down_proj")


def _hidden(cfg, precision, w, tokens):
    x = jnp.take(w["embed_tokens"], tokens, axis=0).astype(jnp.float32)
    layers = {k: w[k] for k in LAYER_KEYS}
    x, _ = jax.lax.scan(lambda c, lw: (_layer(cfg, precision, c, lw), None),
                        x, layers)
    return _rms(x, w["norm"], cfg["rms_norm_eps"])


def _head_chunks(cfg, w):
    """The read-out as (VOCAB_CHUNKS, d, V/chunks) slices, vocab padded."""
    head = w["embed_tokens"].T if dims(cfg)["tied"] else w["lm_head"]
    d, V = head.shape
    c = -(-V // VOCAB_CHUNKS)
    head = jnp.pad(head, ((0, 0), (0, c * VOCAB_CHUNKS - V)))
    return jnp.moveaxis(head.reshape(d, VOCAB_CHUNKS, c), 1, 0), c


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _scan_vocab(w, tokens, targets, temperature, draw_key, draw_temperature,
                cfg_items, precision):
    """Over vocab slices, at every position: the best logit and its argmax;
    the logits of ``targets`` (S, K) token ids (-1: none); the mean and the
    variance of ``y = logit / temperature`` under ``softmax(y)``; and one
    token drawn from ``softmax(logit / draw_temperature)`` by Gumbel-max
    under ``draw_key``, with its logit."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        h = _hidden(cfg, precision, w, tokens)
        chunks, c = _head_chunks(cfg, w)
        V = dims(cfg)["V"]

        def body(carry, xs):
            best, arg, tgt, m, s0, s1, s2, dscore, dtok, dlogit = carry
            i, wc = xs
            lg = _mm(h, wc, precision)                     # (S, c)
            ids = i * c + jnp.arange(c)
            valid = ids[None, :] < V
            lg = jnp.where(valid, lg, -jnp.inf)
            cb = jnp.max(lg, axis=-1)
            ca = i * c + jnp.argmax(lg, axis=-1)
            arg = jnp.where(cb > best, ca, arg)
            best = jnp.maximum(best, cb)
            local = targets - i * c
            inside = (local >= 0) & (local < c)
            got = jnp.take_along_axis(lg, jnp.clip(local, 0, c - 1), axis=-1)
            tgt = jnp.where(inside, got, tgt)
            # running moments of y under softmax(y), rescaled to a new max
            y = lg / temperature
            m_new = jnp.maximum(m, jnp.max(y, axis=-1))
            a = jnp.exp(m - m_new)
            e = jnp.where(valid, jnp.exp(y - m_new[:, None]), 0.0)
            ey = jnp.where(valid, e * y, 0.0)
            s0 = s0 * a + jnp.sum(e, -1)
            s1 = s1 * a + jnp.sum(ey, -1)
            s2 = s2 * a + jnp.sum(jnp.where(valid, ey * y, 0.0), -1)
            g = jax.random.gumbel(jax.random.fold_in(draw_key, i), lg.shape)
            sc = lg / draw_temperature + g
            cs = jnp.max(sc, axis=-1)
            ci = jnp.argmax(sc, axis=-1)
            take = cs > dscore
            dtok = jnp.where(take, i * c + ci, dtok)
            dlogit = jnp.where(
                take, jnp.take_along_axis(lg, ci[:, None], -1)[:, 0], dlogit)
            dscore = jnp.maximum(dscore, cs)
            return (best, arg, tgt, m_new, s0, s1, s2, dscore, dtok,
                    dlogit), None

        S = tokens.shape[0]
        neg = jnp.full((S,), -jnp.inf)
        zero = jnp.zeros((S,))
        init = (neg, jnp.zeros((S,), jnp.int32),
                jnp.full(targets.shape, -jnp.inf), neg, zero, zero, zero,
                neg, jnp.zeros((S,), jnp.int32), zero)
        (best, arg, tgt, _, s0, s1, s2, _, dtok, dlogit), _ = jax.lax.scan(
            body, init, (jnp.arange(VOCAB_CHUNKS), chunks))
    mu = s1 / s0
    return {"best": best, "argmax": arg, "target": tgt, "mean_y": mu,
            "var_y": s2 / s0 - mu * mu, "draw": dtok, "draw_logit": dlogit}


def frozen(cfg: dict) -> tuple:
    """The numeric keys of a configuration as a hashable static argument."""
    keep = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "tie_word_embeddings", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


def logit_scan(cfg: dict, w: dict, tokens, targets, precision="float32",
               temperature: float = 1.0, draw_seed: int = 0,
               draw_temperature: float = 1.0) -> dict:
    """→ ``_scan_vocab``'s readings, (S,) or (S, K) device arrays.
    ``tokens`` (S,) int32; ``targets`` (S, K) int32, -1 where unused.
    Positions past the real sequence are causal padding: they change no
    earlier position."""
    return _scan_vocab(w, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(targets, jnp.int32),
                       jnp.float32(temperature), seed_key(draw_seed),
                       jnp.float32(draw_temperature), frozen(cfg), precision)
