"""Fused in-kernel token sampling — the decode tail.

Greedy argmax or Gumbel-max temperature sampling over each row's (V,)
logits, eight rows and one vocabulary tile per grid step, with a
counter-based RNG hashed from per-row ``(seed, rid, pos)`` — stateless
counters: no RNG state lives on device, every (request, position) pair
draws an independent stream, and replays/retraces are bit-reproducible.

Greedy (``temperature == 0``) is bit-compatible with the host path
(``serving.sampling.sample_token``): both reduce to first-index argmax
over the f32 logits row (the host's f32→f64 cast is monotonic and
injective, so the winning index agrees), which is what lets a serving tick
keep its sampled tokens on device — the engine pulls (B,) int32 tokens
instead of (B, 1, V) logits.

Top-k thresholding needs a per-row k-th order statistic (a sort); that
lives in the jnp reference (``ref.fused_sample_ref``), and ``ops.
fused_sample`` routes ``top_k > 0`` there, still on device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# murmur3 finalizer constants — the avalanche the jnp oracle reimplements
# independently; tests pin kernel == ref BITWISE on the shared space.  The
# kernel hashes in int32 with logical shifts: the bits equal the oracle's
# uint32 arithmetic, and Mosaic lowers every op of it.
M1 = 0x85EBCA6B
M2 = 0xC2B2AE35
GOLDEN = 0x9E3779B9

ROWS = 8          # rows per grid step: one sublane tile
BLOCK_V = 8192    # vocabulary lanes per grid step (a multiple of 128)


def _i32(c: int):
    return jnp.int32(c - (1 << 32) if c >= 1 << 31 else c)


def _mix(x):
    """int32 → int32 avalanche (murmur3 fmix32 on the uint32 bits)."""
    srl = jax.lax.shift_right_logical
    x = x ^ srl(x, jnp.int32(16))
    x = x * _i32(M1)
    x = x ^ srl(x, jnp.int32(13))
    x = x * _i32(M2)
    return x ^ srl(x, jnp.int32(16))


def _sample_kernel(seed_ref, rid_ref, pos_ref, temp_ref, logits_ref,
                   out_ref, best_ref, arg_ref, *, V: int, bv: int):
    """One (ROWS, bv) logits tile; the running (max, first argmax) pair
    lives in VMEM scratch across the sequential vocabulary axis."""
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, -jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    x = logits_ref[...].astype(jnp.float32)                   # (ROWS, bv)
    t = temp_ref[...]                                         # (ROWS, 1)
    key = _mix(_i32(GOLDEN) ^ seed_ref[...])
    key = _mix(key ^ rid_ref[...])
    key = _mix(key ^ pos_ref[...])                            # (ROWS, 1)
    col = vi * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    bits = _mix(key ^ col)
    u = (jax.lax.shift_right_logical(bits, jnp.int32(8)).astype(jnp.float32)
         + 0.5) * (1.0 / (1 << 24))                           # (0, 1)
    g = -jnp.log(-jnp.log(u))
    score = jnp.where(t > 0.0, x / jnp.maximum(t, 1e-30) + g, x)
    score = jnp.where(col < V, score, -jnp.inf)   # the ragged last tile
    m = jnp.max(score, axis=1, keepdims=True)                 # (ROWS, 1)
    first = jnp.min(jnp.where(score == m, col, jnp.int32(V)), axis=1,
                    keepdims=True)
    # strict > keeps the earlier tile on ties: first-index argmax overall
    take = m > best_ref[:, :1]
    best_ref[...] = jnp.broadcast_to(jnp.where(take, m, best_ref[:, :1]),
                                     best_ref.shape)
    arg_ref[...] = jnp.broadcast_to(jnp.where(take, first, arg_ref[:, :1]),
                                    arg_ref.shape)

    @pl.when(vi == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = arg_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_sample_bv(logits, seed, rid, pos, temperature, *,
                    interpret: bool = False):
    """logits: (B, V) float; seed/rid/pos: (B,) int32 RNG counters;
    temperature: (B,) float32 (0 → greedy argmax) → (B,) int32 tokens.

    Rows are padded to a multiple of ROWS; the vocabulary is tiled by
    BLOCK_V lanes with a running argmax, the ragged last tile masked in the
    kernel.  The output is one lane-dense (ROWS, 128) int32 tile per row
    group, every lane holding the row's token."""
    B, V = logits.shape
    Bp = -(-B // ROWS) * ROWS
    bv = min(BLOCK_V, -(-V // 128) * 128)
    nv = -(-V // bv)

    def rows(a, dtype):
        a = jnp.asarray(a, dtype).reshape(B, 1)
        return jnp.pad(a, ((0, Bp - B), (0, 0)))

    row_spec = pl.BlockSpec((ROWS, 1), lambda r, v: (r, 0))
    out = pl.pallas_call(
        functools.partial(_sample_kernel, V=V, bv=bv),
        grid=(Bp // ROWS, nv),
        in_specs=[row_spec, row_spec, row_spec, row_spec,
                  pl.BlockSpec((ROWS, bv), lambda r, v: (r, v))],
        out_specs=pl.BlockSpec((ROWS, 128), lambda r, v: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 128), jnp.int32),
        scratch_shapes=[pltpu.VMEM((ROWS, 128), jnp.float32),
                        pltpu.VMEM((ROWS, 128), jnp.int32)],
        interpret=interpret,
    )(rows(seed, jnp.int32), rows(rid, jnp.int32), rows(pos, jnp.int32),
      rows(temperature, jnp.float32),
      jnp.pad(logits, ((0, Bp - B), (0, 0))))
    return out[:B, 0]
