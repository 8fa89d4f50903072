"""Flash attention for TPU (Pallas): block-tiled online softmax.

TPU adaptation of the FlashAttention idea (DESIGN.md §6): the (block_q ×
block_k) score tile lives in VMEM, the running (m, l, acc) statistics live in
VMEM scratch that persists across the sequential k-block grid dimension (TPU
grids execute the innermost dimension sequentially per core — no atomics /
shared-memory reductions as on GPU), and the two matmuls per tile hit the MXU
with 128-aligned shapes.  Causal and sliding-window masking skip
fully-masked tiles via pl.when.

Layouts: q (B, H, Sq, hd); k/v (B, KV, Sk, hd) — GQA folds q-head groups onto
the same KV block through the index map (kv = h // group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window, bq: int, bk: int,
                  nk: int, kv_len: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    if kv_len < nk * bk:
        ok &= k_pos < kv_len

    # Tile-level skip: causal/window tiles that are fully masked cost nothing.
    q_lo, q_hi = qi * bq, qi * bq + bq - 1
    k_lo, k_hi = ki * bk, ki * bk + bk - 1
    live = jnp.asarray(k_lo < kv_len)
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                    # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG)
        m_prev = m_ref[:, 0]                                   # (bq,)
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)[None, None]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "kv_len", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window=None,
                         block_q: int = 128, block_k: int = 128,
                         kv_len: int | None = None, interpret: bool = False):
    """q: (B, H, Sq, hd); k/v: (B, KV, Sk, hd) → (B, H, Sq, hd).

    ``kv_len`` < Sk masks the keys past it: k/v were padded to a whole
    number of blocks (``ops.flash_attention``)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    grid = (B, H, nq, nk)

    kernel = functools.partial(_flash_kernel, scale=hd ** -0.5, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk,
                               kv_len=Sk if kv_len is None else kv_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
