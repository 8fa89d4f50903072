"""Decode attention for TPU (Pallas): split-K accumulation over the KV cache.

Flash-decoding adapted to the TPU execution model (DESIGN.md §6): on GPU,
split-K shards the KV range across SMs and combines partials with a second
kernel; a TPU core executes grid steps sequentially, so split-K becomes
K-block accumulation in VMEM scratch — the (m, l, acc) running statistics
carry across the innermost (k-block) grid dimension and the output is
normalized on the last block.  Decode is memory-bound KV streaming: each
(bk × hd) cache tile is read exactly once from HBM.

The GQA q-head group (G = H/KV heads sharing one KV head) forms the q tile —
(G, hd) — so the score matmul is (G, hd) × (hd, bk): MXU-shaped when G ≥ 8,
and still a single VREG broadcast otherwise.

Layouts (vector-index contract)::

    q        (B, KV, G, hd)   one query token per batch row
    k/v      (B, KV, Smax, hd) ring-buffer caches
    index    scalar or (B,)   per-row absolute position (scalar broadcasts)
    out      (B, KV, G, hd)

``index`` is scalar-prefetched (SMEM) so each grid row ``b`` reads its own
position before the K/V pipeline issues: row ``b`` masks slots against its own
validity horizon ``slot <= index[b]`` (ring-buffer validity — once a row has
wrapped, ``index >= Smax`` and every slot is live), and the K/V index map
clamps dead blocks to the row's last live block, so the sequential pipeline
re-visits a resident tile instead of streaming dead cache from HBM — a short
row in a continuous batch only pays for its own live KV blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale: float, bk: int, nk: int, smax: int):
    """``smax`` is the ring length; when it is not a multiple of ``bk`` the
    last block runs past the cache, and its rows past ``smax`` (unspecified
    memory) are masked out of the scores and zeroed in V."""
    b = pl.program_id(0)
    ki = pl.program_id(2)
    ragged = smax % bk != 0

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    index = idx_ref[b]
    G = q_ref.shape[2]
    slot = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
    ok = slot <= index
    if ragged:
        ok &= slot < smax

    # skip blocks entirely past this row's valid region
    @pl.when(ki * bk <= index)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                    # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG)                              # (G, bk)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        if ragged:
            row = ki * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < smax, v, 0.0)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)[None, None]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_bkgd(q, k_cache, v_cache, index, *, block_k: int = 512,
                          interpret: bool = False):
    """q: (B, KV, G, hd); caches: (B, KV, Smax, hd); index: scalar or (B,)
    int32 — each batch row is masked against its own position.  Smax need
    not be a multiple of ``block_k``: the last block is then partial and
    masked in the kernel."""
    B, KV, G, hd = q.shape
    Smax = k_cache.shape[2]
    bk = min(block_k, Smax)
    nk = -(-Smax // bk)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))

    def kv_map(b, h, ki, idx_ref):
        # dead blocks re-map to the row's last live block: the sequential
        # pipeline sees an unchanged block index and skips the HBM fetch
        last = jnp.minimum(idx_ref[b] // bk, nk - 1)
        return (b, h, jnp.minimum(ki, last), 0)

    kernel = functools.partial(_decode_kernel, scale=hd ** -0.5, bk=bk, nk=nk,
                               smax=Smax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, i: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="decode_attention_bkgd",    # the kernel's name in a trace
    )(idx, q, k_cache, v_cache)


# ---------------------------------------------------------------------------
# paged decode: the KV pool is (NB, KV, bk, hd) physical blocks and each
# batch row walks its own (nk,) row of a scalar-prefetched block table
# ---------------------------------------------------------------------------


def _decode_paged_kernel(tbl_ref, idx_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale: float, bk: int,
                         nk: int):
    """Body identical to ``_decode_kernel`` — only the K/V routing differs
    (the index maps below translate logical block ki through the table)."""
    del tbl_ref
    _decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, scale=scale, bk=bk, nk=nk, smax=nk * bk)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged_bkgd(q, k_cache, v_cache, tbl, index, *,
                                interpret: bool = False):
    """q: (B, KV, G, hd); caches: (NB, KV, bk, hd) shared physical blocks;
    tbl: (B, nk) int32 block table (row b's logical block j lives in physical
    block tbl[b, j]); index: (B,) int32 per-row absolute position.

    This is ``decode_attention_bkgd`` with one generalization: the K/V index
    map reads the scalar-prefetched table, so logical block ki of row b
    streams physical block ``tbl[b, ki]`` from the pool — the same per-row
    dead-block clamping applies (blocks past the row's validity horizon
    re-map to its last live block and the pipeline skips the HBM fetch)."""
    B, KV, G, hd = q.shape
    NB, _, bk, _ = k_cache.shape
    nk = tbl.shape[1]
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))
    tbl = jnp.asarray(tbl, jnp.int32)

    def kv_map(b, h, ki, tbl_ref, idx_ref):
        last = jnp.minimum(idx_ref[b] // bk, nk - 1)
        return (tbl_ref[b, jnp.minimum(ki, last)], h, 0, 0)

    kernel = functools.partial(_decode_paged_kernel, scale=hd ** -0.5,
                               bk=bk, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, t, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, ki, t, i: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(tbl, idx, q, k_cache, v_cache)


def _paged_update_kernel(blk_ref, off_ref, new_ref, cache_ref, out_ref):
    del blk_ref, off_ref, cache_ref   # routing happens in the out index map
    out_ref[...] = new_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_paged_update_bs(cache, new, blk, off, *, interpret: bool = False):
    """Scatter ``new[b]`` into ``cache[blk[b], off[b]]`` in place.

    cache: (NB, bk, KV, hd) physical block pool (model layout); new:
    (B, KV, hd); blk/off: (B,) int32 physical block id and in-block offset.
    The table-resolved coordinates are scalar-prefetched and consumed by the
    output index map — ``cache_ring_update_bs`` with the row's ring slot
    replaced by a (block, offset) pair routed through the block table."""
    NB, bk, KV, hd = cache.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(new.shape[0],),
        in_specs=[
            pl.BlockSpec((1, 1, KV, hd), lambda b, k, o: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, KV, hd), lambda b, k, o: (k[b], o[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, KV, hd),
                               lambda b, k, o: (k[b], o[b], 0, 0)),
    )
    return pl.pallas_call(
        _paged_update_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={3: 0},     # cache operand aliases the output
        interpret=interpret,
    )(jnp.asarray(blk, jnp.int32), jnp.asarray(off, jnp.int32),
      new[:, None], cache)


# ---------------------------------------------------------------------------
# per-row ring-buffer K/V write
# ---------------------------------------------------------------------------


def _ring_update_kernel(slot_ref, new_ref, cache_ref, out_ref):
    del slot_ref, cache_ref      # routing happens in the out index map
    out_ref[...] = new_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_ring_update_bs(cache, new, slot, *, interpret: bool = False):
    """Scatter ``new[b]`` into ``cache[b, slot[b]]`` in place.

    cache: (B, Smax, KV, hd) (model layout); new: (B, KV, hd); slot: (B,)
    int32 ring slots.  The slot vector is scalar-prefetched and consumed by
    the output index map, so grid step ``b`` touches exactly one (KV, hd)
    cache row; ``input_output_aliases`` makes every untouched row free —
    the donation-friendly form of the jnp ``.at[rows, slot].set`` scatter.
    """
    B, Smax, KV, hd = cache.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, KV, hd), lambda b, s: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, KV, hd), lambda b, s: (b, s[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, KV, hd), lambda b, s: (b, s[b], 0, 0)),
    )
    return pl.pallas_call(
        _ring_update_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},     # cache operand aliases the output
        interpret=interpret,
    )(jnp.asarray(slot, jnp.int32), new[:, None], cache)
