"""jit'd public wrappers around the Pallas kernels.

Model code calls these with model-native layouts; the wrappers transpose to
kernel layouts, pad ragged lengths to whole blocks (the kernels mask the
padding), and pick the execution mode: compiled on a TPU backend,
interpreted on the CPU backend (Pallas TPU kernels execute their body in
Python when interpret=True — the CPU test path), and an error on any
other backend, so no run can leave the chip path without a word.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.decode_attention import (
    cache_paged_update_bs,
    cache_ring_update_bs,
    decode_attention_bkgd,
    decode_attention_paged_bkgd,
)
from repro.kernels.sample import fused_sample_bv
from repro.kernels.ssm_scan import ssm_scan_ssd


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels need a TPU (compiled) or the "
                       f"CPU backend (interpreted); got {backend!r}")


def _blocks(n: int, block: int, align: int = 16) -> tuple[int, int]:
    """(block, padded n) for a length-n axis: the block is ``block`` or,
    for a short axis, n rounded up to ``align`` rows; n is padded to a
    whole number of blocks."""
    b = min(block, -(-n // align) * align)
    return b, -(-n // b) * b


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128, interpret=None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) → (B, Sq, H, hd).

    Ragged lengths are padded to whole blocks: padded keys are masked in
    the kernel and padded query rows are sliced away."""
    interpret = _interpret_default() if interpret is None else interpret
    Sq, Sk = q.shape[1], k.shape[1]
    bq, Sqp = _blocks(Sq, block_q)
    bk, Skp = _blocks(Sk, block_k)
    qt = jnp.swapaxes(q, 1, 2)          # (B, H, Sq, hd)
    kt = jnp.swapaxes(k, 1, 2)          # (B, KV, Sk, hd)
    vt = jnp.swapaxes(v, 1, 2)
    pad = ((0, 0), (0, 0), (0, Skp - Sk), (0, 0))
    out = flash_attention_bhsd(
        jnp.pad(qt, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0))),
        jnp.pad(kt, pad), jnp.pad(vt, pad), causal=causal, window=window,
        block_q=bq, block_k=bk, kv_len=Sk, interpret=interpret)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)


def decode_attention(q, k_cache, v_cache, index, *, block_k: int = 512,
                     interpret=None):
    """q: (B, 1, H, hd); caches: (B, Smax, KV, hd) → (B, 1, H, hd).

    ``index`` is a scalar or a (B,) per-row position vector — both dispatch
    to the same split-K kernel (the scalar broadcasts).  An Smax that is
    not a multiple of ``block_k`` ends in a partial block the kernel
    masks."""
    interpret = _interpret_default() if interpret is None else interpret
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qt = q[:, 0].reshape(B, KV, G, hd)  # head h = kv·G + g, as in sdpa_ref
    kt = jnp.swapaxes(k_cache, 1, 2)    # (B, KV, Smax, hd)
    vt = jnp.swapaxes(v_cache, 1, 2)
    out = decode_attention_bkgd(qt, kt, vt, index, block_k=block_k,
                                interpret=interpret)
    return out.reshape(B, 1, H, hd)


def decode_attention_paged(q, k_cache, v_cache, tbl, index, *, interpret=None):
    """q: (B, 1, H, hd); caches: (NB, bk, KV, hd) physical block pools;
    tbl: (B, nk) int32 block table; index: scalar or (B,) → (B, 1, H, hd).

    The paged analogue of ``decode_attention``: each batch row's logical
    sequence is the concatenation of the pool blocks its table row names,
    so the kernel streams ``tbl[b, ki]`` where the dense kernel streamed
    block ki of row b's private ring."""
    interpret = _interpret_default() if interpret is None else interpret
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qt = q[:, 0].reshape(B, KV, G, hd)  # head h = kv·G + g, as in sdpa_ref
    kt = jnp.swapaxes(k_cache, 1, 2)    # (NB, KV, bk, hd)
    vt = jnp.swapaxes(v_cache, 1, 2)
    out = decode_attention_paged_bkgd(qt, kt, vt, tbl, index,
                                      interpret=interpret)
    return out.reshape(B, 1, H, hd)


def cache_paged_update(cache, new, blk, off, *, interpret=None):
    """Scatter ``new[b]`` into ``cache[blk[b], off[b]]`` — the table-routed
    K/V write.  cache: (NB, bk, KV, hd); new: (B, KV, hd); blk/off: (B,)
    int32 physical block id and in-block offset."""
    interpret = _interpret_default() if interpret is None else interpret
    return cache_paged_update_bs(cache, new, blk, off, interpret=interpret)


def cache_ring_update(cache, new, slot, *, interpret=None):
    """Scatter ``new[b]`` into ``cache[b, slot[b]]`` — the fused per-row
    ring-buffer K/V write.  cache: (B, Smax, KV, hd); new: (B, KV, hd);
    slot: (B,) int32 (already reduced mod Smax)."""
    interpret = _interpret_default() if interpret is None else interpret
    return cache_ring_update_bs(cache, new, slot, interpret=interpret)


def fused_sample(logits, seed, rid, pos, temperature, *, top_k: int = 0,
                 interpret=None):
    """logits: (B, V) float; seed/rid/pos: (B,) int32 stateless RNG
    counters; temperature: (B,) float32 (0 → greedy argmax, bit-compatible
    with the host ``sampling.sample_token``) → (B,) int32 tokens.

    ``top_k`` is static per call (0 = full vocabulary); ``top_k > 0``
    needs a per-row k-th order statistic (a sort), which the kernel does
    not do — it dispatches to the jnp reference, still entirely on
    device."""
    interpret = _interpret_default() if interpret is None else interpret
    if top_k > 0:
        return ref.fused_sample_ref(logits, seed, rid, pos, temperature,
                                    top_k=top_k)
    return fused_sample_bv(logits, seed, rid, pos, temperature,
                           interpret=interpret)


def ssm_scan(x, dt, A, B, C, *, chunk: int = 128, interpret=None):
    """SSD scan — x: (Bsz, L, H, hd); dt: (Bsz, L, H); A: (H,);
    B/C: (Bsz, L, H, N) → y (Bsz, L, H, hd) fp32.

    A ragged L is padded at the end with dt = 0 steps (no decay, no
    input), which leave every real step's output unchanged."""
    interpret = _interpret_default() if interpret is None else interpret
    L = x.shape[1]
    T, Lp = _blocks(L, chunk, align=8)

    def pad(a):
        a = a.astype(jnp.float32)
        return jnp.pad(a, ((0, 0), (0, Lp - L)) + ((0, 0),) * (a.ndim - 2))

    y = ssm_scan_ssd(pad(x), pad(dt), A, pad(B), pad(C), chunk=T,
                     interpret=interpret)
    return y[:, :L]
