"""Vector-index decode kernel: fused-vs-reference equivalence suite.

The split-K Pallas decode kernel accepts a (B,) per-row cache position
(continuous batching — every serving slot sits at its own ring position).
The suite sweeps (B, KV, G, hd, Smax, block_k) and index regimes — all-zero,
fresh (< Smax), ring-wrapped (>= Smax), and mixed batches — in interpret
mode, asserting the kernel matches the pure-jnp oracle; fixed cases pin the
degenerate edges and the per-row ring-scatter write.  A deterministic grid
always runs; hypothesis (when installed) fuzzes the same property over the
full cartesian space.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                       # degrade to the fixed grid, never to a dead module
    from hypothesis import given, settings, strategies as st
except ImportError:
    st = None

from repro.kernels import ops, ref
from repro.kernels.decode_attention import (
    cache_ring_update_bs,
    decode_attention_bkgd,
)

jax.config.update("jax_enable_x64", False)

pytestmark = pytest.mark.kernels

ATOL = 5e-5          # well inside the issue's ≤1e-3 acceptance bound


def _case(seed, B, Smax, H, KV, hd):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (B, 1, H, hd), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, KV, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, KV, hd))
    return q, kc, vc


def _index_vector(regime, rng, B, Smax):
    if regime == "zeros":
        return np.zeros(B, np.int32)
    if regime == "fresh":
        return rng.integers(0, Smax, size=B).astype(np.int32)
    if regime == "wrapped":
        return rng.integers(Smax, 4 * Smax, size=B).astype(np.int32)
    fresh = rng.integers(0, Smax, size=B)
    wrapped = rng.integers(Smax, 4 * Smax, size=B)
    pick = rng.integers(0, 2, size=B).astype(bool)
    return np.where(pick, wrapped, fresh).astype(np.int32)


# ------------------------------------------------------- fused vs reference


def _check_vector_index(B, Smax, KV, G, hd, block_k, regime, seed):
    H = KV * G
    q, kc, vc = _case(seed, B, Smax, H, KV, hd)
    index = jnp.asarray(
        _index_vector(regime, np.random.default_rng(seed), B, Smax))
    out = ops.decode_attention(q, kc, vc, index, block_k=block_k,
                               interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, index)
    np.testing.assert_allclose(out, want, atol=ATOL, rtol=ATOL)


GRID = [
    # (B, Smax, KV, G, hd, block_k, regime)
    (1, 128, 1, 4, 16, 32, "zeros"),
    (2, 128, 2, 2, 32, 64, "fresh"),
    (4, 128, 2, 1, 32, 128, "wrapped"),
    (2, 256, 4, 2, 64, 64, "mixed"),
    (4, 256, 2, 2, 16, 128, "mixed"),
    (1, 256, 1, 1, 64, 256, "wrapped"),
    (2, 128, 1, 2, 32, 128, "zeros"),
    (4, 256, 2, 4, 32, 64, "fresh"),
]


@pytest.mark.parametrize("B,Smax,KV,G,hd,block_k,regime", GRID)
def test_vector_index_matches_ref_grid(B, Smax, KV, G, hd, block_k, regime):
    _check_vector_index(B, Smax, KV, G, hd, block_k, regime,
                        seed=B * Smax + KV + G + hd + block_k)


if st is not None:
    @settings(max_examples=24, deadline=None)
    @given(
        B=st.sampled_from([1, 2, 4]),
        Smax=st.sampled_from([128, 256]),
        KVG=st.sampled_from([(1, 4), (2, 2), (2, 1), (4, 2)]),   # (KV, G)
        hd=st.sampled_from([16, 32, 64]),
        block_k=st.sampled_from([32, 64, 128]),
        regime=st.sampled_from(["zeros", "fresh", "wrapped", "mixed"]),
        seed=st.integers(0, 2**16),
    )
    def test_vector_index_matches_ref_fuzz(B, Smax, KVG, hd, block_k, regime,
                                           seed):
        KV, G = KVG
        _check_vector_index(B, Smax, KV, G, hd, block_k, regime, seed)


def test_vector_of_equal_rows_matches_scalar_dispatch():
    """A constant (B,) vector and the scalar fast path are the same math."""
    B, Smax, H, KV, hd = 3, 256, 4, 2, 32
    q, kc, vc = _case(5, B, Smax, H, KV, hd)
    vec = ops.decode_attention(q, kc, vc, jnp.full((B,), 77, jnp.int32),
                               block_k=64, interpret=True)
    scal = ops.decode_attention(q, kc, vc, 77, block_k=64, interpret=True)
    np.testing.assert_allclose(vec, scal, atol=ATOL, rtol=ATOL)


def test_all_zero_index_reads_only_slot_zero():
    """index[b] == 0 ⇒ each row's output is exactly its v[0] row."""
    B, Smax, H, KV, hd = 2, 128, 4, 2, 32
    q, kc, vc = _case(7, B, Smax, H, KV, hd)
    out = ops.decode_attention(q, kc, vc, jnp.zeros((B,), jnp.int32),
                               block_k=64, interpret=True)
    want = jnp.repeat(vc[:, 0:1], H // KV, axis=2).reshape(B, 1, H, hd)
    np.testing.assert_allclose(out, want, atol=ATOL, rtol=ATOL)


def test_mixed_fresh_and_wrapped_rows():
    """One admitted-yesterday row (ring-wrapped) next to a fresh admission:
    the wrapped row attends to the whole cache, the fresh row only to its
    prefix — per-row horizons, one kernel launch."""
    B, Smax, H, KV, hd = 2, 128, 4, 2, 32
    q, kc, vc = _case(9, B, Smax, H, KV, hd)
    index = jnp.asarray([3 * Smax + 5, 2], jnp.int32)
    out = ops.decode_attention(q, kc, vc, index, block_k=32, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, index)
    np.testing.assert_allclose(out, want, atol=ATOL, rtol=ATOL)
    # row 1 must be invariant to garbage beyond its horizon
    kc2 = kc.at[1, 3:].set(1e3)
    vc2 = vc.at[1, 3:].set(-1e3)
    out2 = ops.decode_attention(q, kc2, vc2, index, block_k=32, interpret=True)
    np.testing.assert_allclose(out2[1], out[1], atol=ATOL, rtol=ATOL)


def test_kernel_layout_entrypoint_broadcasts_scalar():
    """decode_attention_bkgd itself accepts scalar and (B,) alike."""
    B, KV, G, hd, Smax = 2, 2, 2, 16, 128
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (B, KV, G, hd), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, KV, Smax, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, KV, Smax, hd))
    out_s = decode_attention_bkgd(q, kc, vc, 31, block_k=64, interpret=True)
    out_v = decode_attention_bkgd(q, kc, vc, jnp.full((B,), 31, jnp.int32),
                                  block_k=64, interpret=True)
    np.testing.assert_allclose(out_s, out_v, atol=ATOL, rtol=ATOL)


def test_ragged_smax_falls_back_to_ref_exactly():
    """Smax not divisible by the block: the kernel runs with a partial last
    block whose rows past Smax are masked — never a mis-tiled read, and no
    detour to the reference.  Row 1 has wrapped (index >= Smax), so every
    slot of the partial block is live for it."""
    B, Smax, H, KV, hd = 2, 96, 4, 2, 16
    q, kc, vc = _case(13, B, Smax, H, KV, hd)
    index = jnp.asarray([5, 200], jnp.int32)
    out = ops.decode_attention(q, kc, vc, index, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, index)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=ATOL, rtol=ATOL)


# ------------------------------------------------------- ring-scatter write


def _check_ring_update(B, Smax, KV, hd, seed):
    key = jax.random.PRNGKey(seed)
    cache = jax.random.normal(key, (B, Smax, KV, hd), jnp.float32)
    new = jax.random.normal(jax.random.fold_in(key, 1), (B, KV, hd))
    slot = jnp.asarray(
        np.random.default_rng(seed).integers(0, Smax, size=B), jnp.int32)
    out = cache_ring_update_bs(cache, new, slot, interpret=True)
    want = ref.cache_ring_update_ref(cache, new, slot)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("B,Smax,KV,hd", [
    (1, 8, 1, 8), (2, 24, 2, 8), (4, 128, 2, 32), (3, 24, 1, 32),
])
def test_ring_update_matches_jnp_scatter_grid(B, Smax, KV, hd):
    _check_ring_update(B, Smax, KV, hd, seed=B * Smax + KV + hd)


if st is not None:
    @settings(max_examples=16, deadline=None)
    @given(
        B=st.sampled_from([1, 2, 4]),
        Smax=st.sampled_from([8, 24, 128]),
        KV=st.sampled_from([1, 2]),
        hd=st.sampled_from([8, 32]),
        seed=st.integers(0, 2**16),
    )
    def test_ring_update_matches_jnp_scatter_fuzz(B, Smax, KV, hd, seed):
        _check_ring_update(B, Smax, KV, hd, seed)


def test_ring_update_preserves_untouched_rows_bit_exact():
    B, Smax, KV, hd = 3, 16, 2, 8
    key = jax.random.PRNGKey(17)
    cache = jax.random.normal(key, (B, Smax, KV, hd), jnp.float32)
    new = jax.random.normal(jax.random.fold_in(key, 1), (B, KV, hd))
    slot = jnp.asarray([0, 7, 15], jnp.int32)
    out = np.asarray(ops.cache_ring_update(cache, new, slot, interpret=True))
    for b, s in enumerate([0, 7, 15]):
        np.testing.assert_array_equal(out[b, s], np.asarray(new)[b])
        untouched = np.delete(np.asarray(cache)[b], s, axis=0)
        np.testing.assert_array_equal(np.delete(out[b], s, axis=0), untouched)


def test_ring_update_casts_to_cache_dtype():
    cache = jnp.zeros((2, 8, 2, 8), jnp.bfloat16)
    new = jnp.full((2, 2, 8), 1.5, jnp.float32)
    out = ops.cache_ring_update(cache, new, jnp.asarray([1, 2]),
                                interpret=True)
    assert out.dtype == jnp.bfloat16
    assert float(out[0, 1, 0, 0]) == 1.5 and float(out[1, 2, 1, 3]) == 1.5
