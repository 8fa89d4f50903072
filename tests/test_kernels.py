"""Per-kernel allclose validation against the pure-jnp oracles (ref.py).

Each Pallas kernel runs in interpret mode on CPU (the kernel body executes
in Python) and must match the naive reference within dtype tolerance.
Hypothesis sweeps shapes/dtypes; fixed cases pin the block-boundary edges.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # collection must degrade to skips, not errors
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)

pytestmark = pytest.mark.kernels

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(key, B, Sq, Sk, H, KV, hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, Sk, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, Sk, KV, hd), jnp.float32).astype(dtype)
    return q, k, v


# ---------------------------------------------------------------- flash

@settings(max_examples=12, deadline=None)
@given(
    B=st.sampled_from([1, 2]),
    S=st.sampled_from([64, 128, 256]),
    HKV=st.sampled_from([(4, 4), (8, 2), (4, 1)]),
    hd=st.sampled_from([32, 64]),
    causal=st.booleans(),
)
def test_flash_attention_matches_ref(B, S, HKV, hd, causal):
    H, KV = HKV
    q, k, v = _qkv(jax.random.PRNGKey(S + H), B, S, S, H, KV, hd, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, want, atol=TOL[jnp.float32],
                               rtol=TOL[jnp.float32])


@pytest.mark.parametrize("window", [8, 32, 64])
def test_flash_attention_sliding_window(window):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 128, 128, 4, 2, 32, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 128, 128, 4, 4, 64, jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=3e-2, rtol=3e-2)


def test_flash_attention_ragged_falls_back_to_ref():
    # Sq=100 not divisible by any power-of-two block: the wrapper pads to a
    # whole block and the kernel masks the padded keys — still exact.
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 100, 100, 4, 2, 32, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_full_softmax_oracle():
    """ref itself cross-checked against an independent dense softmax."""
    B, S, H, KV, hd = 1, 32, 4, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(3), B, S, S, H, KV, hd, jnp.float32)
    G = H // KV
    k_full = jnp.repeat(k, G, axis=2)
    v_full = jnp.repeat(v, G, axis=2)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.einsum("bshd,bthd->bhst", q, k_full) * hd ** -0.5
    scores = jnp.where(mask[None, None], scores, -1e9)
    want = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, -1), v_full)
    got = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- decode

@settings(max_examples=12, deadline=None)
@given(
    B=st.sampled_from([1, 2]),
    Smax=st.sampled_from([256, 512]),
    HKV=st.sampled_from([(4, 4), (8, 2)]),
    hd=st.sampled_from([32, 64]),
    frac=st.floats(0.1, 1.0),
)
def test_decode_attention_matches_ref(B, Smax, HKV, hd, frac):
    H, KV = HKV
    index = max(1, int(Smax * frac) - 1)
    key = jax.random.PRNGKey(Smax + H + index)
    q = jax.random.normal(key, (B, 1, H, hd), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, KV, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, KV, hd))
    out = ops.decode_attention(q, kc, vc, index, block_k=128, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, index)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_decode_attention_index_zero():
    """Only slot 0 is valid — attention output must equal v[0] exactly."""
    B, Smax, H, KV, hd = 2, 256, 4, 2, 32
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (B, 1, H, hd), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, KV, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, KV, hd))
    out = ops.decode_attention(q, kc, vc, 0, block_k=128, interpret=True)
    want = jnp.repeat(vc[:, 0:1], H // KV, axis=2).reshape(B, 1, H, hd)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- ssm scan

@settings(max_examples=10, deadline=None)
@given(
    B=st.sampled_from([1, 2]),
    L=st.sampled_from([64, 128, 256]),
    H=st.sampled_from([2, 4]),
    hd=st.sampled_from([8, 16]),
    N=st.sampled_from([4, 8]),
    chunk=st.sampled_from([32, 64]),
)
def test_ssm_scan_matches_ref(B, L, H, hd, N, chunk):
    key = jax.random.PRNGKey(L + H + N)
    x = jax.random.normal(key, (B, L, H, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, L, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, L, H, N))
    C = jax.random.normal(jax.random.fold_in(key, 4), (B, L, H, N))
    out = ops.ssm_scan(x, dt, A, Bm, C, chunk=chunk, interpret=True)
    want = ref.ssm_scan_ref(x, dt, A, Bm, C)
    np.testing.assert_allclose(out, want, atol=3e-4, rtol=3e-4)


def test_ssm_scan_state_decay_property():
    """With A→-inf (instant forgetting) the output reduces to
    y_t = (dt_t·x_t)·(B_t·C_t) — no cross-step memory."""
    B, L, H, hd, N = 1, 64, 2, 8, 4
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (B, L, H, hd), jnp.float32)
    dt = jnp.full((B, L, H), 100.0)      # exp(dt·A) ≈ 0 for A ≤ -1
    A = -jnp.ones((H,))
    Bm = jax.random.normal(jax.random.fold_in(key, 1), (B, L, H, N))
    C = jax.random.normal(jax.random.fold_in(key, 2), (B, L, H, N))
    out = ops.ssm_scan(x, dt, A, Bm, C, chunk=32, interpret=True)
    want = (dt[..., None] * x) * jnp.einsum("blhn,blhn->blh", Bm, C)[..., None]
    np.testing.assert_allclose(out, want, atol=1e-3, rtol=1e-3)


def test_ssm_scan_ragged_falls_back():
    B, L, H, hd, N = 1, 100, 2, 8, 4   # L % chunk != 0: padded with dt = 0
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (B, L, H, hd), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, L, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, L, H, N))
    C = jax.random.normal(jax.random.fold_in(key, 4), (B, L, H, N))
    out = ops.ssm_scan(x, dt, A, Bm, C, chunk=64, interpret=True)
    want = ref.ssm_scan_ref(x, dt, A, Bm, C)
    np.testing.assert_allclose(out, want, atol=3e-4, rtol=3e-4)
