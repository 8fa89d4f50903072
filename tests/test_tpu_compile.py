"""Compile the main path's Pallas kernels, and one whole decode step, for a
described TPU v5e — nothing runs; the TPU compiler answers whether Mosaic
accepts each kernel's tiling and whether the step fits the chip's memory.

Every kernel is called with ``interpret=False`` (the default backend here is
the CPU, which would otherwise interpret them).  The topology is described
inside a module-scoped fixture, never at import: only one process at a time
may load the TPU library, and under several pytest workers only the worker
given this file loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.steps import make_fused_decode_step, params_axes_and_structs
from repro.serving.slots import make_pool

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
HBM_BYTES = 15.75e9            # what the v5e compiler lets one program use

# Qwen2.5-3B widths: 16 q heads over 2 kv heads, head_dim 128
B, H, KV, HD = 8, 16, 2, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache would store executables it cannot read back here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *structs):
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _s(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("smax", [2048, 1000])
def test_dense_decode_and_ring_update_compile(one_chip, smax):
    """1000 is not a multiple of the 512-slot block: a partial last block."""
    s = lambda *a: _s(one_chip, *a)  # noqa: E731
    _compile(lambda q, k, v, i: ops.decode_attention(q, k, v, i,
                                                     interpret=False),
             s((B, 1, H, HD), BF16), s((B, smax, KV, HD), BF16),
             s((B, smax, KV, HD), BF16), s((B,), I32))
    _compile(lambda c, n, i: ops.cache_ring_update(c, n, i, interpret=False),
             s((B, smax, KV, HD), BF16), s((B, KV, HD), BF16), s((B,), I32))


def test_paged_decode_and_update_compile(one_chip):
    s = lambda *a: _s(one_chip, *a)  # noqa: E731
    nb, bk, nk = 256, 16, 128
    _compile(lambda q, k, v, t, i: ops.decode_attention_paged(
                 q, k, v, t, i, interpret=False),
             s((B, 1, H, HD), BF16), s((nb, bk, KV, HD), BF16),
             s((nb, bk, KV, HD), BF16), s((B, nk), I32), s((B,), I32))
    _compile(lambda c, n, b, o: ops.cache_paged_update(c, n, b, o,
                                                       interpret=False),
             s((nb, bk, KV, HD), BF16), s((B, KV, HD), BF16), s((B,), I32),
             s((B,), I32))


@pytest.mark.parametrize("sq", [200, 17])
def test_flash_prefill_ragged_compiles(one_chip, sq):
    s = lambda *a: _s(one_chip, *a)  # noqa: E731
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                 interpret=False),
             s((1, sq, H, HD), BF16), s((1, sq, KV, HD), BF16),
             s((1, sq, KV, HD), BF16))


def test_fused_sample_compiles_at_qwen_vocab(one_chip):
    s = lambda *a: _s(one_chip, *a)  # noqa: E731
    _compile(lambda lg, a, b, c, t: ops.fused_sample(lg, a, b, c, t,
                                                     interpret=False),
             s((B, 151936), F32), s((B,), I32), s((B,), I32), s((B,), I32),
             s((B,), F32))


def test_ssd_scan_compiles_at_zamba2_widths(one_chip):
    cfg = get_config("zamba2-2.7b")
    nh, hd, n = cfg.ssm_heads, cfg.ssm.headdim, cfg.ssm.d_state
    s = lambda *a: _s(one_chip, *a)  # noqa: E731
    L = 512
    _compile(lambda x, dt, a, b, c: ops.ssm_scan(x, dt, a, b, c,
                                                 chunk=cfg.ssm.chunk,
                                                 interpret=False),
             s((1, L, nh, hd), F32), s((1, L, nh), F32), s((nh,), F32),
             s((1, L, nh, n), F32), s((1, L, nh, n), F32))


def _full_width_structs(sharding_of):
    """(params, dense-pool cache) ShapeDtypeStructs of Qwen2.5-3B at full
    width, bf16 weights, 8 slots over 2048 positions; ``sharding_of(leaf)``
    places each."""
    cfg = get_config("qwen2.5-3b", param_dtype="bfloat16", use_pallas=True)
    _, params = params_axes_and_structs(cfg)
    cache = jax.eval_shape(lambda: make_pool(cfg, B, 2048).cache)
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding_of(x)), t)
    return cfg, place(params), place(cache)


@pytest.fixture(scope="module")
def full_width_step(one_chip):
    """The serving tick at Qwen2.5-3B's published widths with bf16 weights,
    compiled: 8 slots over a 2048-position dense pool.  The step's own code
    takes the CPU branch here (interpreted kernels), so the fixture steers
    it to the compiled kernels.  → (compiled, params structs)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret_default", lambda: False)
        cfg, params, cache = _full_width_structs(lambda x: one_chip)
        vec = _s(one_chip, (B,), I32)
        compiled = jax.jit(make_fused_decode_step(cfg), donate_argnums=(2,)
                           ).lower(params, _s(one_chip, (B, 1), I32), cache,
                                   vec, vec, vec,
                                   _s(one_chip, (B,), F32)).compile()
    return compiled, params


def test_full_width_fused_decode_step_fits_one_chip(full_width_step):
    compiled, params = full_width_step
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights < 6.5e9                  # bf16: ~6.17 GB
    assert used < HBM_BYTES, used


def test_full_width_step_keeps_the_names_a_trace_is_read_by(full_width_step):
    """A device trace names the step's module and the decode attention
    kernel's op as the compiled text does; the benchmark's step and kernel
    metrics find their device time by these two names."""
    text = full_width_step[0].as_text()
    assert re.match(r"HloModule jit_fused_decode_step[,\s]", text), text[:80]
    calls = re.findall(r"^\s*%(decode_attention_bkgd)(?:\.\d+)? = [^\n]*"
                       r"custom-call\([^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text, flags=re.M)
    assert calls, "no decode_attention_bkgd custom call in the step"


def test_sharded_topology_compiles_on_four_chips(topo, monkeypatch):
    """The ``sharded`` topology's programs over a ("data",) mesh of the
    four chips: the replicated-weight prefill (Mosaic kernels cannot be
    partitioned by XLA, so it must run under shard_map), the slot-sharded
    decode step, and its sampler over either's logits."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.serving.replica import (
        make_sharded_decode, make_sharded_prefill, make_sharded_sample,
    )

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",),
                axis_types=(AxisType.Auto,))
    rep = NamedSharding(mesh, P())
    cfg, params, _ = _full_width_structs(lambda x: rep)
    _, _, cache = _full_width_structs(lambda x: NamedSharding(
        mesh, P("data") if x.ndim == 1 else P(None, "data")))
    prefill = make_sharded_prefill(cfg, mesh, 2048).lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, 200), I32, sharding=rep)}
    ).compile()
    decode = make_sharded_decode(cfg, mesh, B, 2048).lower(
        params, jax.ShapeDtypeStruct((B, 1), I32,
                                     sharding=NamedSharding(mesh, P("data"))),
        cache).compile()
    for compiled in (prefill, decode):
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().argument_size_in_bytes < HBM_BYTES
    sample = make_sharded_sample(cfg)
    for n, rows in ((1, rep), (B, NamedSharding(mesh, P("data")))):
        vec = lambda d: jax.ShapeDtypeStruct((n,), d, sharding=rep)  # noqa
        sample.lower(jax.ShapeDtypeStruct((n, 1, cfg.vocab), BF16,
                                          sharding=rows),
                     vec(I32), vec(I32), vec(I32), vec(F32)).compile()
