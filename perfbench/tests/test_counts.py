"""Operation and byte counts against hand-worked cases."""
import numpy as np
import pytest

from perfbench.counts import decode_attention, decode_step
from perfbench.counts._shapes import layer_matmul_params, weight_bytes

CFG = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 2, "intermediate_size": 16, "vocab_size": 10,
       "num_hidden_layers": 3, "tie_word_embeddings": True}


def test_decode_attention_counts_valid_context_of_busy_rows():
    # rows with 3 and 5 valid keys; H=4, hd=2, KV=2, L=3
    flops, nbytes = decode_attention.flops_bytes(CFG, [3, 5])
    assert flops == 3 * 4 * 4 * 2 * (3 + 5)
    kv = 2 * 2 * 2 * (3 + 5) * 2          # K and V, KV heads, hd, keys, bf16
    qo = 2 * 2 * 4 * 2 * 2                # q and out, rows, H, hd, bf16
    assert nbytes == 3 * (kv + qo)


def test_ragged_context_counts_keys_not_the_pool():
    # the same number of valid keys, however they are spread, is the same
    # work; a pool of max_seq positions never enters the count
    assert decode_attention.flops_bytes(CFG, [1, 7])[0] == \
        decode_attention.flops_bytes(CFG, [4, 4])[0]


def test_padded_slot_is_not_counted():
    # a free slot is no row: the harness passes busy rows only
    assert decode_attention.flops_bytes(CFG, [3]) != \
        decode_attention.flops_bytes(CFG, [3, 0])
    assert decode_attention.flops_bytes(CFG, [3])[0] == \
        decode_attention.flops_bytes(CFG, [3, 0])[0]


def test_step_counts_add_the_matmuls_once_per_token():
    per_layer = 8 * (4 + 2 * 2) * 2 + 4 * 2 * 8 + 3 * 8 * 16
    assert layer_matmul_params(CFG) == per_layer
    f, _ = decode_step.flops_bytes(CFG, [3, 5])
    assert f == 2 * 2 * (3 * per_layer + 8 * 10) + \
        decode_attention.flops_bytes(CFG, [3, 5])[0]
    _, b = decode_step.flops_bytes(CFG, [3, 5])
    assert b > weight_bytes(CFG)


def test_weight_bytes_match_the_reference_weights():
    from perfbench.models import qwen2

    cfg = dict(CFG, rms_norm_eps=1e-6, rope_theta=1e4)
    for tied in (True, False):
        cfg["tie_word_embeddings"] = tied
        n = sum(int(np.prod(s)) for s in qwen2.weight_shapes(cfg).values())
        assert weight_bytes(cfg) == 2 * n


def test_roofline_share_of_a_kernel_at_its_bound_is_100():
    from perfbench.metrics._common import roofline_share

    ctx = {"peaks": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}}
    # 50 flops (0.5 s) and 20 bytes (2 s): memory bound, 2 s at best
    assert roofline_share(ctx, 50.0, 20.0, int(2e9)) == pytest.approx(100.0)
    assert roofline_share(ctx, 50.0, 20.0, int(4e9)) == pytest.approx(50.0)
    assert roofline_share(ctx, 50.0, 20.0, 0) is None
