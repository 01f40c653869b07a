"""The operations and bytes functions against hand counts."""

import pytest

from perfbench.flops import (flash_attention, paged_decode_attention,
                             roofline, transformer)
from perfbench.manifest import Manifest
from perfbench import weights

pytestmark = pytest.mark.tier1


def test_starcoder2_3b_hand_count():
    m = Manifest().config("starcoder2-3b")["model"]
    # per layer: q 3072x3072, kv 3072x(2*2*128), proj 3072x3072,
    # ffn 2 x 3072x12288; head 3072x49152
    layer = 3072 * 3072 + 3072 * 512 + 3072 * 3072 + 2 * 3072 * 12288
    assert layer == 95_944_704
    assert transformer.matmul_params(m) == 30 * layer + 3072 * 49152
    # all parameters: + embedding, biases, norms (3.18B with the untied head)
    assert weights.n_params(m) == pytest.approx(3.18e9, rel=0.005)
    # attention at T=4096, causal: 30 layers x 2 matmuls x 2 x 128 x 24 x 2048.5
    attn = 30 * 2 * 2 * 128 * 24 * 2048.5
    assert transformer.attention_flops_per_token(m, 4096) == attn
    per_token = 3 * (2 * (30 * layer + 3072 * 49152) + attn)
    assert transformer.train_flops_per_token(m, 4096) == per_token
    assert per_token == pytest.approx(20.4e9, rel=0.01)


def test_gpt2_xl_hand_count():
    m = Manifest().config("gpt2-xl")["model"]
    layer = 1600 * 4800 + 1600 * 1600 + 2 * 1600 * 6400
    assert transformer.matmul_params(m) == 48 * layer + 1600 * 50257
    # published 1.5577B tied; the program's separate head adds 80.4M + bias
    assert weights.n_params(m) == pytest.approx(1.5577e9 + 80.4e6, rel=0.003)


def test_flash_attention_units():
    # one unit: 2 x Dh x T(T+1)/2 per row and head
    assert flash_attention.unit_flops(1, 24, 4096, 128) == \
        2 * 128 * (4096 * 4097 / 2) * 24
    f = flash_attention.forward(1, 24, 2, 4096, 128)
    b = flash_attention.backward(1, 24, 2, 4096, 128)
    assert b["flops"] == 2.5 * f["flops"]
    # q, o: 4096x24x128 bf16; k, v: 4096x2x128 bf16; lse 4096x24 f32
    assert f["bytes"] == 2 * 4096 * 24 * 128 * 2 + 2 * 4096 * 2 * 128 * 2 \
        + 4096 * 24 * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.least_seconds(f, peaks)[1] == "compute"


def test_paged_decode_is_bytes_bound_and_counts_resident_context():
    # 32 slots of 300 resident positions, 25 heads (MHA) of 64, bf16
    ctx = 32 * 300
    need = paged_decode_attention.call(ctx, 32, 25, 25, 64)
    assert need["bytes"] == 2 * ctx * 25 * 64 * 2 + 2 * 32 * 25 * 64 * 2
    assert need["flops"] == 4 * ctx * 25 * 64
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    sec, bound = roofline.least_seconds(need, peaks)
    assert bound == "bytes" and sec == need["bytes"] / 819e9
    # grouped-query: the same queries read a twelfth of the rows
    gqa = paged_decode_attention.call(ctx, 32, 24, 2, 128)
    assert gqa["bytes"] < need["bytes"] / 3
