"""The operations and bytes of the served work, against values worked
out by hand at qwen2.5-3b and phi3-medium shapes."""
import pytest

import cells
from work import paged_flash_attention as attn
from work import swiglu_qgemv as ffn

DENSE = cells.family(cells.config("qwen2.5-3b-int4"))
QWEN = DENSE.dims(cells.config("qwen2.5-3b-int4"))
PHI3 = DENSE.dims(cells.config("phi3-medium-14b-int4"))


def test_dims_are_the_published_widths():
    assert (QWEN["d"], QWEN["h"], QWEN["g"], QWEN["hd"], QWEN["f"],
            QWEN["v"], QWEN["L"], QWEN["tied"], QWEN["bias"]) == (
        2048, 16, 2, 128, 11008, 151936, 36, True, True)
    assert (PHI3["d"], PHI3["h"], PHI3["g"], PHI3["hd"], PHI3["f"],
            PHI3["v"], PHI3["L"], PHI3["tied"], PHI3["bias"]) == (
        5120, 40, 10, 128, 17920, 32064, 10, False, False)


def test_paged_attention_counts_live_rows():
    # qwen, int8 KV, two tokens with contexts 100 and 300, one layer:
    # flops 4 * 400 * 16 * 128; K and V rows 2 * 400 * 2 heads *
    # (128 + 4 scale) bytes; q and out 2 * 2 tokens * 16 * 128 * 2 bytes
    w = attn.per_layer(QWEN, [100, 300], 1)
    assert w["flops"] == 4 * 400 * 16 * 128 == 3_276_800
    assert w["bytes"] == 2 * 400 * 2 * 132 + 2 * 2 * 2 * 16 * 128 == 227_584
    t = attn.total(QWEN, [100, 300], 1)
    assert t["bytes"] == 36 * 227_584
    # phi3, bf16 KV, one token of context 1000: no scales
    w = attn.per_layer(PHI3, [1000], 2)
    assert w["bytes"] == 2 * 1000 * 10 * 256 + 2 * 2 * 40 * 128 == 5_140_480


def test_swiglu_call_streams_both_packed_weights_once():
    # qwen: 2 * (2048 * 11008 / 2 + 4 * 16 * 11008) = 23_953_408 weight
    # bytes; 16 rows in, bf16: 2 * 16 * 2048 + 2 * 16 * 11008
    w = ffn.per_call(QWEN, 16)
    assert w["bytes"] == 23_953_408 + 65_536 + 352_256
    assert w["flops"] == 4 * 16 * 2048 * 11008
    # phi3: 2 * (5120 * 17920 / 2 + 4 * 40 * 17920) = 97_484_800
    assert ffn.per_call(PHI3, 32)["bytes"] == (
        97_484_800 + 2 * 32 * 5120 + 2 * 32 * 17920)


def test_decode_step_bytes_and_flops():
    # qwen per layer: q 2048x2048, k and v 2048x256, o 2048x2048, gate,
    # up 2048x11008, down 11008x2048 -> 77_070_336 weights
    assert sum(k * n for k, n in DENSE.matrix_shapes(QWEN).values()) == \
        77_070_336
    assert DENSE.matmul_weights(QWEN) == \
        36 * 77_070_336 + 2048 * 151936
    # packed: half a byte per weight and an f32 scale per 128 rows and
    # column; the head once; bf16 norms (2 per layer + final) and biases
    layer = sum(k * n // 2 + 4 * (k // 128) * n
                for k, n in DENSE.matrix_shapes(QWEN).values())
    head = 2048 * 151936 // 2 + 4 * 16 * 151936
    small = 2 * (2 * 2048 * 36 + 2048) + 2 * (16 + 4) * 128 * 36
    assert DENSE.streamed_bytes(QWEN) == 36 * layer + head + small
    w = DENSE.decode_work(QWEN, [10, 20], 3, 16, 1)
    a = attn.total(QWEN, [10, 20], 1)
    new_rows = 2 * 2 * 2 * 132 * 36
    assert w["bytes"] == 3 * (DENSE.streamed_bytes(QWEN)
                              + 4 * 16 * 151936) + a["bytes"] + new_rows
    assert w["flops"] == pytest.approx(
        2 * 2 * DENSE.matmul_weights(QWEN) + a["flops"])


def test_phi3_decode_step_streams_the_untied_head_not_the_embedding():
    layer = sum(k * n // 2 + 4 * (k // 128) * n
                for k, n in DENSE.matrix_shapes(PHI3).values())
    head = 5120 * 32064 // 2 + 4 * 40 * 32064
    small = 2 * (2 * 5120 * 10 + 5120)
    assert DENSE.streamed_bytes(PHI3) == 10 * layer + head + small
