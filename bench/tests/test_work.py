"""The roofline yardstick against counts made by hand."""

from bench import cells as C
from bench import work

DIMS = {"heads": 8, "kv_heads": 1, "head_dim": 128, "hidden": 5120,
        "ffn": 3200, "vocab": 18992, "layers": 64}


def test_decode_row_by_hand():
    # one slot, 100 keys: QK and PV are 2·Dh FLOPs per key and head
    flops, nbytes = work.decode_row(100, DIMS)
    assert flops == 2 * 2 * 128 * 100 * 8
    # K and V of 100 keys for the one KV head, in bf16; one query row in
    # and one output row out per head, in bf16
    assert nbytes == 100 * 1 * 128 * 2 * 2 + 2 * 8 * 128 * 2


def test_prefill_chunk_by_hand():
    # rows at positions 10, 11, 12 see 11, 12 and 13 keys
    flops, nbytes = work.prefill_chunk(10, 3, DIMS)
    assert flops == 4 * 8 * 128 * (11 + 12 + 13)
    assert nbytes == 13 * 1 * 128 * 2 * 2 + 2 * 3 * 8 * 128 * 2


def test_prefill_from_zero_is_causal_triangle():
    flops, _ = work.prefill_chunk(0, 512, DIMS)
    assert flops == 4 * 8 * 128 * (512 * 513 // 2)


def test_matmul_flops_per_token():
    per_layer = 5120 * (8 + 2) * 128 + 8 * 128 * 5120 + 3 * 5120 * 3200
    dense = C.arch("dense")
    assert dense.matmul_flops_per_token(DIMS) == 2 * per_layer * 64


def test_dense_attention_is_every_layer_alike():
    dense = C.arch("dense")
    assert dense.decode_attention(100, DIMS) == tuple(
        64 * x for x in work.decode_row(100, DIMS))
    assert dense.prefill_attention(10, 3, DIMS) == tuple(
        64 * x for x in work.prefill_chunk(10, 3, DIMS))


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000.0, 50.0, peak) == 10.0
    assert work.roofline_s(100.0, 50.0, peak) == 5.0


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    assert work.peaks("TPU v5 lite")["bf16_flops"] == 197e12
