"""FLOPs and bytes from shapes, against hand-worked Mistral-7B counts."""

import pytest

from benchmark import shapes
from benchmark.spec import Spec

M = Spec().config("mistral-7b-int8")


def test_layer_matrix_parameters_by_hand():
    # q, o: 4096 x 4096 = 16,777,216 each; k, v: 4096 x 1024 = 4,194,304
    # each; gate, up, down: 4096 x 14336 = 58,720,256 each
    assert shapes.layer_matrix_params(M) == (
        2 * 16_777_216 + 2 * 4_194_304 + 3 * 58_720_256) == 218_103_808
    assert shapes.head_params(M) == 134_217_728


def test_matmul_flops_per_token_by_hand():
    # 32 layers x 218,103,808 parameters x 2
    assert shapes.matmul_flops_per_token(M) == 13_958_643_712
    assert shapes.prefill_flops(M, 512) == 512 * 13_958_643_712


@pytest.mark.parametrize("weight_bytes,chips,want", [
    (1, 1, 32 * 218_103_808 + 134_217_728),          # int8: 7,113,539,584
    (2, 1, 2 * (32 * 218_103_808 + 134_217_728)),    # bf16
    (2, 4, (32 * 218_103_808 + 134_217_728) / 2),    # bf16 over four chips
])
def test_weight_bytes_a_decode_step_reads(weight_bytes, chips, want):
    assert shapes.weight_bytes_per_step(M, weight_bytes, chips) == want


def test_kv_bytes_per_context_token_by_hand():
    # keys and values x 32 layers x 8 heads x 128 x 2 bytes = 128 KiB
    assert shapes.kv_bytes_per_token(M, 2) == 131_072
    assert shapes.kv_bytes_per_token(M, 2, chips=4) == 32_768


def test_decode_bytes_adds_weights_per_step_and_kv_per_context_token():
    got = shapes.decode_bytes(M, steps=10, context_tokens=8 * 300 * 10,
                              weight_bytes=1, kv_bytes=2)
    assert got == 10 * 7_113_539_584 + 24_000 * 131_072
    # at 819 GB/s one int8 step over 8 rows of 300 tokens cannot take less
    # than (7.11 GB + 0.31 GB) / 819 GB/s = 9.07 ms
    assert got / 10 / 819e9 == pytest.approx(9.07e-3, rel=2e-3)
