"""Bytes of a Trinity-Mini decode step against hand-worked counts, and the
reader that prices them from the program's counters."""

import os

import pytest

from benchmark import trace
from benchmark.spec import Spec

SPEC = Spec()
M = SPEC.config("trinity-mini-bf16")
S = SPEC.shapes("shapes_afmoe")


def test_parameters_by_hand():
    # q, o, gate: 2048 x 4096 = 8,388,608 each; k, v: 2048 x 512 = 1,048,576
    assert S.attention_params(M) == 3 * 8_388_608 + 2 * 1_048_576 == 27_262_976
    # one expert: gate, up, down of 2048 x 1024
    assert S.expert_params(M) == 3 * 2_097_152 == 6_291_456
    assert M["memory"]["table"]["one routed expert"] == 12_582_912
    assert M["memory"]["weights_bytes"] == 8_482_979_840


def test_what_a_step_reads_whatever_it_routed():
    # five layers' attention, one dense MLP (3 x 2048 x 6144 = 37,748,736),
    # four shared experts, the head (2048 x 200192 = 409,993,216), in bf16;
    # four float32 routers of 2048 x 128
    params = (5 * 27_262_976 + 37_748_736 + 4 * 6_291_456 + 409_993_216)
    assert params == 609_222_656
    assert S.fixed_bytes_per_step(M, 2) == 2 * 609_222_656 + 4 * 1_048_576 \
        == 1_222_639_616


def test_decode_bytes_by_hand():
    # 10 steps of 32 rows at 2,560 tokens; every step touches 112 experts in
    # each of 4 layers; 4 window layers see 2,048 of each row, the full
    # layer all 2,560; keys and values 2 x 4 x 128 x 2 B = 2 KiB a token
    assert S.kv_bytes_per_layer_token(M, 2) == 2048
    got = S.decode_bytes(
        M, steps=10, context_tokens=10 * 32 * 2560,
        experts_touched=10 * 4 * 112, window_visible=10 * 32 * 2048 * 4,
        weight_bytes=2, kv_bytes=2)
    assert got == (10 * 1_222_639_616 + 4480 * 12_582_912
                   + (819_200 + 2_621_440) * 2048)
    # 7.56 GB a step: 9.2 ms at 819 GB/s, of which the experts are 75%
    assert got / 10 / 819e9 == pytest.approx(9.23e-3, rel=2e-3)
    assert 4480 * 12_582_912 / got == pytest.approx(0.745, abs=0.005)


def _ctx(after_engine):
    red = trace.Reduced(trace.load(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "trace_small.json")))
    return {"spec": SPEC, "config": M, "peak": SPEC.peak("TPU v5 lite"),
            "trace": red,
            "trace_before": {"engine": {"pad_by_phase": {}}},
            "trace_after": {"engine": after_engine}}, red


def test_the_routed_roofline_reader_prices_what_the_counters_say():
    mf = SPEC.layer_metric("decode_hbm_roofline.moe")
    read = SPEC.reader(mf["reader"]["kind"]).read
    ctx, red = _ctx({
        "pad_by_phase": {"decode": {"real": 81_920, "pad": 5000}},
        "moe": {"experts_touched": 448, "layer_steps": 4},
        "window": {"tokens_visible": 262_144}})
    # the small trace holds ONE whole jit_decode a chip, 4,000 ns
    device_s = red.program_total_s(mf["reader"]["pattern"])
    assert device_s == pytest.approx(4e-6)
    least_s = (1_222_639_616 + 448 * 12_582_912
               + (81_920 + 262_144) * 2048) / 819e9
    assert read(ctx, mf["reader"]) == pytest.approx(
        100.0 * least_s / device_s, rel=1e-12)


@pytest.mark.parametrize("engine", [
    {"pad_by_phase": {}},                                  # the parent
    {"pad_by_phase": {}, "moe": {"experts_touched": 3}},   # no window group
], ids=["no-counters", "half-the-counters"])
def test_the_routed_reader_returns_nothing_where_the_program_has_no_counter(
        engine):
    mf = SPEC.layer_metric("decode_hbm_roofline.moe")
    ctx, _ = _ctx(engine)
    assert SPEC.reader(mf["reader"]["kind"]).read(ctx, mf["reader"]) is None
    ctx["trace"] = None
    assert SPEC.reader(mf["reader"]["kind"]).read(ctx, mf["reader"]) is None


@pytest.mark.parametrize("name", [
    "experts_touched_mean.moe", "expert_load_max_over_mean.moe",
    "window_skipped_share.moe", "pool_dead_share.moe"])
def test_counter_metrics_read_nothing_from_a_program_without_them(name):
    mf = SPEC.layer_metric(name)
    read = SPEC.reader(mf["reader"]["kind"]).read
    bare = {"engine": {"steps": 5}}
    assert read({"before": bare, "after": bare}, mf["reader"]) is None


def test_counter_metrics_by_hand():
    before = {"engine": {}}
    after = {"engine": {
        "moe": {"layer_steps": 40, "assignments": 40 * 256,
                "experts_touched": 40 * 112, "load_max": 40 * 7},
        "window": {"tokens_walked": 600, "tokens_skipped": 200,
                   "pool_dead_token_steps": 100, "pool_token_steps": 1000}}}
    ctx = {"before": before, "after": after}
    want = {"experts_touched_mean.moe": 112.0,
            # a largest load of 7 where the mean is 256 / 128 = 2
            "expert_load_max_over_mean.moe": 3.5,
            "window_skipped_share.moe": 25.0, "pool_dead_share.moe": 10.0}
    for name, value in want.items():
        mf = SPEC.layer_metric(name)
        assert SPEC.reader(mf["reader"]["kind"]).read(
            ctx, mf["reader"]) == pytest.approx(value)
