"""The trace reduction, on a small trace kept with the test.

``data/trace_small.json`` is in the plain form ``benchmark.trace`` reduces
(what ``load_xplane`` makes of a profiler file): two chips, a window of
10,000 ns, three programs a chip. Every number below is worked by hand from
the file. ``data/trace_chip.json``, where present, is a slice recorded on a
v5e chip; its numbers are checked against a brute-force count."""

import json
import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def red():
    return trace.Reduced(trace.load(os.path.join(HERE, "data",
                                                 "trace_small.json")))


def test_window_is_the_benchmarks_own_annotation(red):
    assert red.window == (0, 10000)
    assert red.window_s == pytest.approx(10e-6)


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window(red):
    # chip 0: [1000,4000] + [7000,9000] + [9500,10000 clipped] = 5500 ns
    # chip 1: [1000,4000] (fusion overlaps the all-reduce) + [7000,9000] + 500
    assert red.busy_s_by_device == {0: pytest.approx(5.5e-6),
                                    1: pytest.approx(5.5e-6)}
    assert red.busy_s == pytest.approx(5.5e-6)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.45)


def test_program_time_counts_only_programs_whole_inside_the_window(red):
    # the last jit_decode runs past the window's end and is left out
    assert red.program_s["jit_decode"] == [pytest.approx(4e-6)] * 2
    assert red.program_mean_s("^jit_decode") == pytest.approx(4e-6)
    assert red.program_mean_s("^jit_(prefill|cont)") == pytest.approx(2e-6)
    assert red.program_count("^jit_decode") == 1
    assert red.program_total_s("^jit_prefill") == pytest.approx(2e-6)
    assert red.program_mean_s("^jit_verify") is None


def test_op_time_is_self_time_per_chip(red):
    # chip 0: the while shell holds flash (900) and fusion.6 (1000): its own
    # 100 ns stay with it. fusion: 1000 + 1000 + 500 (clipped) = 2500
    # chip 1: fusion 1500 + 2000 + 500 = 4000, less the 500 ns in which the
    # all-reduce nests inside fusion.1 (self time gives those to the child)
    assert red.op_s["fusion"] == pytest.approx((2.5e-6 + 3.5e-6) / 2)
    assert red.op_s["while"] == pytest.approx(0.1e-6 / 2)
    assert red.op_s["flash_attention"] == pytest.approx(0.9e-6 / 2)
    assert red.op_total_s(["flash_attention", "paged_decode_attention"]) \
        == pytest.approx((0.9e-6 + 1.5e-6 + 1.5e-6) / 2)


def test_collective_time_exposed_is_what_no_compute_covers(red):
    # chip 0: the all-reduce [2000,2500] runs alone: 500 ns exposed
    # chip 1: fusion.1 [1000,2500] covers it: 0 exposed
    assert red.collective_s == pytest.approx(0.5e-6)
    assert red.collective_exposed_s == pytest.approx(0.25e-6)


@pytest.mark.parametrize("raw,is_collective", [
    ("%all-reduce.7 = bf16[8,4096]{1,0} all-reduce(...)", True),
    ("%all-reduce-start.3", True), ("%all-reduce-done.3", True),
    ("%all-gather.12", True), ("%reduce-scatter.1", True),
    ("%all-reduce-scatter.2", True), ("%fusion.all-gather.4", True),
    ("%collective-permute-done.9", True),
    ("%fusion.88", False), ("%paged_decode_attention.3", False),
    ("%slice-done.5", False), ("%convolution_multiply_fusion.1", False),
])
def test_which_op_names_read_as_collectives(raw, is_collective):
    assert bool(trace.COLLECTIVE.search(trace.op_name(raw))) is is_collective


def test_the_asynchronous_pair_is_exposed_only_where_nothing_else_runs():
    # start [0,100] alone, fusion [100,600], done [500,900]: the pair holds
    # 500 ns, of which the 100 under the fusion are hidden
    tr = {"planes": {"/device:TPU:0": {"XLA Ops": [
        ["%all-reduce-start.1", 0, 100], ["%fusion.2", 100, 500],
        ["%all-reduce-done.1", 500, 400]]},
        "/host:CPU": {"t": [["bench_window", 0, 1000]]}}}
    got = trace.Reduced(tr)
    assert got.collective_s == pytest.approx(500e-9)
    assert got.collective_exposed_s == pytest.approx(400e-9)


def test_gaps_are_named_by_programs_and_the_host_span(red):
    gaps = dict(red.gaps)
    # [5000,7000] between decode and prefill: np.asarray covers 1500 of it,
    # engine.prefill 200; the thread-long span is passed over
    assert gaps["jit_decode -> jit_prefill | host: np.asarray(jax.Array)"] \
        == pytest.approx(2e-6)
    assert gaps["- -> jit_decode | host: -"] == pytest.approx(1e-6)
    assert gaps["jit_prefill -> jit_decode | host: -"] == pytest.approx(0.5e-6)
    # inside jit_decode(1000..5000) chip 0 ran ops for 3000 ns
    assert gaps["inside programs (between ops)"] == pytest.approx(1e-6)
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)


def test_breakdown_has_the_contracts_shape(red):
    b = red.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion"
    json.dumps(b)


@pytest.mark.parametrize("raw,want", [
    ("%fusion.123", "fusion"), ("fusion.1.2", "fusion"),
    ("%paged_decode_attention.3", "paged_decode_attention"),
    ("all-reduce-start.5", "all-reduce-start"), ("copy", "copy")])
def test_op_names_lose_their_serial_numbers(raw, want):
    assert trace.op_name(raw) == want


def test_union_and_subtract():
    u = trace.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)])
    assert u == [(1, 4), (5, 8)]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace._subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_recorded_chip_slice_against_brute_force():
    path = os.path.join(HERE, "data", "trace_chip.json")
    if not os.path.exists(path):
        pytest.skip("no slice recorded on the chip is checked in")
    tr = trace.load(path)
    red = trace.Reduced(tr)
    lo, hi = red.window
    assert hi > lo and red.devices
    # brute force: mark every 100 ns tick an op covers
    ops = tr["planes"][red.devices[0][1]][trace.OPS_LINE]
    step = 100.0
    n = int((hi - lo) / step)
    covered = bytearray(n)
    for _, s, d in ops:
        a = max(0, int((s - lo) / step))
        b = min(n, int((s + d - lo) / step) + 1)
        for i in range(a, b):
            covered[i] = 1
    brute = sum(covered) * step / 1e9
    first = red.busy_s_by_device[red.devices[0][0]]
    assert first == pytest.approx(brute, rel=0.02)
    assert any(k.startswith("jit_") for k in red.program_s)
    # what that chip run read (PR 23, mistral-7b-int8.decode-sat, 8 rows):
    # one decode step of 27.37 ms, of which paged attention 17.70 ms
    assert red.program_mean_s("^jit_decode") == pytest.approx(27.369e-3,
                                                              rel=1e-3)
    assert red.op_total_s(["paged_decode_attention"]) == pytest.approx(
        17.703e-3, rel=1e-3)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.2183, abs=1e-3)
    assert sum(dict(red.gaps).values()) == pytest.approx(
        red.window_s - first, rel=1e-6)
