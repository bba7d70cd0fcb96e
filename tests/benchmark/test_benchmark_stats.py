"""Percentiles and the window: which token, which request counts."""

import pytest

from benchmark import stats
from benchmark.client import Record
from benchmark.traffic import Planned


def rec(due, tokens, in_window=True, n_out=None, finish="length",
        failure=None):
    r = Record(plan=Planned(0, due, 10, n_out or len(tokens), "x"), due=due,
               sent=due, in_window=in_window)
    r.token_times = list(tokens)
    r.finish_reason = finish
    r.failure = failure
    r.done = tokens[-1] if tokens else due
    return r


@pytest.mark.parametrize("values,p,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([5, 1, 4, 2, 3], 90, 4.6),
    ([10], 95, 10.0),
    (list(range(101)), 95, 95.0),
    ([0, 10], 25, 2.5),
])
def test_percentile_interpolates_between_ranks(values, p, want):
    assert stats.percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_token_after_the_window_does_not_count_towards_the_rate():
    t0, t1 = 100.0, 110.0
    records = [rec(109.0, [109.5, 109.9, 110.0, 110.4]),    # two inside
               rec(95.0, [99.9, 100.0, 100.1], in_window=False)]  # two
    assert stats.tokens_in_window(records, t0, t1) == 4
    assert stats.client_metric("out_tok_per_s", records, t0, t1, 60) == 0.4


def test_a_request_due_inside_counts_wherever_its_tokens_fell():
    t0, t1 = 100.0, 110.0
    late = rec(109.9, [110.3, 110.5])          # due inside, tokens after
    early = rec(99.0, [100.5, 100.6], in_window=False)   # warm-up request
    ttft = stats.ttfts_ms([late, early], 60)
    assert ttft == [pytest.approx(400.0)]
    assert stats.gaps_ms([late, early]) == [pytest.approx(200.0)]
    assert stats.summary([late, early], t0, t1)["attempted"] == 1


def test_a_failed_request_stands_in_the_tail_with_the_timeout():
    ok = rec(1.0, [1.1])
    bad = rec(2.0, [], failure={"cause": "http_429"})
    assert sorted(stats.ttfts_ms([ok, bad], 120.0)) == [
        pytest.approx(100.0), 120000.0]
    s = stats.summary([ok, bad], 0.0, 10.0)
    assert (s["attempted"], s["failed"]) == (2, 1)


@pytest.mark.parametrize("finish,n_tokens,n_out,ok", [
    ("length", 8, 8, True), ("length", 7, 8, False), ("stop", 3, 8, True),
    ("stop", 0, 8, True), ("stop", 9, 8, False), (None, 8, 8, False),
])
def test_tokens_delivered_against_the_stop_reason(finish, n_tokens, n_out, ok):
    r = rec(0.0, [0.1 * i for i in range(1, n_tokens + 1)], n_out=n_out,
            finish=finish)
    assert stats.accounts_for_its_tokens(r) is ok


def test_an_eos_stop_is_complete_and_counted_apart():
    r = rec(1.0, [1.1, 1.2], n_out=8, finish="stop")
    s = stats.summary([r], 0.0, 10.0)
    assert (s["failed"], s["eos_stops"]) == (0, 1)


@pytest.mark.parametrize("name", ["ttft_p50_ms", "ttft_p90_ms", "gap_p95_ms",
                                  "gap_p50_ms", "out_tok_per_s"])
def test_client_metric_names(name):
    records = [rec(float(i), [i + 0.1, i + 0.15, i + 0.3]) for i in range(10)]
    assert stats.client_metric(name, records, 0.0, 20.0, 60.0) > 0


def test_unknown_client_metric_name_is_an_error():
    with pytest.raises(ValueError):
        stats.client_metric("latency_p99", [], 0.0, 1.0, 60.0)
