"""``stream_tokens_per_event.serve`` (PR 39): how many tokens an SSE event
carried. A data file over the ``counter_ratio`` reader that was there:
``stream.tokens_sent`` over ``stream.events_sent``, over the window. A
stream that keeps up sends one token an event (1.0); one that has fallen
behind sends everything its queue held as one event (above 1.0).
"""

import json
import os

import pytest

from benchmark.spec import Spec
from scalable_hw_agnostic_inference_tpu.obs.steploop import StepTelemetry

SPEC = Spec()
NAME = "stream_tokens_per_event.serve"
SERVE = ["mistral-7b-int8.decode-sat", "mistral-7b-bf16-tp4.decode-sat",
         "trinity-mini-bf16.decode-sat-4k",
         "kanana-2-30b-a3b-bf16.decode-sat-8k"]


def _read(before, after):
    params = SPEC.layer_metric(NAME)["reader"]
    ctx = {"before": {"t": 100.0, "engine": before, "histograms": {}},
           "after": {"t": 130.0, "engine": after, "histograms": {}}}
    return SPEC.reader(params["kind"]).read(ctx, params)


def test_the_entry_is_appended_and_the_benchmark_is_whole():
    assert SPEC.problems() == []
    names = [m["name"] for m in SPEC.bench["per_layer"]]
    entry = SPEC.metric_entry(NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "HTTP and lanes",
        "moves": "out_tok_per_s"}
    # the saturated cells that were there come first; a later one may join
    assert entry["workloads"][:len(SERVE)] == SERVE
    # what was there is as it was: directly behind the last entry of the PR
    # before this one (and not necessarily LAST: the next PR appends too)
    assert names[names.index(NAME) - 1] == "stream_deliver_mean_ms.rate"
    with open(os.path.join(SPEC.dir, "layer_metrics", NAME + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == {"kind": "counter_ratio",
                              "numerator": "stream.tokens_sent",
                              "denominator": "stream.events_sent"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    for cell in SERVE:
        assert NAME in SPEC.cell_layer_metrics(cell)


@pytest.mark.parametrize("tokens,events,want", [
    (3000, 3000, 1.0),      # every stream kept up
    (3000, 1200, 2.5),      # the streams had fallen behind
])
def test_it_is_the_windows_tokens_over_the_windows_events(tokens, events,
                                                          want):
    before = {"stream": {"tokens_sent": 500, "events_sent": 500}}
    after = {"stream": {"tokens_sent": 500 + tokens,
                        "events_sent": 500 + events}}
    assert _read(before, after) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({"steps": 1}, {"steps": 9}),               # a program with no counters
    ({"stream": {"tokens_sent": 5, "events_sent": 5}},
     {"stream": {"tokens_sent": 5, "events_sent": 5}}),   # nothing streamed
])
def test_it_reads_nothing_where_there_is_nothing_to_read(before, after):
    assert _read(before, after) is None


@pytest.mark.parametrize("k", [1, 4])
def test_it_reads_what_the_programs_own_counters_say(k):
    """Against the real ``StreamTrack``: ten turns of ``k`` tokens each."""
    tele = StepTelemetry()
    before = tele.snapshot()
    track = tele.stream_open()
    for _ in range(10):
        tele.phase_t0 = 1.0
        for tok in range(k):
            track.put(tok)
        for _ in range(k):
            track.took(track.q.get(timeout=1)[1])
        track.hand_on()
        track.sent(10)
        track.wrote()
    assert _read(before, tele.snapshot()) == pytest.approx(float(k))
