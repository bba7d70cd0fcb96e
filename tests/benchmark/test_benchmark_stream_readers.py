"""The readers of a token's way out, a request's way in and the loop thread's
time off the CPU (PR 38), each against a ``ctx`` made by hand, and the
metrics that use them.

The three new readers (``counter_rate``, ``histogram_quantile``,
``phase_offcpu_share``) return nothing where the program keeps no such
counter, histogram or CPU seconds, as the parent commit does not; the
metric files over the readers that were there (``histogram_mean``,
``step_mean``, ``step_max``) name keys the program really has.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.spec import Spec
from scalable_hw_agnostic_inference_tpu.obs.steploop import (
    NON_WAITING_PHASES,
    STREAM_BUCKETS,
    StepTelemetry,
)

SPEC = Spec()
SERVE = ["mistral-7b-int8.decode-sat", "mistral-7b-bf16-tp4.decode-sat",
         "trinity-mini-bf16.decode-sat-4k",
         "kanana-2-30b-a3b-bf16.decode-sat-8k"]
RATE = ["mistral-7b-int8.prefill-rate",
        "kimi-linear-48b-a3b-bf16-ep2.prefill-rate-16k"]
#: the six cells the benchmark had when these metrics came (PR 38), in
#: ``BENCHMARK.json``'s order: a later cell may join any list behind them
KNOWN = [SERVE[0], RATE[0], SERVE[1], SERVE[2], SERVE[3], RATE[1]]
HTTP, ADM = "HTTP and lanes", "admission and scheduler"
#: metric -> (reader kind, source, layer, moves, cells)
NEW = {
    "engine_tok_per_s.serve": ("counter_rate", "program_counter", ADM,
                               "out_tok_per_s", SERVE),
    "stream_wake_mean_ms.serve": ("histogram_mean", "program_span", HTTP,
                                  "out_tok_per_s", SERVE),
    "stream_encode_mean_ms.serve": ("histogram_mean", "program_span", HTTP,
                                    "out_tok_per_s", SERVE),
    "stream_write_mean_ms.serve": ("histogram_mean", "program_span", HTTP,
                                   "out_tok_per_s", SERVE),
    "stream_deliver_mean_ms.serve": ("histogram_mean", "program_span", HTTP,
                                     "out_tok_per_s", SERVE),
    "stream_finish_lag_mean_ms.serve": ("histogram_mean", "program_span",
                                        HTTP, "out_tok_per_s", SERVE),
    "stream_deliver_p99_ms.serve": ("histogram_quantile", "program_span",
                                    HTTP, "out_tok_per_s", SERVE),
    "callers_draining_mean.serve": ("step_mean", "program_counter", HTTP,
                                    "out_tok_per_s", SERVE),
    "callers_ingress_mean.serve": ("step_mean", "program_counter", HTTP,
                                   "out_tok_per_s", SERVE),
    "stream_backlog_peak.serve": ("step_max", "program_counter", HTTP,
                                  "out_tok_per_s", SERVE),
    "ingress_mean_ms.serve": ("histogram_mean", "program_span", HTTP,
                              "out_tok_per_s", SERVE),
    "loop_offcpu_share.serve": ("phase_offcpu_share", "program_span", ADM,
                                "out_tok_per_s", SERVE),
    "ingress_mean_ms.rate": ("histogram_mean", "program_span", HTTP,
                             "ttft_p90_ms", RATE),
    "stream_deliver_mean_ms.rate": ("histogram_mean", "program_span", HTTP,
                                    "gap_p95_ms", RATE),
}


def read(kind, ctx, **params):
    return SPEC.reader(kind).read(ctx, {"kind": kind, **params})


def _ctx(before, after, t0=100.0, t1=130.0, hist0=None, hist1=None):
    return {"before": {"t": t0, "engine": before, "histograms": hist0 or {}},
            "after": {"t": t1, "engine": after, "histograms": hist1 or {}}}


# -- counter_rate -------------------------------------------------------------

def test_counter_rate_is_the_change_over_the_seconds_between():
    ctx = _ctx({"tokens_committed": 1000, "stream": {"tokens_sent": 10}},
               {"tokens_committed": 70000, "stream": {"tokens_sent": 310}})
    assert read("counter_rate", ctx, counter="tokens_committed") == \
        pytest.approx(2300.0)
    assert read("counter_rate", ctx, counter="stream.tokens_sent") == \
        pytest.approx(10.0)
    assert read("counter_rate", ctx, counter="tokens_committed",
                scale=0.001) == pytest.approx(2.3)


@pytest.mark.parametrize("before,after", [
    ({"steps": 1}, {"steps": 9}),                         # the parent commit
    ({"steps": 1}, {"steps": 9, "tokens_committed": 5}),  # not at both ends
    ({"stream": 3}, {"stream": 4}),                       # no nested entry
])
def test_counter_rate_reads_nothing_where_the_counter_is_missing(before,
                                                                 after):
    ctx = _ctx(before, after)
    assert read("counter_rate", ctx, counter="tokens_committed") is None
    assert read("counter_rate", ctx, counter="stream.tokens_sent") is None


def test_counter_rate_reads_nothing_over_no_time():
    ctx = _ctx({"tokens_committed": 1}, {"tokens_committed": 2}, t1=100.0)
    assert read("counter_rate", ctx, counter="tokens_committed") is None


# -- histogram_quantile -------------------------------------------------------

def _hist(counts, bounds=(0.001, 0.01, 0.1)):
    """A histogram snapshot as ``BucketHistogram.snapshot`` gives it."""
    out, cum = [], 0
    for b, c in zip(bounds, counts):
        cum += c
        out.append((b, cum))
    n = sum(counts)
    return {"buckets": out + [("+Inf", n)], "sum": 0.0, "count": n}


@pytest.mark.parametrize("quantile,want_ms", [
    (0.5, 1.0), (0.9, 1.0), (0.95, 10.0), (0.99, 100.0), (1.0, 100.0)])
def test_histogram_quantile_is_the_bound_the_quantile_falls_under(quantile,
                                                                  want_ms):
    # over the window: 90 under 1 ms, 8 under 10 ms, 2 under 100 ms; the
    # 1,000 observations before it, all slow, are none of its business
    a = _hist([0, 0, 1000, 0])
    b = _hist([90, 8, 1002, 0])
    ctx = _ctx({}, {}, hist0={"stream_deliver_seconds": a},
               hist1={"stream_deliver_seconds": b})
    assert read("histogram_quantile", ctx,
                histogram="stream_deliver_seconds", quantile=quantile,
                scale=1000.0) == pytest.approx(want_ms)


def test_histogram_quantile_past_the_last_bound_reads_the_last_bound():
    ctx = _ctx({}, {}, hist0={"h": _hist([0, 0, 0, 0])},
               hist1={"h": _hist([1, 0, 0, 9])})
    assert read("histogram_quantile", ctx, histogram="h",
                quantile=0.99) == pytest.approx(0.1)


def test_histogram_quantile_reads_nothing_where_nothing_was_observed():
    h = _hist([3, 0, 0, 0])
    ctx = _ctx({}, {}, hist0={"h": h}, hist1={"h": h})
    assert read("histogram_quantile", ctx, histogram="h",
                quantile=0.99) is None
    # the parent commit keeps no such histogram
    ctx = _ctx({}, {}, hist0={"ttft_seconds": h}, hist1={"ttft_seconds": h})
    assert read("histogram_quantile", ctx, histogram="h",
                quantile=0.99) is None


def test_histogram_quantile_reads_the_programs_own_snapshot():
    tele = StepTelemetry()
    before = tele.histograms()
    for v in [0.0002] * 98 + [0.3, 0.3]:
        tele.stream_deliver.observe(v)
    ctx = _ctx({}, {}, hist0=before, hist1=tele.histograms())
    params = SPEC.layer_metric("stream_deliver_p99_ms.serve")["reader"]
    assert SPEC.reader(params["kind"]).read(ctx, params) == \
        pytest.approx(500.0)
    assert 0.5 in STREAM_BUCKETS


# -- phase_offcpu_share -------------------------------------------------------

def test_phase_offcpu_share_is_wall_less_cpu_over_wall():
    # phase_s is every step's; the CPU clock is read in one step of a few,
    # and the share is over the wall seconds of those same phases
    before = {"phase_s": {"engine.commit": 8.0},
              "phase_cpu_wall_s": {"engine.commit": 1.0, "engine.admit": 0.5,
                                   "engine.fetch": 9.0},
              "phase_cpu_s": {"engine.commit": 0.9, "engine.admit": 0.5,
                              "engine.fetch": 0.1}}
    after = {"phase_s": {"engine.commit": 32.0},
             "phase_cpu_wall_s": {"engine.commit": 4.0, "engine.admit": 1.5,
                                  "engine.fetch": 30.0},
             "phase_cpu_s": {"engine.commit": 2.4, "engine.admit": 1.3,
                             "engine.fetch": 0.2}}
    ctx = _ctx(before, after)
    # commit: 3.0 wall, 1.5 cpu; admit: 1.0 wall, 0.8 cpu; fetch not asked
    assert read("phase_offcpu_share", ctx,
                phases=["engine.commit", "engine.admit"]) == \
        pytest.approx(100.0 * (4.0 - 2.3) / 4.0)
    assert read("phase_offcpu_share", ctx, phases=["engine.commit"]) == \
        pytest.approx(50.0)
    # a phase the run never entered adds nothing
    assert read("phase_offcpu_share", ctx,
                phases=["engine.commit", "engine.verify"]) == \
        pytest.approx(50.0)


def test_phase_offcpu_share_reads_nothing_without_cpu_seconds():
    before = {"phase_s": {"engine.commit": 1.0}}
    after = {"phase_s": {"engine.commit": 4.0}}
    assert read("phase_offcpu_share", _ctx(before, after),
                phases=["engine.commit"]) is None
    after.update(phase_cpu_s={"engine.commit": 2.0},
                 phase_cpu_wall_s={"engine.commit": 3.0})
    assert read("phase_offcpu_share", _ctx(before, after),
                phases=["engine.commit"]) is None
    # and nothing where no sampled phase took any time
    same = {"phase_cpu_wall_s": {"engine.commit": 1.0},
            "phase_cpu_s": {"engine.commit": 1.0}}
    assert read("phase_offcpu_share", _ctx(same, same),
                phases=["engine.commit"]) is None


def test_the_cpu_clock_is_read_in_one_step_of_a_few(monkeypatch):
    from scalable_hw_agnostic_inference_tpu.obs import steploop

    reads = []
    monkeypatch.setattr(steploop.time, "thread_time",
                        lambda: reads.append(1) or 0.001 * len(reads))
    tele = StepTelemetry()
    n = 4 * steploop.CPU_SAMPLE_EVERY
    for _ in range(n):
        tele.begin_step(0)
        tele.phase_enter("engine.commit")
        tele.record_step(kind="decode", duration_s=0.0, n_running=1,
                         n_waiting=0, n_chunking=0, blocks_free=0)
        tele.phase_enter("loop.resolve")
    tele.phase_enter(None)
    # three boundaries a step here: a sampled step reads the clock at each
    # and the next step's first boundary closes its last phase unread
    assert 3 * 4 <= len(reads) <= 3 * 4 + 4
    snap = tele.snapshot()
    assert snap["phase_cpu_s"]["engine.commit"] == pytest.approx(0.004)
    assert snap["phase_cpu_s"]["engine.admit"] == pytest.approx(0.004)
    assert 0 < snap["phase_cpu_wall_s"]["engine.admit"] \
        < snap["phase_s"]["engine.admit"]


# -- the metric files and their entries ---------------------------------------

def _a_programs_reading():
    """What a telemetry object that saw one step shows the readers."""
    tele = StepTelemetry()
    tele.begin_step(0)
    tele.record_step(kind="decode", duration_s=0.0, n_running=1, n_waiting=0,
                     n_chunking=0, blocks_free=0, tokens=1)
    tele.phase_enter(None)
    return tele.snapshot(), tele.histograms(), tele.recent_steps()[-1]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_names_a_reader_and_keys_that_exist(name):
    kind, source, layer, moves, cells = NEW[name]
    entry, mf = SPEC.metric_entry(name), SPEC.layer_metric(name)
    assert mf["reader"]["kind"] == kind
    assert hasattr(SPEC.reader(kind), "read")
    assert (entry["source"], entry["layer"], entry["moves"]) == \
        (source, layer, moves)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert mf[key] == entry[key]
    snap, hists, rec = _a_programs_reading()
    params = mf["reader"]
    if kind in ("histogram_mean", "histogram_quantile"):
        assert params["histogram"] in hists
        assert params["scale"] == 1000.0 and entry["unit"] == "ms"
    elif kind in ("step_mean", "step_max"):
        assert params["field"] in rec
        if kind == "step_mean":
            assert params["kinds"] == ["decode"]
    elif kind == "counter_rate":
        assert params["counter"] in snap
    else:
        assert tuple(params["phases"]) == NON_WAITING_PHASES
        assert set(params["phases"]) <= set(snap["phase_cpu_s"])
        assert set(params["phases"]) <= set(snap["phase_cpu_wall_s"])


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_reported_in_its_cells_alone(name):
    """Of the cells that were there: its list starts with them, in their
    order, and none of the others reports it."""
    cells = NEW[name][-1]
    assert SPEC.metric_entry(name)["workloads"][:len(cells)] == cells
    assert [w for w in KNOWN if name in SPEC.cell_layer_metrics(w)] == cells


def test_the_suffixes_are_the_cells_of_their_end_to_end_metric():
    by = {m["name"]: m for m in SPEC.bench["end_to_end"]}
    # each list starts with the accepted cells; a later cell stands behind
    assert by["out_tok_per_s"]["workloads"][:len(SERVE)] == SERVE
    assert by["ttft_p90_ms"]["workloads"][:len(RATE)] == RATE
    assert by["gap_p95_ms"]["workloads"][:len(RATE)] == RATE
    assert sorted(n for n in NEW if n.endswith(".serve")) == sorted(
        n for n, v in NEW.items() if v[-1] == SERVE)
    assert sorted(n for n in NEW if n.endswith(".rate")) == sorted(
        n for n, v in NEW.items() if v[-1] == RATE)


def test_the_new_entries_are_appended_and_the_benchmark_is_whole():
    assert SPEC.problems() == []
    names = [m["name"] for m in SPEC.bench["per_layer"]]
    first = names.index("engine_tok_per_s.serve")
    assert sorted(names[first:first + len(NEW)]) == sorted(NEW)
    # what was there comes first and is as it was: the one metric the layer
    # had, and the last entry of the PR before this one
    assert names.index("shed_share.prefill") < first
    assert names[first - 1] == "moe_tiled_layers_per_program.kda"
    for kind in ("counter_rate", "histogram_quantile", "phase_offcpu_share"):
        assert os.path.exists(os.path.join(
            SPEC.dir, "readers", kind + ".py"))


def test_the_hops_sum_to_the_delivery_through_the_readers():
    tele = StepTelemetry()
    before = tele.histograms()
    track = tele.stream_open()
    for _ in range(5):
        tele.phase_t0 = time.monotonic()
        track.put(1)
        tok, t_commit = track.q.get(timeout=1)
        track.took(t_commit)
        track.hand_on()
        track.sent(10)
        track.wrote()
    ctx = _ctx({}, {}, hist0=before, hist1=tele.histograms())

    def metric(name):
        params = SPEC.layer_metric(name)["reader"]
        return SPEC.reader(params["kind"]).read(ctx, params)

    hops = sum(metric(f"stream_{k}_mean_ms.serve")
               for k in ("wake", "encode", "write"))
    assert hops == pytest.approx(metric("stream_deliver_mean_ms.serve"),
                                 rel=1e-6)
    assert metric("stream_finish_lag_mean_ms.serve") is None


# -- the command, on the CPU ---------------------------------------------------

@pytest.mark.parametrize("cell", ["mistral-7b-int8.decode-sat",
                                  "mistral-7b-int8.prefill-rate"])
def test_a_traced_dry_run_reports_every_new_metric(cell, tmp_path):
    """One saturated and one open-loop cell, tiny, on the CPU: every new
    metric of the cell is in its line (none of them needs the device
    trace), and the counters balance."""
    p = subprocess.run(
        [sys.executable, os.path.join(SPEC.root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--dry-run", "--out", str(tmp_path / "out")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=SPEC.root,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    metrics = result["metrics"]
    want = {n for n, v in NEW.items() if cell in v[-1]}
    assert want and want <= set(metrics), want - set(metrics)
    assert not {n for n in NEW if n not in want} & set(metrics)
    assert "left out" not in p.stderr
    assert result["correct"] is True and result["failed"] == 0
    suffix = ".serve" if cell in SERVE else ".rate"
    assert metrics["stream_deliver_mean_ms" + suffix]["value"] > 0
    assert metrics["ingress_mean_ms" + suffix]["value"] > 0
    if cell in SERVE:
        hops = sum(metrics[f"stream_{k}_mean_ms.serve"]["value"]
                   for k in ("wake", "encode", "write"))
        assert hops == pytest.approx(
            metrics["stream_deliver_mean_ms.serve"]["value"], rel=0.02)
        assert (metrics["stream_deliver_p99_ms.serve"]["value"]
                >= metrics["stream_deliver_mean_ms.serve"]["value"] / 2.5)
        assert metrics["engine_tok_per_s.serve"]["value"] > 0
        assert 0 <= metrics["loop_offcpu_share.serve"]["value"] <= 100
        assert metrics["callers_draining_mean.serve"]["value"] >= 0
